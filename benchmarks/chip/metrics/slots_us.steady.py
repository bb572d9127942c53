"""Time in the routing slot kernel (the device twin's host-device round
trip, the program's ``cluster.route.slots`` spans) per record routed
(Σ count of ``cluster.route``), in the window."""

from chipbench.program_spans import totals


def read(run, out):
    route = totals(run, "cluster.route")
    if not route or not route[0]:
        return None
    return totals(run, "cluster.route.slots")[1] / route[0] * 1e-3
