"""Calls of the routing slot kernel (the program's
``cluster.route.slots`` spans) per routing round (``cluster.route``
spans), in the window."""

from chipbench.program_spans import tracer


def read(run, out):
    tr = tracer()
    if tr is None:
        return None
    rounds = len(tr.select("cluster.route", *run.window))
    if not rounds:
        return None
    return len(tr.select("cluster.route.slots", *run.window)) / rounds
