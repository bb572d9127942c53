"""Self time of the cluster's routing round (``LcapCluster._route``,
journal reads and the device routing twin included) per routed
record, in the window."""


def read(run, out):
    n = out["records_routed"]
    return 1e6 * out["spans"].total("route", run.window) / n if n else None
