"""Self time of the rest of ``LcapCluster.pump`` (watermark collection,
the collective ack and journal trim) per routed record: the pump's span
less its routing and shard-pump children, in the window."""


def read(run, out):
    n = out["records_routed"]
    if not n:
        return None
    s, w = out["spans"], run.window
    rest = s.total("round", w) - s.total("route", w) - s.total("shard_pump", w)
    return 1e6 * rest / n
