"""95th percentile of the delivery latency of every record due in the
window (the sample ``delivery_p50_ms`` takes its median of)."""


def read(run, out):
    return out["end_to_end"]["delivery_p95_ms"]
