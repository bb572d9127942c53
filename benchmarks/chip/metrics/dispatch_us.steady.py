"""Self time of the shards' ingest and dispatch (each in-process
shard's ``pump``) per dispatched record, in the window."""


def read(run, out):
    n = out["records_dispatched"]
    return 1e6 * out["spans"].total("shard_pump", run.window) / n \
        if n else None
