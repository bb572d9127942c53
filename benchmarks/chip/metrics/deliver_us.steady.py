"""Self time of the session layer (the members' ``fetch`` and
``commit``) per delivered record, in the window."""


def read(run, out):
    n = out["records_fetched"]
    return 1e6 * out["spans"].total("deliver", run.window) / n if n else None
