"""Self time of the MDT journals' write path (``Llog.log_batch``) per
appended record, in the window."""


def read(run, out):
    n = out["records_appended"]
    return 1e6 * out["spans"].total("append", run.window) / n if n else None
