"""Host time per step in ``ShardedTokenPipeline.__next__``, summed over
the simulated hosts."""


def read(run, out):
    n = out["steps"]
    total = out["spans"].total("data", run.window)
    return 1e3 * total / n if n and out["spans"].count("data", run.window) \
        else None
