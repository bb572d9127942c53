"""Host time per step in ``Trainer.pump_consumers`` (proxy pump, the
MetricsDB workers, the checkpoint committer, the straggler detector)."""


def read(run, out):
    n = out["steps"]
    total = out["spans"].total("pump", run.window)
    return 1e3 * total / n if n and out["spans"].count("pump", run.window) \
        else None
