"""Share of the window in which no operation ran on the chip (one minus
the union of the trace's operation intervals over the window)."""


def read(run, out):
    r = out.get("reduced")
    return None if r is None else 100.0 * r.idle_share
