"""Backend compiles (persistent-cache loads included) inside the window,
from ``jax.monitoring``."""


def read(run, out):
    return float(out["window_compiles"])
