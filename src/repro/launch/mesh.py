"""Production mesh definitions (TPU v5e pods: 256 chips/pod).

``make_production_mesh`` is a FUNCTION (never module-level state) so
importing this module touches no jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # ``jax.make_mesh`` makes Explicit axes by default, under which
    # ``with_sharding_constraint`` and the embedding gather refuse
    # unannotated ops; the logical rules (runtime/sharding.py) assume Auto.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices are available —
    used by tests and the elastic runtime."""
    return _auto_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per-chip effective)
CHIPS_PER_POD = 256
