"""Model operations counted from a configuration's widths, with the
benchmark's own arithmetic, so that a change to the model code cannot
move the yardstick."""

from __future__ import annotations

import math
from typing import Dict


def mamba2_layout(m: Dict) -> Dict:
    """Leaf shapes and initial scales of a Mamba-2 language model laid
    out as the program stores it: one stacked body over the layers, the
    embedding table padded to a multiple of 128 rows and tied to the
    output head.  ``m`` is the ``model`` section of a configuration
    file.  Each leaf is ``(shape, init)``: a normal std, ``0.0`` for
    zeros, ``"ones"``, or ``"A_log"`` (log of 1..8 spread over the
    heads)."""
    L, D = m["n_layers"], m["d_model"]
    I = m["ssm_expand"] * D
    N, G, K = m["ssm_state"], m["ssm_groups"], m["ssm_conv"]
    H = I // m["ssm_head_dim"]
    V = -(-m["vocab_size"] // 128) * 128
    conv_dim = I + 2 * G * N
    d_in = 2 * I + 2 * G * N + H
    ssm = {
        "w_in": ((L, D, d_in), D ** -0.5),
        "conv_w": ((L, conv_dim, K), conv_dim ** -0.5),
        "conv_b": ((L, conv_dim), 0.0),
        "dt_bias": ((L, H), 0.0),
        "A_log": ((L, H), "A_log"),
        "skip_D": ((L, H), "ones"),
        "w_norm": ((L, I), 0.0),
        "w_out": ((L, I, D), I ** -0.5),
    }
    return {"embed": ((V, D), D ** -0.5), "final_norm": ((D,), 0.0),
            "body": {"slot0": {"ln1": ((L, D), 0.0), "ssm": ssm}}}


def _leaves(layout):
    for v in layout.values():
        if isinstance(v, dict):
            yield from _leaves(v)
        else:
            yield v


def param_count(m: Dict) -> int:
    """Parameters of the model, every leaf counted once (the tied
    embedding included)."""
    return sum(math.prod(shape) for shape, _ in _leaves(mamba2_layout(m)))


def train_flops_per_token(m: Dict) -> float:
    """The 6·N convention: forward and backward operations per trained
    token, recomputation not counted.  Mamba-2 has no experts, so every
    parameter is active."""
    return 6.0 * param_count(m)
