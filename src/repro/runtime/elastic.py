"""Elastic scaling: mesh (re)planning + checkpoint resharding.

Membership comes from ELASTIC_JOIN/LEAVE changelog records (the
ElasticController consumer).  On a generation change the runtime:
  1. drains in-flight steps, async-checkpoints,
  2. rebuilds the mesh from the surviving hosts (largest usable 2^k),
  3. restores the (mesh-agnostic) checkpoint with the new shardings,
  4. resumes from the DATA_CONSUME watermark.

Checkpoints are mesh-agnostic (unsharded numpy per leaf), so resharding
is just device_put against the new mesh — no format conversion.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax

from ..models import transformer as T
from ..optim import adamw
from .sharding import LogicalRules
from .specs import shardings_of


def plan_mesh_shape(n_devices: int) -> Tuple[int, int]:
    """Largest usable power-of-two (data, model) grid <= n_devices."""
    usable = 1 << int(math.log2(max(n_devices, 1)))
    data = 1 << (int(math.log2(usable)) // 2)
    return data, usable // data


def make_elastic_mesh(n_devices: Optional[int] = None):
    devs = jax.devices()
    n = n_devices or len(devs)
    data, model = plan_mesh_shape(n)
    import numpy as np
    grid = np.array(devs[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def state_shardings(cfg, rules: LogicalRules):
    """(param shardings, AdamW-state shardings) under ``rules``: m/v
    shard exactly like the params, the step counter is replicated."""
    p_sh = shardings_of(rules, T.param_axes(cfg))
    o_sh = adamw.AdamWState(
        step=jax.sharding.NamedSharding(rules.mesh,
                                        jax.sharding.PartitionSpec()),
        m=p_sh, v=p_sh)
    return p_sh, o_sh


def reshard_state(cfg, params, opt_state, mesh,
                  overrides: Optional[Dict] = None):
    """Land host (numpy) param/opt trees on ``mesh`` with the logical
    rules — the elastic restore path."""
    rules = LogicalRules(mesh, overrides)
    p_sh, o_sh = state_shardings(cfg, rules)
    params = jax.tree.map(jax.device_put, params, p_sh)
    if opt_state is not None:
        opt_state = jax.tree.map(jax.device_put, opt_state, o_sh)
    return params, opt_state, rules
