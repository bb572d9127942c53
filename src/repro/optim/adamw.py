"""AdamW with decoupled weight decay + cosine schedule + global-norm
clipping.  Pure pytree implementation; optimizer state shards exactly
like the parameters (ZeRO — the sharding rules apply to m/v because
they are tree-mapped from the same layout)."""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray
    m: Any
    v: Any


def init(params) -> AdamWState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros,
                      v=jax.tree.map(jnp.copy, zeros))


def abstract_state(abstract_params) -> AdamWState:
    z = jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                     abstract_params)
    return AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32), m=z, v=z)


def cosine_lr(step, *, peak: float, warmup: int, total: int,
              floor_frac: float = 0.1):
    warm = peak * (step + 1) / max(warmup, 1)
    t = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = peak * (floor_frac + (1 - floor_frac) * 0.5 *
                  (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warmup, warm, cos)


def clip_by_global_norm(grads, max_norm: float):
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


def update(grads, state: AdamWState, params, *, lr, b1=0.9, b2=0.95,
           eps=1e-8, weight_decay=0.1, max_norm: float = 1.0):
    """One AdamW step, under the name scope ``adamw``."""
    with jax.named_scope("adamw"):
        grads, gnorm = clip_by_global_norm(grads, max_norm)
        step = state.step + 1
        m = jax.tree.map(
            lambda m_, g: b1 * m_ + (1 - b1) * g.astype(jnp.float32),
            state.m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ +
                         (1 - b2) * jnp.square(g.astype(jnp.float32)),
                         state.v, grads)
        bc1 = 1 - b1 ** step.astype(jnp.float32)
        bc2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, m_, v_):
            u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + weight_decay * p
            return (p - lr * u).astype(p.dtype)

        new_params = jax.tree.map(upd, params, m, v)
    return new_params, AdamWState(step=step, m=m, v=v), gnorm
