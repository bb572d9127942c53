"""Flash attention Pallas TPU kernel.

TPU-native blockwise attention: KV streamed HBM->VMEM block by block,
running (max, denom, accumulator) kept in VMEM scratch across the
innermost grid dimension, MXU-aligned (block and head dims padded to
multiples of 128 by the ops.py wrapper).  Supports causal masking,
sliding window, logit softcap (gemma2) and GQA (the kv BlockSpec index
map folds q-head -> kv-head).

Grid: (batch*q_heads, n_q_blocks, n_kv_blocks); the kv dimension is
"arbitrary" (sequential) so scratch persists across it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, cap: float, causal: bool, window: int,
                  block_q: int, block_k: int, n_k: int, seq_q: int,
                  seq_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap:
        s = jnp.tanh(s / cap) * cap

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = (k_pos < seq_k) & (q_pos < seq_q)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                                 # (bq,)
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    # rows that are fully masked keep p==exp(NEG_INF-...)->0 via the guard:
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[:, 0] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:, 0] = m_new
    l_ref[:, 0] = l_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal=True, window=0, cap=0.0,
                         scale=None, block_q=512, block_k=512,
                         seq_q=None, seq_k=None, interpret=False):
    """q: (BH, Sq, D); k, v: (BKV, Sk, D) with BH = B*H, BKV = B*KV.
    Sq/Sk/D must already be padded to block/lane multiples by the caller;
    ``seq_q``/``seq_k`` give the pre-padding logical lengths.
    Returns (BH, Sq, D)."""
    BH, Sq, D = q.shape
    BKV, Sk, _ = k.shape
    G = BH // BKV  # q heads per kv head, per batch handled in index map
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    n_q = Sq // block_q
    n_k = Sk // block_k
    seq_q = seq_q or Sq
    seq_k = seq_k or Sk

    kernel = functools.partial(
        _flash_kernel, scale=scale, cap=cap, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, seq_q=seq_q, seq_k=seq_k)

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, qi, ki: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
