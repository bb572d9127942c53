"""Jitted wrapper around the flash attention Pallas kernel.

Handles: GQA head folding, padding of sequence lengths to block
multiples and head_dim to the 128-lane MXU width, and the
models.layers-compatible calling convention.  ``interpret=True`` runs
the kernel body in Python for validation off the TPU; callers ask for
it explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, causal=True,
                    window=0, cap=0.0, scale=None, block_q=512,
                    block_k=512, interpret=False, **_ignored):
    """q: (B,Sq,H,D); k,v: (B,Sk,KV,D) -> (B,Sq,H,D).

    Positions are assumed contiguous from 0 (training/prefill layout);
    the q_pos/k_pos arguments exist for signature compatibility with
    ``models.layers.attention_core``."""
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    scale = scale if scale is not None else D ** -0.5

    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    pd = (-D) % 128
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, pd)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, pd)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, pd)))

    qf = qp.transpose(0, 2, 1, 3).reshape(B * H, Sq + pq, D + pd)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pk, D + pd)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * KV, Sk + pk, D + pd)

    of = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                              cap=cap, scale=scale, block_q=block_q,
                              block_k=block_k, seq_q=Sq, seq_k=Sk,
                              interpret=interpret)
    o = of.reshape(B, H, Sq + pq, D + pd).transpose(0, 2, 1, 3)
    return o[:, :Sq, :, :D]
