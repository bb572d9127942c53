"""Mamba2 SSD (state-space duality) block — TPU-native chunked form.

The sequence is split into chunks of Q tokens.  Within a chunk the
computation is a masked, decay-weighted attention-like matmul
(MXU-friendly); across chunks a first-order recurrence over the running
state (B, H, P, N) is evaluated with ``lax.scan``.  This is the Mamba2
paper's algorithm; Jamba's Mamba-1 layers are instantiated with the same
block (d_state from config) — see DESIGN.md §Hardware-adaptation.

Shapes: D = d_model, I = d_inner, H = ssm heads, P = head dim,
G = groups, N = d_state, K = conv kernel width, Q = chunk.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime.sharding import lshard
from .config import ModelConfig
from .layers import rms_norm_gated

# Dry-run probe hook (see layers.UNROLL_BLOCKS): unroll the chunk scan so
# cost_analysis counts every chunk.  Above UNROLL_CHUNKS_MAX chunks the
# scan stays rolled: compile time would explode while the intra-chunk
# matmuls the loop hides are only ~4-8% of an SSM layer's FLOPs (the
# in/out projections dominate — that is the point of SSD's linear cost);
# the residual undercount is documented in EXPERIMENTS.md §Methodology.
UNROLL_CHUNKS = False
UNROLL_CHUNKS_MAX = 64


def ssd_params_layout(cfg: ModelConfig):
    D, I, H = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    d_in = 2 * I + 2 * G * N + H
    conv_dim = cfg.conv_dim
    return {
        "w_in": ((D, d_in), ("embed", "ssm_inner"), D ** -0.5),
        "conv_w": ((conv_dim, K), ("ssm_inner", "conv"), conv_dim ** -0.5),
        "conv_b": ((conv_dim,), ("ssm_inner",), 0.0),
        "dt_bias": ((H,), ("ssm_heads",), 0.0),
        "A_log": ((H,), ("ssm_heads",), 0.0),
        "skip_D": ((H,), ("ssm_heads",), 0.0),
        "w_norm": ((I,), ("ssm_inner",), 0.0),
        "w_out": ((I, D), ("ssm_inner", "embed"), I ** -0.5),
    }


def _split_in(h, cfg: ModelConfig):
    I, G, N, H = cfg.d_inner, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    z, xc, Bm, Cm, dt = jnp.split(
        h, [I, 2 * I, 2 * I + G * N, 2 * I + 2 * G * N], axis=-1)
    return z, xc, Bm, Cm, dt


def _causal_conv(x, w, b, cache: Optional[jnp.ndarray] = None):
    """Depthwise causal conv.  x: (B,S,C); w: (C,K); cache: (B,K-1,C)
    holds the trailing inputs of the previous segment.  Returns
    (y (B,S,C), new_cache (B,K-1,C))."""
    K = w.shape[1]
    if cache is None:
        cache = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xx = jnp.concatenate([cache, x], axis=1)               # (B, S+K-1, C)
    # K is tiny (4): express the conv as K shifted multiply-adds
    y = sum(xx[:, i:i + x.shape[1], :] * w[:, i][None, None, :]
            for i in range(K))
    y = y + b[None, None, :]
    new_cache = xx[:, -(K - 1):, :] if K > 1 else cache
    return y, new_cache


def ssd_scan(xh, dt, A, Bm, Cm, chunk: int,
             init_state: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD.  xh: (B,S,H,P); dt: (B,S,H); A: (H,) (negative);
    Bm, Cm: (B,S,G,N).  Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    B, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    Q = min(chunk, S)
    S_in = S
    pad = (-S) % Q
    if pad:  # padded tail has dt=0 => zero contribution to the state
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)) + ((0, 0),) * (dt.ndim - 2))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q

    a = dt * A[None, None, :]                               # (B,S,H) <= 0
    # chunk views, scan over the chunk axis
    ach = a.reshape(B, nc, Q, H).transpose(1, 0, 2, 3)      # (nc,B,Q,H)
    xch = xh.reshape(B, nc, Q, H, P).transpose(1, 0, 2, 3, 4)
    dtch = dt.reshape(B, nc, Q, H).transpose(1, 0, 2, 3)
    Bch = Bm.reshape(B, nc, Q, G, N).transpose(1, 0, 2, 3, 4)
    Cch = Cm.reshape(B, nc, Q, G, N).transpose(1, 0, 2, 3, 4)

    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))

    def chunk_step(state, inp):
        a_c, x_c, dt_c, B_c, C_c = inp                      # leading dim B
        cum = jnp.cumsum(a_c, axis=1)                       # (B,Q,H)
        # intra-chunk (attention-like, per head through its group)
        CB = jnp.einsum("bqgn,bkgn->bgqk", C_c.astype(jnp.float32),
                        B_c.astype(jnp.float32))            # (B,G,Q,Q)
        # mask the exponent, not the exponential: above the diagonal the
        # difference is positive and overflows over a long chunk, and
        # where(mask, inf, 0) back-propagates 0 * inf = NaN
        seg = jnp.where(causal[None, :, :, None],
                        cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf)
        Ldec = jnp.exp(seg)                                 # (B,Q,K,H)
        CBh = jnp.repeat(CB, hpg, axis=1)                   # (B,H,Q,K)
        scores = CBh.transpose(0, 2, 3, 1) * Ldec * dt_c[:, None, :, :]
        y_diag = jnp.einsum("bqkh,bkhp->bqhp", scores,
                            x_c.astype(jnp.float32))
        # inter-chunk: contribution of the carried state (group-aware)
        state_g = state.reshape(B, G, hpg, P, N)
        y_off = jnp.einsum("bqgn,bghpn->bqghp", C_c.astype(jnp.float32),
                           state_g).reshape(B, Q, H, P)
        y_off = y_off * jnp.exp(cum)[..., None]
        # new chunk state
        decay_tail = jnp.exp(cum[:, -1:, :] - cum)          # (B,Q,H)
        sB = jnp.repeat(B_c, hpg, axis=2)                   # (B,Q,H,N)
        contrib = jnp.einsum("bqhn,bqhp->bhpn",
                             (sB * (dt_c * decay_tail)[..., None]
                              ).astype(jnp.float32),
                             x_c.astype(jnp.float32))
        state_new = state * jnp.exp(jnp.sum(a_c, axis=1))[..., None, None] \
            + contrib
        return state_new, (y_diag + y_off).astype(xh.dtype)

    state0 = init_state if init_state is not None else \
        jnp.zeros((B, H, P, N), jnp.float32)
    final, ych = lax.scan(
        chunk_step, state0, (ach, xch, dtch, Bch, Cch),
        unroll=nc if (UNROLL_CHUNKS and nc <= UNROLL_CHUNKS_MAX) else 1)
    y = ych.transpose(1, 0, 2, 3, 4).reshape(B, S, H, P)
    return y[:, :S_in], final


def ssd_layer(p, x, cfg: ModelConfig, cache: Optional[dict] = None,
              return_cache: bool = False):
    """Full-sequence SSD block: (B,S,D) -> (B,S,D).

    With ``return_cache`` also returns {"conv": (B,K-1,conv_dim),
    "state": (B,H,P,N)} for subsequent decode."""
    B, S, D = x.shape
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    h = x @ p["w_in"].astype(x.dtype)
    z, xc, Bm, Cm, dt = _split_in(h, cfg)
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)
    conv_out, conv_tail = _causal_conv(
        conv_in, p["conv_w"].astype(x.dtype), p["conv_b"].astype(x.dtype),
        None if cache is None else cache.get("conv"))
    conv_out = jax.nn.silu(conv_out)
    xc = conv_out[..., :cfg.d_inner]
    Bm = conv_out[..., cfg.d_inner:cfg.d_inner + G * N].reshape(B, S, G, N)
    Cm = conv_out[..., cfg.d_inner + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xc.reshape(B, S, H, P)
    xh = lshard(xh, "batch", "seq", "ssm_heads", None)
    y, state = ssd_scan(xh, dt, A, Bm, Cm, cfg.ssm_chunk,
                        None if cache is None else cache.get("state"))
    y = y + xh.astype(jnp.float32).astype(y.dtype) * \
        p["skip_D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, cfg.d_inner)
    y = rms_norm_gated(y, z, p["w_norm"], cfg.norm_eps)
    out = y @ p["w_out"].astype(x.dtype)
    if return_cache:
        return out, {"conv": conv_tail, "state": state}
    return out


def ssd_decode(p, x, cache: dict, cfg: ModelConfig):
    """Single-token decode: x (B,1,D); cache {"conv": (B,K-1,conv_dim),
    "state": (B,H,P,N)}.  Returns (out (B,1,D), new_cache)."""
    B = x.shape[0]
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_groups, cfg.ssm_state
    hpg = H // G
    h = x @ p["w_in"].astype(x.dtype)                       # (B,1,d_in)
    z, xc, Bm, Cm, dt = _split_in(h, cfg)
    conv_in = jnp.concatenate([xc, Bm, Cm], axis=-1)        # (B,1,conv_dim)
    window = jnp.concatenate([cache["conv"], conv_in], axis=1)  # (B,K,c)
    w = p["conv_w"].astype(x.dtype)                         # (c,K)
    conv_out = jnp.einsum("bkc,ck->bc", window, w) + p["conv_b"].astype(x.dtype)
    conv_out = jax.nn.silu(conv_out)[:, None, :]            # (B,1,c)
    new_conv = window[:, 1:, :]
    xc = conv_out[..., :cfg.d_inner]
    Bm = conv_out[..., cfg.d_inner:cfg.d_inner + G * N].reshape(B, G, N)
    Cm = conv_out[..., cfg.d_inner + G * N:].reshape(B, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) +
                         p["dt_bias"][None, None, :])[:, 0]  # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xc.reshape(B, H, P)
    decay = jnp.exp(dt * A[None, :])                        # (B,H)
    Bh = jnp.repeat(Bm, hpg, axis=1)                        # (B,H,N)
    state = cache["state"] * decay[..., None, None] + \
        jnp.einsum("bh,bhn,bhp->bhpn", dt, Bh.astype(jnp.float32),
                   xh.astype(jnp.float32))
    Ch = jnp.repeat(Cm, hpg, axis=1)
    y = jnp.einsum("bhn,bhpn->bhp", Ch.astype(jnp.float32), state)
    y = y + xh.astype(jnp.float32) * p["skip_D"][None, :, None]
    y = y.reshape(B, 1, cfg.d_inner).astype(x.dtype)
    y = rms_norm_gated(y, z, p["w_norm"], cfg.norm_eps)
    out = y @ p["w_out"].astype(x.dtype)
    return out, {"conv": new_conv, "state": state}
