"""Unified model: dense / MoE / hybrid(SSM+attn) / VLM / enc-dec / SSM.

One parameterized decoder (plus an optional encoder for whisper) covers
all ten assigned architectures.  Layers are stacked into scan *bodies*
of ``cfg.scan_period`` layer slots (1 for homogeneous stacks, 2 for
gemma2 local/global alternation, 8 for jamba's 1:7 attn:mamba pattern)
and iterated with ``lax.scan`` — one compiled body regardless of depth.

Params are plain nested dicts.  ``param_layout`` is the single source of
truth: every leaf is (shape, logical_axes, init_std), from which we
derive random init, abstract ShapeDtypeStructs (dry-run) and shardings.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..runtime.sharding import lshard
from .config import ModelConfig
from . import layers as L
from . import ssd as S

Layout = Dict[str, Any]           # nested: name -> (shape, axes, std) | dict
COMPUTE_DTYPE = jnp.bfloat16

# Dry-run probe hook (see layers.UNROLL_BLOCKS): unroll the layer scans so
# XLA cost_analysis counts every body exactly once per trip.
UNROLL_LAYERS = False


def _unroll(n: int) -> int:
    return n if UNROLL_LAYERS else 1


# ------------------------------------------------------------------ layout
def _slot_layout(cfg: ModelConfig, i: int, decoder: bool = True) -> Layout:
    """Layout of layer slot ``i`` (absolute index within a body)."""
    D = cfg.d_model
    slot: Layout = {"ln1": ((D,), ("embed",), 0.0)}
    if cfg.layer_kind(i) == "ssm":
        slot["ssm"] = S.ssd_params_layout(cfg)
    else:
        slot["attn"] = L.attn_params_layout(cfg)
    if cfg.is_encoder_decoder and decoder:
        slot["lnx"] = ((D,), ("embed",), 0.0)
        slot["xattn"] = L.attn_params_layout(cfg, cross=True)
    slot["ln2"] = ((D,), ("embed",), 0.0)
    if cfg.layer_is_moe(i):
        slot["moe"] = L.moe_params_layout(cfg)
    elif cfg.family == "ssm":
        pass                       # mamba2: no MLP, SSD block is the layer
    else:
        slot["mlp"] = L.mlp_params_layout(cfg)
    if cfg.family == "ssm":
        slot.pop("ln2", None)
    return slot


def param_layout(cfg: ModelConfig) -> Layout:
    D, V = cfg.d_model, cfg.padded_vocab
    out: Layout = {
        "embed": ((V, D), ("vocab", "embed"), D ** -0.5),
        "final_norm": ((D,), ("embed",), 0.0),
    }
    if not cfg.tie_embeddings:
        out["unembed"] = ((D, V), ("embed", "vocab"), D ** -0.5)
    body = {f"slot{i}": _slot_layout(cfg, i) for i in range(cfg.scan_period)}
    out["body"] = _stack_layout(body, cfg.n_bodies)
    if cfg.is_encoder_decoder:
        enc_body = {"slot0": {
            "ln1": ((D,), ("embed",), 0.0),
            "attn": L.attn_params_layout(cfg),
            "ln2": ((D,), ("embed",), 0.0),
            "mlp": L.mlp_params_layout(cfg),
        }}
        out["enc_body"] = _stack_layout(enc_body, cfg.n_encoder_layers)
        out["enc_norm"] = ((D,), ("embed",), 0.0)
    return out


def _stack_layout(layout: Layout, n: int) -> Layout:
    def stack(leaf):
        shape, axes, std = leaf
        return ((n, *shape), ("layers", *axes), std)
    return _map_leaves(layout, stack)


def _map_leaves(layout: Layout, f):
    if isinstance(layout, dict):
        return {k: _map_leaves(v, f) for k, v in layout.items()}
    return f(layout)


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def init_params(cfg: ModelConfig, seed: int = 0,
                dtype=jnp.float32) -> Dict:
    layout = param_layout(cfg)

    def init(path, leaf):
        shape, axes, std = leaf
        # crc32, not hash(): str hashes are salted per process, and the
        # same seed must give the same weights in every process
        key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                 zlib.crc32(path.encode()) % (1 << 31))
        if std == 0.0:
            x = jnp.zeros(shape, dtype)
            if path.endswith("A_log"):
                x = jnp.broadcast_to(
                    jnp.log(jnp.linspace(1.0, 8.0, shape[-1], dtype=dtype)),
                    shape)
            if path.endswith("skip_D"):
                x = jnp.ones(shape, dtype)
            return x
        return jax.random.normal(key, shape, dtype) * std

    return _walk(layout, init, "")


def abstract_params(cfg: ModelConfig, dtype=jnp.float32) -> Dict:
    return _map_leaves(param_layout(cfg),
                       lambda leaf: jax.ShapeDtypeStruct(leaf[0], dtype))


def param_axes(cfg: ModelConfig) -> Dict:
    return _map_leaves(param_layout(cfg), lambda leaf: leaf[1])


def _walk(layout, f, path):
    if isinstance(layout, dict):
        return {k: _walk(v, f, f"{path}/{k}") for k, v in layout.items()}
    return f(path, layout)


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    total = 0

    def add(path, leaf):
        nonlocal total
        shape, axes, _ = leaf
        n = int(np.prod(shape))
        if active_only and "experts" in axes:
            n = int(n * cfg.top_k / max(cfg.n_experts, 1))
        total += n
        return None

    _walk(param_layout(cfg), add, "")
    return total


def model_flops_per_token(cfg: ModelConfig) -> float:
    """MODEL_FLOPS convention: 6·N (dense) / 6·N_active (MoE) per token."""
    return 6.0 * count_params(cfg, active_only=True)


# ----------------------------------------------------------------- forward
def _embed(params, cfg: ModelConfig, tokens, image_embeds=None, scale=None):
    x = params["embed"].astype(COMPUTE_DTYPE)[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), COMPUTE_DTYPE)
    if image_embeds is not None and cfg.n_image_patches:
        n = cfg.n_image_patches
        x = jnp.concatenate([image_embeds.astype(COMPUTE_DTYPE), x[:, n:]], 1)
    return x


def _unembed(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        w = params["embed"].astype(COMPUTE_DTYPE)          # (V_pad, D)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        logits = x @ params["unembed"].astype(COMPUTE_DTYPE)
    logits = logits.astype(jnp.float32)
    logits = L.softcap(logits, cfg.logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:                 # mask pad rows
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return lshard(logits, "batch", "seq", "vocab")


def _slot_forward(slot_p, x, cfg: ModelConfig, i: int, positions,
                  enc_kv=None, impl="naive"):
    """One layer slot, full-sequence path.  Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.layer_kind(i) == "ssm":
        with jax.named_scope("ssd_block"):
            h = L.rms_norm(x, slot_p["ln1"], cfg.norm_eps)
            x = x + S.ssd_layer(slot_p["ssm"], h, cfg)
        if cfg.family == "ssm":
            return x, aux
    else:
        h = L.rms_norm(x, slot_p["ln1"], cfg.norm_eps)
        x = x + L.attention_layer(slot_p["attn"], h, cfg, positions=positions,
                                  window=cfg.layer_window(i), impl=impl)
    if "xattn" in slot_p:
        hx = L.rms_norm(x, slot_p["lnx"], cfg.norm_eps)
        x = x + L.cross_attention_layer(slot_p["xattn"], hx, enc_kv, cfg)
    h2 = L.rms_norm(x, slot_p["ln2"], cfg.norm_eps)
    if "moe" in slot_p:
        out, a = L.moe_layer(slot_p["moe"], h2, cfg)
        x = x + out
        aux = aux + a
    else:
        x = x + L.mlp_layer(slot_p["mlp"], h2, cfg)
    return x, aux


def _body_scan(params_body, x, cfg: ModelConfig, positions, enc_kv=None,
               impl="naive", remat: bool = False, remat_policy=None):
    def body(carry, slot_params):
        x, aux = carry
        for i in range(cfg.scan_period):
            x, a = _slot_forward(slot_params[f"slot{i}"], x, cfg, i,
                                 positions, enc_kv=enc_kv, impl=impl)
            aux = aux + a
        x = lshard(x, "batch", "seq", None)
        return (x, aux), None

    if remat:
        body = jax.checkpoint(
            body, policy=remat_policy or
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    n = jax.tree.leaves(params_body)[0].shape[0]
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)), params_body,
                           unroll=_unroll(n))
    return x, aux


def _encode(params, cfg: ModelConfig, frames, impl="naive"):
    """Whisper encoder over stub frame embeddings (B,F,D)."""
    B, F, D = frames.shape
    x = frames.astype(COMPUTE_DTYPE) + \
        L.sinusoidal_positions(F, D)[None].astype(COMPUTE_DTYPE)
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))

    def body(carry, slot_params):
        x, _ = carry
        sp = slot_params["slot0"]
        h = L.rms_norm(x, sp["ln1"], cfg.norm_eps)
        # bidirectional self-attention, no rope
        q, k, v = L._proj_qkv(sp["attn"], h, cfg, rope=False,
                              positions=positions)
        o = L.run_attention(q, k, v, positions, positions, cfg,
                            causal=False, impl=impl)
        x = x + o.reshape(B, F, -1) @ sp["attn"]["wo"].astype(x.dtype)
        h2 = L.rms_norm(x, sp["ln2"], cfg.norm_eps)
        x = x + L.mlp_layer(sp["mlp"], h2, cfg)
        return (x, carry[1]), None

    n = jax.tree.leaves(params["enc_body"])[0].shape[0]
    (x, _), _ = lax.scan(body, (x, jnp.zeros(())), params["enc_body"],
                         unroll=_unroll(n))
    return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _enc_kv(params, cfg: ModelConfig, enc_out):
    """Precompute per-slot cross K/V from encoder output: stacked over
    bodies -> (n_bodies, B, F, KV, hd) each."""
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    def per_body(slot_params):
        p = slot_params["slot0"]["xattn"]
        k = L._split_heads(enc_out @ p["wk"].astype(enc_out.dtype), KV, hd)
        v = L._split_heads(enc_out @ p["wv"].astype(enc_out.dtype), KV, hd)
        return k, v

    return jax.vmap(per_body, in_axes=0)(params["body"])


def forward(params, cfg: ModelConfig, tokens, *, frames=None,
            image_embeds=None, impl="naive", remat=False,
            remat_policy=None):
    """Full-sequence forward: tokens (B,S) -> (logits (B,S,V) f32, aux)."""
    B, Sq = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None],
                                 (B, Sq))
    with jax.named_scope("embed"):
        x = _embed(params, cfg, tokens, image_embeds)
    if cfg.sinusoidal_pos:
        x = x + L.sinusoidal_positions(Sq, cfg.d_model)[None].astype(x.dtype)
    x = lshard(x, "batch", "seq", None)
    enc_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, cfg, frames, impl=impl)
        enc_kv = _enc_kv(params, cfg, enc_out)
        # vmapped per-body kv: consumed inside the scan via xs
        x, aux = _body_scan_encdec(params, x, cfg, positions, enc_kv,
                                   impl=impl, remat=remat,
                                   remat_policy=remat_policy)
    else:
        x, aux = _body_scan(params["body"], x, cfg, positions, impl=impl,
                            remat=remat, remat_policy=remat_policy)
    with jax.named_scope("head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return _unembed(params, cfg, x), aux


def _body_scan_encdec(params, x, cfg, positions, enc_kv, impl, remat,
                      remat_policy=None):
    def body(carry, xs):
        x, aux = carry
        slot_params, kv = xs
        x, a = _slot_forward(slot_params["slot0"], x, cfg, 0, positions,
                             enc_kv=kv, impl=impl)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(
            body, policy=remat_policy or
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    n = jax.tree.leaves(params["body"])[0].shape[0]
    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                           (params["body"], enc_kv), unroll=_unroll(n))
    return x, aux


# -------------------------------------------------------------------- loss
def loss_fn(params, cfg: ModelConfig, tokens, labels, **fw_kw):
    logits, aux = forward(params, cfg, tokens, **fw_kw)
    with jax.named_scope("loss"):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        loss = jnp.mean(lse - ll)
    return loss + aux, (loss, aux)


# ----------------------------------------------------------- decode caches
def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=COMPUTE_DTYPE, abstract: bool = False) -> Dict:
    """Stacked-over-bodies cache pytree for decode."""
    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    nb = cfg.n_bodies

    def arr(shape, dt):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    cache: Dict[str, Any] = {}
    for i in range(cfg.scan_period):
        if cfg.layer_kind(i) == "attn":
            w = cfg.layer_window(i)
            s_slot = min(max_seq, w) if w else max_seq  # ring buffer
            cache[f"slot{i}"] = {
                "k": arr((nb, batch, s_slot, KV, hd), dtype),
                "v": arr((nb, batch, s_slot, KV, hd), dtype)}
        else:
            cache[f"slot{i}"] = {
                "conv": arr((nb, batch, cfg.ssm_conv - 1, cfg.conv_dim), dtype),
                "state": arr((nb, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), jnp.float32)}
    if cfg.is_encoder_decoder:
        cache["cross"] = {
            "k": arr((nb, batch, cfg.n_frames, KV, hd), dtype),
            "v": arr((nb, batch, cfg.n_frames, KV, hd), dtype)}
    return cache


def cache_axes(cfg: ModelConfig) -> Dict:
    """Logical axes matching init_cache's structure."""
    axes: Dict[str, Any] = {}
    for i in range(cfg.scan_period):
        if cfg.layer_kind(i) == "attn":
            axes[f"slot{i}"] = {
                "k": ("layers", "batch", "seq_kv", "kv_heads", "head_dim"),
                "v": ("layers", "batch", "seq_kv", "kv_heads", "head_dim")}
        else:
            axes[f"slot{i}"] = {
                "conv": ("layers", "batch", None, "ssm_inner"),
                "state": ("layers", "batch", "ssm_heads", None, "state")}
    if cfg.is_encoder_decoder:
        axes["cross"] = {
            "k": ("layers", "batch", "frames", "kv_heads", "head_dim"),
            "v": ("layers", "batch", "frames", "kv_heads", "head_dim")}
    return axes


def _slot_decode(slot_p, x, cfg: ModelConfig, i: int, slot_cache, pos,
                 cross_kv=None):
    new_cache = {}
    h = L.rms_norm(x, slot_p["ln1"], cfg.norm_eps)
    if cfg.layer_kind(i) == "ssm":
        out, new_cache = S.ssd_decode(slot_p["ssm"], h, slot_cache, cfg)
        x = x + out
        if cfg.family == "ssm":
            return x, new_cache
    else:
        out, ck, cv = L.decode_attention(slot_p["attn"], h, slot_cache["k"],
                                         slot_cache["v"], pos, cfg,
                                         window=cfg.layer_window(i))
        new_cache = {"k": ck, "v": cv}
        x = x + out
    if "xattn" in slot_p:
        hx = L.rms_norm(x, slot_p["lnx"], cfg.norm_eps)
        x = x + L.cross_attention_layer(slot_p["xattn"], hx, cross_kv, cfg)
    h2 = L.rms_norm(x, slot_p["ln2"], cfg.norm_eps)
    if "moe" in slot_p:
        out, _ = L.moe_layer(slot_p["moe"], h2, cfg)
        x = x + out
    else:
        x = x + L.mlp_layer(slot_p["mlp"], h2, cfg)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, token, cache, pos):
    """One decode step.  token (B,1) int32; pos (B,) int32 = position of
    this token.  Returns (logits (B,1,V) f32, new_cache)."""
    x = _embed(params, cfg, token)
    if cfg.sinusoidal_pos:
        pe_all = L.sinusoidal_positions(_max_pos(cfg, cache), cfg.d_model)
        x = x + pe_all[pos][:, None, :].astype(x.dtype)
    x = lshard(x, "batch", "seq", None)

    def body(carry, xs):
        x = carry
        if cfg.is_encoder_decoder:
            slot_params, slot_cache, cross_kv = xs
        else:
            slot_params, slot_cache = xs
            cross_kv = None
        new_cache = {}
        for i in range(cfg.scan_period):
            x, nc = _slot_decode(slot_params[f"slot{i}"], x, cfg, i,
                                 slot_cache[f"slot{i}"], pos,
                                 cross_kv=cross_kv)
            new_cache[f"slot{i}"] = nc
        return x, new_cache

    body_cache = {k: v for k, v in cache.items() if k != "cross"}
    if cfg.is_encoder_decoder:
        cross = (cache["cross"]["k"], cache["cross"]["v"])
        x, new_cache = lax.scan(body, x, (params["body"], body_cache, cross),
                                unroll=_unroll(cfg.n_bodies))
    else:
        x, new_cache = lax.scan(body, x, (params["body"], body_cache),
                                unroll=_unroll(cfg.n_bodies))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x)
    if cfg.is_encoder_decoder:
        new_cache["cross"] = cache["cross"]
    return logits, new_cache


def _max_pos(cfg, cache):
    for slot in cache.values():
        if "k" in slot:
            return slot["k"].shape[2]
    return 4096


def prefill(params, cfg: ModelConfig, tokens, *, frames=None,
            image_embeds=None, max_seq: Optional[int] = None, impl="naive"):
    """Run the full prompt, return (logits_last (B,V), cache) with the KV
    cache sized to max_seq (>= prompt length)."""
    B, Sq = tokens.shape
    max_seq = max_seq or Sq
    positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None],
                                 (B, Sq))
    x = _embed(params, cfg, tokens, image_embeds)
    if cfg.sinusoidal_pos:
        x = x + L.sinusoidal_positions(Sq, cfg.d_model)[None].astype(x.dtype)
    x = lshard(x, "batch", "seq", None)
    enc_kv = None
    if cfg.is_encoder_decoder:
        enc_out = _encode(params, cfg, frames, impl=impl)
        enc_kv = _enc_kv(params, cfg, enc_out)

    KV, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    pad = max_seq - Sq

    def to_cache(k, v, window):
        """Lay k/v (B,Sq,KV,hd) out as this slot's decode cache: plain
        (padded to max_seq) for full attention; ring buffer of ``window``
        slots (slot = position %% window) for sliding-window layers."""
        w = min(max_seq, window) if window else 0
        if not w:
            kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            tail = min(Sq, w)
            slots = (jnp.arange(Sq - tail, Sq) % w).astype(jnp.int32)
            kp = jnp.zeros((B, w, KV, hd), k.dtype).at[:, slots].set(
                k[:, Sq - tail:])
            vp = jnp.zeros((B, w, KV, hd), v.dtype).at[:, slots].set(
                v[:, Sq - tail:])
        return (lshard(kp, "batch", "seq_kv", "kv_heads", "head_dim"),
                lshard(vp, "batch", "seq_kv", "kv_heads", "head_dim"))

    def body(carry, xs):
        x = carry
        slot_params = xs if enc_kv is None else xs[0]
        kvx = None if enc_kv is None else (xs[1][0], xs[1][1])
        new_cache = {}
        for i in range(cfg.scan_period):
            sp = slot_params[f"slot{i}"]
            h = L.rms_norm(x, sp["ln1"], cfg.norm_eps)
            if cfg.layer_kind(i) == "ssm":
                out, sc = S.ssd_layer(sp["ssm"], h, cfg, return_cache=True)
                x = x + out
                new_cache[f"slot{i}"] = sc
                if cfg.family == "ssm":
                    continue
            else:
                q, k, v = L._proj_qkv(sp["attn"], h, cfg, rope=True,
                                      positions=positions)
                o = L.run_attention(q, k, v, positions, positions, cfg,
                                    causal=True,
                                    window=cfg.layer_window(i), impl=impl)
                x = x + o.reshape(B, Sq, -1) @ sp["attn"]["wo"].astype(x.dtype)
                kp, vp = to_cache(k, v, cfg.layer_window(i))
                new_cache[f"slot{i}"] = {"k": kp, "v": vp}
            if "xattn" in sp:
                hx = L.rms_norm(x, sp["lnx"], cfg.norm_eps)
                x = x + L.cross_attention_layer(sp["xattn"], hx, kvx, cfg)
            h2 = L.rms_norm(x, sp["ln2"], cfg.norm_eps)
            if "moe" in sp:
                out, _ = L.moe_layer(sp["moe"], h2, cfg)
                x = x + out
            elif "mlp" in sp:
                x = x + L.mlp_layer(sp["mlp"], h2, cfg)
        x = lshard(x, "batch", "seq", None)
        return x, new_cache

    if cfg.is_encoder_decoder:
        x, cache = lax.scan(body, x, (params["body"], enc_kv),
                            unroll=_unroll(cfg.n_bodies))
        cache["cross"] = {"k": enc_kv[0], "v": enc_kv[1]}
    else:
        x, cache = lax.scan(body, x, params["body"],
                            unroll=_unroll(cfg.n_bodies))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x[:, -1:, :])
    return logits[:, 0, :], cache
