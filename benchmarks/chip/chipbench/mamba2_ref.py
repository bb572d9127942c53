"""Plain reference of the Mamba-2 training step: loss, gradients and
AdamW in straightforward ``jax.numpy`` and float32, with every matrix
product at ``Precision.HIGHEST``.

It imports nothing of the program.  It makes its own weights from the
seed (the program's convention: each leaf drawn from
``fold_in(PRNGKey(seed), crc32(path))``) and its own batches (the
program's data convention: Philox keyed by the seed, counter
``[0, 0, host, step]``), so a program that drifts from either reads as
a gap here.

The selective-state-space layer is computed in its quadratic (dual)
form over the whole sequence: ``y_t = sum_{s<=t} (C_t . B_s)
exp(cum_t - cum_s) dt_s x_s + D x_t`` with ``cum`` the running sum of
``dt * A``.  That is the Mamba-2 paper's SSD identity without the
chunking the program uses, so chunk boundaries and the carried state
are checked against an independent path.

``quant="fp8"`` is the control: every matrix product's operands are
rounded to float8_e4m3fn with one scale per tensor, and the gradient
each product's backward takes in to float8_e5m2, the step below the
bfloat16 the configuration computes in.  ``rows`` takes the loss over the first ``rows`` rows of
each batch only, the mean over them: the half-batch fault.
"""

from __future__ import annotations

import zlib
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .flops import mamba2_layout

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


# ------------------------------------------------------------------ data
def batch_at(seed: int, vocab: int, seq_len: int, global_batch: int,
             n_hosts: int, step: int) -> Dict[str, np.ndarray]:
    """The global batch of ``step``: each host's rows in host order."""
    local = global_batch // n_hosts
    parts = []
    for h in range(n_hosts):
        rng = np.random.Generator(np.random.Philox(
            key=seed, counter=[0, 0, h, step]))
        parts.append(rng.integers(0, vocab, (local, seq_len + 1),
                                  dtype=np.int64).astype(np.int32))
    toks = np.concatenate(parts)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# --------------------------------------------------------------- weights
def _walk(layout, f, path=""):
    if isinstance(layout, dict):
        return {k: _walk(v, f, f"{path}/{k}") for k, v in layout.items()}
    return f(path, layout)


def init_params(m: Dict, seed: int):
    """Float32 weights of the model from ``seed`` (jit this)."""
    root = jax.random.PRNGKey(seed)

    def leaf(path, spec):
        shape, init = spec
        if init == "ones":
            return jnp.ones(shape, jnp.float32)
        if init == "A_log":
            return jnp.broadcast_to(
                jnp.log(jnp.linspace(1.0, 8.0, shape[-1],
                                     dtype=jnp.float32)), shape)
        if init == 0.0:
            return jnp.zeros(shape, jnp.float32)
        key = jax.random.fold_in(root, zlib.crc32(path.encode()) % (1 << 31))
        return jax.random.normal(key, shape, jnp.float32) * init

    return _walk(mamba2_layout(m), leaf)


# --------------------------------------------------------------- forward
def _fp8(x, dtype, top):
    """``x`` rounded to ``dtype`` under one scale for the whole tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _q8(x):
    """Forward operand in float8_e4m3fn, straight through backward."""
    return x + jax.lax.stop_gradient(_fp8(x, jnp.float8_e4m3fn, _E4M3_MAX)
                                     - x)


@jax.custom_vjp
def _g8(y):
    """Identity forward; the gradient a product's backward takes in is
    rounded to float8_e5m2."""
    return y


def _g8_fwd(y):
    return y, None


def _g8_bwd(_, g):
    return (_fp8(g, jnp.float8_e5m2, _E5M2_MAX),)


_g8.defvjp(_g8_fwd, _g8_bwd)


def _matmul(quant: Optional[str]):
    """``einsum(spec, a, b)`` at the highest precision, or in float8
    (forward and backward) for the control."""
    if quant is None:
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    return lambda spec, a, b: _g8(jnp.einsum(spec, _q8(a), _q8(b),
                                             precision=HIGHEST))


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w)


def forward_loss(params, tokens, labels, m: Dict, quant=None, rows=None):
    """Mean next-token cross entropy of the model on one batch."""
    ein = _matmul(quant)

    D, eps = m["d_model"], m.get("norm_eps", 1e-6)
    I = m["ssm_expand"] * D
    N, G, K = m["ssm_state"], m["ssm_groups"], m["ssm_conv"]
    P = m["ssm_head_dim"]
    H = I // P
    V = m["vocab_size"]
    if rows is not None:
        tokens, labels = tokens[:rows], labels[:rows]
    Bsz, S = tokens.shape

    def ssd(p, h):
        zxbcdt = ein("bsd,de->bse", h, p["w_in"])
        z = zxbcdt[..., :I]
        xbc = zxbcdt[..., I:2 * I + 2 * G * N]
        dt = zxbcdt[..., 2 * I + 2 * G * N:]
        pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = sum(pad[:, k:k + S, :] * p["conv_w"][:, k] for k in range(K))
        xbc = jax.nn.silu(xbc + p["conv_b"])
        x = xbc[..., :I].reshape(Bsz, S, H, P)
        Bm = xbc[..., I:I + G * N].reshape(Bsz, S, G, N)
        Cm = xbc[..., I + G * N:].reshape(Bsz, S, G, N)
        dt = jax.nn.softplus(dt + p["dt_bias"])              # (B,S,H)
        A = -jnp.exp(p["A_log"])                             # (H,)
        cum = jnp.cumsum(dt * A, axis=1)                     # (B,S,H)
        causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(
            causal, cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))
        cb = ein("btgn,bsgn->bgts", Cm, Bm)                  # (B,G,t,s)
        cb = jnp.repeat(cb, H // G, axis=1)                  # (B,H,t,s)
        w = cb.transpose(0, 2, 3, 1) * decay * dt[:, None, :, :]
        y = ein("btsh,bshp->bthp", w, x)
        y = y + x * p["skip_D"][None, None, :, None]
        y = y.reshape(Bsz, S, I) * jax.nn.silu(z)
        y = _rms(y, p["w_norm"], eps)
        return ein("bsi,id->bsd", y, p["w_out"])

    @jax.checkpoint
    def layer(x, lp):
        return x + ssd(lp["ssm"], _rms(x, lp["ln1"], eps)), None

    x = params["embed"][tokens]
    x, _ = jax.lax.scan(layer, x, params["body"]["slot0"])
    x = _rms(x, params["final_norm"], eps)
    logits = ein("bsd,vd->bsv", x, params["embed"])
    logits = jnp.where(jnp.arange(logits.shape[-1]) < V, logits, -1e30)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


# ------------------------------------------------------------- optimizer
def lr_at(step, opt: Dict):
    """Cosine schedule with linear warm-up, at 0-based update ``step``."""
    peak, warmup, total = opt["peak_lr"], opt["warmup"], opt["total_steps"]
    floor = opt.get("floor_frac", 0.1)
    warm = peak * (step + 1) / max(warmup, 1)
    t = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = peak * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warmup, warm, cos)


def leaf_norms(tree) -> Dict[str, jnp.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v)))
            for k, v in flat}


def make_step(m: Dict, opt: Dict, quant=None, rows=None):
    """One AdamW step with global-norm clipping, as the configuration
    states it.  Returns (params, mu, nu, loss, per-leaf norms of the
    gradient the optimizer got)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]

    def step(params, mu, nu, tokens, labels, k):
        loss, g = jax.value_and_grad(forward_loss)(params, tokens, labels, m,
                                                   quant, rows)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["max_grad_norm"] / (gn + 1e-9)), g)
        t = (k + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
        lr = lr_at(k, opt)

        def upd(p, a, c):
            u = (a / (1 - b1 ** t)) / (jnp.sqrt(c / (1 - b2 ** t)) + eps)
            return p - lr * (u + opt["weight_decay"] * p)

        return (jax.tree.map(upd, params, mu, nu), mu, nu, loss,
                leaf_norms(g))

    return jax.jit(step, donate_argnums=(0, 1, 2))


def readings(m: Dict, opt: Dict, seed: int, batches: List[Dict],
             quant=None, rows=None) -> Dict:
    """Drive the reference through ``len(batches)`` steps from the
    seed's weights.  Returns the loss of every step, the per-leaf norms
    of the first step's gradient (as the optimizer got it) and the
    per-leaf norms of the parameters' change over all the steps.  The
    device holds one copy of the weights, AdamW's two moments and one
    gradient at a time; the change is taken against a second
    initialisation once the moments are freed."""
    # the seed is an argument, not a constant of the program, so one
    # compiled program serves every seed
    init = jax.jit(lambda s: init_params(m, s))
    key = jnp.asarray(seed, jnp.int32)
    params = init(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    step = make_step(m, opt, quant, rows)
    losses, grad = [], None
    for k, b in enumerate(batches):
        params, mu, nu, loss, gnorms = step(
            params, mu, nu, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]),
            jnp.asarray(k, jnp.int32))
        losses.append(float(loss))
        if grad is None:
            grad = {k_: float(v) for k_, v in gnorms.items()}
    del mu, nu
    p0 = init(key)
    change = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
    moved = {k: float(v) for k, v in change(params, p0).items()}
    del params, p0
    return {"losses": losses, "grad": grad, "change": moved}
