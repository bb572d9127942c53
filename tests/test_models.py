"""Per-architecture smoke tests: reduced same-family configs, one
forward + one train-grad step + prefill/decode consistency on CPU.
Asserts output shapes and absence of NaNs (assignment requirement)."""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import (decode_step, forward, init_cache, init_params,
                          loss_fn, prefill, count_params)

ARCHS = C.list_archs()
B, S = 2, 16


def inputs_for(cfg, batch=B, seq=S):
    rng = np.random.RandomState(0)
    kw = {}
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    if cfg.is_encoder_decoder:
        kw["frames"] = jnp.asarray(
            rng.randn(batch, cfg.n_frames, cfg.d_model), jnp.float32)
    if cfg.n_image_patches:
        kw["image_embeds"] = jnp.asarray(
            rng.randn(batch, cfg.n_image_patches, cfg.d_model), jnp.float32)
    return tokens, kw


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = C.get_smoke(arch)
    params = init_params(cfg, seed=0)
    tokens, kw = inputs_for(cfg)
    logits, aux = forward(params, cfg, tokens, **kw)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    assert bool(jnp.isfinite(aux)), f"{arch}: non-finite aux loss"


@pytest.mark.parametrize("arch", ARCHS)
def test_train_grad_step(arch):
    cfg = C.get_smoke(arch)
    params = init_params(cfg, seed=0)
    tokens, kw = inputs_for(cfg)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss(p):
        l, _ = loss_fn(p, cfg, tokens, labels, **kw)
        return l

    val, grads = jax.value_and_grad(loss)(params)
    assert bool(jnp.isfinite(val))
    # a sensible init: loss near ln(vocab)
    assert float(val) < 2 * np.log(cfg.vocab_size) + 1
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves), arch
    # gradients actually flow to the embedding and deep layers
    gnorm = sum(float(jnp.abs(g).sum()) for g in leaves)
    assert gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    """decode(t) after prefill(0..t-1) must reproduce the full-sequence
    forward logits at position t."""
    cfg = C.get_smoke(arch)
    params = init_params(cfg, seed=0)
    tokens, kw = inputs_for(cfg, seq=S)
    full_logits, _ = forward(params, cfg, tokens, **kw)

    cut = S - 1
    last_logits, cache = prefill(params, cfg, tokens[:, :cut],
                                 max_seq=S, **kw)
    np.testing.assert_allclose(
        np.asarray(last_logits), np.asarray(full_logits[:, cut - 1]),
        rtol=0.12, atol=0.12)

    pos = jnp.full((B,), cut, jnp.int32)
    step_logits, cache = decode_step(params, cfg, tokens[:, cut:cut + 1],
                                     cache, pos)
    np.testing.assert_allclose(
        np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, cut]),
        rtol=0.12, atol=0.12)


@pytest.mark.parametrize("arch", ["granite-8b", "gemma2-9b", "mamba2-780m",
                                  "jamba-v0.1-52b"])
def test_blockwise_attention_matches_naive(arch):
    cfg = C.get_smoke(arch)
    params = init_params(cfg, seed=0)
    tokens, kw = inputs_for(cfg)
    naive, _ = forward(params, cfg, tokens, impl="naive", **kw)
    block, _ = forward(params, cfg, tokens, impl="blockwise", **kw)
    np.testing.assert_allclose(np.asarray(naive), np.asarray(block),
                               rtol=0.05, atol=0.05)


def test_full_configs_match_assignment():
    """The full configs carry the exact assigned hyperparameters."""
    expect = {
        "starcoder2-3b": (30, 3072, 24, 2, 12288, 49152),
        "gemma2-9b": (42, 3584, 16, 8, 14336, 256000),
        "granite-8b": (36, 4096, 32, 8, 14336, 49152),
        "qwen2.5-14b": (48, 5120, 40, 8, 13824, 152064),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49155),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, 768, 151936),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
    }
    for arch, (L_, D, H, KV, F, V) in expect.items():
        cfg = C.get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads) == \
            (L_, D, H, KV), arch
        ff = cfg.moe_d_ff if cfg.family == "moe" else cfg.d_ff
        assert ff == F and cfg.vocab_size == V, arch
    m = C.get_config("mamba2-780m")
    assert (m.n_layers, m.d_model, m.vocab_size, m.ssm_state) == \
        (48, 1536, 50280, 128)
    q = C.get_config("qwen3-moe-30b-a3b")
    assert (q.n_experts, q.top_k) == (128, 8)
    g = C.get_config("granite-moe-1b-a400m")
    assert (g.n_experts, g.top_k) == (32, 8)
    j = C.get_config("jamba-v0.1-52b")
    assert (j.n_experts, j.top_k, j.hybrid_period) == (16, 2, 8)


def test_param_counts_in_expected_range():
    """Sanity: derived param counts are in the ballpark the arch names
    claim (loose bounds; head_dim derives from the assigned table)."""
    expect_b = {"starcoder2-3b": (2.0, 4.5), "gemma2-9b": (7.5, 11.5),
                "granite-8b": (6.5, 9.5), "qwen2.5-14b": (11.0, 16.0),
                "mamba2-780m": (0.6, 1.0), "jamba-v0.1-52b": (38.0, 60.0)}
    for arch, (lo, hi) in expect_b.items():
        n = count_params(C.get_config(arch)) / 1e9
        assert lo <= n <= hi, f"{arch}: {n:.2f}B not in [{lo},{hi}]"


def test_ring_buffer_window_cache_multi_step():
    """Sliding-window decode with a ring cache of exactly `window` slots
    must reproduce full-sequence forward logits across several
    wrap-arounds."""
    cfg = C.get_smoke("gemma2-9b")          # window=8, alternating local
    params = init_params(cfg, seed=0)
    S_total = 24
    tokens, kw = inputs_for(cfg, seq=S_total)
    full_logits, _ = forward(params, cfg, tokens, **kw)

    cut = 4                                  # prefill shorter than window
    _, cache = prefill(params, cfg, tokens[:, :cut], max_seq=S_total, **kw)
    # local slots use ring buffers of size window (8), not S_total
    assert cache["slot0"]["k"].shape[2] == 8
    assert cache["slot1"]["k"].shape[2] == S_total
    for t in range(cut, S_total):            # 20 steps, 2+ wraps
        pos = jnp.full((B,), t, jnp.int32)
        step_logits, cache = decode_step(params, cfg, tokens[:, t:t + 1],
                                         cache, pos)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, t]),
            rtol=0.15, atol=0.15, err_msg=f"step {t}")


def test_init_params_same_in_every_process():
    """A seed gives the same weights in every process: the per-leaf key
    must not come from Python's salted ``hash``."""
    script = ("from repro import configs as C\n"
              "from repro.models import init_params\n"
              "import jax, numpy as np\n"
              "p = init_params(C.get_smoke('mamba2-780m'), seed=0)\n"
              "print(sum(float(np.abs(np.asarray(x, np.float64)).sum())\n"
              "          for x in jax.tree.leaves(p)).hex())\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sums = []
    for hash_seed in ("1", "2"):
        env = dict(os.environ, PYTHONPATH="src", PYTHONHASHSEED=hash_seed)
        r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                           text=True, env=env, cwd=root)
        assert r.returncode == 0, r.stderr[-3000:]
        sums.append(r.stdout.split()[-1])
    assert sums[0] == sums[1]


# ------------------------------------------------ names on device work
@pytest.fixture(scope="module")
def lowered_with_debug_info():
    """The mamba2 smoke config's train step and the routing twin,
    lowered with their debug info (locations carry the name scopes)."""
    from repro.kernels import stream_ops
    from repro.optim import adamw
    from repro.runtime.steps import TrainHParams, build_train_step

    cfg = C.get_smoke("mamba2-780m")
    params = init_params(cfg, seed=0)
    tokens = jnp.zeros((B, S), jnp.int32)
    step = jax.jit(build_train_step(cfg, TrainHParams(n_micro=1)))
    z = np.zeros(8, np.uint32)
    return {
        "train": step.lower(params, adamw.init(params),
                            {"tokens": tokens, "labels": tokens}
                            ).as_text(debug_info=True),
        "route": stream_ops._fid_slots_jit.lower(
            z, z, z, z, n_slots=64).as_text(debug_info=True)}


@pytest.mark.parametrize("program,scope", [
    ("train", "embed"), ("train", "ssd_block"), ("train", "head"),
    ("train", "loss"), ("train", "adamw"), ("route", "fid_slots")])
def test_named_scopes_reach_the_lowered_programs(lowered_with_debug_info,
                                                 program, scope):
    # "adamw/...", or "jvp(embed)/..." where the step differentiates
    assert re.search(rf'loc\("([^"]*[/(])?{scope}[/)]',
                     lowered_with_debug_info[program]), scope
