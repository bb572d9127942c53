"""Distribution: logical rules, sharded train step on a small host
mesh, SSD block vs sequential reference, head padding correctness."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.runtime.sharding import DEFAULT_RULES, LogicalRules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rules_spec_no_duplicate_mesh_axes():
    mesh = make_host_mesh(1, 1)
    rules = LogicalRules(mesh)
    spec = rules.spec(("vocab", "mlp"))     # both map to "model"
    assert list(spec) == ["model", None]    # second use dropped


def test_multipod_rules_batch_spans_pod_and_data():
    import numpy as _np
    devs = _np.array(jax.devices()[:1]).reshape(1, 1, 1)
    mesh = jax.sharding.Mesh(devs, ("pod", "data", "model"))
    rules = LogicalRules(mesh)
    assert rules.rules["batch"] == ("pod", "data")


def test_sharded_train_step_runs_on_host_mesh():
    """Lower + run one real train step on a 2x2 host-device mesh; the
    same code path the production mesh uses (pjit, rules, remat)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro import configs as C
        from repro.launch.mesh import make_host_mesh
        from repro.models.config import ShapeConfig
        from repro.runtime import specs as SP
        from repro.runtime.sharding import use_rules
        from repro.runtime.steps import TrainHParams, build_train_step
        from repro.models import transformer as T
        from repro.optim import adamw

        cfg = C.get_smoke("qwen2.5-14b")   # qkv-bias + non-div heads
        shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
        mesh = make_host_mesh(2, 2)
        rules = SP.cell_rules(cfg, shape, mesh)
        with use_rules(rules):
            step = build_train_step(cfg, TrainHParams(n_micro=2,
                                                      attn_impl="blockwise"))
            args, in_sh, out_sh = SP.train_cell(cfg, shape, rules)
            jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            params = T.init_params(cfg, 0)
            opt = adamw.init(params)
            params = jax.tree.map(jax.device_put, params, in_sh[0])
            opt = jax.tree.map(jax.device_put, opt, in_sh[1])
            rng = np.random.RandomState(0)
            batch = {"tokens": jnp.asarray(
                         rng.randint(0, cfg.vocab_size, (4, 16)), jnp.int32),
                     "labels": jnp.asarray(
                         rng.randint(0, cfg.vocab_size, (4, 16)), jnp.int32)}
            with mesh:
                p2, o2, m = jitted(params, opt, batch)
        assert np.isfinite(float(m["loss"])), m
        # params stayed sharded per the rules
        leaf = jax.tree.leaves(p2)[0]
        assert leaf.sharding.mesh.shape == {"data": 2, "model": 2}
        print("LOSS", float(m["loss"]))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "LOSS" in r.stdout


def test_sharded_equals_unsharded_loss():
    """The sharded (2x2) loss equals the single-device loss — sharding
    must not change numerics."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs as C
        from repro.launch.mesh import make_host_mesh
        from repro.models.config import ShapeConfig
        from repro.runtime import specs as SP
        from repro.runtime.sharding import use_rules
        from repro.models import transformer as T

        cfg = C.get_smoke("granite-8b")
        params = T.init_params(cfg, 0)
        rng = np.random.RandomState(1)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 16)),
                             jnp.int32)
        labels = jnp.roll(tokens, -1, 1)

        ref, _ = jax.jit(lambda p: T.loss_fn(p, cfg, tokens, labels))(params)

        mesh = make_host_mesh(2, 2)
        shape = ShapeConfig("t", 16, 4, "train")
        rules = SP.cell_rules(cfg, shape, mesh)
        with use_rules(rules), mesh:
            shl, _ = jax.jit(lambda p: T.loss_fn(p, cfg, tokens, labels))(params)
        print("DIFF", abs(float(ref) - float(shl)))
        assert abs(float(ref) - float(shl)) < 5e-2
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]


def test_head_padding_preserves_gqa_semantics():
    """Padded-head attention == unpadded attention for awkward head
    counts (24, 40, 12 q-heads vs tp=16)."""
    from repro.models import layers as L
    from repro.runtime.sharding import use_rules

    class FakeRules:
        rules = {"heads": "model"}
        mesh = None

        def sharding(self, axes):
            raise AssertionError("lshard must not be called without mesh")

    key = jax.random.PRNGKey(0)
    for H, KV in ((24, 2), (40, 8), (12, 12)):
        q = jax.random.normal(key, (2, 8, H, 16))
        k = jax.random.normal(key, (2, 8, KV, 16))
        v = jax.random.normal(key, (2, 8, KV, 16))
        q2, k2, v2, H0 = L.pad_heads_for_tp(q, k, v)   # tp=1: no-op
        assert q2.shape[2] == H and H0 == H
    # simulate tp=16 via monkeypatched axis_size
    import repro.models.layers as ML
    import repro.runtime.sharding as SH
    orig = ML.axis_size
    ML.axis_size = lambda name: 16 if name == "heads" else 1
    try:
        pos = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))
        for H, KV in ((24, 2), (40, 8), (12, 12)):
            q = jax.random.normal(key, (2, 8, H, 16))
            k = jax.random.normal(key, (2, 8, KV, 16))
            v = jax.random.normal(key, (2, 8, KV, 16))
            q2, k2, v2, H0 = ML.pad_heads_for_tp(q, k, v)
            assert q2.shape[2] % 16 == 0 and q2.shape[2] % k2.shape[2] == 0
            from repro.models.config import ModelConfig
            cfg = ModelConfig(arch_id="t", family="dense", n_layers=1,
                              d_model=H * 16, n_heads=H, n_kv_heads=KV,
                              d_ff=32, vocab_size=8)
            ref = ML.attention_core_naive(q, k, v, pos, pos, causal=True)
            out = ML.run_attention(q, k, v, pos, pos, cfg, causal=True)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
    finally:
        ML.axis_size = orig


def test_ssd_scan_matches_sequential_reference():
    """Chunked SSD == naive per-token recurrence."""
    from repro.models.ssd import ssd_scan
    B, S, H, P, G, N = 2, 24, 4, 8, 2, 6
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 5)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))

    y, fin = ssd_scan(xh, dt, A, Bm, Cm, chunk=8)

    # sequential oracle
    hpg = H // G
    state = np.zeros((B, H, P, N))
    ys = np.zeros((B, S, H, P))
    for t in range(S):
        for h in range(H):
            g = h // hpg
            a = float(np.exp(np.asarray(dt[:, t, h] * A[h]))[0])
        for b in range(B):
            for h in range(H):
                g = h // hpg
                a = np.exp(float(dt[b, t, h]) * float(A[h]))
                state[b, h] = state[b, h] * a + float(dt[b, t, h]) * \
                    np.outer(np.asarray(xh[b, t, h]), np.asarray(Bm[b, t, g]))
                ys[b, t, h] = state[b, h] @ np.asarray(Cm[b, t, g])
    np.testing.assert_allclose(np.asarray(y), ys, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(fin), state, rtol=2e-3, atol=2e-3)


def test_ssd_scan_gradients_finite_over_a_long_chunk():
    """mamba2-780m's 256-token chunk: the decay exponent above the
    diagonal reaches hundreds, which overflows exp in f32; masking it
    must not turn the backward pass into NaN."""
    from repro.models.ssd import ssd_scan
    B, S, H, P, G, N = 1, 256, 2, 4, 1, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, G, N))
    Cm = jax.random.normal(ks[2], (B, S, G, N))
    dt = jnp.full((B, S, H), 0.7)
    A = -jnp.array([1.0, 8.0])          # the A_log init's range

    def loss(xh, dt, A, Bm, Cm):
        y, fin = ssd_scan(xh, dt, A, Bm, Cm, chunk=256)
        return jnp.sum(y) + jnp.sum(fin)

    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(xh, dt, A, Bm, Cm)
    for g in grads:
        assert bool(jnp.isfinite(g).all())
