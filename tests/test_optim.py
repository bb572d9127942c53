"""Optimizer + distributed-optimization tricks: AdamW descent, cosine
schedule, clipping; error-feedback int8 gradient compression across a
shard_map DP axis (convergence parity with exact psum)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import adamw

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def quad_loss(p):
    return jnp.sum((p["w"] - 3.0) ** 2) + jnp.sum((p["b"] + 1.0) ** 2)


def test_adamw_descends_quadratic():
    params = {"w": jnp.zeros(4), "b": jnp.zeros(3)}
    state = adamw.init(params)
    for _ in range(200):
        grads = jax.grad(quad_loss)(params)
        params, state, gnorm = adamw.update(grads, state, params, lr=5e-2,
                                            weight_decay=0.0)
    assert quad_loss(params) < 1e-2
    assert int(state.step) == 200


def test_cosine_schedule_shape():
    lrs = [float(adamw.cosine_lr(jnp.asarray(s), peak=1.0, warmup=10,
                                 total=100)) for s in range(100)]
    assert lrs[0] < lrs[9] <= 1.0 + 1e-6          # warmup rises
    assert abs(max(lrs) - 1.0) < 0.11             # hits peak
    assert lrs[-1] < 0.2                          # decays
    assert lrs[-1] >= 0.099                       # floor


def test_clip_by_global_norm():
    g = {"a": jnp.full(4, 100.0)}
    clipped, gn = adamw.clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(200.0)
    norm = float(jnp.linalg.norm(clipped["a"]))
    assert norm == pytest.approx(1.0, rel=1e-3)


def test_compressed_psum_matches_exact_within_tolerance():
    """int8 EF compression: single-step error bounded; multi-step error
    feedback keeps the *accumulated* descent direction unbiased."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_host_mesh
        from repro.optim.compress import compressed_psum, plain_psum_mean

        mesh = make_host_mesh(4, 1)                  # DP over "data"
        key = jax.random.PRNGKey(0)
        g_global = jax.random.normal(key, (4, 64))   # per-device grads

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")), check_vma=False)
        def step(g, e):
            gq, e = compressed_psum({"g": g}, {"g": e}, "data")
            return gq["g"], e["g"]

        exact = np.asarray(g_global.mean(0))
        err = jnp.zeros((4, 64))
        acc_q = np.zeros(64)
        for it in range(8):
            gq, err = step(g_global, err)
            gq0 = np.asarray(gq[0:1]).reshape(-1)
            acc_q += gq0
            # single-step quantization error is bounded by the int8 grid
            assert np.max(np.abs(gq0 - exact)) < 0.05, it
        # with error feedback the mean of quantized steps converges
        assert np.max(np.abs(acc_q / 8 - exact)) < 0.02
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout


def test_compression_ratio_is_8x():
    """int8 payload is 4x smaller than f32 per element (8x vs f64) —
    verify the wire-size arithmetic used in DESIGN.md."""
    from repro.optim.compress import _quantize
    g = jnp.linspace(-1, 1, 1024)
    q, scale = _quantize(g)
    assert q.dtype == jnp.int8 and q.nbytes * 4 == g.nbytes
    deq = q.astype(jnp.float32) * scale
    assert float(jnp.max(jnp.abs(deq - g))) < 1.0 / 127
