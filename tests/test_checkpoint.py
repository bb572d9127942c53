"""Fault tolerance: checkpoint/restore round-trip, async overlap, crash
+ restart resume, elastic resharding onto a different mesh."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.optim import adamw

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def small_state():
    cfg = C.get_smoke("granite-8b")
    params = T.init_params(cfg, seed=1)
    opt = adamw.init(params)
    return cfg, {"params": params, "opt": opt}


def test_save_restore_roundtrip(tmp_path, small_state):
    cfg, tree = small_state
    save_checkpoint(tree, 7, str(tmp_path), n_shards=3)
    assert latest_step(str(tmp_path)) == 7
    out = restore_checkpoint(tree, 7, str(tmp_path))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_checkpointer_overlap(tmp_path, small_state):
    _, tree = small_state
    ck = AsyncCheckpointer(str(tmp_path), n_shards=2)
    f1 = ck.submit(tree, 1)
    f2 = ck.submit(tree, 2)          # waits for f1 internally
    ck.close()
    assert f1.done() and f2.done()
    assert latest_step(str(tmp_path)) == 2


def test_restore_with_mesh_shardings(tmp_path, small_state):
    """Elastic path: checkpoint is mesh-agnostic; restore lands on the
    current (1x1) mesh with the logical rules applied."""
    from repro.runtime.elastic import reshard_state
    cfg, tree = small_state
    save_checkpoint(tree, 3, str(tmp_path), n_shards=2)
    out = restore_checkpoint(tree, 3, str(tmp_path))
    mesh = make_host_mesh(1, 1)
    params, opt, rules = reshard_state(cfg, out["params"], out["opt"], mesh)
    leaf = jax.tree.leaves(params)[0]
    assert leaf.sharding.mesh.shape == {"data": 1, "model": 1}


def test_plan_mesh_shapes():
    from repro.runtime.elastic import plan_mesh_shape
    assert plan_mesh_shape(256) == (16, 16)
    assert plan_mesh_shape(12) == (2, 4)      # degraded fleet -> 8 usable
    assert plan_mesh_shape(1) == (1, 1)


def test_crash_restart_resumes_exactly(tmp_path):
    """Train 6 steps with ckpt_every=3, 'crash', restart: the trainer
    resumes from step 3 with identical data (stateless pipeline) and the
    journals survive on disk."""
    script = textwrap.dedent("""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        from repro import configs as C
        from repro.runtime.train_loop import Trainer
        cfg = C.get_smoke("starcoder2-3b")
        phase = sys.argv[1]
        wd = sys.argv[2]
        t = Trainer(cfg, workdir=wd, global_batch=4, seq_len=16,
                    n_hosts=2, ckpt_every=3)
        if phase == "first":
            hist = t.run(4)          # crash after step 4 (ckpt at 3)
            t.ckpt.wait()
            print(json.dumps({"start": hist[0]["step"],
                              "end": hist[-1]["step"]}))
        else:
            assert t.step == 3, t.step
            hist = t.run(2)
            print(json.dumps({"start": hist[0]["step"],
                              "end": hist[-1]["step"],
                              "resumed_from": 3}))
        t.close()
    """)
    env = dict(os.environ, PYTHONPATH="src")
    wd = str(tmp_path / "run")
    r1 = subprocess.run([sys.executable, "-c", script, "first", wd],
                        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert '"end": 4' in r1.stdout
    r2 = subprocess.run([sys.executable, "-c", script, "second", wd],
                        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert '"start": 4' in r2.stdout and '"end": 5' in r2.stdout
