"""Validate the differential-probe cost model: the 4-point linear solve
(probe depths PROBE_BODIES, both in the multi-layer regime) must
reproduce the cost_analysis of a FULLY UNROLLED compile of the
production-depth config (all numbers from compiled artifacts)."""

import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_probe_extrapolation_matches_unrolled_compile():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses, json
        import jax
        from repro import configs as C
        from repro.launch.mesh import make_host_mesh
        from repro.models import layers as ML, ssd as MS, transformer as T
        from repro.models.config import ShapeConfig
        from repro.runtime import specs as SP
        from repro.runtime.sharding import use_rules
        from repro.launch.dryrun import (PROBE_BODIES, _compile_and_measure,
                                         _reduced, predict_probe_model,
                                         solve_probe_model)

        cfg = C.get_smoke("granite-8b").replace(n_layers=5)
        mesh = make_host_mesh(2, 2)
        shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
        rules = SP.cell_rules(cfg, shape, mesh)
        dp = 2

        ML.UNROLL_BLOCKS = MS.UNROLL_CHUNKS = T.UNROLL_LAYERS = True
        pts = {}
        for k in PROBE_BODIES:
            for bl in (1, 2):
                ps = dataclasses.replace(shape, global_batch=dp * bl)
                with use_rules(rules):
                    pts[(k, bl, 1)] = _compile_and_measure(
                        _reduced(cfg, k), ps, rules, mesh, 1, "blockwise")
        # ground truth: production depth (5 bodies), local batch 4,
        # fully unrolled -> cost_analysis is exact
        truth_shape = dataclasses.replace(shape, global_batch=dp * 4)
        with use_rules(rules):
            truth = _compile_and_measure(cfg, truth_shape, rules, mesh, 1,
                                         "blockwise")
        T.UNROLL_LAYERS = ML.UNROLL_BLOCKS = MS.UNROLL_CHUNKS = False

        out = {}
        for m in ("flops", "bytes", "coll"):
            pred = predict_probe_model(solve_probe_model(pts, m), 5, 4)
            out[m] = (pred, truth[m])
        print(json.dumps(out))
        for m, (pred, tru) in out.items():
            if tru == 0:
                assert abs(pred) < 1e6, (m, pred)
            else:
                rel = abs(pred - tru) / abs(tru)
                # at smoke scale (d_model=64) constant-size ops are
                # proportionally large; production cells are dominated by
                # the linear terms the model fits.  bytes-accessed gets a
                # wider band (CPU fusion choices vary with shapes and the
                # metric is only reported as an upper bound).
                tol = {"bytes": 0.20, "coll": 0.15}.get(m, 0.10)
                assert rel < tol, (m, pred, tru, rel)
        print("VALIDATED")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "VALIDATED" in r.stdout, r.stdout
