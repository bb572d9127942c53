"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Two artifacts per cell:

1. FULL compile (the dry-run gate): the production-depth step function
   must lower+compile on the 16x16 single-pod mesh and the 2x16x16
   multi-pod mesh.  Yields memory_analysis + the collective schedule.

2. Differential probes (single-pod roofline): XLA cost_analysis counts
   while-loop bodies ONCE, so scanned layers/microbatches/attention
   blocks are undercounted.  We therefore compile reduced-depth,
   reduced-batch variants (inner loops unrolled) and solve the
   per-device linear cost model

       f(bodies b, B_local, micros M) =
           opt(b) + M*g(b) + B_local*(e + b*c)

   with opt(b) = o0 + b*o1 (once per step: optimizer, grad init),
   g(b) = g0 + b*g1 (once per MICROBATCH, batch-independent: FSDP
   weight all-gathers — g ~ 0 when XLA hoists them out of the loop),
   and e + b*c per local batch row (fwd+bwd compute/activations).
   Train cells use 6 probes ((b,B) in PROBE_BODIES x {1,2} at M=1,
   plus two M=2 points); serve cells use the 4-point M=1 model.  The
   probe depths are {2,3} bodies, NOT {1,2}: a single-body graph
   compiles to a qualitatively different schedule (whole-graph fusion,
   different all-gather placement), which poisons the linear fit —
   both probe points must sit in the multi-layer regime.  Every number
   still derives from a compiled artifact (assignment: cost_analysis +
   as_text); tests/test_roofline.py validates the model against a fully
   unrolled small config.

Usage:
    python -m repro.launch.dryrun --arch starcoder2-3b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --skip-existing
"""

import argparse
import json
import os
import subprocess
import sys
import time

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")
METRICS = ("flops", "bytes", "coll")


def _build_jitted(cfg, shape, rules, n_micro, attn_impl="blockwise",
                  param_dtype=None, remat_policy="dots"):
    import jax

    from ..runtime import specs as SP
    from ..runtime.steps import (TrainHParams, build_decode_step,
                                 build_prefill_step, build_train_step)

    if shape.kind == "train":
        hp = TrainHParams(n_micro=n_micro, attn_impl=attn_impl,
                          remat_policy=remat_policy)
        step = build_train_step(cfg, hp)
        args, in_sh, out_sh = SP.train_cell(cfg, shape, rules)
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                       donate_argnums=(0, 1)), args
    if shape.kind == "prefill":
        step = build_prefill_step(cfg, max_seq=shape.seq_len,
                                  attn_impl=attn_impl)
        args, in_sh, out_sh = SP.prefill_cell(cfg, shape, rules,
                                              param_dtype)
        return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh), args
    step = build_decode_step(cfg)
    args, in_sh, out_sh = SP.decode_cell(cfg, shape, rules, param_dtype)
    return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                   donate_argnums=(1,)), args


def _compile_and_measure(cfg, shape, rules, mesh, n_micro,
                         attn_impl="blockwise", param_dtype=None,
                         remat_policy="dots"):
    from .hlo_analysis import collective_bytes, total_collective_bytes

    jitted, args = _build_jitted(cfg, shape, rules, n_micro, attn_impl,
                                 param_dtype, remat_policy)
    t0 = time.time()
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    per_coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(total_collective_bytes(per_coll)),
        "per_coll": per_coll,
        "compiled": compiled,
        "wall_s": time.time() - t0,
    }


def _reduced(cfg, k):
    """Config with k scan bodies (and k encoder layers for enc-dec)."""
    kw = {"n_layers": k * cfg.scan_period}
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = k
    return cfg.replace(**kw)


#: probe depths for the differential solve — both in the multi-layer
#: regime (see the module docstring for why b=1 is excluded)
PROBE_BODIES = (2, 3)


def solve_probe_model(pts, metric):
    """Fit f(b, B, M) = opt(b) + M*g(b) + B*(e + b*c) to the probe
    compiles in ``pts`` (keyed ``(bodies, B_local, M)``), for one
    metric.  Returns the coefficient dict {o0, o1, g0, g1, e, c}."""
    b1, b2 = PROBE_BODIES
    db = b2 - b1
    f11, f21 = pts[(b1, 1, 1)][metric], pts[(b2, 1, 1)][metric]
    f12, f22 = pts[(b1, 2, 1)][metric], pts[(b2, 2, 1)][metric]
    c = (f22 - f21 - f12 + f11) / db
    e = f12 - f11 - b1 * c
    a1 = (f21 - f11) / db - c       # = o1 + g1 (one micro at M=1)
    a0 = f11 - b1 * a1 - e - b1 * c  # = o0 + g0
    g0 = g1 = 0.0
    if (b1, 2, 2) in pts:
        gb1 = pts[(b1, 2, 2)][metric] - f12     # g(b1) = g0 + b1*g1
        gb2 = pts[(b2, 2, 2)][metric] - f22     # g(b2) = g0 + b2*g1
        g1 = (gb2 - gb1) / db
        g0 = gb1 - b1 * g1
    return {"o0": a0 - g0, "o1": a1 - g1, "g0": g0, "g1": g1,
            "e": e, "c": c}


def predict_probe_model(coeffs, bodies, b_local, n_micro=1):
    """Evaluate the fitted per-device cost model at production depth."""
    return (coeffs["o0"] + bodies * coeffs["o1"]
            + n_micro * (coeffs["g0"] + bodies * coeffs["g1"])
            + b_local * (coeffs["e"] + bodies * coeffs["c"]))


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             overrides_json: str = "", tag: str = "",
             probes: bool = True, attn_impl: str = "blockwise",
             n_micro: int = 0, serve_dtype: str = "",
             cfg_overrides: str = "", remat_policy: str = "dots") -> dict:
    import jax

    from .. import configs as C
    from ..models import layers as ML
    from ..models import ssd as MS
    from ..models import transformer as T
    from ..models.config import SHAPES, shape_applicable
    from ..runtime import specs as SP
    from ..runtime.sharding import use_rules
    from . import mesh as M
    from .hlo_analysis import roofline

    cfg = C.get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**json.loads(cfg_overrides))
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
              "tag": tag, "status": "skip", "reason": reason}
    os.makedirs(out_dir, exist_ok=True)
    fn = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}"
                      + (f"__{tag}" if tag else "") + ".json")
    if not ok:
        with open(fn, "w") as fh:
            json.dump(result, fh, indent=1)
        return result

    mesh = M.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    overrides = json.loads(overrides_json) if overrides_json else None
    rules = SP.cell_rules(cfg, shape, mesh, overrides)
    dp = SP._axis_size(mesh, rules.rules["batch"])
    n_micro_full = max(1, shape.global_batch // max(dp, 1)) \
        if shape.kind == "train" else 1
    if n_micro:
        n_micro_full = n_micro
    param_dtype = None
    if serve_dtype:
        import jax.numpy as jnp
        param_dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[serve_dtype]
    n_bodies = cfg.n_bodies

    # ---------------------------------------------------- 1. full compile
    with use_rules(rules):
        full = _compile_and_measure(cfg, shape, rules, mesh, n_micro_full,
                                    attn_impl, param_dtype, remat_policy)
    compiled = full.pop("compiled")
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception:  # noqa: BLE001
        mem_info = {}

    result.update({
        "status": "ok", "n_devices": n_dev, "dp": dp,
        "n_micro": n_micro_full, "n_bodies": n_bodies,
        "compile_wall_s": round(full["wall_s"], 1),
        "raw": {k: full[k] for k in METRICS},
        "collectives_full": full["per_coll"],
        "memory": mem_info,
    })

    # ------------------------------------------------ 2. roofline probes
    if probes:
        import dataclasses

        ML.UNROLL_BLOCKS = True
        MS.UNROLL_CHUNKS = True
        T.UNROLL_LAYERS = True
        # per-device local batch of the production cell
        b_loc_full = max(1, shape.global_batch // max(dp, 1))
        try:
            pts = {}
            for k in PROBE_BODIES:    # bodies
                for bl in (1, 2):     # local batch rows per device
                    pshape = dataclasses.replace(
                        shape, global_batch=max(dp, 1) * bl)
                    with use_rules(rules):
                        pts[(k, bl, 1)] = _compile_and_measure(
                            _reduced(cfg, k), pshape, rules, mesh, 1,
                            attn_impl, param_dtype, remat_policy)
            if shape.kind == "train" and n_micro_full > 1:
                pshape = dataclasses.replace(shape,
                                             global_batch=max(dp, 1) * 2)
                for k in PROBE_BODIES:  # measure the per-micro term g(b)
                    with use_rules(rules):
                        pts[(k, 2, 2)] = _compile_and_measure(
                            _reduced(cfg, k), pshape, rules, mesh, 2,
                            attn_impl, param_dtype, remat_policy)
        finally:
            ML.UNROLL_BLOCKS = False
            MS.UNROLL_CHUNKS = False
            T.UNROLL_LAYERS = False

        corrected = {}
        coeffs = {}
        for m in METRICS:
            coeffs[m] = solve_probe_model(pts, m)
            corrected[m] = predict_probe_model(coeffs[m], n_bodies,
                                               b_loc_full, n_micro_full)
        result["probe_walls_s"] = {str(k): round(v["wall_s"], 1)
                                   for k, v in pts.items()}
        result["probe_coeffs"] = coeffs
        result["corrected"] = corrected
        flops, bytes_, coll = (corrected[m] for m in METRICS)
    else:
        flops, bytes_, coll = (full[m] for m in METRICS)

    # useful-model-FLOPs accounting (per step, global)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    per_tok = T.model_flops_per_token(cfg)           # 6·N_active
    if shape.kind != "train":
        per_tok /= 3.0                                # 2·N_active (no bwd)
    model_flops = per_tok * tokens

    rf = roofline(flops, bytes_, coll, peak_flops=M.PEAK_FLOPS_BF16,
                  hbm_bw=M.HBM_BW, ici_bw=M.ICI_BW)
    result.update({
        "flops_per_device": flops, "bytes_per_device": bytes_,
        "collective_bytes_per_device": coll,
        "model_flops_global": model_flops,
        "hlo_flops_global": flops * n_dev,
        "model_flops_ratio": (model_flops / (flops * n_dev)
                              if flops else None),
        **rf,
    })
    with open(fn, "w") as fh:
        json.dump(result, fh, indent=1)
    return result


def main() -> int:
    # a CPU compile tool by design: 512 host devices stand in for the
    # production meshes; set here, before jax initializes, so importing
    # this module changes nobody's devices
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probes", action="store_true")
    ap.add_argument("--attn-impl", default="blockwise")
    ap.add_argument("--out", default=os.path.abspath(ARTIFACTS))
    ap.add_argument("--overrides", default="",
                    help="JSON dict of logical-rule overrides (perf exps)")
    ap.add_argument("--tag", default="", help="artifact suffix for perf exps")
    ap.add_argument("--n-micro", type=int, default=0,
                    help="override microbatch count (train cells)")
    ap.add_argument("--serve-dtype", default="",
                    help="param dtype for serve cells (bf16|f32)")
    ap.add_argument("--cfg-overrides", default="",
                    help="JSON dict applied via ModelConfig.replace")
    ap.add_argument("--remat-policy", default="dots",
                    choices=["dots", "none", "everything"])
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        from .. import configs as C
        from ..models.config import SHAPES
        failures = []
        for arch in C.list_archs():
            for shape in SHAPES:
                for mesh_kind in meshes:
                    fn = os.path.join(args.out,
                                      f"{arch}__{shape}__{mesh_kind}.json")
                    if args.skip_existing and os.path.exists(fn):
                        print(f"[skip] {arch} {shape} {mesh_kind}",
                              flush=True)
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", mesh_kind, "--out", args.out]
                    if mesh_kind == "multi" or args.no_probes:
                        cmd.append("--no-probes")  # roofline is single-pod
                    t0 = time.time()
                    print(f"[run ] {arch} {shape} {mesh_kind}", flush=True)
                    rc = subprocess.call(cmd, stdout=subprocess.DEVNULL)
                    print(f"       rc={rc} {time.time()-t0:.0f}s", flush=True)
                    if rc != 0:
                        failures.append((arch, shape, mesh_kind))
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    res = run_cell(args.arch, args.shape, meshes[0], args.out,
                   overrides_json=args.overrides, tag=args.tag,
                   probes=not args.no_probes, attn_impl=args.attn_impl,
                   n_micro=args.n_micro, serve_dtype=args.serve_dtype,
                   cfg_overrides=args.cfg_overrides,
                   remat_policy=args.remat_policy)
    if res.get("status") == "skip":
        print(f"SKIP {args.arch} {args.shape}: {res['reason']}")
        return 0
    print(json.dumps({k: v for k, v in res.items()
                      if k not in ("collectives_full", "memory", "raw")},
                     indent=1))
    print("memory:", res.get("memory"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
