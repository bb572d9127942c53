#!/usr/bin/env python3
"""Readings that the training cell's limits are set from, one process:

    python3 benchmarks/chip/calibrate_train.py --workload mamba2-780m.train \\
        --seeds 1 2 3 [--control] [--out FILE]

For each seed: the program's first steps through ``Trainer.run`` (as
the cell's set-up drives them) against the plain float32 reference,
and, with ``--control``, the reference put in the program's place in
the precision below the configuration's (fp8 matrix products) and with
half of each batch left out.  Prints one JSON line per seed with the
gaps of each (``chipbench.train.gaps``), the raw readings they come
from (every step's loss, every leaf's norms) and the seconds each
took.  Runs on the chip it is started on, at the cell's own sizes.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mamba2-780m.train")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chipbench import harness as H
    from chipbench import train as TR

    spec = H.resolve(H.load_benchmark(), args.workload)
    cfg, mix = spec["config"], spec["mix"]
    H.check_devices(spec["cell"]["chips"])
    H.enable_compile_cache()
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    steps = mix["check_steps"]
    dead = cfg["check"]["dead_leaf_frac"]
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        run = H.Run(workload=args.workload, config=cfg, mix=mix, seed=seed,
                    seconds=0, trace=False)
        t0 = time.perf_counter()
        line = {"seed": seed, "gaps": {}, "readings": {}, "seconds": {}}
        ref = TR.reference_readings(run, steps)
        line["readings"]["reference"] = ref
        line["seconds"]["reference"] = time.perf_counter() - t0
        others = {}
        t1 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="chipbench_cal_") as wd:
            trainer = TR.build_trainer(run, wd)
            try:
                others["program"] = TR.program_readings(
                    trainer, steps, cfg["optimizer"]["b1"])
            finally:
                trainer.close()
            del trainer
            gc.collect()
        line["seconds"]["program"] = time.perf_counter() - t1
        if args.control:
            t1 = time.perf_counter()
            others["control_fp8"] = TR.reference_readings(run, steps,
                                                          quant="fp8")
            line["seconds"]["control_fp8"] = time.perf_counter() - t1
            others["half_batch"] = TR.reference_readings(
                run, steps, rows=mix["global_batch"] // 2)
        for name, rd in others.items():
            line["gaps"][name] = TR.gaps(rd, ref, dead)
            line["readings"][name] = rd
        line["seconds"]["all"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
