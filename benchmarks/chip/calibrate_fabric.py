#!/usr/bin/env python3
"""A fabric cell's check against faults planted under its timed path,
at the cell's own size, in one process on the chip:

    python3 benchmarks/chip/calibrate_fabric.py --workload mdtest.steady \\
        --seeds 1 2 3 --faults none alter drop_half --seconds 5

For each seed and fault (``none`` is the sound run), one run of the
cell with a window of ``--seconds``; prints one JSON line with
``correct`` and every number compared.  ``--drain-timeout`` bounds the
wait after the window (a fault that loses records never drains).
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mdtest.steady")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="+", default=["none"])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--drain-timeout", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chipbench import fabric
    from chipbench import harness as H
    from chipbench.spans import CompileClock

    spec = H.resolve(H.load_benchmark(), args.workload)
    devices, peaks = H.check_devices(spec["cell"]["chips"])
    H.enable_compile_cache()
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    mix = copy.deepcopy(spec["mix"])
    if args.drain_timeout is not None:
        mix["drain_timeout_s"] = args.drain_timeout
    clock = CompileClock()
    out = open(args.out, "a") if args.out else None
    for seed in args.seeds:
        for fault in args.faults:
            run = H.Run(workload=args.workload, config=spec["config"],
                        mix=mix, seed=seed, seconds=args.seconds,
                        trace=False, clock=clock, devices=devices[:1],
                        peaks=peaks, t_process_wall=time.time(),
                        fault=None if fault == "none" else fault)
            res = fabric.run(run)
            line = {"seed": seed, "fault": fault, "correct": res["correct"],
                    "compared": {k: v["value"]
                                 for k, v in res["compared"].items()},
                    "records_per_s": res["end_to_end"]["records_per_s"]}
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
