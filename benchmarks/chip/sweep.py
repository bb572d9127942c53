#!/usr/bin/env python3
"""Knee sweep of a fabric cell, in one process on the chip:

    python3 benchmarks/chip/sweep.py --workload mdtest.steady \\
        --rates 20000 30000 40000 --seconds 10 --seed 7 [--out FILE]

For each offered rate: the cell's set-up and warm-up at that rate, then
a window of ``--seconds``; prints one JSON line with the records fetched
per second, the journals' undelivered backlog at the window's start and
end, and the delivery p50 and p95, then one line ``{"knee": rate}``.  The knee
is the highest rate below the first whose backlog grows over the window
by more than 1% of the records offered in it.  No drain and no check follow each
window; the cells' own runs check their answers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def knee(lines, seconds: float, slack: float = 0.01):
    """The highest offered rate below the first whose backlog grew over
    its window (by more than ``slack`` of the records offered in it)."""
    best = None
    for ln in sorted(lines, key=lambda ln: ln["rate"]):
        if ln["backlog_end"] - ln["backlog_start"] > \
                slack * ln["rate"] * seconds:
            break
        best = ln["rate"]
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="mdtest.steady")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from chipbench import fabric
    from chipbench import harness as H
    from chipbench.spans import CompileClock

    spec = H.resolve(H.load_benchmark(), args.workload)
    devices, peaks = H.check_devices(spec["cell"]["chips"])
    H.enable_compile_cache()
    sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))
    clock = CompileClock()
    out = open(args.out, "a") if args.out else None
    lines = []
    for rate in args.rates:
        run = H.Run(workload=args.workload, config=spec["config"],
                    mix=spec["mix"], seed=args.seed, seconds=args.seconds,
                    trace=False, clock=clock, devices=devices[:1],
                    peaks=peaks, t_process_wall=time.time())
        res = fabric.run(run, rate=rate, drain=False)
        e2e = res["end_to_end"]
        line = {"rate": rate, "records_per_s": e2e["records_per_s"],
                "delivery_p50_ms": e2e["delivery_p50_ms"],
                "delivery_p95_ms": e2e["delivery_p95_ms"],
                "backlog_start": res["backlog_start"],
                "backlog_end": res["backlog_end"],
                "late": res["failed"], "attempted": res["attempted"],
                "window_compiles": res["window_compiles"]}
        lines.append(line)
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    text = json.dumps({"knee": knee(lines, args.seconds)})
    print(text, flush=True)
    if out:
        out.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
