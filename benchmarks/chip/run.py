#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the machine it is started on.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Run it from the root of a checkout, as the only JAX process on the
chip.  The cells are the ``workloads`` of ``BENCHMARK.json``.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``; ``compared`` comes last, each number the check
compared beside its limit, and the same numbers end standard error.

Without a TPU, with fewer chips than the cell asks for, or on a chip
that has no published peaks, it exits 1 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed at exit)")
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip, execute

    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), trace_dir=args.trace_dir)
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    except Exception:
        traceback.print_exc()
        return 1
    readings = result.pop("readings", None)
    if readings:
        print("readings " + json.dumps(readings, default=float),
              file=sys.stderr)
    print("seconds " + " ".join(f"{k}={v:.3f}"
                                for k, v in result["seconds"].items()),
          file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
