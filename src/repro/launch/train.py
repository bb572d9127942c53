"""Training launcher.

On real hardware each host runs this entrypoint (jax.distributed
handles process groups); on CPU it drives reduced configs end-to-end
with the full LCAP tracking stack.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
        --smoke --steps 20 --workdir /tmp/run1
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--n-hosts", type=int, default=2)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate N host devices (sets XLA_FLAGS; must "
                         "be first jax use in the process)")
    ap.add_argument("--workdir", default="/tmp/repro_train")
    args = ap.parse_args()

    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")

    from .. import configs as C
    from ..runtime.train_loop import Trainer
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = C.get_smoke(args.arch) if args.smoke else C.get_config(args.arch)
    trainer = Trainer(cfg, workdir=args.workdir,
                      global_batch=args.global_batch, seq_len=args.seq_len,
                      n_hosts=args.n_hosts, ckpt_every=args.ckpt_every)
    hist = trainer.run(args.steps)
    trainer.ckpt.wait()
    rows = trainer.metrics[0].query(
        "SELECT COUNT(*), COUNT(DISTINCT type) FROM events")
    print(json.dumps({
        "arch": cfg.arch_id,
        "steps": [h["step"] for h in hist[-3:]],
        "loss_first": hist[0]["loss"], "loss_last": hist[-1]["loss"],
        "metrics_rows": rows[0][0], "event_types": rows[0][1],
        "stragglers": sorted(trainer.straggler.flagged),
        "last_ckpt": trainer.committer.latest_committed(),
    }, indent=1))
    trainer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
