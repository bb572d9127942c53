"""Driver of a training cell: the program's ``Trainer`` with tracking on,
driven synchronously as ``Trainer.run`` takes its steps.

Set-up builds one ``Trainer`` and drives it through its first steps
(compiling the step); the check reads them.  The window then runs the
same object one step at a time until the window's seconds are up;
``train_tokens_per_s`` is every token of every step completed in the
window, ``pump_consumers()`` included, over the whole window.

Once the window has closed, ``memory_peak_bytes`` has been read and the
trainer is freed, the plain reference (``mamba2_ref``) runs the same
first steps from the same seed, and the gaps decide ``correct``.
"""

from __future__ import annotations

import gc
import math
import sys
import tempfile
import time
from typing import Dict, List

import numpy as np

from . import mamba2_ref
from .flops import train_flops_per_token


# --------------------------------------------------------------- checking
def gaps(prog: Dict, ref: Dict, dead_frac: float) -> Dict[str, float]:
    """The numbers compared, each the worst over steps or leaves:

    - ``loss_gap``: |loss - reference loss| / reference loss, per step;
    - ``grad_gap``: per leaf, the gap between the norms of the first
      gradient the optimizer got, over the larger of the reference
      leaf's norm and the median leaf's;
    - ``change_gap``: the same for the parameters' change over the
      checked steps, over the leaves whose reference gradient is at
      least ``dead_frac`` of the median leaf's (a leaf whose gradient
      is nought to rounding moves under Adam by round-off alone).
    """
    if len(prog["losses"]) != len(ref["losses"]):
        loss_gap = math.inf
    else:
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad"]
    g_med = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(prog["grad"][k] - v) / max(v, g_med)
                   for k, v in g_ref.items())
    live = [k for k, v in g_ref.items() if v >= dead_frac * g_med]
    c_ref = ref["change"]
    c_med = float(np.median([c_ref[k] for k in live]))
    change_gap = max(abs(prog["change"][k] - c_ref[k]) / max(c_ref[k], c_med)
                     for k in live)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def compared(values: Dict[str, float], limits: Dict[str, float]) -> Dict:
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def within(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(values[k]) and values[k] <= limits[k]
               for k in limits)


# ----------------------------------------------------------------- driver
def _host_leaves(tree) -> Dict[str, np.ndarray]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(jax.device_get(v))
            for k, v in flat}


def _device_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: {
        jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v)))
        for k, v in jax.tree_util.tree_flatten_with_path(t)[0]})(tree)
    return {k: float(v) * scale for k, v in norms.items()}


def hparams(config: Dict):
    from repro.runtime.steps import TrainHParams

    opt = config["optimizer"]
    return TrainHParams(n_micro=1, peak_lr=opt["peak_lr"],
                        warmup=opt["warmup"], total_steps=opt["total_steps"],
                        weight_decay=opt["weight_decay"],
                        max_grad_norm=opt["max_grad_norm"],
                        attn_impl="naive", remat=config["remat"],
                        remat_policy=config["remat_policy"])


def model_config(config: Dict):
    from repro.models.config import ModelConfig

    return ModelConfig(**config["model"])


def build_trainer(run, workdir: str):
    from repro.runtime.train_loop import Trainer

    cfg, mix = run.config, run.mix
    trainer = Trainer(model_config(cfg), workdir=workdir, hp=hparams(cfg),
                      global_batch=mix["global_batch"],
                      seq_len=cfg["seq_len"], n_hosts=mix["hosts"],
                      ckpt_every=mix["ckpt_every"],
                      n_metrics_workers=mix["metrics_workers"],
                      seed=run.seed32)
    if run.fault:
        plant(trainer, run.fault)
    return trainer


def plant(trainer, fault: str) -> None:
    """Break the timed path underneath the driver, for the check's own
    tests: each fault must turn ``correct`` false.

    - ``frozen``: the step computes its loss but returns the state it
      was given;
    - ``half_batch``: the step sees only the first half of the batch's
      rows, the mean taken over them.
    """
    import jax

    from repro.runtime.steps import build_train_step

    step = trainer.train_step
    if fault == "frozen":
        free = jax.jit(build_train_step(trainer.cfg, trainer.hp))

        def frozen(params, opt_state, batch):
            _, _, metrics = free(params, opt_state, batch)
            return params, opt_state, metrics

        trainer.train_step = frozen
    elif fault == "half_batch":
        def half(params, opt_state, batch):
            n = len(batch["tokens"]) // 2
            return step(params, opt_state, {k: v[:n] for k, v in
                                            batch.items()})

        trainer.train_step = half
    else:
        raise ValueError(f"unknown fault {fault!r}")


def program_readings(trainer, steps: int, b1: float) -> Dict:
    """Drive ``trainer`` through its first ``steps`` steps through its own
    ``run``; read the loss of each, the first gradient the optimizer got
    (from AdamW's first moment after one step, ``m = (1 - b1) g``) and
    the parameters' change over all of them.  ``check_s`` is the time
    spent on reading alone (host copies and norms), not on the steps."""
    t0 = time.perf_counter()
    p0 = _host_leaves(trainer.params)
    check_s = time.perf_counter() - t0
    trainer.run(1)
    t0 = time.perf_counter()
    grad = _device_norms(trainer.opt_state.m, 1.0 / (1.0 - b1))
    check_s += time.perf_counter() - t0
    trainer.run(steps - 1)
    t0 = time.perf_counter()
    p_n = _host_leaves(trainer.params)
    change = {k: float(np.linalg.norm((p_n[k] - p0[k]).ravel()))
              for k in p0}
    check_s += time.perf_counter() - t0
    return {"losses": [h["loss"] for h in trainer.history[:steps]],
            "grad": grad, "change": change, "check_s": check_s}


def step_memory(trainer, run) -> Dict[str, int]:
    """The chip's peak during a step, by the compiled step's own account:
    what the chip holds between steps besides the step's arguments, plus
    XLA's peak for the step (arguments, temporaries and outputs as their
    lifetimes overlap).  The allocator's ``peak_bytes_in_use``, which
    misses the step's temporaries, is read beside it; ``peak`` is the
    larger of the two."""
    import jax

    from repro.runtime.sharding import use_rules

    peak = int(run.memory_peak_bytes())
    if not hasattr(trainer.train_step, "lower"):       # a planted fault
        return {"allocator_peak": peak, "peak": peak}
    rows, seq = run.mix["global_batch"], run.config["seq_len"]
    spec = jax.ShapeDtypeStruct((rows, seq), np.int32)
    with use_rules(trainer.rules), trainer.mesh:
        mem = trainer.train_step.lower(
            trainer.params, trainer.opt_state,
            {"tokens": spec, "labels": spec}).compile().memory_analysis()
    held = max((d.memory_stats() or {}).get("bytes_in_use", 0)
               for d in run.devices)
    out = {"held": int(held), "argument": int(mem.argument_size_in_bytes),
           "temp": int(mem.temp_size_in_bytes),
           "compiled_peak": int(mem.peak_memory_in_bytes),
           "allocator_peak": peak}
    out["step_peak"] = out["held"] - out["argument"] + out["compiled_peak"]
    out["peak"] = max(out["step_peak"], out["allocator_peak"])
    return out


def tracked_commits(trainer) -> int:
    """STEP_COMMIT records folded into the MetricsDB, one per step and
    host when tracking is sound."""
    trainer.pump_consumers()
    rows = trainer.metrics[0].query(
        "SELECT host, ver, COUNT(*) FROM events WHERE type = 32 "
        "GROUP BY host, ver")
    want = {(h, s) for h in range(len(trainer.trackers))
            for s in range(1, trainer.step + 1)}
    got = {(h, v) for h, v, n in rows if n == 1}
    return len(want ^ got)


def reference_readings(run, steps: int, quant=None, rows=None) -> Dict:
    cfg, mix = run.config, run.mix
    batches = [mamba2_ref.batch_at(run.seed32, cfg["model"]["vocab_size"],
                                   cfg["seq_len"], mix["global_batch"],
                                   mix["hosts"], k) for k in range(steps)]
    return mamba2_ref.readings(cfg["model"], cfg["optimizer"], run.seed32,
                               batches, quant=quant, rows=rows)


def run(run) -> Dict:
    import jax

    cfg, mix = run.config, run.mix
    steps = mix["check_steps"]
    tokens_per_step = mix["global_batch"] * cfg["seq_len"]
    spans = run.spans
    with tempfile.TemporaryDirectory(prefix="chipbench_train_") as wd:
        trainer = build_trainer(run, wd)
        try:
            prog = program_readings(trainer, steps,
                                    cfg["optimizer"]["b1"])
            run.check_in_setup_s += prog.pop("check_s")
            from repro.data.pipeline import ShardedTokenPipeline

            spans.wrap(trainer, "pump_consumers", "pump")
            spans.wrap(ShardedTokenPipeline, "__next__", "data")
            first = trainer.step
            run.start_window()
            try:
                while time.perf_counter() - run.window[0] < run.seconds:
                    trainer.run(1)
            finally:
                run.end_window()
                spans.restore()
            n = trainer.step - first
            losses = [h["loss"] for h in trainer.history]
            t_mem = time.perf_counter()
            memory = step_memory(trainer, run)
            memory["seconds"] = time.perf_counter() - t_mem
            untracked = tracked_commits(trainer)
        finally:
            trainer.close()
        del trainer
        gc.collect()
    t_check = time.perf_counter()
    ref = reference_readings(run, steps)
    values = gaps(prog, ref, cfg["check"]["dead_leaf_frac"])
    limits = cfg["check"]["limits"]
    values["untracked_steps"] = float(untracked)
    values["nonfinite_losses"] = float(sum(not math.isfinite(x)
                                           for x in losses))
    all_limits = {**limits, "untracked_steps": 0.0, "nonfinite_losses": 0.0}
    rate = n * tokens_per_step / run.window_s
    return {
        "correct": within(values, all_limits),
        "attempted": n, "failed": int(values["nonfinite_losses"]),
        "end_to_end": {"setup_s": run.setup_s, "train_tokens_per_s": rate},
        "memory_peak_bytes": memory["peak"],
        "check_s": time.perf_counter() - t_check,
        "compared": compared(values, all_limits),
        "spans": spans, "steps": n,
        "flops_per_token": train_flops_per_token(cfg["model"]),
        "readings": {"program": prog, "reference": ref, "memory": memory},
    }
