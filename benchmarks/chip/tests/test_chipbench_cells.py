"""Each cell, run through the harness on the CPU at a smoke size (the look
for a chip skipped), comes out correct; with the timed path broken
underneath it, or the reference's control in the program's place, it
comes out not correct."""

import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import harness as H  # noqa: E402
from chipbench import train as TR  # noqa: E402

sys.path.insert(0, os.path.join(H.CHECKOUT, "src"))

SEED = 2 ** 33 + 4242
TRAIN_SMOKE = {"n_layers": 2, "d_model": 64, "vocab_size": 256,
               "ssm_state": 16, "ssm_head_dim": 32, "ssm_chunk": 8}
#: limits at the smoke size (the chip's are set from chip readings at
#: the cell's own size).  Over eight seeds on the CPU sound smoke runs
#: read at most loss 6.4e-4, gradient 0.040, change 0.0088; the fp8
#: control at least 1.5e-3, 0.011, 0.0128, failing loss or change on
#: every seed; half the batch at least 0.0068, 0.10, 0.046.
SMOKE_LIMITS = {"loss_gap": 0.0015, "grad_gap": 0.06, "change_gap": 0.01}


def shrink(spec, backlog_s=0.0):
    spec = copy.deepcopy(spec)
    cfg, mix = spec["config"], spec["mix"]
    if cfg["driver"] == "train":
        cfg["model"].update(TRAIN_SMOKE)
        cfg["seq_len"] = 32
        cfg["check"]["limits"].update(SMOKE_LIMITS)
    else:
        cfg.update(n_mdt=2, batch_size=32)
        mix.update(rate=1000, warmup_s=0.3, backlog_s=backlog_s,
                   drain_timeout_s=3)
    return spec


def smoke_execute(mp, workload, fault=None, seconds=1.0, backlog_s=0.0):
    """``harness.execute`` on the CPU at the smoke size; ``backlog_s`` of
    the traffic logged before a fabric cell's warm-up."""
    import jax

    resolve = H.resolve
    mp.setattr(H, "resolve", lambda bench, w, root=H.CHECKOUT:
               shrink(resolve(bench, w, root), backlog_s))
    mp.setattr(H, "check_devices",
               lambda chips: (jax.devices(), {"bf16_flops": 197e12}))
    mp.setattr(H, "enable_compile_cache", lambda: None)
    return H.execute(workload, SEED, seconds, False, fault=fault)


@pytest.fixture(scope="module")
def train_ok():
    with pytest.MonkeyPatch.context() as mp:
        readings = {}
        drive = TR.run

        def keep(run):
            out = drive(run)
            readings.update(out["readings"], run=run)
            return out

        mp.setattr(TR, "run", keep)
        return smoke_execute(mp, "mamba2-780m.train"), readings


def test_train_cell_is_correct(train_ok):
    out, _ = train_ok
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["metrics"]) == ["setup_s", "train_tokens_per_s"]
    assert list(out["compared"]) == ["loss_gap", "grad_gap", "change_gap",
                                     "untracked_steps", "nonfinite_losses"]


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
def test_train_cell_catches_a_broken_step(monkeypatch, fault):
    out = smoke_execute(monkeypatch, "mamba2-780m.train", fault=fault,
                        seconds=0.3)
    assert not out["correct"]


def test_train_check_catches_a_wrong_reference_feed(train_ok):
    """The same comparison fails when the reference is fed another
    program's readings: the fp8 control's and a shifted loss."""
    _, rd = train_ok
    run = rd["run"]
    dead = run.config["check"]["dead_leaf_frac"]
    limits = run.config["check"]["limits"]
    ref, prog = rd["reference"], rd["program"]
    assert TR.within(TR.gaps(prog, ref, dead), limits)
    ctl = TR.reference_readings(run, run.mix["check_steps"], quant="fp8")
    assert not TR.within(TR.gaps(ctl, ref, dead), limits)
    shifted = dict(prog, losses=[x * 1.05 for x in prog["losses"]])
    assert not TR.within(TR.gaps(shifted, ref, dead), limits)


@pytest.mark.parametrize("backlog_s", [0.0, 0.3])
def test_fabric_cells_are_correct(monkeypatch, backlog_s):
    out = smoke_execute(monkeypatch, "mdtest.steady", backlog_s=backlog_s)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0
    assert out["metrics"]["records_per_s"]["value"] > 0
    assert all(v["value"] == 0 for v in out["compared"].values())


@pytest.mark.parametrize("fault", ["drop_half", "alter", "bad_slot",
                                   "no_ack"])
def test_fabric_cell_catches_a_broken_path(monkeypatch, fault):
    out = smoke_execute(monkeypatch, "mdtest.steady", fault=fault,
                        seconds=0.5)
    assert not out["correct"]
    assert any(v["value"] > v["limit"] for v in out["compared"].values())
