"""The harness is data: every name in BENCHMARK.json resolves to its
file, a cell added as files alone is found, and the command refuses to
run without a chip it knows."""

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import flops  # noqa: E402
from chipbench import harness as H  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

ROOT = H.CHECKOUT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return H.load_benchmark()


def test_every_name_resolves_to_its_file(bench):
    assert bench["paths"] == ["benchmarks/chip"]
    assert os.path.isfile(os.path.join(ROOT, bench["command"][1]))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(bench["paths"][0] + "/")
    for w in bench["workloads"]:
        spec = H.resolve(bench, w["name"])
        assert os.path.isfile(H.mix_path(w["traffic"]))
        assert os.path.isfile(H.driver_path(spec["driver"]))
        assert spec["end_to_end"][0]["name"] == "setup_s"
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
        for m in spec["per_layer"]:
            assert callable(H.load_reader(m["name"]))
    for m in bench["per_layer"]:
        assert os.path.isfile(H.metric_path(m["name"]))


def test_benchmark_keeps_to_its_format(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        ns = [x["name"] for x in bench[group]]
        assert len(ns) == len(set(ns))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        for w in m["workloads"]:
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_cell_added_as_files_only_is_found(tmp_path, bench):
    """A new mix, a new metric reader and entries in BENCHMARK.json are
    all a new cell needs."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = json.loads(json.dumps(bench))
    with open(H.mix_path("steady")) as fh:
        mix = json.load(fh)
    mix["rate"] = mix["rate"] // 2
    with open(tmp_path / "benchmarks/chip/mixes/quiet.json", "w") as fh:
        json.dump(mix, fh)
    with open(tmp_path / "benchmarks/chip/metrics/quiet_rate.py", "w") as fh:
        fh.write("def read(run, out):\n    return 42.0\n")
    added["workloads"].append({
        "name": "mdtest.quiet", "config": "mdtest-16mdt",
        "traffic": "quiet", "chips": 1, "why": "half the steady rate"})
    for m in added["end_to_end"]:
        if m["name"] == "records_per_s":
            m["workloads"].append("mdtest.quiet")
    added["per_layer"].append({
        "name": "quiet_rate", "unit": "records/s", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "records_per_s", "workloads": ["mdtest.quiet"]})
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(added, fh)
    root = str(tmp_path)
    spec = H.resolve(H.load_benchmark(root), "mdtest.quiet", root)
    assert spec["mix"]["rate"] == mix["rate"]
    assert spec["driver"] == "fabric"
    assert [m["name"] for m in spec["end_to_end"]] == ["setup_s",
                                                      "records_per_s"]
    assert [m["name"] for m in spec["per_layer"]] == ["quiet_rate"]
    assert H.per_layer_values(spec, None, {}, root) == {
        "quiet_rate": {"value": 42.0, "unit": "records/s"}}


def test_unknown_device_kind_raises():
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99 imaginary")


def test_no_chip_and_unknown_chip_are_refused(monkeypatch):
    import jax

    with pytest.raises(H.NoChip):
        H.check_devices(1)          # the CPU backend
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(H.NoChip):
        H.check_devices(1)
    fake.device_kind = "TPU v5 lite"
    with pytest.raises(H.NoChip):
        H.check_devices(4)
    assert H.check_devices(1)[1]["hbm_bytes_per_s"] == 819e9


def test_command_without_a_tpu_exits_nonzero_and_prints_no_result(bench):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for w in bench["workloads"]:
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, bench["command"][1]),
             "--workload", w["name"], "--seed", str(2 ** 33 + 1),
             "--seconds", "1", "--trace", "0"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
        assert "no TPU" in proc.stderr


def test_param_count_matches_the_program():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs as C
    from repro.models.transformer import count_params

    with open(os.path.join(BENCH, "configs", "mamba2-780m.json")) as fh:
        cfg = json.load(fh)
    assert flops.param_count(cfg["model"]) == \
        count_params(C.get_config("mamba2-780m"))
    from repro.models.config import ModelConfig

    assert ModelConfig(**cfg["model"]) == C.get_config("mamba2-780m")
    assert flops.train_flops_per_token(cfg["model"]) == \
        6 * flops.param_count(cfg["model"])


def test_set_up_is_frozen_before_the_window_and_thawed_after():
    import gc

    run = H.Run(workload="w", config={}, mix={}, seed=1, seconds=1.0,
                trace=False)
    left = [[i] for i in range(1000)]          # what set-up leaves behind
    run.settle()
    frozen = gc.get_freeze_count()
    assert frozen >= len(left)
    more = [[i] for i in range(1000)]          # made after set-up
    run.start_window()                         # settles once only
    assert gc.get_freeze_count() <= frozen
    assert len(more) == 1000
    gc.collect(0)
    run.end_window()
    assert gc.get_freeze_count() == 0
    summary = run.gc_pauses.summary()
    assert summary["count"] >= 1 and summary["total_s"] >= 0.0
    assert summary["longest_s"] <= summary["total_s"]
    gc.collect(0)                              # disarmed after the window
    assert run.gc_pauses.summary()["count"] == summary["count"]
