"""The per-layer metrics that read the program's own spans: after an
untraced smoke run of each cell on the CPU, each reader returns a finite
value over the run's window, the fabric's fallback share is nought, and
a program without the recorder leaves every one of them out."""

import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import fabric as FAB  # noqa: E402
from chipbench import harness as H  # noqa: E402
from chipbench import train as TR  # noqa: E402
from test_chipbench_cells import smoke_execute  # noqa: E402

READERS = {
    "mdtest.steady": ["journal_wait_ms.steady", "buffer_wait_ms.steady",
                      "outbox_wait_ms.steady", "slots_us.steady",
                      "fallback_share.steady"],
    "mamba2-780m.train": ["host_ms.train", "launch_ms.train",
                          "track_ms.train"],
}
CELL_MODULES = {"mdtest.steady": FAB, "mamba2-780m.train": TR}


@pytest.fixture(scope="module", params=sorted(READERS))
def smoke_run(request):
    """(workload, run, the cell module's output) of one untraced smoke
    run."""
    workload = request.param
    module = CELL_MODULES[workload]
    kept = {}
    with pytest.MonkeyPatch.context() as mp:
        drive = module.run

        def keep(run):
            out = drive(run)
            kept.update(run=run, out=out)
            return out

        mp.setattr(module, "run", keep)
        result = smoke_execute(mp, workload)
    assert result["correct"], result["compared"]
    return workload, kept["run"], kept["out"]


def test_every_reader_is_declared_for_its_cell():
    bench = H.load_benchmark()
    for workload, names in READERS.items():
        layer = {m["name"] for m in H.resolve(bench, workload)["per_layer"]}
        assert set(names) <= layer


def test_program_span_readers_read_finite_values(smoke_run):
    workload, run, out = smoke_run
    values = {name: H.load_reader(name)(run, out)
              for name in READERS[workload]}
    for name, v in values.items():
        assert v is not None and math.isfinite(v) and v >= 0, (name, v)
    if workload == "mdtest.steady":
        assert values["fallback_share.steady"] == 0.0
        assert values["journal_wait_ms.steady"] > 0
    else:
        assert values["host_ms.train"] >= values["launch_ms.train"]


def test_readers_leave_the_metric_out_without_the_recorder(monkeypatch,
                                                           smoke_run):
    """A program that predates the recorder: every reader gives None."""
    from chipbench import program_spans

    workload, run, out = smoke_run
    monkeypatch.setattr(program_spans, "tracer", lambda: None)
    for name in READERS[workload]:
        assert H.load_reader(name)(run, out) is None
