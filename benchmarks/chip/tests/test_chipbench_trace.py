"""The trace reduction: interval arithmetic, and the whole reduction on a
small trace recorded here on the CPU backend (its XLA thread stands in
for a chip's operation line)."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import trace as T  # noqa: E402
from chipbench.spans import Spans  # noqa: E402


def test_union_covered_and_gaps():
    iv = [(5, 7), (0, 2), (1, 3), (10, 12)]
    assert T.union(iv) == [(0, 3), (5, 7), (10, 12)]
    assert T.covered(iv, 0, 12) == 3 + 2 + 2
    assert T.covered(iv, 2, 11) == 1 + 2 + 1
    assert T.gaps(iv, 0, 12) == [(3, 5), (7, 10)]
    assert T.gaps(iv, -1, 13) == [(-1, 0), (3, 5), (7, 10), (12, 13)]
    assert T.gaps([], 0, 4) == [(0, 4)]


def test_spans_at_picks_the_innermost_open_span():
    spans = [("window", 0, 100), ("round", 10, 50), ("route", 12, 20),
             ("deliver", 60, 70)]
    assert T.spans_at(spans, [15, 30, 65, 80, 5]) == \
        ["route", "round", "deliver", "other", "other"]


def test_reduce_busy_idle_modules_and_gaps():
    tr = T.Trace(
        ops={"/device:TPU:0": [("fusion.1", 10, 30), ("fusion.2", 25, 40),
                               ("copy", 60, 70), ("outside", 200, 300)]},
        modules={"/device:TPU:0": [("jit_step(7)", 10, 40),
                                   ("jit_step(7)", 60, 70)]},
        spans=[("window", 0, 100), ("pump", 40, 60), ("data", 70, 100)])
    r = T.reduce(tr, ["/device:TPU:0"])
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.idle_share == pytest.approx(0.6)
    assert r.module_s == {"jit_step": pytest.approx(40e-9)}
    assert r.module_n == {"jit_step": 2}
    assert dict(r.top_ops)["fusion.1"] == pytest.approx(20e-9)
    assert "outside" not in dict(r.top_ops)
    gaps = dict(r.idle_gaps)
    assert gaps["other"] == pytest.approx(10e-9)
    assert gaps["pump"] == pytest.approx(20e-9)
    assert gaps["data"] == pytest.approx(30e-9)


def test_top_ops_count_self_time_of_nested_ops():
    """A ``while`` and the operations of its body share one line; each is
    counted for the time no operation inside it ran."""
    ops = [("while", 0, 100), ("body.a", 10, 40), ("body.b", 50, 90),
           ("inner", 55, 65), ("after", 100, 120)]
    own = {n: s for n, _, _, s in T.self_times(ops)}
    assert own == {"while": 30, "body.a": 30, "body.b": 30, "inner": 10,
                   "after": 20}
    tr = T.Trace(ops={"/device:TPU:0": ops},
                 spans=[("window", 0, 110)])
    r = T.reduce(tr, ["/device:TPU:0"])
    assert dict(r.top_ops)["while"] == pytest.approx(30e-9)
    assert dict(r.top_ops)["after"] == pytest.approx(10e-9)
    assert r.busy_s == pytest.approx(110e-9)


@pytest.mark.parametrize("name,key", [
    ("%fusion.344 = (f32[48,3072]{1,0:T(8,128)}, f32[8]{0}) fusion(f32[8]"
     "{0:T(128)} %p.1), kind=kLoop, calls=%fused_computation.450",
     "%fusion.344 fusion kLoop"),
    ("%while.259 = (s32[]{:T(128)}, bf16[2,512]{1,0:T(8,128)(2,1)}) while("
     "(s32[]{:T(128)}) %tuple.341), condition=%c, body=%b",
     "%while.259 while"),
    ("%copy-done = u32[120]{0:T(128)S(1)} copy-done((u32[120]) %copy-start)",
     "%copy-done copy-done"),
    ("fusion.1", "fusion.1"),
])
def test_op_key_shortens_hlo_instructions(name, key):
    assert T.op_key(name) == key


def test_chip_planes_are_the_numbered_devices():
    """A TPU trace also holds ``/device:CUSTOM:Megascale Trace``, which
    sorts before ``/device:TPU:0`` and holds no operation."""
    assert T.is_device_plane("/device:TPU:0")
    assert not T.is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not T.is_device_plane("/host:CPU")
    tr = T.Trace(ops={"/device:TPU:10": [], "/device:TPU:2": [],
                      "/device:TPU:0": []})
    assert T.chip_planes(tr, 2) == ["/device:TPU:0", "/device:TPU:2"]


def test_reduce_needs_one_window():
    tr = T.Trace(ops={"/device:TPU:0": [("a", 0, 1)]})
    with pytest.raises(ValueError):
        T.reduce(tr, ["/device:TPU:0"])


def test_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384))
    f(x).block_until_ready()
    spans = Spans(enabled=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with spans.span("window"):
            for _ in range(3):
                with spans.span("step"):
                    f(x).block_until_ready()
                with spans.span("pump"):
                    time.sleep(0.02)
    finally:
        jax.profiler.stop_trace()
    path = T.newest_xplane(str(tmp_path))
    assert path is not None
    host = "/host:CPU"
    layout = T.load(path).layout
    xla_lines = [ln for ln, n in layout[host].items() if "XLA" in ln and n]
    tr = T.load(path, device_plane=lambda name: name == host,
                op_lines=xla_lines)
    names = [n for n, _, _ in tr.spans]
    assert names.count("step") == 3 and names.count("pump") == 3
    assert names.count("window") == 1
    r = T.reduce(tr, [host])
    assert 0 < r.busy_s < r.window_s
    assert r.window_s == pytest.approx(spans.total("window"), rel=0.05)
    gaps = dict(r.idle_gaps)
    # the sleeps inside the pump spans are idle time of the "device"
    assert gaps.get("pump", 0.0) >= 0.05
    assert spans.count("pump") == 3
