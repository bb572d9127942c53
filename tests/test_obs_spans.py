"""The program's span recorder (``repro.obs.spans``): nesting, parents,
self time and counts; queue-wait spans and their record-weighted mean;
window slicing; the ring's overwrite guard; the off switch; the
registry export; and the profiler annotation under ``repro.``."""

import glob
import threading
import time

import numpy as np
import pytest

from repro.obs import MetricsRegistry, render_prometheus
from repro.obs.spans import (PREFIX, ROOT, TRACER, WAIT, RingOverwritten,
                             SpanRecorder)


def busy(seconds):
    t = time.perf_counter() + seconds
    while time.perf_counter() < t:
        pass


def test_nesting_parents_self_time_and_counts():
    rec = SpanRecorder(size=64)
    with rec.span("outer") as outer:
        busy(0.002)
        with rec.span("inner") as a:
            a.count = 3
            busy(0.004)
        with rec.span("inner") as b:
            b.count = 4
            with rec.span("leaf"):
                busy(0.003)
        outer.count = 7
    (o,) = rec.select("outer")
    inner = rec.select("inner")
    (leaf,) = rec.select("leaf")
    assert o["parent"] == ROOT and o["count"] == 7
    assert list(inner["parent"]) == [o["seq"], o["seq"]]
    assert list(inner["count"]) == [3, 4]
    assert leaf["parent"] == inner[1]["seq"]
    dur = lambda r: (r["t1"] - r["t0"]) * 1e-9              # noqa: E731
    assert outer.seconds == pytest.approx(dur(o))
    assert rec.self_time("outer") == pytest.approx(
        dur(o) - dur(inner).sum(), abs=1e-9)
    assert rec.self_time("inner") == pytest.approx(
        dur(inner).sum() - dur(leaf), abs=1e-9)
    assert rec.self_time("leaf") == pytest.approx(dur(leaf))
    assert rec.self_time("outer") >= 0.002
    assert rec.self_time("missing") == 0.0


def test_parents_are_per_thread():
    rec = SpanRecorder(size=64)

    def worker():
        with rec.span("other"):
            pass

    with rec.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    (other,) = rec.select("other")
    assert other["parent"] == ROOT


def test_threads_lose_no_span_and_keep_their_own_parents():
    """More threads than cores, switching often: every span lands once,
    each inner span's parent is an outer span (of its own thread), and
    the exported totals count every record."""
    import sys

    rec = SpanRecorder(size=1 << 15)
    reg = MetricsRegistry()
    rec.attach_registry(reg)
    n_threads, n_spans = 16, 400

    def worker():
        for _ in range(n_spans):
            with rec.span("outer") as o:
                o.count = 1
                with rec.span("inner") as i:
                    i.count = 2

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    outer, inner = rec.select("outer"), rec.select("inner")
    total = n_threads * n_spans
    assert len(outer) == len(inner) == total
    assert len(set(outer["seq"]) | set(inner["seq"])) == 2 * total
    assert (outer["parent"] == ROOT).all()
    parents = inner["parent"].tolist()
    assert set(parents) == set(outer["seq"].tolist())
    assert len(set(parents)) == total                  # one child each
    recs = {lb["span"]: v for lb, v in
            reg.snapshot()["lcap_span_records_total"]["samples"]}
    assert recs == {"outer": total, "inner": 2 * total}


def test_wait_spans_give_the_exact_record_weighted_mean():
    """Each pass records one wait span whose start is the mean of its
    records' stamps; Σ count·duration ÷ Σ count over the spans is then
    the mean wait of every record, exactly."""
    rec = SpanRecorder(size=64)
    rng = np.random.default_rng(7)
    stamps, waits = [], []
    for p in range(6):
        t_out = 1_000_000 * (p + 1)
        enq = t_out - rng.integers(1, 900_000, size=int(rng.integers(1, 40)))
        stamps.append(enq)
        waits.extend((t_out - enq).tolist())
        rec.record("q.wait", int(enq.sum()) // len(enq), t_out, len(enq))
    rows = rec.select("q.wait")
    assert list(rows["parent"]) == [WAIT] * 6
    mean = ((rows["t1"] - rows["t0"]) * rows["count"]).sum() / \
        rows["count"].sum()
    # integer division of each pass's mean stamp: under 1 ns a record
    assert mean == pytest.approx(np.mean(waits), abs=1.0)
    assert rows["count"].sum() == sum(len(s) for s in stamps)


def test_window_slicing_by_start_and_by_end_for_waits():
    rec = SpanRecorder(size=64)
    lo = time.perf_counter()
    with rec.span("s"):
        pass
    mid = time.perf_counter()
    now = time.perf_counter_ns()
    rec.record("w", now - 10**9, now, 5)      # began long before lo
    with rec.span("s"):
        pass
    hi = time.perf_counter()
    assert len(rec.select("s", lo, hi)) == 2
    assert len(rec.select("s", mid, hi)) == 1
    assert len(rec.select("s", lo, mid)) == 1
    assert len(rec.select("w", lo, hi)) == 1           # it ended inside
    assert len(rec.select("w", hi)) == 0
    assert len(rec.select("s", hi)) == 0


def test_ring_overwrites_and_the_readers_then_raise():
    rec = SpanRecorder(size=16)
    lo = time.perf_counter()
    for _ in range(20):
        with rec.span("s"):
            pass
    with pytest.raises(RingOverwritten):
        rec.select("s", lo)
    with pytest.raises(RingOverwritten):
        rec.self_time("s", lo)
    lo2 = time.perf_counter()
    for _ in range(10):
        with rec.span("s"):
            pass
    assert len(rec.select("s", lo2)) == 10             # all of it kept
    # a span overtaken by the ring while open is dropped, not misfiled
    # over the newer span that holds its slot, and its window raises
    lo3 = time.perf_counter()
    with rec.span("long"):
        for _ in range(20):
            with rec.span("s"):
                pass
    assert "long" not in rec._names
    assert rec._names.count("s") == 16
    with pytest.raises(RingOverwritten):
        rec.select("long", lo3)
    with pytest.raises(ValueError):
        SpanRecorder(size=12)


def test_disabled_recorder_records_nothing():
    rec = SpanRecorder(size=16)
    rec.enabled = False
    with rec.span("s") as s:
        s.count = 3
    rec.record("w", 0, 10, 2)
    assert s.seconds is None
    assert len(rec.select("s")) == 0 and len(rec.select("w")) == 0
    rec.enabled = True
    with rec.span("s"):
        pass
    assert len(rec.select("s")) == 1


def test_registry_collector_exports_the_span_totals():
    rec = SpanRecorder(size=16)
    with rec.span("before") as s:     # before any registry: not counted
        s.count = 1
    reg = MetricsRegistry()
    rec.attach_registry(reg)
    rec.attach_registry(reg)          # a second attach adds no collector
    with rec.span("route") as s:
        s.count = 10
    with rec.span("route") as s2:
        s2.count = 5
    rec.record("wait", 0, 2_000_000, 4)               # 2 ms for 4 records
    snap = reg.snapshot()
    secs = {lb["span"]: v for lb, v in
            snap["lcap_span_seconds_total"]["samples"]}
    recs = {lb["span"]: v for lb, v in
            snap["lcap_span_records_total"]["samples"]}
    assert set(secs) == {"route", "wait"}
    assert recs == {"route": 15, "wait": 4}
    assert secs["route"] == pytest.approx(s.seconds + s2.seconds)
    assert secs["wait"] == pytest.approx(0.008)       # record-seconds
    assert len(snap["lcap_span_records_total"]["samples"]) == 2
    text = render_prometheus(snap)
    assert '# TYPE lcap_span_seconds_total counter' in text
    assert 'lcap_span_records_total{span="route"} 15' in text


def test_spans_are_annotated_into_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    rec = SpanRecorder(size=16)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("test.annotated"):
            jax.numpy.ones(4).block_until_ready()
        rec.record("test.wait", 0, 1, 1)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert PREFIX + "test.annotated" in names
    assert PREFIX + "test.wait" not in names          # waits: ring only


def test_process_recorder_is_on_by_default():
    assert TRACER.enabled and TRACER.size == 1 << 18
