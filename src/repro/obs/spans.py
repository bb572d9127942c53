"""Spans: where the program's time goes, recorded where the work happens.

One process-wide recorder, ``TRACER``, on the clock
``time.perf_counter_ns()``.  A span records its name, start, end,
parent (the span open on the same thread when it began) and a record
``count``::

    with TRACER.span("cluster.route") as s:
        ...
        s.count = routed

Spans go into a ring of ``RING`` entries preallocated at import: the
recorder is on by default, as a flight recorder, and keeps the newest
spans.  ``TRACER.enabled = False`` makes ``span()`` hand out a shared
object that records nothing.

While a profiler session is active every span also opens a
``jax.profiler.TraceAnnotation`` named ``"repro." + name``, so the
program's spans lie in the profiler's trace beside the device's
operations, on the profiler's own clock, selectable by that one prefix.
The recorder imports nothing of JAX: it annotates only once JAX has
been imported by the process.

**Queue waits** are spans whose start lies in the past:
``TRACER.record(name, t0_ns, t1_ns, count)`` writes one directly, with
no annotation.  Each pass that takes records off a queue writes one: its
start is the record-weighted mean of the records' enqueue stamps, its
end the dequeue time, its count the number of records.  Over a window,
Σ count·duration ÷ Σ count is then exactly the mean wait per record.

Readers: ``select(name, lo, hi)`` returns the spans of ``name`` that
started in ``[lo, hi)`` (perf_counter seconds), or that ended there for
wait spans; ``self_time`` sums their durations less their children's.
Both raise ``RingOverwritten`` when the ring has dropped a span that
began after ``lo``: a window is read whole or not at all.

``attach_registry(registry)`` publishes running totals per span name
from then on, through one pull collector per registry:
``lcap_span_seconds_total{span}`` (record-seconds for waits, so that
seconds ÷ records is the mean wait) and ``lcap_span_records_total``.
"""

from __future__ import annotations

import itertools
import sys
import threading
import weakref
from time import perf_counter_ns
from typing import Dict, List, Optional

import numpy as np

__all__ = ["TRACER", "SpanRecorder", "RingOverwritten", "PREFIX", "RING"]

#: entries in the ring
RING = 1 << 18
#: the profiler annotation of span ``name`` is ``PREFIX + name``
PREFIX = "repro."
#: parent of a span opened with no span open on its thread
ROOT = -1
#: parent of a wait span: waits are not nested in time
WAIT = -2

#: dtype of what ``select`` returns; times in perf_counter nanoseconds
SPAN_DTYPE = np.dtype([("seq", np.int64), ("t0", np.int64),
                       ("t1", np.int64), ("parent", np.int64),
                       ("count", np.int64)])


class RingOverwritten(RuntimeError):
    """The ring no longer holds every span of the window asked for."""


class _Off:
    """What ``span()`` hands out while the recorder is off."""

    __slots__ = ("count",)
    seconds = None

    def __enter__(self):
        return self

    def __exit__(self, et, ev, tb):
        return False


_OFF = _Off()


class Span:
    """One span, open from ``__enter__`` to ``__exit__``; it reaches the
    ring when it closes."""

    __slots__ = ("name", "count", "seq", "parent", "t0", "t1", "_rec",
                 "_stack", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str):
        self._rec = rec
        self.name = name
        self.count = 0

    def __enter__(self) -> "Span":
        rec = self._rec
        try:
            stack = rec._local.stack
        except AttributeError:
            stack = rec._local.stack = []
        self.parent = stack[-1] if stack else ROOT
        self.seq = seq = next(rec._seq)
        stack.append(seq)
        self._stack = stack
        ann = rec._annotation or rec._find_annotation()
        if ann is not None and ann.is_enabled():
            self._ann = ann(PREFIX + self.name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        self.t1 = t1 = perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        self._stack.pop()
        self._rec._write(self.seq, self.name, self.t0, t1, self.parent,
                         self.count)
        return False

    @property
    def seconds(self) -> float:
        """Duration of the closed span."""
        return (self.t1 - self.t0) * 1e-9


class SpanRecorder:
    """A bounded ring of spans; see the module docstring.  Slot
    ``seq % size`` holds span ``seq`` once it has closed; ``_seqs`` says
    which span a slot holds."""

    def __init__(self, size: int = RING):
        if size & (size - 1):
            raise ValueError("the ring's size must be a power of two")
        self.enabled = True
        self.size = size
        self._mask = size - 1
        self._seqs = [-1] * size
        self._names: List[Optional[str]] = [None] * size
        self._t0 = [0] * size
        self._t1 = [0] * size
        self._parents = [ROOT] * size
        self._counts = [0] * size
        self._seq = itertools.count()
        #: one past the newest span written (a hint: threads race on it)
        self._n = 0
        self._local = threading.local()
        self._waits = set()
        self._annotation = None
        #: name -> [ns, records], kept once a registry is attached
        self._totals: Optional[Dict[str, List[int]]] = None
        self._totals_lock = threading.Lock()
        self._registries = weakref.WeakSet()

    # ------------------------------------------------------------ writing
    def span(self, name: str):
        """A context manager recording span ``name``."""
        return Span(self, name) if self.enabled else _OFF

    def record(self, name: str, t0_ns: int, t1_ns: int, count: int) -> None:
        """Write a wait span: ``count`` records waited, on the mean, from
        ``t0_ns`` to ``t1_ns``."""
        if self.enabled:
            if name not in self._waits:
                self._waits.add(name)
            self._write(next(self._seq), name, t0_ns, t1_ns, WAIT, count)

    def _write(self, seq: int, name: str, t0: int, t1: int, parent: int,
               count: int) -> None:
        if seq >= self._n:
            self._n = seq + 1
        if self._n - seq <= self.size:          # not overtaken while open
            i = seq & self._mask
            self._seqs[i] = seq
            self._names[i] = name
            self._t0[i] = t0
            self._t1[i] = t1
            self._parents[i] = parent
            self._counts[i] = count
        if self._totals is not None:
            with self._totals_lock:
                tot = self._totals.get(name)
                if tot is None:
                    tot = self._totals[name] = [0, 0]
                tot[0] += (t1 - t0) * (count if parent == WAIT else 1)
                tot[1] += count

    def _find_annotation(self):
        prof = sys.modules.get("jax.profiler")
        if prof is not None:
            self._annotation = prof.TraceAnnotation
        return self._annotation

    # ------------------------------------------------------------ reading
    def _kept(self):
        """The slots holding spans, oldest first, as (seq, slot) arrays,
        and one past the newest number handed out (taking one: the gap
        it leaves is a slot never filled)."""
        n = next(self._seq)
        first = max(0, n - self.size)
        seqs = np.arange(first, n, dtype=np.int64)
        slots = seqs & self._mask
        held = np.asarray(self._seqs, np.int64)[slots] == seqs
        return seqs[held], slots[held], n

    def _key(self, slot: int) -> int:
        """When a span entered the ring's order: its start, or for a
        wait span its end."""
        return self._t1[slot] if self._parents[slot] == WAIT \
            else self._t0[slot]

    def _check(self, lo_ns: int, seqs: np.ndarray, slots: np.ndarray,
               n: int) -> None:
        if n <= self.size:
            return
        # spans take their numbers in the order they begin (waits: end),
        # so every span dropped began before the oldest one kept
        if not len(seqs) or self._key(int(slots[0])) >= lo_ns:
            raise RingOverwritten(
                f"the span ring ({self.size} entries) has dropped spans "
                f"that began after the window's start")

    def select(self, name: str, lo: float = float("-inf"),
               hi: float = float("inf")) -> np.ndarray:
        """Spans of ``name`` that started in ``[lo, hi)``, perf_counter
        seconds (for a wait span: that ended there), as a ``SPAN_DTYPE``
        array with times in perf_counter nanoseconds."""
        lo_ns = _ns(lo)
        seqs, slots, n = self._kept()
        self._check(lo_ns, seqs, slots, n)
        return self._select(name, lo_ns, _ns(hi), seqs, slots)

    def _select(self, name, lo_ns, hi_ns, seqs, slots) -> np.ndarray:
        names = self._names
        pick = [k for k, i in enumerate(slots.tolist()) if names[i] == name]
        idx = slots[pick]
        out = np.empty(len(pick), SPAN_DTYPE)
        out["seq"] = seqs[pick]
        for field, col in (("t0", self._t0), ("t1", self._t1),
                           ("parent", self._parents),
                           ("count", self._counts)):
            out[field] = [col[i] for i in idx.tolist()]
        key = out["t1"] if name in self._waits else out["t0"]
        return out[(key >= lo_ns) & (key < hi_ns)]

    def self_time(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> float:
        """Seconds of the spans ``select`` returns, less the time of
        their children."""
        lo_ns = _ns(lo)
        seqs, slots, n = self._kept()
        self._check(lo_ns, seqs, slots, n)
        rows = self._select(name, lo_ns, _ns(hi), seqs, slots)
        if not len(rows):
            return 0.0
        total = int((rows["t1"] - rows["t0"]).sum())
        parents = np.asarray(self._parents, np.int64)[slots]
        for i in slots[np.isin(parents, rows["seq"])].tolist():
            total -= self._t1[i] - self._t0[i]
        return total * 1e-9

    # ------------------------------------------------------------- export
    def attach_registry(self, registry) -> None:
        """Publish the running totals per span name into ``registry``
        (once per registry, however many components attach it)."""
        with self._totals_lock:
            if self._totals is None:
                self._totals = {}
            if registry in self._registries:
                return
            self._registries.add(registry)
        registry.register_collector(self._collect)

    def _collect(self):
        with self._totals_lock:
            totals = {k: tuple(v) for k, v in self._totals.items()}
        out = []
        for name, (ns, count) in sorted(totals.items()):
            lb = {"span": name}
            out.append(("lcap_span_seconds_total", "counter",
                        "seconds inside each program span (record-seconds "
                        "waited for queue-wait spans)", lb, ns * 1e-9))
            out.append(("lcap_span_records_total", "counter",
                        "records each program span handled", lb, count))
        return out


def _ns(t: float) -> int:
    if t == float("inf"):
        return 1 << 62
    if t == float("-inf"):
        return -(1 << 62)
    return int(round(t * 1e9))


#: the process-wide recorder
TRACER = SpanRecorder()
