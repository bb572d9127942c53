"""Readings of the program's own spans (``repro.obs.spans.TRACER``) over
a run's window, for the per-layer metrics that read them.

Each returns None where the program records no such span (a program
that predates the recorder, or a window without the work), so that a
metric is left out rather than read as nought."""

from __future__ import annotations

from typing import Optional, Tuple


def tracer():
    """The program's recorder, or None if the program has none."""
    try:
        from repro.obs.spans import TRACER
    except ImportError:
        return None
    return TRACER


def mean_wait_ms(run, name: str) -> Optional[float]:
    """Record-weighted mean of wait span ``name`` in the window, ms:
    Σ count·duration ÷ Σ count."""
    tr = tracer()
    if tr is None:
        return None
    rows = tr.select(name, *run.window)
    n = int(rows["count"].sum())
    if not n:
        return None
    return float(((rows["t1"] - rows["t0"]) * rows["count"]).sum()) / n * 1e-6


def per_step_ms(run, name: str) -> Optional[float]:
    """Time in span ``name`` per ``train.step`` in the window, ms."""
    tr = tracer()
    if tr is None:
        return None
    steps = len(tr.select("train.step", *run.window))
    rows = tr.select(name, *run.window)
    if not steps or not len(rows):
        return None
    return float((rows["t1"] - rows["t0"]).sum()) / steps * 1e-6


def totals(run, name: str) -> Optional[Tuple[int, int]]:
    """(Σ count, Σ duration in ns) of span ``name`` in the window."""
    tr = tracer()
    if tr is None:
        return None
    rows = tr.select(name, *run.window)
    return int(rows["count"].sum()), int((rows["t1"] - rows["t0"]).sum())
