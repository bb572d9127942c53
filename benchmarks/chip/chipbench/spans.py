"""Host spans and counters the benchmark wraps around calls into the
program's layers, and the compile counter over ``jax.monitoring``.

A span is recorded only while the recorder is enabled (the ``--trace 1``
run); then it also opens a ``jax.profiler.TraceAnnotation`` named
``bench.<span>``, so the trace reduction can say what the host was doing
in each idle gap of the device.  Disabled, a wrapped call costs one
attribute test."""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Tuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_PREFIX = "bench."


class CompileClock:
    """Backend compiles (persistent-cache loads included) and their
    seconds, from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


class GcPauses:
    """Pauses of Python's cyclic collector (``gc.callbacks``) while armed:
    ``(generation, seconds)`` each."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []
        self._t0 = 0.0

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def arm(self) -> None:
        import gc

        if self._on not in gc.callbacks:
            gc.callbacks.append(self._on)

    def disarm(self) -> None:
        import gc

        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)

    def summary(self) -> Dict[str, float]:
        full = [s for g, s in self.pauses if g == 2]
        return {"count": len(self.pauses), "full": len(full),
                "total_s": sum(s for _, s in self.pauses),
                "longest_s": max((s for _, s in self.pauses), default=0.0)}


class Spans:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.times: Dict[str, List[Tuple[float, float]]] = {}
        self._restore: List[Callable[[], None]] = []
        self._annotation = None
        if enabled:
            import jax

            self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled:
            yield
            return
        t0 = time.perf_counter()
        with self._annotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.times.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def wrap(self, owner, attr: str, name: str) -> None:
        """Wrap ``owner.attr`` (an instance or a class) in span ``name``
        until ``restore()``."""
        if not self.enabled:
            return
        inner = getattr(owner, attr)
        had_own = attr in vars(owner)

        def wrapped(*args, **kw):
            with self.span(name):
                return inner(*args, **kw)

        setattr(owner, attr, wrapped)

        def undo():
            if had_own:
                setattr(owner, attr, inner)
            else:
                delattr(owner, attr)

        self._restore.append(undo)

    def restore(self) -> None:
        while self._restore:
            self._restore.pop()()

    # ------------------------------------------------------------ reading
    def durations(self, name: str, window=None) -> List[float]:
        """Durations of span ``name``; with ``window`` (a perf_counter
        pair) only of those that started inside it."""
        lo, hi = window if window else (float("-inf"), float("inf"))
        return [b - a for a, b in self.times.get(name, ()) if lo <= a < hi]

    def total(self, name: str, window=None) -> float:
        return sum(self.durations(name, window))

    def count(self, name: str, window=None) -> int:
        return len(self.durations(name, window))
