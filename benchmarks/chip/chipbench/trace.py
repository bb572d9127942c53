"""Reduction of a profiler trace to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation that ran, their ``XLA Modules`` line one event per program
launch.  Host spans are the benchmark's own ``bench.<name>``
annotations on the ``/host:CPU`` plane.

Busy time is the union of the operation intervals inside the window,
per chip, averaged over the chips used; the idle share is one minus
busy over the window.  The top operations are ranked by self time (a
``while`` less the body operations nested in it), under short names.
Each idle gap is attributed to the innermost host span open at its
midpoint (``"other"`` where none is).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .spans import SPAN_PREFIX

Interval = Tuple[float, float]          # (start_ns, end_ns)
WINDOW_SPAN = "window"


_CHIP_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")


def is_device_plane(name: str) -> bool:
    """A chip's plane, ``/device:TPU:<n>``; not the profiler's other
    ``/device:`` planes, such as ``/device:CUSTOM:Megascale Trace``."""
    return _CHIP_PLANE.match(name) is not None


def chip_planes(tr: "Trace", n: int) -> List[str]:
    """The first ``n`` chips' planes of the trace, by device number."""
    def number(name):
        m = _CHIP_PLANE.match(name)
        return int(m.group(1)) if m else -1
    return sorted(tr.ops, key=number)[:n]


@dataclass
class Trace:
    #: chip id -> [(op name, start_ns, end_ns)]
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    #: chip id -> [(module name, start_ns, end_ns)]
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)
    #: [(span name without the prefix, start_ns, end_ns)]
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    #: plane name -> {line name: events}, for reading a trace by hand
    layout: Dict[str, Dict[str, int]] = field(default_factory=dict)


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def load(path: str, device_plane: Callable[[str], bool] = is_device_plane,
         op_lines: Sequence[str] = ("XLA Ops",),
         module_lines: Sequence[str] = ("XLA Modules",)) -> Trace:
    """Read the ``.xplane.pb`` at ``path``.  ``device_plane`` picks the
    planes whose ``op_lines`` hold device operations (a test on the CPU
    backend points it at the host's XLA thread)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        chip = device_plane(plane.name)
        if chip:
            out.ops.setdefault(plane.name, [])
            out.modules.setdefault(plane.name, [])
        lines = out.layout[plane.name] = {}
        for line in plane.lines:
            dest = None
            if chip and line.name in op_lines:
                dest = out.ops[plane.name]
            elif chip and line.name in module_lines:
                dest = out.modules[plane.name]
            n = 0
            for ev in line.events:
                n += 1
                if dest is not None and ev.duration_ns > 0:
                    dest.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
                elif ev.name.startswith(SPAN_PREFIX):
                    out.spans.append((ev.name[len(SPAN_PREFIX):],
                                      ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
            lines[line.name] = n
    return out


# ------------------------------------------------------------ intervals
def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, non-overlapping cover of ``intervals``."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(b - a for a, b in clip(union(intervals), lo, hi))


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for a, b in clip(union(intervals), lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def spans_at(spans: Sequence[Tuple[str, float, float]],
             times: Sequence[float]) -> List[str]:
    """For each of ``times``, the innermost (shortest) host span open
    then, or ``"other"``: one sweep over spans sorted by start."""
    order = sorted(range(len(times)), key=lambda i: times[i])
    todo = sorted((a, b, name) for name, a, b in spans
                  if name != WINDOW_SPAN)
    out = ["other"] * len(times)
    active: List[Tuple[float, float, str]] = []
    k = 0
    for i in order:
        t = times[i]
        while k < len(todo) and todo[k][0] <= t:
            active.append(todo[k])
            k += 1
        active = [s for s in active if s[1] > t]
        if active:
            out[i] = min(active, key=lambda s: s[1] - s[0])[2]
    return out


# ------------------------------------------------------------- reduction
@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over the chips used
    module_s: Dict[str, float]          # module name -> seconds, all chips
    module_n: Dict[str, int]
    top_ops: List[Tuple[str, float]]    # by self time, all chips
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(tr: Trace) -> Interval:
    ws = [(a, b) for name, a, b in tr.spans if name == WINDOW_SPAN]
    if len(ws) != 1:
        raise ValueError(f"expected one '{SPAN_PREFIX}{WINDOW_SPAN}' span in "
                         f"the trace, found {len(ws)}")
    return ws[0]


def _module_key(name: str) -> str:
    # "jit_train_step(1234)" -> "jit_train_step"
    return name.split("(", 1)[0]


_OPCODE = re.compile(r"(?:^|[\s}])([a-z][a-z0-9-]*)\(")
_KIND = re.compile(r"kind=(\w+)")


def op_key(name: str) -> str:
    """An operation's short name: a TPU trace names each operation by its
    whole HLO instruction, ``%fusion.3 = (f32[..]..) fusion(..),
    kind=kLoop, ..``, which becomes ``%fusion.3 fusion kLoop``."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    parts = [lhs]
    op = _OPCODE.search(rhs)
    if op:
        parts.append(op.group(1))
    kind = _KIND.search(rhs)
    if kind:
        parts.append(kind.group(1))
    return " ".join(parts)


def self_times(ops: Sequence[Tuple[str, float, float]]
               ) -> List[Tuple[str, float, float, float]]:
    """Each operation with its self time: its interval less the
    operations nested inside it (a ``while`` holds its body's
    operations on the same line).  Returns (name, start, end, self)."""
    out: List[List] = []
    stack: List[int] = []
    for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and out[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= out[stack[-1]][2]:
            out[stack[-1]][3] -= b - a
        out.append([name, a, b, b - a])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def reduce(tr: Trace, chips: Sequence[str], top: int = 10) -> Reduced:
    """Busy, idle and per-module time of ``chips`` inside the window."""
    if not chips:
        raise ValueError("no device plane in the trace")
    lo, hi = window_of(tr)
    busy = 0.0
    op_time: Dict[str, float] = defaultdict(float)
    mod_time: Dict[str, float] = defaultdict(float)
    mod_n: Dict[str, int] = defaultdict(int)
    gap_time: Dict[str, float] = defaultdict(float)
    for chip in chips:
        ops = tr.ops.get(chip, [])
        iv = [(a, b) for _, a, b in ops]
        busy += covered(iv, lo, hi)
        for name, a, b, own in self_times(ops):
            if a >= lo and b <= hi:
                op_time[op_key(name)] += own
            else:
                # cut by the window's edge: its share of the self time
                for a2, b2 in clip([(a, b)], lo, hi):
                    op_time[op_key(name)] += own * (b2 - a2) / (b - a)
        for name, a, b in tr.modules.get(chip, []):
            for a2, b2 in clip([(a, b)], lo, hi):
                mod_time[_module_key(name)] += b2 - a2
                mod_n[_module_key(name)] += 1
        idle = gaps(iv, lo, hi)
        for (a, b), name in zip(idle, spans_at(
                tr.spans, [(a + b) / 2 for a, b in idle])):
            gap_time[name] += (b - a) / len(chips)
    ns = 1e-9
    return Reduced(
        window_s=(hi - lo) * ns, busy_s=busy / len(chips) * ns,
        module_s={k: v * ns for k, v in mod_time.items()},
        module_n=dict(mod_n),
        top_ops=sorted(((k, v * ns) for k, v in op_time.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v * ns) for k, v in gap_time.items()),
                         key=lambda kv: -kv[1])[:top])
