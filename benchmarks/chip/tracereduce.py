"""Reduction of one profiler trace to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote, keeping
three kinds of events: each TPU's operations (line "XLA Ops"), each TPU's
program executions (line "XLA Modules"), and the host spans the probe
opened (``jax.profiler.TraceAnnotation``, names in ``host_names``).  The
rest of this file is plain arithmetic on those events, so the tests can
check it on a small recorded trace without a chip.

The traced window is the host span ``WINDOW``.  Busy time is the union of a
device's operation intervals inside it, averaged over the devices; an idle
gap is a stretch of the window in which no operation runs, labelled by the
innermost host span around its midpoint.  Operations nest (a ``while``
holds its body's operations), so an operation's time in the breakdown is
its self time, and its name is the HLO instruction's name alone.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import pathlib
import sys
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.traced"
UNLABELLED = "host.other"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> operations
    modules: Dict[str, List[Event]]    # device plane -> program executions
    host: List[Event]                  # the probe's host spans


def _is_device(plane_name: str) -> bool:
    prefix = "/device:TPU:"
    return plane_name.startswith(prefix) and plane_name[len(prefix):].isdigit()


def load(profile_dir, host_names: Sequence[str]) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    keep = set(host_names) | {WINDOW}
    trace = Trace({}, {}, [])

    def events(line, name=lambda n: n, names=None):
        return [Event(name(e.name), e.start_ns * 1e-9,
                      (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events if names is None or e.name in names]

    for plane in data.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    trace.ops.setdefault(plane.name, []).extend(
                        events(line, short_name))
                elif line.name == "XLA Modules":
                    trace.modules.setdefault(plane.name, []).extend(
                        events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                trace.host.extend(events(line, names=keep))
    return trace


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, w0: float, w1: float) -> List[Tuple[float, float]]:
    return [(max(s, w0), min(e, w1)) for s, e in intervals if e > w0 and s < w1]


def gaps(busy: Sequence[Tuple[float, float]], w0: float,
         w1: float) -> List[Tuple[float, float]]:
    """The stretches of [w0, w1) that the sorted, disjoint ``busy``
    intervals leave uncovered."""
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < w1:
        out.append((t, w1))
    return out


class Labels:
    """What the host was doing at a time: the innermost of the nested host
    spans (one thread's, so they nest) around it, the window excepted."""

    def __init__(self, spans: Sequence[Event]):
        self.segs: List[Tuple[float, float, str]] = []
        stack: List[Event] = []
        cursor = float("-inf")

        def close_until(t):
            nonlocal cursor
            while stack and stack[-1].end <= t:
                top = stack.pop()
                self._emit(cursor, top.end, top.name)
                cursor = max(cursor, top.end)

        for s in sorted((s for s in spans if s.name != WINDOW),
                        key=lambda s: (s.start, -s.end)):
            close_until(s.start)
            if stack:
                self._emit(cursor, s.start, stack[-1].name)
            cursor = s.start
            stack.append(s)
        close_until(float("inf"))
        self.starts = [s for s, _, _ in self.segs]

    def _emit(self, s: float, e: float, name: str) -> None:
        if e > s:
            self.segs.append((s, e, name))

    def __call__(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        return self.segs[i][2] if i >= 0 and t < self.segs[i][1] \
            else UNLABELLED


def short_name(op: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return sys.intern(op.split(" = ", 1)[0].lstrip("%"))


def self_times(events: Sequence[Event], w0: float, w1: float) -> Dict[str, float]:
    """Seconds inside [w0, w1) of each operation name (short names, as
    ``load`` keeps them), minus the time of the operations nested in it."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[Event] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= e.start:
            stack.pop()
        inside = max(0.0, min(e.end, w1) - max(e.start, w0))
        out[e.name] += inside
        if stack:
            parent = stack[-1]
            out[parent.name] -= max(
                0.0, min(e.end, parent.end, w1) - max(e.start, w0))
        stack.append(e)
    return out


def window(trace: Trace) -> Optional[Tuple[float, float]]:
    spans = [s for s in trace.host if s.name == WINDOW]
    return (spans[0].start, spans[0].end) if spans else None


def reduce(trace: Trace, module_prefix: str) -> Optional[dict]:
    """busy_s (averaged over devices), window_s, idle_share, the durations
    of the executions of programs whose name starts with
    ``module_prefix``, and the breakdown: top device operations by time and
    idle seconds by what the host was doing.  None if the trace holds no
    window or no device operation."""
    win = window(trace)
    if win is None or not any(trace.ops.values()):
        return None
    w0, w1 = win
    busy_s, idle_by = [], collections.Counter()
    op_time = collections.Counter()
    labels = Labels(trace.host)
    for ops in trace.ops.values():
        inside = clip([(e.start, e.end) for e in ops], w0, w1)
        merged = union(inside)
        busy_s.append(sum(e - s for s, e in merged))
        for s, e in gaps(merged, w0, w1):
            idle_by[labels((s + e) / 2)] += e - s
        op_time.update(self_times(ops, w0, w1))
    n_dev = len(trace.ops)
    window_s = w1 - w0
    busy = sum(busy_s) / n_dev
    steps = sorted((e for mods in trace.modules.values() for e in mods
                    if e.name.startswith(module_prefix)
                    and e.start >= w0 and e.end <= w1),
                   key=lambda e: e.start)
    return {
        "busy_s": busy, "window_s": window_s,
        "idle_share": 1.0 - busy / window_s,
        "step_device_s": [e.end - e.start for e in steps],
        "device_ops": [[n, t / n_dev] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[n, t / n_dev] for n, t in idle_by.most_common(TOP)],
    }
