"""Reduction of a profiler trace (``.xplane.pb``) to what the readers
ask for: per-chip device busy time as a union of intervals, kernel time
by name pattern, the top device ops, and the longest idle gaps named by
the host span that covers them.

Read with nothing but JAX (``jax.profiler.ProfileData``).  Times are
seconds.  Checked on a recorded trace in ``tests/test_tracelib.py``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
#: the host span the drivers put around the traced part of the window
WINDOW_SPAN = "chipbench_window"

Interval = Tuple[float, float]          # (start_s, end_s)
Event = Tuple[float, float, str]        # (start_s, end_s, name)


class Trace:
    """``device_ops[plane]`` = op events of one chip, ``host_spans`` =
    named host events (the program's spans ride in as TraceAnnotations),
    ``window`` = the traced window's (start, end)."""

    def __init__(self, device_ops: Dict[str, List[Event]],
                 host_spans: List[Event],
                 window: Optional[Interval] = None):
        self.device_ops = device_ops
        self.host_spans = host_spans
        if window is None:
            found = [e for e in host_spans if e[2] == WINDOW_SPAN]
            if found:
                window = (found[0][0], found[0][1])
            else:
                every = [e for ops in device_ops.values() for e in ops]
                window = (min(e[0] for e in every),
                          max(e[1] for e in every)) if every else (0., 0.)
        self.window = window
        lo, hi = window
        self.device_ops = {
            p: sorted((max(s, lo), min(e, hi), n) for s, e, n in ops
                      if e > lo and s < hi)
            for p, ops in device_ops.items()}

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                ops = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((s, s + ev.duration_ns * 1e-9, ev.name))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    host.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return Trace(device_ops, host)


# ------------------------------------------------------------- reductions
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the chips traced."""
    if not trace.device_ops:
        return 0.0
    per_chip = [sum(e - s for s, e in union((s, e) for s, e, _ in ops))
                for ops in trace.device_ops.values()]
    return sum(per_chip) / len(per_chip)


def kernel_seconds(trace: Trace, patterns: Sequence[str]
                   ) -> Tuple[float, int]:
    """(seconds, events) of the ops whose name matches any pattern,
    averaged over the chips.  Nested events are not double counted:
    the union of the matching intervals is taken per chip."""
    regs = [re.compile(p) for p in patterns]
    total, count = 0.0, 0
    for ops in trace.device_ops.values():
        hit = [(s, e) for s, e, n in ops if any(r.search(n) for r in regs)]
        count += len(hit)
        total += sum(e - s for s, e in union(hit))
    n_chips = max(1, len(trace.device_ops))
    return total / n_chips, count


def short_name(name: str) -> str:
    """An op event carries its whole HLO line.  Keep what tells ops
    apart: the instruction's name without the digits that number its
    instances, the shape it produces and, for a custom call, its
    target."""
    head, _, rest = name.partition(" = ")
    key = re.sub(r"[.\-_]\d+$", "", head.lstrip("%"))
    if not rest:
        return key
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    parts = [key] + ([shape.group(0).lstrip("(")] if shape else []) \
        + ([target.group(1)] if target else [])
    return " ".join(parts)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The n ops with most device time, by :func:`short_name`, summed
    over the chips."""
    acc: Dict[str, float] = {}
    for ops in trace.device_ops.values():
        for s, e, name in ops:
            key = short_name(name)
            acc[key] = acc.get(key, 0.0) + (e - s)
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in rows]


def idle_gaps(trace: Trace, n: int = 10,
              names: Optional[Sequence[str]] = None) -> List[List]:
    """The n longest gaps in which no op ran on the first chip, each
    named by the innermost host span that covers the gap's midpoint.
    ``names`` keeps only those host events (the program's spans; the
    host plane also holds the runtime's and the interpreter's own)."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    lo, hi = trace.window
    busy = union((s, e) for s, e, _ in ops)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    spans = [h for h in trace.host_spans if h[2] != WINDOW_SPAN
             and (names is None or h[2] in names)]
    out = []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [h for h in spans if h[0] <= mid <= h[1]]
        name = min(cover, key=lambda h: h[1] - h[0])[2] if cover \
            else "no_span"
        out.append([name, e - s])
    return out
