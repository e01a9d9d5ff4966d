"""A kernel's share of its roofline, its work taken from the program's
own spans: the device time of the ops matching ``patterns`` INSIDE the
spans called ``span`` (their annotations on the trace's host plane),
against the least time for the work those same spans state — attribute
``attr`` (in the ring copy of each span) times the per-unit FLOPs
``flops_fn`` and bytes ``bytes_fn`` of ``flops/<config>.py`` — percent.
A span cut by an edge of the stretch is left out on both sides.  No
such span, no such attribute or no such op → nothing to read.

The ring's clock (epoch microseconds) and the trace's (seconds from its
start) differ by a constant; a span is in the ring and not on the host
plane if it opened before the profiler did, and the other way round if
it closed after the ring was copied.  :func:`pair` finds the shift of
one list against the other under which that constant is one number."""

import statistics

from chipbench import harness as H
from chipbench import tracelib

#: the two clocks of one span agree to a few microseconds (the
#: annotation opens just before the span's own clock starts)
CLOCK_TOLERANCE_S = 200e-6


def pair(host, ring):
    """[(host event, ring span)] for the spans that are in both lists
    (each sorted by start).  The ring's leading extras are skipped by
    the shift under which the differences of the two clocks, at the
    spans' starts and ends, agree best (their median deviation from
    their median: a pair may be off by a pre-empted thread, the pairing
    by index still holds)."""
    best = None
    for shift in range(len(ring)):
        both = list(zip(host, ring[shift:]))
        if len(both) < 2:
            break
        diffs = [d for h, r in both
                 for d in (h[0] - r["ts"] * 1e-6,
                           h[1] - (r["ts"] + r["dur"]) * 1e-6)]
        mid = statistics.median(diffs)
        off = statistics.median(abs(d - mid) for d in diffs)
        if best is None or off < best[0]:
            best = (off, both)
    if best is None or best[0] > CLOCK_TOLERANCE_S:
        return []
    return best[1]


def read(run, span, attr, patterns, flops_fn, bytes_fn):
    tr = run.get("trace")
    if not tr:
        return None
    trace = tr["trace"]
    lo, hi = trace.window
    host = sorted(e for e in trace.host_spans if e[2] == span)
    ring = sorted((e for e in tr.get("spans", ()) if e["name"] == span
                   and attr in e.get("args", {})), key=lambda e: e["ts"])
    whole = [(h, r) for h, r in pair(host, ring)
             if h[0] >= lo and h[1] <= hi]
    if not whole:
        return None
    inside = tracelib.Trace(
        {p: [(max(s, h[0]), min(e, h[1]), n) for s, e, n in ops
             for h, _ in whole if e > h[0] and s < h[1]]
         for p, ops in trace.device_ops.items()}, [], trace.window)
    secs, events = tracelib.kernel_seconds(inside, patterns)
    if not events or secs <= 0:
        return None
    cell, ctx = run["cell"], run["ctx"]
    fl = H.load_module("flops", cell.config_name, ctx["here"])
    units = sum(float(r["args"][attr]) for _, r in whole)
    least = max(
        getattr(fl, flops_fn)(run["sizes"], 1.0) * units
        / ctx["peaks"]["bf16_flops_per_s"],
        getattr(fl, bytes_fn)(run["sizes"]) * units
        / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
