"""Device-idle time that lies under the program's spans ``spans`` and
under none of ``except_spans``, percent of the traced window.  Idle is
the complement of the union of the first chip's op intervals (as
``tracelib.idle_gaps`` takes it), every gap, not the longest; the spans
are the program's own, riding on the trace's host plane as annotations.
Readers over disjoint span sets give parts of ``device_idle_share``.
No such span in the trace → nothing to read."""

from chipbench import tracelib


def intersect(a, b):
    """The overlap of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """What is left of the disjoint intervals ``a`` outside ``b``."""
    out = []
    for lo, hi in a:
        for s, e in b:
            if e <= lo or s >= hi:
                continue
            if s > lo:
                out.append((lo, s))
            lo = max(lo, e)
        if hi > lo:
            out.append((lo, hi))
    return out


def idle_intervals(trace):
    """Where no op ran on the first chip, inside the window."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = tracelib.union((s, e) for s, e, _ in ops)
    return subtract([trace.window], busy)


def read(run, spans, except_spans=()):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    trace = tr["trace"]
    under = tracelib.union((s, e) for s, e, n in trace.host_spans
                           if n in spans)
    if not under:
        return None
    outside = tracelib.union((s, e) for s, e, n in trace.host_spans
                             if n in except_spans)
    idle = intersect(idle_intervals(trace), subtract(under, outside))
    return 100.0 * sum(e - s for s, e in idle) / tr["window_s"]
