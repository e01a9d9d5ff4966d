"""Device time inside the program's spans called ``span`` that is NOT
in an op matching ``except_patterns``, per span, milliseconds: what a
step runs beside its kernels.  The spans are taken from their
annotations on the trace's host plane; one cut by an edge of the stretch
is left out.  Busy time is the union of the op intervals inside the
spans (nested events count once), and the excepted ops' union is taken
out of it.  No such span, or no op inside one → nothing to read."""

from chipbench import tracelib


def read(run, span, except_patterns):
    tr = run.get("trace")
    if not tr:
        return None
    trace = tr["trace"]
    lo, hi = trace.window
    whole = [h for h in trace.host_spans
             if h[2] == span and h[0] >= lo and h[1] <= hi]
    if not whole:
        return None
    inside = tracelib.Trace(
        {p: [(max(s, h[0]), min(e, h[1]), n) for s, e, n in ops
             for h in whole if e > h[0] and s < h[1]]
         for p, ops in trace.device_ops.items()}, [], trace.window)
    busy = tracelib.busy_seconds(inside)
    if busy <= 0:
        return None
    excepted, _ = tracelib.kernel_seconds(inside, except_patterns)
    return 1e3 * (busy - excepted) / len(whole)
