"""Device time of the ops that lie under the program's scopes ``under``
(and under none of ``except_under``), less the ops whose HLO line
matches one of ``except_patterns``, over the device's busy time in the
traced stretch, percent.  ``complement`` turns the choice of scopes
round: the ops under none of ``under``.

An op's scope is the ``tf_op`` its HLO line has in the table that the
program's profiler window kept (``paddle_tpu/utils/profiler.py::
last_window_ops``; a scope is matched as whole path components,
``in_scope`` there), and its time is exclusive: a ``while`` counts what
its body's ops leave, so the shares of scopes that share no op add up
to at most 100.

A program whose profiler keeps no table names nothing: every share of
it reads 0 and a ``complement`` reads all that ``except_patterns``
leaves, which is true of that program.  Nothing to read: no trace, or
a table in which under half of the busy time finds its HLO line (the
join of event names and table is broken: never a 0 in its place)."""

import re

from chipbench import tracelib


def rows_of(trace, table):
    """``(path, HLO line, seconds, the table has the line)`` of every
    HLO line of every chip's ops."""
    from paddle_tpu.utils import profiler

    out = []
    for plane, events in trace.device_ops.items():
        ops = table.get(plane, {})
        out += [(path, line, seconds, line in ops) for path, line, seconds
                in profiler.scope_seconds(events, ops)]
    return out


def read(run, under, except_under=(), except_patterns=(),
         complement=False):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    from paddle_tpu.utils import profiler

    trace, busy = tr["trace"], tr["busy_s"]
    table = getattr(profiler, "last_window_ops", lambda: None)()
    if table is None:
        if not complement:
            return 0.0
        excepted, _ = tracelib.kernel_seconds(trace, except_patterns)
        return 100.0 * (busy - excepted) / busy
    if "scope_rows" not in tr:           # one pass over the events a run
        tr["scope_rows"] = rows_of(trace, table)
    rows = tr["scope_rows"]
    if sum(r[2] for r in rows if r[3]) < 0.5 * sum(r[2] for r in rows):
        return None
    excepted = [re.compile(p) for p in except_patterns]
    total = 0.0
    for path, line, seconds, _ in rows:
        chosen = any(profiler.in_scope(path, s) for s in under) \
            and not any(profiler.in_scope(path, s) for s in except_under)
        if chosen != complement \
                and not any(r.search(line) for r in excepted):
            total += seconds
    return 100.0 * total / (busy * max(1, len(trace.device_ops)))
