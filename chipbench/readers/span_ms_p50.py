"""Median duration of the program's spans called ``span`` in the traced
stretch, ms (the program's own clock around the call)."""

import statistics


def read(run, span):
    tr = run.get("trace")
    if not tr:
        return None
    durs = [e["dur"] for e in tr.get("spans", ()) if e["name"] == span]
    return statistics.median(durs) / 1e3 if durs else None
