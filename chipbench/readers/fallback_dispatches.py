"""Rows of the program's trace-time ``*_dispatch_total`` counters that
carry a non-empty ``reason``: dispatch sites that declined a kernel."""


def read(run):
    return float(sum(r["count"] for r in run["counters"]
                     if r.get("reason")))
