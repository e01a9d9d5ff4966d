"""Median submit-to-first-token time of the requests that got their
first token in the window, ms (the server's Request clocks)."""

import statistics


def read(run):
    t0, t1 = run["window"]
    v = [r["t_first"] - r["t_submit"] for r in run["requests"]
         if r["t_first"] is not None and t0 <= r["t_first"] <= t1]
    return 1e3 * statistics.median(v) if v else None
