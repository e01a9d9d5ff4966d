"""Pooled time per output token of the requests that finished in the
window: sum(t_done - t_first) / sum(n - 1), ms (the server's Request
clocks; there is no per-token time, the server does not stream)."""


def read(run):
    t0, t1 = run["window"]
    num = den = 0.0
    for r in run["requests"]:
        if r["state"] == "done" and t0 <= r["t_done"] <= t1 \
                and len(r["tokens"]) > 1:
            num += r["t_done"] - r["t_first"]
            den += len(r["tokens"]) - 1
    return 1e3 * num / den if den else None
