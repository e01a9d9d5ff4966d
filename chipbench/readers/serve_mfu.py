"""The whole serving step's share of the chip's peak: model FLOPs of
every prompt whose prefill ran in the window and of every output token
emitted in it (``flops/<config>.py``), over window x peak bf16 FLOP/s,
percent.  A request's tokens are taken as evenly spaced between its
first and its last (the server keeps no per-token time)."""

from chipbench import harness as H


def tokens_between(r, a, b):
    """(count, mean context) of r's decode tokens emitted in [a, b]."""
    if r["t_first"] is None:
        return 0.0, 0.0
    n = len(r["tokens"]) if r["tokens"] is not None else r["n_at_close"]
    end = r["t_done"] if r.get("t_done") is not None else r["t_close"]
    if n <= 1 or end <= r["t_first"]:
        return 0.0, 0.0
    rate = (n - 1) / (end - r["t_first"])
    lo = max(a, r["t_first"])
    hi = min(b, end)
    if hi <= lo:
        return 0.0, 0.0
    k0, k1 = (lo - r["t_first"]) * rate, (hi - r["t_first"]) * rate
    return k1 - k0, len(r["prompt"]) + 1 + 0.5 * (k0 + k1)


def read(run):
    cell, ctx = run["cell"], run["ctx"]
    fl = H.load_module("flops", cell.config_name, ctx["here"])
    sizes = run["sizes"]
    t0, t1 = run["window"]
    total = 0.0
    for r in run["requests"]:
        if r["t_first"] is not None and t0 <= r["t_first"] <= t1:
            total += fl.prefill_flops(sizes, len(r["prompt"]))
        k, ctxlen = tokens_between(r, t0, t1)
        total += k * fl.token_flops(sizes, ctxlen)
    if total <= 0:
        return None
    return 100.0 * total / ((t1 - t0) * ctx["peaks"]["bf16_flops_per_s"])
