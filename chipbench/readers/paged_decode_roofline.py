"""Paged decode attention's share of its roofline in the traced
stretch: the least time the chip needs for the work (the larger of its
FLOPs over peak and the bytes of live K/V over peak bandwidth, both
from ``flops/<config>.py`` and the requests' own lengths) over the
device time of the ops matching ``patterns``, percent.  Nothing
matched → nothing to read."""

from chipbench import harness as H
from chipbench import tracelib
tokens_between = H.load_module("readers", "serve_mfu").tokens_between


def read(run, patterns):
    tr = run.get("trace")
    if not tr:
        return None
    secs, n = tracelib.kernel_seconds(tr["trace"], patterns)
    if not n or secs <= 0:
        return None
    cell, ctx = run["cell"], run["ctx"]
    fl = H.load_module("flops", cell.config_name, ctx["here"])
    sizes = run["sizes"]
    a, b = tr["span"]
    live_tokens = 0.0
    for r in run["requests"]:
        k, ctxlen = tokens_between(r, a, b)
        live_tokens += k * ctxlen
    flops = fl.decode_attention_flops(sizes, 1.0) * live_tokens
    nbytes = fl.kv_bytes_per_token(sizes) * live_tokens
    least = max(flops / ctx["peaks"]["bf16_flops_per_s"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs
