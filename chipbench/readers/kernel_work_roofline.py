"""Named Pallas kernels' share of their roofline in the traced stretch.

For each entry of ``kernels`` (the program's kernel name → the pattern
that finds its op events): the least time the chip needs for one call —
the larger of its FLOPs over the peak and its bytes over the peak
bandwidth, both the mean per traced call of the program's own
``pallas_kernel_work_total{kernel, kind}`` — times the matching events,
summed over the kernels, over the device time of those events, percent.
A program without the counter, or a trace without the kernels → nothing
to read."""

from chipbench import tracelib

COUNTER = "pallas_kernel_work_total"


def work_per_call():
    """{kernel: (flops, bytes) of its mean traced call}."""
    from paddle_tpu import observe

    metric = observe.REGISTRY.find(COUNTER)
    rows = {}
    for s in (metric.samples() if metric is not None else ()):
        labels = s["labels"]
        rows.setdefault(labels["kernel"], {})[labels["kind"]] = s["value"]
    return {k: (r["flops"] / r["calls"], r["bytes"] / r["calls"])
            for k, r in rows.items() if r.get("calls")}


def read(run, kernels):
    tr = run.get("trace")
    if not tr:
        return None
    peaks = run["ctx"]["peaks"]
    work = work_per_call()
    n_chips = max(1, len(tr["trace"].device_ops))
    least = secs = 0.0
    for kernel, pattern in kernels.items():
        took, events = tracelib.kernel_seconds(tr["trace"], [pattern])
        if not events or kernel not in work:
            continue
        flops, nbytes = work[kernel]
        least += events / n_chips * max(
            flops / peaks["bf16_flops_per_s"],
            nbytes / peaks["hbm_bytes_per_s"])
        secs += took
    return 100.0 * least / secs if secs > 0 else None
