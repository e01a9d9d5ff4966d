"""Device time of the ops whose name matches ``patterns`` over the
device's busy time in the traced stretch, percent.  Nothing matched →
nothing to read."""

from chipbench import tracelib


def read(run, patterns):
    tr = run.get("trace")
    if not tr or tr["busy_s"] <= 0:
        return None
    secs, n = tracelib.kernel_seconds(tr["trace"], patterns)
    return 100.0 * secs / tr["busy_s"] if n else None
