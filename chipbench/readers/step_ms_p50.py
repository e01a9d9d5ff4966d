"""Median host time between the fences of consecutive steps, ms.  With
steps in flight this is the device's steady step time; a step shorter
than the host clock's half millisecond of error reads coarsely."""

import statistics


def read(run):
    f = run.get("step_fences") or []
    gaps = [b - a for a, b in zip(f, f[1:])]
    return 1e3 * statistics.median(gaps) if len(gaps) >= 3 else None
