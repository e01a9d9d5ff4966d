"""One traced stretch of a window: the program's own profiler window
(so that its spans ride into the trace as annotations), the span the
reduction clips to, and the reduction itself.  The trace directory lies
inside the checkout, is listed in ``.gitignore`` and is removed once
read: a run writes little to disk."""

from __future__ import annotations

import contextlib
import os
import shutil
from typing import Any, Dict

from chipbench import tracelib


@contextlib.contextmanager
def traced(ctx: Dict[str, Any], out: Dict[str, Any]):
    """``with traced(ctx, out): <drive the device>`` fills ``out`` with
    ``trace`` (a :class:`tracelib.Trace`), ``busy_s``, ``window_s``,
    ``breakdown`` and the program's ``spans`` of the stretch."""
    import jax

    from paddle_tpu.observe import trace as ptrace
    from paddle_tpu.utils import profiler

    logdir = os.path.join(ctx["root"], ".chipbench_trace",
                          ctx["cell"].name)
    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    # ring only, no per-step fence: the traced stretch dispatches as
    # the timed one does
    ptrace.enable(fences=False)
    try:
        with profiler.trace(logdir):
            with jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN):
                yield
        out["spans"] = ptrace.events()
    finally:
        ptrace.disable()
    keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
    try:
        path = tracelib.find_xplane(logdir)
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(
                keep, ctx["cell"].name + ".xplane.pb"))
        tr = tracelib.load(path)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    out["trace"] = tr
    out["busy_s"] = tracelib.busy_seconds(tr)
    out["window_s"] = tr.window_s
    spans = {e["name"] for e in out.get("spans", ())}
    out["breakdown"] = {"device_ops": tracelib.top_ops(tr, 10),
                        "idle_gaps": tracelib.idle_gaps(tr, 10, spans)}
