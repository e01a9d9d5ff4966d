"""Profiling & tracing (SURVEY §5 aux subsystems).

The reference aggregates RAII wall timers per named section
(``REGISTER_TIMER``/``StatSet``, ``paddle/utils/Stat.h:63-242``) and opens
nvprof windows via ``hl_profiler_start/end``
(``hl_cuda_device.cc:675-677``).  TPU equivalents:

- named wall timers: :mod:`paddle_tpu.utils.stat` (already per-section);
- device traces: :func:`trace` wraps ``jax.profiler`` so a window of
  steps lands in an xprof/TensorBoard trace directory;
- FP-fault trapping (``feenableexcept`` in ``TrainerMain.cpp:49``):
  :func:`enable_fp_exceptions` flips ``jax_debug_nans``/``jax_debug_infs``
  so the first NaN/Inf inside a jitted computation raises at the op.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import socket
import threading
import time
from typing import Iterator, Optional

import jax
from jax._src import profiler as _jax_profiler

from ..analysis.lockorder import named_lock
from .logger import get_logger, warn_once

log = get_logger("profiler")

# open-window bookkeeping: jax.profiler.start_trace is NOT re-entrant
# (a nested start raises), so only the outermost trace() opens/closes
# the window and inner uses are warn-once no-ops.  The depth doubles as
# the "is an xprof window open" signal observe.trace keys on to wrap
# spans in TraceAnnotations (host-span <-> XLA-op correlation).
_depth_lock = named_lock("profiler.depth")
_trace_depth = 0


def trace_active() -> bool:
    """True while an xprof window opened by :func:`trace` is live."""
    return _trace_depth > 0


def _stop_trace() -> None:
    """``jax.profiler.stop_trace`` less its second export.  The
    session's XSpace is written where jax writes it,
    ``<logdir>/plugins/profile/<time>/<host>.xplane.pb`` — what
    TensorBoard, xprof and ``jax.profiler.ProfileData`` read; the
    Chrome-trace JSON that jax derives from it besides is not made: at
    the million device events that ten seconds of a server at 70 steps
    a second leave, it is a large part of the time the stop holds its
    caller (PERF.md §6, PR 28)."""
    state = _jax_profiler._profile_state
    with state.lock:
        if state.profile_session is None:
            raise RuntimeError("No profile started")
        logdir = str(state.log_dir)
        xspace = state.profile_session.stop()
        state.reset()
    run = os.path.join(logdir, "plugins", "profile",
                       time.strftime("%Y_%m_%d_%H_%M_%S"))
    os.makedirs(run, exist_ok=True)
    with open(os.path.join(run, socket.gethostname() + ".xplane.pb"),
              "wb") as f:
        f.write(xspace)


# glibc's mallopt parameters and, per phase, what they are set to.  The
# stop of a window builds the session's XSpace, copies it and serializes
# it: hundreds of megabytes in buffers that grow by doubling, each of
# which glibc's own thresholds would give an mmap of its own.  Measured,
# behind a server at 210 launches a second: with large blocks kept on
# the heap and the heap not trimmed, the stop of a ten-second window
# takes 8-10 s where it took 20-25, whatever ProfileOptions said
# (PERF.md §6, PR 31).  The settings hold from a window's start to its
# stop; glibc's own come back behind it, as fixed values (a threshold
# once set is no longer adjusted by glibc as the process allocates).
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
_HEAP_WHILE_COLLECTING = ((_M_TRIM_THRESHOLD, 1 << 30),
                          (_M_MMAP_THRESHOLD, 1 << 30),
                          (_M_TOP_PAD, 1 << 28))
_HEAP_AS_IT_COMES = ((_M_TRIM_THRESHOLD, 128 << 10),         # glibc's own
                     (_M_MMAP_THRESHOLD, 128 << 10), (_M_TOP_PAD, 128 << 10))


def _tune_heap(settings) -> None:
    """``mallopt`` each (parameter, value) where the process's C
    library has it (glibc); elsewhere the allocator is left alone."""
    mallopt = getattr(ctypes.CDLL(None), "mallopt", None)
    if mallopt is not None:
        for parameter, value in settings:
            mallopt(parameter, value)


@contextlib.contextmanager
def trace(logdir: str = "/tmp/paddle_tpu_trace") -> Iterator[None]:
    """``with profiler.trace(dir): ...`` — xprof window (nvprof-window
    equivalent); view with TensorBoard's profile plugin.

    Re-entrancy-safe: a nested ``trace`` (around a code path that
    opens its own window) warns once and rides
    the already-open window instead of raising.  Windows are
    tick-counted (``profiler_trace_windows_total``) so a run's artifact
    records how many xprof dumps it produced."""
    global _trace_depth
    with _depth_lock:
        nested = _trace_depth > 0
        _trace_depth += 1
    try:
        if nested:
            warn_once("profiler_trace_nested",
                      "nested profiler.trace(%r): jax.profiler windows "
                      "don't nest — riding the already-open window "
                      "(reported once)", logdir, logger=log)
            yield
            return
        from .. import observe

        # the device, the runtime's host events and the program's own
        # spans (TraceAnnotations), not every Python call: behind a
        # server at 70 steps a second the Python tracer leaves a
        # million events in ten seconds, nine in ten of the host's
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        _tune_heap(_HEAP_WHILE_COLLECTING)
        try:
            jax.profiler.start_trace(logdir, profiler_options=options)
            observe.counter("profiler_trace_windows_total",
                            "xprof/jax.profiler trace windows opened"
                            ).inc()
            log.info("profiler trace started → %s", logdir)
            try:
                yield
            finally:
                _stop_trace()
                log.info("profiler trace written to %s", logdir)
        finally:
            _tune_heap(_HEAP_AS_IT_COMES)
    finally:
        with _depth_lock:
            _trace_depth -= 1


def annotate(name: str):
    """Named sub-trace region (``REGISTER_TIMER_INFO`` equivalent inside
    traced code)."""
    return jax.profiler.TraceAnnotation(name)


def enable_fp_exceptions(enable: bool = True) -> None:
    """Trap NaN/Inf produced by jitted computations — the
    ``feenableexcept(FE_INVALID|FE_DIVBYZERO|FE_OVERFLOW)`` equivalent."""
    jax.config.update("jax_debug_nans", enable)
    jax.config.update("jax_debug_infs", enable)


def parameter_stats(params) -> str:
    """Per-parameter |value| stats line (``--show_parameter_stats_period``,
    ``TrainerInternal.cpp:99-111``)."""
    import numpy as np

    # ONE device_get over the whole dict: per-param serial gets pay a
    # D2H round-trip each (hundreds of sync points on a big model);
    # batching lets jax gather every leaf in a single transfer
    values = jax.device_get(dict(params))
    rows = []
    for name in sorted(values):
        v = np.asarray(values[name])
        rows.append(f"{name}: shape={tuple(v.shape)} "
                    f"absmax={np.abs(v).max():.4g} "
                    f"mean={v.mean():.4g} std={v.std():.4g}")
    return "\n".join(rows)
