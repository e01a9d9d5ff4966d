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
import functools
import os
import re
import socket
import time
from typing import (Dict, Iterable, Iterator, NamedTuple, Optional, Tuple)

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
    global _window_ops
    t0 = time.perf_counter()
    _window_ops = read_ops(xspace)
    log.info("profiler window: %d HLO lines named in %.1f ms",
             sum(len(t) for t in _window_ops.values()),
             1e3 * (time.perf_counter() - t0))


# ---------------------------------------------------------------------------
# The names of what a window recorded.  On a device plane every op event
# points at an *event metadata* entry, one an HLO instruction, whose name
# is the instruction's HLO line (what ``jax.profiler.ProfileData`` gives
# as the event's name) and whose stats carry ``tf_op``: JAX's
# ``op_name``, the ``jax.named_scope`` stack with the transformations
# and the primitive (``jit(f)/transpose(jvp(batch_norm))/res2a_bn/mul:``).
# ``ProfileData`` shows an event's own stats and not its metadata's, so
# the table is read from the serialized XSpace itself: a walk of the
# protobuf wire format that needs no schema and no import, and skips a
# plane's ``lines`` (the events: all but a few MB of the file) by their
# length.  Field numbers from ``xplane.proto``.
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_METADATA_NAME, _EVENT_METADATA_STATS = 2, 5
_XSTAT_UINT64, _XSTAT_INT64 = 3, 4
_XSTAT_STR, _XSTAT_REF = 5, 7
_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


class OpInfo(NamedTuple):
    """What a window's device plane says of one HLO instruction.
    ``tf_op`` is its scope path (JAX's ``op_name`` less the ``:type``
    behind it), ``""`` where the compiler recorded none and None where
    two programs of the window give the one HLO line different paths."""
    tf_op: Optional[str]
    program_id: int
    bytes_accessed: int
    flops: int


#: plane name → HLO line → :class:`OpInfo`
OpTable = Dict[str, Dict[str, OpInfo]]
_window_ops: Optional[OpTable] = None


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = b[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int
            ) -> Iterator[Tuple[int, int, int, int]]:
    """The fields of the message in ``b[lo:hi]`` as (number, wire type,
    a, e): a varint's value in ``a``, a length-delimited field's extent
    ``b[a:e]`` (not copied, not entered).  A field that runs past ``hi``
    raises ``ValueError``."""
    i, cut = lo, "the XSpace ends inside a field: truncated"
    try:
        while i < hi:
            key, i = _varint(b, i)
            number, wire = key >> 3, key & 7
            if wire == _VARINT:
                value, i = _varint(b, i)
                yield number, wire, value, i
            elif wire == _BYTES:
                n, i = _varint(b, i)
                i += n
                if i <= hi:
                    yield number, wire, i - n, i
            elif wire in (_FIXED64, _FIXED32):
                i += 8 if wire == _FIXED64 else 4
            else:
                raise ValueError(f"wire type {wire} at byte {i}")
    except IndexError:
        raise ValueError(cut) from None
    if i != hi:
        raise ValueError(cut)


def _map_values(b: bytes, entries) -> Iterator[Tuple[int, int]]:
    """The extent of the value of each map entry in ``entries``."""
    for lo, hi in entries:
        for number, wire, a, e in _fields(b, lo, hi):
            if number == _MAP_VALUE and wire == _BYTES:
                yield a, e


def _plane_ops(b: bytes, event_metadata, stat_metadata
               ) -> Dict[str, OpInfo]:
    names: Dict[int, str] = {}
    for lo, hi in _map_values(b, stat_metadata):
        sid, name = 0, ""
        for number, wire, a, e in _fields(b, lo, hi):
            if number == 1 and wire == _VARINT:
                sid = a
            elif number == _METADATA_NAME and wire == _BYTES:
                name = b[a:e].decode("utf-8", "replace")
        names[sid] = name
    wanted = {sid for sid, name in names.items() if name in OpInfo._fields}
    out: Dict[str, OpInfo] = {}
    for lo, hi in _map_values(b, event_metadata):
        line, stats = "", {}
        for number, wire, a, e in _fields(b, lo, hi):
            if wire != _BYTES:
                continue
            if number == _METADATA_NAME:
                line = b[a:e].decode("utf-8", "replace")
            elif number == _EVENT_METADATA_STATS and b[a] == 0x08:
                # a stat opens with its metadata's id (field 1): one of
                # the dozen that is not wanted is not entered
                sid, a = _varint(b, a + 1)
                if sid not in wanted:
                    continue
                for n2, w2, a2, e2 in _fields(b, a, e):
                    if n2 in (_XSTAT_UINT64, _XSTAT_INT64):
                        stats[names[sid]] = a2
                    elif n2 == _XSTAT_STR and w2 == _BYTES:
                        stats[names[sid]] = b[a2:e2].decode("utf-8",
                                                            "replace")
                    elif n2 == _XSTAT_REF:
                        stats[names[sid]] = names.get(a2, "")
        # ``<op_name>:<op type>``, the type empty
        info = OpInfo(str(stats.get("tf_op") or "").rsplit(":", 1)[0],
                      int(stats.get("program_id") or 0),
                      int(stats.get("bytes_accessed") or 0),
                      int(stats.get("flops") or 0))
        before = out.get(line)
        if before is not None and before.tf_op != info.tf_op:
            info = info._replace(tf_op=None)
        out[line] = info
    return out


def read_ops(xspace: bytes) -> OpTable:
    """The table ``HLO line → OpInfo`` of every device plane of a
    serialized XSpace (the bytes of an ``.xplane.pb``): 0.2-0.4 s at
    the nine thousand HLO lines of a served decoder's window, whatever
    the number of events (PERF.md §6, PR 35).  ``ValueError`` on a
    truncated file."""
    table: OpTable = {}
    for number, wire, lo, hi in _fields(xspace, 0, len(xspace)):
        if number != _XSPACE_PLANES or wire != _BYTES:
            continue
        name, event_metadata, stat_metadata = "", [], []
        for n2, w2, a, e in _fields(xspace, lo, hi):
            if w2 != _BYTES:
                continue
            if n2 == _XPLANE_NAME:
                name = xspace[a:e].decode("utf-8", "replace")
            elif n2 == _XPLANE_EVENT_METADATA:
                event_metadata.append((a, e))
            elif n2 == _XPLANE_STAT_METADATA:
                stat_metadata.append((a, e))
        if name.startswith("/device:"):
            table[name] = _plane_ops(xspace, event_metadata, stat_metadata)
    return table


def last_window_ops() -> Optional[OpTable]:
    """The table of the last window :func:`trace` closed; None before
    the first has closed and while the next is open."""
    return _window_ops


def scope_seconds(events: Iterable[Tuple[float, float, str]],
                  ops: Dict[str, OpInfo]
                  ) -> Iterator[Tuple[Optional[str], str, float]]:
    """Device time by scope path: ``(path, HLO line, seconds)`` once an
    HLO line of ``events``, the ``(start, end, HLO line)`` op events of
    one device plane, with ``ops`` that plane's table.  The seconds are
    **exclusive**: an event that holds others (a ``while``, a call)
    counts what its children leave, so the rows sum to the plane's busy
    time.  ``path`` is the line's :attr:`OpInfo.tf_op`: ``""`` for a
    line the table lacks or names nothing for, None for an ambiguous
    one, which is then under no scope (:func:`in_scope`)."""
    own: Dict[str, float] = {}
    open_: list = []                              # (end, line) outermost first
    for start, end, line in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while open_ and open_[-1][0] <= start:
            open_.pop()
        own[line] = own.get(line, 0.0) + (end - start)
        if open_:
            parent_end, parent = open_[-1]
            own[parent] -= min(end, parent_end) - start
        open_.append((end, line))
    for line, seconds in own.items():
        info = ops.get(line)
        yield (info.tf_op if info else ""), line, seconds


@functools.lru_cache(maxsize=None)
def _scope_pattern(scope: str):
    names = (re.escape(c).replace(r"\*", "[^/()]*")
             for c in scope.split("/"))
    # JAX closes a transformation behind the first name under it:
    # ``transpose(jvp(batch_norm))/res2a_bn``
    return re.compile(r"(?:^|[/(])" + r"\)*/(?:\w+\()*".join(names)
                      + r"(?=$|[/)])")


def in_scope(path: Optional[str], scope: str) -> bool:
    """Whether the scope path of an op lies under ``scope``: one or
    more ``/``-joined names (``*`` stands for any run inside one name)
    that match whole, consecutive path components inside whatever
    transformations wrap them: ``batch_norm`` and ``batch_norm/bn2a``
    find ``jit(step)/transpose(jvp(batch_norm))/bn2a/mul``, and
    ``batch_norm`` does not find ``my_batch_norm/mul``."""
    return bool(path) and _scope_pattern(scope).search(path) is not None


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
        global _window_ops
        _window_ops = None
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
