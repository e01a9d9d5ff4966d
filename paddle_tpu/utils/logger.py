"""Logging setup (glog-equivalent: ``paddle/utils/Logging.h``).

Level selection (first match wins):

1. ``set_log_level("debug")`` in code,
2. ``--log_level`` CLI flag (applied by the entry points after flag
   parsing — :mod:`paddle_tpu.cli`),
3. ``PADDLE_TPU_LOG_LEVEL`` environment variable at import,
4. INFO.

:func:`warn_once` is the process-wide one-time structured warning
(keyed): dispatch-tier fallbacks and similar per-shape diagnostics log
each distinct situation exactly once per process instead of flooding the
training loop (the hand-rolled ``_fallback_warned`` sets this replaces).
"""

from __future__ import annotations

import logging
import os
import sys
import threading
from typing import Optional, Set, Union

from ..analysis.lockorder import named_lock

_FMT = "%(levelname).1s %(asctime)s.%(msecs)03d %(name)s] %(message)s"
_DATEFMT = "%m%d %H:%M:%S"

_LEVELS = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "warn": logging.WARNING,
    "error": logging.ERROR,
    "fatal": logging.CRITICAL,
    "critical": logging.CRITICAL,
}


def _parse_level(level: Union[str, int]) -> int:
    if isinstance(level, int):
        return level
    try:
        return _LEVELS[level.strip().lower()]
    except KeyError:
        raise ValueError(
            f"unknown log level {level!r} "
            f"(choose from {sorted(set(_LEVELS))})") from None


_root = logging.getLogger("paddle_tpu")
if not _root.handlers:
    h = logging.StreamHandler(sys.stderr)
    h.setFormatter(logging.Formatter(_FMT, _DATEFMT))
    _root.addHandler(h)
    # a typo'd fleet-wide env var must not make the package
    # unimportable: degrade to INFO with a warning (the explicit
    # set_log_level / --log_level paths stay strict)
    try:
        _root.setLevel(_parse_level(
            os.environ.get("PADDLE_TPU_LOG_LEVEL") or "info"))
    except ValueError as e:
        _root.setLevel(logging.INFO)
        _root.warning("PADDLE_TPU_LOG_LEVEL ignored (%s); using INFO", e)
    _root.propagate = False


def get_logger(name: str = "") -> logging.Logger:
    return _root.getChild(name) if name else _root


def set_log_level(level: Union[str, int]) -> None:
    """Set the framework-wide level ("debug"|"info"|"warning"|"error"|
    "fatal", or a :mod:`logging` constant)."""
    _root.setLevel(_parse_level(level))


_warned: Set[str] = set()
_warned_lock = named_lock("logger.warn_once")


def warn_once(key: str, msg: str, *args,
              logger: Optional[logging.Logger] = None) -> bool:
    """Log ``msg % args`` as a warning the FIRST time ``key`` is seen in
    this process; later calls are no-ops.  Returns True iff it logged.

    Key per distinct situation (e.g. ``f"fused_lstm_fallback:{B}x{H}"``)
    so a hot loop reports each shape once, not once per step.
    """
    with _warned_lock:
        if key in _warned:
            return False
        _warned.add(key)
    (logger or _root).warning(msg, *args)
    return True


def reset_warn_once(key: Optional[str] = None) -> None:
    """Forget every warn_once key (tests) — or, with ``key``, re-arm
    just that one: a sink that RECOVERED from degradation wants its
    failure warning to fire again on the next incident, not stay
    silenced for the process lifetime."""
    with _warned_lock:
        if key is None:
            _warned.clear()
        else:
            _warned.discard(key)
