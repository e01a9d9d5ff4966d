"""Precision policy.

The reference computes in fp32 (fp64 behind ``WITH_DOUBLE``); on TPU the MXU
wants bfloat16 inputs with fp32 accumulation.  The policy object carries the
three dtypes modern mixed-precision uses (param/compute/output) and is what
layers consult instead of hard-coding dtypes.  ``checkgrad`` mode forces full
fp32 so finite-difference tolerances hold (SURVEY §7 hard parts).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, Optional

import jax.numpy as jnp
import numpy as np

from ..utils import FLAGS

# ---------------------------------------------------------------------
# Canonical dtype-name <-> numpy mapping (the DataType proto equivalent).
#
# bfloat16 is the one name plain numpy cannot parse (``np.dtype("bfloat16")``
# raises — the type lives in ml_dtypes, re-exported as ``jnp.bfloat16``),
# so every boundary that round-trips dtypes BY NAME — DataFeeder feeds,
# serving manifests (``serving/export._feed_spec`` / ``loader``),
# checkpoint var metadata — resolves through this table instead of
# ``np.dtype(name)`` directly.
_NP_DTYPES: Dict[str, np.dtype] = {
    name: np.dtype(t) for name, t in {
        "float32": np.float32, "float64": np.float64,
        "float16": np.float16, "bfloat16": jnp.bfloat16,
        "int8": np.int8, "int16": np.int16,
        "int32": np.int32, "int64": np.int64,
        "uint8": np.uint8, "bool": np.bool_,
    }.items()
}


def np_dtype(name) -> np.dtype:
    """Dtype name (or dtype-like) → numpy dtype, bfloat16 included."""
    if isinstance(name, str) and name in _NP_DTYPES:
        return _NP_DTYPES[name]
    return np.dtype(name)


def dtype_name(dt) -> str:
    """Canonical string name of a (numpy/jax) dtype — the inverse of
    :func:`np_dtype`; ``str(np.dtype)`` already yields "bfloat16" for
    the ml_dtypes extension type."""
    return str(np.dtype(dt))


@dataclasses.dataclass(frozen=True)
class Policy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.bfloat16
    output_dtype: jnp.dtype = jnp.float32

    def cast_compute(self, *xs):
        out = tuple(
            x.astype(self.compute_dtype)
            if hasattr(x, "astype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x
            for x in xs
        )
        return out if len(out) != 1 else out[0]

    def cast_output(self, x):
        if hasattr(x, "astype") and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(self.output_dtype)
        return x


_f32 = Policy(jnp.float32, jnp.float32, jnp.float32)
_bf16 = Policy(jnp.float32, jnp.bfloat16, jnp.float32)
# Full-bf16 activations: layer outputs stay bf16, halving activation HBM
# traffic (the usual TPU bottleneck).  Params and losses remain fp32;
# numerically-sensitive ops (softmax, log, batch-norm stats) compute in
# fp32 internally regardless.  Enabled with --bf16_activations.
_bf16_act = Policy(jnp.float32, jnp.bfloat16, jnp.bfloat16)

_override: list = []


def resolve_precision(opt_config=None) -> str:
    """The active end-to-end precision policy name: "fp32" | "bf16".

    An explicit ``OptimizationConfig.precision`` wins; empty inherits
    the ``--precision`` flag (default fp32).  This is the ONE resolution
    point the trainer and the op-level policy share.
    """
    prec = getattr(opt_config, "precision", "") or FLAGS.precision
    if prec not in ("fp32", "bf16"):
        raise ValueError(
            f"precision must be 'fp32' or 'bf16', got {prec!r}")
    return prec


def policy_for(precision: str) -> Policy:
    """Op-dispatch policy of a named precision: bf16 = bf16 compute
    with fp32 accumulation/outputs (bf16 activation storage only when
    --bf16_activations additionally opts in); fp32 = full fp32."""
    if precision == "bf16":
        return _bf16_act if FLAGS.bf16_activations else _bf16
    return _f32


def current_policy() -> Policy:
    if _override:
        return _override[-1]
    if FLAGS.precision == "bf16":
        # the one-flag mixed-precision policy overrides the legacy knobs
        return policy_for("bf16")
    if not FLAGS.use_bf16:
        return _f32
    return _bf16_act if FLAGS.bf16_activations else _bf16


@contextlib.contextmanager
def policy_scope(policy: Policy) -> Iterator[None]:
    # the override stack is a TRACE-TIME construct by design: ops read
    # it while the jaxpr is built, and the finally rebalances it even
    # when tracing aborts — no state leaks into the compiled program
    _override.append(policy)   # ptpu: lint-ok[PT-TRACE] trace-time stack
    try:
        yield
    finally:
        _override.pop()        # ptpu: lint-ok[PT-TRACE] trace-time stack


@contextlib.contextmanager
def full_precision() -> Iterator[None]:
    """fp32 everywhere — used by the gradient checker."""
    with policy_scope(_f32):
        yield


def record_op_precision(op: str) -> None:
    """Tick ``precision_dispatch_total{op,dtype}``: which compute dtype
    an op family actually dispatched with.  Ops run at TRACE time under
    jit, so this counts once per compiled program per shape — the same
    convention as ``rnn_dispatch_total``/``conv_dispatch_total`` — and
    the artifact/test answer to "did the bf16 policy actually reach
    this kernel" no longer rests on reading the lowering."""
    from ..observe import counter  # lazy: keeps core import-light

    counter(
        "precision_dispatch_total",
        "op dispatches by resolved compute dtype (trace-time: one tick "
        "per compiled program per shape, labels op + policy compute "
        "dtype)",
    ).inc(op=op, dtype=dtype_name(current_policy().compute_dtype))
