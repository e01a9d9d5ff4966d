"""Pallas embedding row-gather: scalar-prefetched touched-row DMA.

The sparse-exchange gather (``parallel/sparse.py``): a batch's deduped
row-index table rides the grid spec's scalar prefetch, so each grid
step's HBM→VMEM DMA fetches exactly the ONE (8, D) tile that holds a
touched table row — the [V, D] table is never streamed, only the tiles
of the K rows the batch actually uses (the
PR 14 pattern: attention pair tables / page tables, transferred to
row-index prefetch; Ragged Paged Attention lineage).  Pad rows
(``height`` from ``unique_rows_sorted``, or -1 from ``unique_rows``)
clamp to a valid row in the index map — a repeated block index costs
no re-DMA — and their gathered values are dropped downstream
(``mode='drop'`` scatters / zero cotangents).

Fallback tier (the ``rnn_dispatch_total`` convention): shapes the
kernel doesn't cover take the plain XLA ``take`` gather with a
one-time warning; ``--embedding_kernel=false`` is the kill switch —
the dense gather path, byte-for-byte (both paths copy rows verbatim).
Off-TPU the dispatch also falls back (reason ``no_tpu``): interpret
mode executes the grid one emulated step at a time — seconds per call
at production K — so it is a numerics harness, not a runtime tier;
``--embedding_kernel_interpret`` opts tests into it at tiny shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import pallas_interpret
from . import kernels as K
from ..observe import counter
from ..utils import FLAGS
from ..utils.logger import get_logger, warn_once

_log = get_logger("ops.embedding")

def record_embedding_dispatch(path: str, reason: str = "") -> None:
    """Count one embedding-gather lowering decision (trace-time: once
    per compiled program per shape).  ``reason`` is set when a
    kernel-capable call took the dense fallback, with the same labels
    the one-time fallback warnings use."""
    counter(
        "embedding_dispatch_total",
        "embedding row-gather lowering decisions by path (trace-time; "
        "reason labels match the one-time fallback warnings)",
    ).inc(path=path, reason=reason)


#: Rows per HBM tile of an f32 table: Mosaic moves (8, 128) tiles, so
#: the kernel fetches the whole 8-row tile that holds a touched row.
_TILE_ROWS = 8


def _gather_kernel(rows_ref, tile_ref, out_ref):
    # the index map already steered this step's DMA to the 8-row tile
    # holding the selected row; pick the row and drop it into this
    # step's slot of the (revisited, VMEM-resident) output tile
    i = pl.program_id(0)
    r = rows_ref[i] % _TILE_ROWS
    row = tile_ref[0, pl.ds(r, 1), :]
    out_ref[0, pl.ds(i % _TILE_ROWS, 1), :] = row  # ptpu: lint-ok[PT-TRACE]


def gather_rows_reference(table: jax.Array, rows: jax.Array) -> jax.Array:
    """Dense XLA gather — the interpret-mode numerics contract and the
    kill-switch/fallback path.  Pad rows (-1 or >= V) clamp to a valid
    row; their values are unused by every caller."""
    safe = jnp.clip(rows.astype(jnp.int32), 0, table.shape[0] - 1)
    return jnp.take(table, safe, axis=0)


def _gather_rows_kernel(table: jax.Array, rows: jax.Array) -> jax.Array:
    v, d = table.shape
    k = rows.shape[0]
    # clamp pads (-1 / height) to a real row index at prefetch time so
    # the index map stays a pure table lookup; round K up to whole
    # output tiles (the surplus slots re-read row 0 and are sliced off)
    k_pad = -(-k // _TILE_ROWS) * _TILE_ROWS
    safe = jnp.pad(jnp.clip(rows.astype(jnp.int32), 0, v - 1),
                   (0, k_pad - k))
    # Mosaic blocks must be (8, 128)-aligned in their last two dims, so
    # a (1, D) row block is not expressible.  The [V, D] table is viewed
    # as [V/8, 8, D] — the same bytes, one (8, 128)-tile row group per
    # leading index — and each grid step DMAs the one tile that holds
    # its row (sorted rows revisit a tile without a re-DMA).
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k_pad,),
            in_specs=[
                pl.BlockSpec((1, _TILE_ROWS, d),
                             lambda i, rows: (rows[i] // _TILE_ROWS, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, _TILE_ROWS, d),
                                   lambda i, rows: (i // _TILE_ROWS, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (k_pad // _TILE_ROWS, _TILE_ROWS, d), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.EMBEDDING_GATHER,
    )(safe, table.reshape(v // _TILE_ROWS, _TILE_ROWS, d))
    return out.reshape(k_pad, d)[:k]


def _kernel_fallback_reason(table, rows, allow_kernel: bool) -> str:
    """Why this gather can't run the Pallas kernel ('' = it can)."""
    if not FLAGS.embedding_kernel:
        return "flag_off"
    if pallas_interpret() and not FLAGS.embedding_kernel_interpret:
        # interpret mode emulates the grid step by step (seconds per
        # call at production K) — numerics-contract harness only
        return "no_tpu"
    if not allow_kernel:
        # caller-side veto: the table is mesh-sharded (the kernel is a
        # single-device program; the SPMD gather stays with XLA)
        return "sharded"
    if table.ndim != 2 or rows.ndim != 1:
        return "rank"
    if table.shape[1] % 128 != 0 or table.shape[0] % _TILE_ROWS != 0:
        return "unaligned"
    if table.dtype != jnp.float32:
        return "dtype"
    return ""


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_rows(table, rows, allow_kernel):
    reason = _kernel_fallback_reason(table, rows, allow_kernel)
    if not reason:
        record_embedding_dispatch("kernel")
        return _gather_rows_kernel(table, rows)
    record_embedding_dispatch("dense", reason=reason)
    if reason not in ("flag_off", "sharded", "no_tpu"):
        warn_once(
            f"embedding_gather_dense_fallback:{reason}:"
            f"{tuple(table.shape)}",
            "embedding row gather: dense XLA fallback taken for table "
            "%s rows [%d]: %s", tuple(table.shape), rows.shape[0],
            reason, logger=_log)
    return gather_rows_reference(table, rows)


def _gather_rows_fwd(table, rows, allow_kernel):
    return _gather_rows(table, rows, allow_kernel), (rows, table)


def _gather_rows_bwd(allow_kernel, res, g):
    # cotangent w.r.t. the table: scatter the row cotangents back
    # (pads routed out of bounds and dropped).  Only taken when someone
    # differentiates THROUGH the gather — the trainer's exchange path
    # differentiates w.r.t. the gathered block instead, so the dense
    # [V, D] cotangent never appears there.
    rows, table = res
    v = table.shape[0]
    idx = jnp.where((rows < 0) | (rows >= v), v, rows)
    dt = jnp.zeros_like(table).at[idx].add(g.astype(table.dtype),
                                           mode="drop")
    return dt, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def gather_rows(table: jax.Array, rows: jax.Array,
                allow_kernel: bool = True) -> jax.Array:
    """Gather ``table[rows]`` → [K, D], Pallas scalar-prefetch kernel
    on capable shapes (2-D fp32 table, lane-aligned D, ``allow_kernel``
    — callers veto when the table is mesh-sharded), dense XLA gather
    otherwise.  Pad rows (-1 or >= V) yield a clamped row whose value
    every caller discards."""
    return _gather_rows(table, rows, bool(allow_kernel))
