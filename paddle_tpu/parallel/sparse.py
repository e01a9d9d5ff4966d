"""Row-sparse parameter machinery — the reference's large-model story.

Re-expresses, TPU-first:

- ``SelectedRows`` (``paddle/framework/selected_rows.h:23``): a row-sparse
  value — ``rows`` indices + ``values`` block — used for embedding-style
  gradients and fixed-capacity prefetches.
- Growable/prefetching row-sparse matrices
  (``paddle/math/SparseRowMatrix.h:29,204,235``): on TPU the table itself
  stays a dense (optionally 'model'-axis row-sharded) HBM array — XLA has
  no growable buffers — but *work* is row-sparse: batches touch a fixed
  capacity of unique rows, gathered once up front (the sparse-remote
  "prefetch rows for this batch" contract,
  ``paddle/trainer/RemoteParameterUpdater.h:265``) and scatter-updated.
- Lazy row-sparse optimizer updates (``SparseRowCpuMatrix::sgdUpdate``,
  sparse ``SelectedRows`` optimizer kernels in
  ``paddle/operators/math/selected_rows_functor.cc``): only rows touched
  by the batch get value *and* moment updates; untouched rows — and their
  Adam/Adagrad slots — are left bit-identical.

Two composition styles:

1. **In-graph lazy masking** (`touched_row_mask` + ``Optimizer.apply(...,
   sparse_masks=...)``): the autodiff gradient stays dense-shaped, but the
   update is masked to touched rows.  O(V) elementwise work — fully fused
   by XLA, zero extra HBM traffic beyond the gradient — with exact lazy
   semantics.  This is what ``ParamAttr(sparse_update=True)`` turns on in
   the Trainer.
2. **Fixed-capacity prefetch** (`prefetch_rows` → compute on the gathered
   block → ``Optimizer.apply_rows``): O(K) work and memory, K = unique-row
   capacity; the table is never materialized in the gradient.  For giant
   (sharded) tables — CTR/NCE scale — where O(V) per step is unacceptable.
"""

from __future__ import annotations

import contextlib
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class SelectedRows(NamedTuple):
    """Row-sparse value (``selected_rows.h:23``): ``values[i]`` belongs to
    dense row ``rows[i]``; ``rows`` may contain -1 padding (ignored)."""

    rows: jax.Array        # [K] int32, -1 = empty slot
    values: jax.Array      # [K, ...] row block
    height: int            # dense row count (static)

    def to_dense(self) -> jax.Array:
        """Materialize: scatter-add values into a zero dense tensor
        (duplicate rows accumulate, like SelectedRows merge_add)."""
        dense = jnp.zeros((self.height,) + self.values.shape[1:],
                          self.values.dtype)
        return row_scatter_add(dense, self.rows, self.values)


def unique_rows(ids: jax.Array, capacity: int
                ) -> Tuple[jax.Array, jax.Array]:
    """Deduplicate ids into a fixed-capacity row set (jit-static shapes).

    Returns ``(rows [capacity] int32 padded with -1, inverse)`` with
    ``rows[inverse] == ids.ravel()``.  Capacity overflow policy: jnp.unique
    keeps the smallest ids; callers size capacity >= max unique ids per
    batch (the reference's prefetch buffer is sized the same way,
    ``SparsePrefetchRowCpuMatrix`` ``SparseRowMatrix.h:204``).
    """
    flat = ids.astype(jnp.int32).ravel()
    rows, inverse = jnp.unique(flat, size=capacity, fill_value=-1,
                               return_inverse=True)
    return rows, inverse.reshape(ids.shape)


def row_gather(table: jax.Array, rows: jax.Array) -> jax.Array:
    """Gather table rows; -1 padded slots read row 0 (value unused)."""
    safe = jnp.where(rows < 0, 0, rows)
    return jnp.take(table, safe, axis=0)


def row_scatter_add(table: jax.Array, rows: jax.Array,
                    values: jax.Array) -> jax.Array:
    """table[rows] += values; -1 padded slots are routed out of bounds
    and dropped (mode='drop'), so they can't alias row 0."""
    idx = jnp.where(rows < 0, table.shape[0], rows)
    return table.at[idx].add(values.astype(table.dtype), mode="drop")


def row_scatter_set(table: jax.Array, rows: jax.Array,
                    values: jax.Array) -> jax.Array:
    """table[rows] = values, ignoring -1 padded slots (callers guarantee
    unique real rows — unique_rows output)."""
    idx = jnp.where(rows < 0, table.shape[0], rows)
    return table.at[idx].set(values.astype(table.dtype), mode="drop")


def touched_row_mask(grad: jax.Array,
                     ids: Optional[jax.Array] = None) -> jax.Array:
    """[V] bool mask of rows touched this batch.

    From ``ids`` when the caller has them (exact — the reference's
    SelectedRows rows set); else inferred from non-zero gradient rows
    (equivalent for gather-style layers: untouched rows get exactly-zero
    cotangents from autodiff).
    """
    if ids is not None:
        mask = jnp.zeros((grad.shape[0],), bool)
        return mask.at[ids.astype(jnp.int32).ravel()].set(True)
    return jnp.any(grad != 0, axis=tuple(range(1, grad.ndim)))


def prefetch_rows(table: jax.Array, ids: jax.Array, capacity: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The sparse-remote prefetch contract
    (``RemoteParameterUpdater.h:265``): dedupe this batch's ids, gather
    that fixed-capacity row block once.

    Returns ``(rows [K], block [K, D], inverse ids.shape)``; downstream
    compute uses ``block[inverse]`` and differentiates w.r.t. ``block``
    (a [K, D] cotangent — the table never appears in the gradient).
    On a 'model'-axis row-sharded table the gather lowers to an XLA
    all-gather of just the K rows over ICI.
    """
    rows, inverse = unique_rows(ids, capacity)
    return rows, row_gather(table, rows), inverse


def sparse_embedding_lookup(block: jax.Array, inverse: jax.Array
                            ) -> jax.Array:
    """Second half of the prefetch pattern: ids-shaped embedding from the
    prefetched block ([K, D] → inverse.shape + [D])."""
    return jnp.take(block, inverse, axis=0)


# ================================================ sparse gradient exchange
#
# The trainer-side composition of the fixed-capacity prefetch: with
# ``--sparse_grads`` the jitted train step dedupes each embedding
# table's batch ids ONCE (``unique_rows_sorted``), gathers the touched
# rows into a [K, D] block (Pallas scalar-prefetch kernel on capable
# shapes, ops/pallas_embedding.py), and routes every lookup of that
# table through the block via a TRACE-TIME substitution scope the
# EmbeddingLayer consults.  Autodiff then yields a [K, D] cotangent —
# the (rows, values) exchange payload; the dense [V, D] gradient is
# never materialized, and on a row-sharded table the update is a
# shard-local scatter-add instead of a dense all-reduce (the
# SparseRemoteParameterUpdater exchange, expressed in SPMD).

def unique_rows_sorted(ids: jax.Array, capacity: int, height: int
                       ) -> jax.Array:
    """Dedupe ids into a SORTED fixed-capacity row set padded with
    ``height`` (one-past-the-end, kept sorted — unlike the -1 padding
    of :func:`unique_rows`) so presence lookups are a searchsorted.
    Pad rows route out of bounds in every scatter (mode='drop') and
    clamp in every gather, exactly like -1 pads."""
    flat = ids.astype(jnp.int32).ravel()
    return jnp.unique(flat, size=capacity, fill_value=height)


def lookup_rows(rows: jax.Array, block: jax.Array, ids: jax.Array
                ) -> jax.Array:
    """ids-shaped embedding from a sorted row set + gathered block:
    ``block[searchsorted(rows, ids)]``.  Exact whenever every id is
    present in ``rows`` (the exchange scope's contract — rows came from
    this batch's own ids at sufficient capacity)."""
    pos = jnp.searchsorted(rows, ids.astype(jnp.int32))
    return jnp.take(block, pos.reshape(ids.shape), axis=0)


# Param name → (rows, block) substitution entries for the CURRENT trace.
# A trace-time construct by design: the trainer pushes the scope while
# the step jaxpr is built and the EmbeddingLayer reads it during the
# same trace; the finally rebalances even when tracing aborts.
_exchange_scope: list = []


@contextlib.contextmanager
def exchange_scope(entries):
    """Route embedding lookups of the named tables through their
    prefetched ``(rows, block)`` pair for the duration of this trace
    (``entries``: param name → (rows [K], block [K, D]))."""
    _exchange_scope.append(dict(entries))  # ptpu: lint-ok[PT-TRACE] trace-time stack
    try:
        yield
    finally:
        _exchange_scope.pop()              # ptpu: lint-ok[PT-TRACE] trace-time stack


def exchange_entry(param_name: str):
    """The active ``(rows, block)`` substitution for ``param_name``,
    else None (the dense lookup path)."""
    if _exchange_scope:
        return _exchange_scope[-1].get(param_name)
    return None
