"""Routed experts: a router that picks ``top_k`` of E experts a token,
tokens sorted by expert, one **grouped matmul** over the sorted rows
that reads only the experts that have tokens, and the weighted combine.

The serving decoder's routed feed-forward (``serving/model.py``) is the
caller.  At decode a step routes a hundred token-choices over 128
experts: the layer is bound by the bytes of the experts that were hit,
so a product of every expert with every token (the plain reference's
way) would read all of them.  At prefill thousands of tokens reach every
expert and the layer is bound by the MXU, so the rows of one expert
have to share one pass over its weights.  Both are one walk here:

- :func:`grouped_matmul` — ``lhs[rows of group g] @ rhs[g]`` for rows
  sorted by group.  The grid walks (row tile, group) visits listed in
  scalar-prefetched tables, as many as there are, not E of them: a
  group with no rows has no visit, so its weights are never fetched;
  a tile that two groups share is visited once for each, and each
  stores its own rows.  The contraction is not tiled (``k`` is a model
  width), so consecutive tiles of one group re-use the weight block
  that is already in VMEM.
- :func:`grouped_glu` — the same walk over two weights: a visit
  multiplies its row tile by the group's gate block and by its up
  block and stores ``silu(gate) * up`` once, in the dtype the next
  product reads.  The float32 products live in VMEM only: between the
  sort and the combine a row's activations cross HBM once.
- :func:`routed_experts` — the whole layer but its shared expert:
  scores, selection, sort, two kernel calls (gate and up with the
  activation, then down) and the combine.  Returns the group sizes
  beside the result, from which a serving step counts the experts it
  hit.

Router mathematics (the configuration's, ``chipbench/reference``):
``s = sigmoid(x·W_r)`` in float32, or with ``score="softmax"`` ``s =
softmax(x·W_r)`` over all E experts; the ``top_k`` largest of ``s + b``
are chosen (the bias chooses, it does not weigh); weights
``scale · s_e / (Σ_chosen s + 1e-20)``.  No token is dropped and there
is no capacity factor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import pallas_interpret
from ..observe import counter
from ..utils import enforce
from . import kernels as K
from . import scopes as S

#: rows a tile holds at most; whole widths of ``k``; columns a tile
#: holds at most: one rule for both kernels, a function of the shapes
#: alone.  The sweeps on the chip (PERF.md §6, PR 27 and 34): at the
#: decode shapes (64-128 rows over 24-66 experts) every tiling reads the
#: bytes' time (0.40-0.42 ms a product, 0.44-0.58 the fused pair); at
#: the prefill shapes 128 x 1024 is the fastest product (49,152 rows:
#: 2.05 / 2.39 ms for 2.24 / 2.74 at 256 x 512) and the fused pair is
#: within 4 % of its best at the three expert widths (16,384 rows of
#: 1536: 2.32 ms at 128 x 768, 2.25 at the whole width, 3.18 at 512
#: rows).  The column tile divides ``n`` (:func:`_column_tile`): 768 at
#: an expert width of 1536
TILE_M, TILE_N = 128, 1024
#: the kernels' VMEM allowance: two buffers of a [k, TILE_N] bf16 weight
#: block (of two in the fused pair: 16 MB), of a row tile and of its
#: result, and the f32 products
VMEM_LIMIT = 64 * 1024 * 1024


def record_moe_dispatch(path: str, reason: str = "") -> None:
    """Count one routed-expert lowering decision (trace-time: once per
    compiled program per call site — the ``*_dispatch_total``
    convention; ``reason`` is set only where a call left the grouped
    kernel)."""
    counter(
        "moe_dispatch_total",
        "routed-expert lowering decisions by path (trace-time; a "
        "reason marks a fallback from the grouped matmul)",
    ).inc(path=path, reason=reason)


def weight_matmul(a, w):
    """``a @ w`` with ``a`` rounded to the dtype ``w`` is stored in and
    float32 accumulation: bfloat16 operands for a bfloat16 model, the
    plain float32 product for a float32 one."""
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def weight_einsum(spec: str, a, w):
    """:func:`weight_matmul` for a weight with more axes than two:
    ``einsum(spec, a, w)`` with ``a`` rounded to ``w``'s dtype and
    float32 accumulation."""
    return jnp.einsum(spec, a.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _round_up(x: int, to: int) -> int:
    return -(-x // to) * to


def _column_tile(n: int) -> int:
    """Columns a tile of :func:`grouped_matmul` holds: all ``n`` where
    they fit a tile, else the largest multiple of 128 lanes up to
    ``TILE_N`` that divides ``n``.  The grid walks ``n // tile`` column
    blocks, so a tile that left a remainder would leave the columns
    behind the last whole block unwritten."""
    if n <= TILE_N:
        return n
    tn = next((t for t in range(TILE_N, 0, -128) if n % t == 0), 0)
    enforce(tn > 0, f"grouped_matmul: no column tile of whole 128-lane "
                    f"tiles up to {TILE_N} divides n = {n}")
    return tn


def _visits(group_sizes, m: int, tm: int):
    """The (row tile, group) visits of rows sorted by group, in row
    order: ``(offsets [E+1], group_of [V], tile_of [V], n)`` with
    ``V = m/tm + E - 1`` slots of which the first ``n`` are used.  A
    group visits every tile that holds one of its rows; an empty group
    visits none."""
    e = group_sizes.shape[0]
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = starts // tm
    n_of = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    slots = tiles_m + e - 1
    group_of = jnp.repeat(jnp.arange(e, dtype=jnp.int32), n_of,
                          total_repeat_length=slots)
    # the visit's rank among its group's visits, added to the group's
    # first tile
    before = jnp.cumsum(n_of) - n_of
    tile_of = first[group_of] + (jnp.arange(slots, dtype=jnp.int32)
                                 - before[group_of])
    n = n_of.sum()
    # slots past the used ones repeat the last visit (never run)
    tile_of = jnp.clip(tile_of, 0, tiles_m - 1)
    return (offsets.astype(jnp.int32), group_of,
            tile_of.astype(jnp.int32), n.astype(jnp.int32))


def _store_own_rows(off_ref, grp_ref, tile_ref, out_ref, value, tm):
    """Store the rows of the visit's tile that belong to its group; the
    others keep what an earlier visit of the same tile stored."""
    v = pl.program_id(1)
    g = grp_ref[v]
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, value.shape, 0)
    mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
    out_ref[...] = jnp.where(mine, value.astype(out_ref.dtype),
                             out_ref[...])


def _gmm_kernel(off_ref, grp_ref, tile_ref, lhs_ref, rhs_ref, out_ref, *,
                tm):
    """One visit: the tile's rows times the group's weight block."""
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    _store_own_rows(off_ref, grp_ref, tile_ref, out_ref, acc, tm)


def _glu_kernel(off_ref, grp_ref, tile_ref, lhs_ref, gate_ref, up_ref,
                out_ref, *, tm):
    """One visit: the tile's rows times the group's gate block and its
    up block, and ``silu(gate) * up`` of the two float32 products."""
    rows = lhs_ref[...]
    gate = jnp.dot(rows, gate_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(rows, up_ref[...], preferred_element_type=jnp.float32)
    _store_own_rows(off_ref, grp_ref, tile_ref, out_ref,
                    jax.nn.silu(gate) * up, tm)


def _grouped_call(kernel, lhs, weights, group_sizes, out_dtype):
    """The walk both kernels share: ``kernel`` once a (row tile, group)
    visit and column block, on the tile's rows and that block of each
    of ``weights`` (``[E, k, n]`` each, read where they lie).  Counts
    one call of ``2·M·k·n`` FLOPs a weight."""
    m, k = lhs.shape
    n = weights[0].shape[2]
    tm = min(TILE_M, _round_up(m, 16))
    tn = _column_tile(n)
    m_pad = _round_up(m, tm)
    if m_pad != m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    offsets, group_of, tile_of, n_visits = _visits(
        group_sizes.astype(jnp.int32), m_pad, tm)
    out_shape = jax.ShapeDtypeStruct((m_pad, n), out_dtype)
    K.record_kernel_work(K.MOE_GMM, 2.0 * m * k * n * len(weights),
                         (lhs, *weights), (out_shape,))
    weight_block = pl.BlockSpec(
        (None, k, tn), lambda j, v, off, grp, tile: (grp[v], 0, j))
    out = pl.pallas_call(
        functools.partial(kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # columns outermost: within one column block, consecutive
            # visits of one group find its weight blocks in place
            grid=(n // tn, n_visits),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, off, grp, tile: (tile[v], 0)),
                *[weight_block] * len(weights),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, grp, tile: (tile[v], j)),
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pallas_interpret(),
        name=K.MOE_GMM,
    )(offsets, group_of, tile_of, lhs, *weights)
    return out[:m]


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=jnp.float32):
    """``out[r] = lhs[r] @ rhs[g(r)]`` for rows sorted by group.

    - ``lhs``: ``[M, k]``, its first ``Σ group_sizes`` rows in group
      order; the rows after them are padding and their result is
      **unspecified** (nothing visits them): mask them where they are
      used;
    - ``rhs``: ``[E, k, n]``; ``group_sizes``: int32 ``[E]``.

    Returns ``[M, n]`` in ``out_dtype``.  ``M`` is padded up to whole
    row tiles here.
    """
    return _grouped_call(_gmm_kernel, lhs, (rhs,), group_sizes, out_dtype)


def grouped_glu(lhs, w_gate, w_up, group_sizes, out_dtype):
    """``out[r] = silu(lhs[r] @ w_gate[g(r)]) * (lhs[r] @ w_up[g(r)])``
    for rows sorted by group: :func:`grouped_matmul`'s walk with two
    weight blocks a visit, both products and the activation in float32
    on the one row tile, and one store in ``out_dtype``.  What
    ``silu(grouped_matmul(gate)) * grouped_matmul(up)`` cast to
    ``out_dtype`` gives, without the two float32 ``[M, n]`` arrays
    between.  Arguments and padding as there; ``w_gate``, ``w_up``:
    ``[E, k, n]`` each."""
    enforce(w_up.shape == w_gate.shape and w_up.dtype == w_gate.dtype,
            f"grouped_glu: gate {w_gate.shape} {w_gate.dtype} and up "
            f"{w_up.shape} {w_up.dtype} differ")
    return _grouped_call(_glu_kernel, lhs, (w_gate, w_up), group_sizes,
                         out_dtype)


#: how a router turns its logits into scores: every expert on its own,
#: or one distribution over all of them
SCORES = {"sigmoid": jax.nn.sigmoid,
          "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def route(x, router_w, router_bias, top_k: int, route_scale: float,
          score: str = "sigmoid"):
    """``(experts [T, top_k] int32, weights [T, top_k] float32)``: the
    ``top_k`` largest of ``s + bias``, weighed by their scores ``s``
    alone, normalised and scaled; ``s`` is the ``score`` of ``x·W_r``
    (:data:`SCORES`).  float32 at the highest matmul precision: a score
    that rounds differently picks another expert."""
    enforce(score in SCORES, f"router score {score!r} is none of "
            f"{sorted(SCORES)}")
    s = SCORES[score](jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(s + router_bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(s, experts, axis=1)
    weights = route_scale * picked / (
        picked.sum(axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights


def routed_experts(x, router_w, router_bias, w_gate, w_up, w_down, *,
                   top_k: int, route_scale: float, valid=None,
                   score: str = "sigmoid"):
    """The routed half of a mixture-of-experts feed-forward.

    - ``x``: ``[T, d]`` float32 (the block's normed input);
    - ``router_w`` ``[d, E]``, ``router_bias`` ``[E]`` (float32);
    - ``w_gate``, ``w_up`` ``[E, d, f]``, ``w_down`` ``[E, f, d]`` in
      their storage dtype: each expert is ``(silu(x·Wg) ⊙ x·Wu)·Wd``;
    - ``valid``: optional bool ``[T]``; a token that is not valid
      (padding, an idle batch slot) is routed nowhere: it reads no
      expert, counts in no group and gets zeros;
    - ``score``: how the router scores (:func:`route`).

    Returns ``(y [T, d] float32, group_sizes [E] int32)``.
    """
    t, d = x.shape
    e = router_w.shape[1]
    record_moe_dispatch("grouped")
    with jax.named_scope(S.ROUTE):
        experts, weights = route(x, router_w, router_bias, top_k,
                                 route_scale, score)
    with jax.named_scope(S.SORT):
        # the flat list of choices is choice-major, token t's choice c
        # at c·T + t: the results back in that order are ``top_k`` slabs
        # of [T, d] that the weighted sum adds (as [T, top_k, d] a top_k
        # of 4 is second-minor, and the view a relayout copy of every
        # row)
        flat = experts.T.reshape(-1)
        if valid is not None:
            # the sentinel group E sorts behind every expert
            flat = jnp.where(jnp.tile(valid, top_k), flat, e)
        order = jnp.argsort(flat, stable=True)                # [k·T]
        # a compare and a sum, not a scatter of k·T ones (serial on the
        # chip); the sentinel equals no expert
        group_sizes = (flat[:, None] == jnp.arange(e, dtype=flat.dtype)
                       ).sum(axis=0, dtype=jnp.int32)
        xs = x.astype(w_gate.dtype)[order % t]                # [k·T, d]
    with jax.named_scope(S.EXPERTS):
        h = grouped_glu(xs, w_gate, w_up, group_sizes, w_down.dtype)
        ys = grouped_matmul(h, w_down, group_sizes)           # [k·T, d]
    with jax.named_scope(S.COMBINE):
        # back to the flat list's order: choice c lies at row inv[c]
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        per_choice = ys[inv].reshape(top_k, t, d)
        if valid is not None:
            per_choice = jnp.where(valid[None, :, None], per_choice, 0.0)
        y = jnp.sum(per_choice * weights.T[:, :, None], axis=0)
    return y, group_sizes
