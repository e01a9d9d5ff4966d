"""Flash attention as a Pallas TPU kernel — block-sparse (splash-style).

The reference hand-wrote its hot kernels in CUDA (``hl_lstm``,
``hl_top_k``); the TPU analogue of that tier is Pallas.  This module
implements blockwise (flash) attention: k/v stream through VMEM one
block per grid step with an online softmax (running max / normalizer
kept in VMEM scratch), so the [T, T] score matrix never exists in HBM
and VMEM holds only O(block²+block·D) — sequence length is bounded by
HBM for q/k/v themselves, not by attention intermediates.

Round 19 makes the kernel truly **block-sparse**: the (q-block,
k-block) iteration space is flattened into a scalar-prefetched *pair
table* that statically drops every block fully above the causal
diagonal (≈half of T²/2 at large T), and per-row dynamic windows
(valid-key lengths, packed segment ranges) clamp the k/v BlockSpec
index maps so dead blocks are **neither DMA'd nor visited** — the old
grid fetched every block and only skipped the compute (``pl.when``),
saving FLOPs but none of the HBM traffic.  The same pair tables and
ONE shared masking helper (:func:`_tile_mask` / element masks,
:func:`_causal_block_live` / block liveness) drive the forward, dq and
dk/dv kernels, so forward and backward sparsity can never diverge.
``--flash_block_sparse=false`` restores the legacy full grid;
``--flash_kernel=false`` restores the dense XLA composition.

Three entry points:

- :func:`flash_attention` — padded batches ([B, T, H, D] + optional
  int32 [B] key lengths), causal or not;
- :func:`flash_attention_packed` — sequence packing / ragged batching:
  mixed-length sequences share one [B, T_total, H, D] layout with an
  int32 segment id per token (−1 = padding; ids non-decreasing along
  the token axis — the packing contract); cross-segment and padding
  blocks do zero work.  ``--attention_packing=false`` upstream
  (layers/attention.py) disables the packed lowering entirely;
- :func:`paged_decode_attention` — the serving decode primitive: a
  small-Tq query batch attends a block-paged KV cache through a
  per-row page table + valid lengths (ROADMAP items 1 and 5's shared
  base; *Ragged Paged Attention*, arxiv 2604.15464).  One kernel
  invocation loops over each row's live pages only — several pages of
  one row a loop step (:func:`_pages_per_step`), all heads in it, each
  page fetched by its own DMA from the pool left in HBM as the server
  stores it (``[P, page, G·D]``), operands in the pool's dtype — so
  its time follows the live K/V, not the page table's width.
  Inference-only (no VJP).

Layout matches :mod:`paddle_tpu.parallel.ring_attention`'s
``full_attention``: q, k, v are ``[B, T, H, D]``; output ``[B, T, H, D]``.

Backward: custom VJP with the standard recomputation formulation — the
saved residuals are (q, k, v, out, per-row logsumexp).  When the shapes
tile, backward runs as TWO Pallas kernels (a dq pass streaming k/v and
a dk/dv pass streaming q/do, each rebuilding p blockwise from the saved
logsumexp) so the [T, T] score matrix never exists in HBM in either
direction; otherwise it falls back to dense einsums.

On non-TPU backends the kernel runs in Pallas interpret mode so the CPU
test mesh exercises the exact same code path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import pallas_interpret
from . import kernels as K
from ..observe import counter, gauge
from ..utils import enforce
from ..utils.logger import get_logger, warn_once

NEG_INF = -1e30

_log = get_logger("ops.attention")

def record_attention_dispatch(path: str, reason: str = "") -> None:
    """Count one attention lowering decision (trace-time: once per
    compiled program per shape — the ``rnn_dispatch_total`` /
    ``conv_dispatch_total`` convention).  ``reason`` is set when a
    flash-capable call took a fallback, with the same labels the
    one-time fallback warnings use."""
    counter(
        "attention_dispatch_total",
        "attention lowering decisions by path (trace-time; reason "
        "labels match the one-time fallback warnings)",
    ).inc(path=path, reason=reason)


def _warn_dense_fallback(reason: str, tq: int, tk: int, bq: int,
                         bk: int) -> None:
    warn_once(
        f"flash_attention_dense_fallback:{reason}:{tq}x{tk}",
        "flash_attention: dense XLA fallback taken for Tq=%d Tk=%d "
        "(blocks %d/%d): %s", tq, tk, bq, bk, reason, logger=_log)


def _choose_block(t: int, want: int) -> int:
    b = min(want, t)
    while t % b:
        b //= 2
    return max(b, 1)


# --------------------------------------------------- shared mask helpers
def _sees_up_to(qi, causal_block: int):
    """The newest position query ``qi`` sees under a causal mask: itself
    (``causal_block`` 0 or 1), or the last position of its block of
    ``causal_block`` positions (a power of two; blocks counted from
    position 0), so the queries of a block see the whole block and every
    block before it.  Python ints and traced values alike."""
    return qi | (causal_block - 1) if causal_block > 1 else qi


def _causal_block_live(q_off, k_off, block_q):
    """Block-level causal liveness: the (q, k) tile contains at least
    one pair on or below the diagonal.  THE shared predicate — the
    static pair tables, the legacy-grid skip conditions and the
    backward kernels all call this one function, so forward and
    backward block sparsity can never diverge.  Works on python ints
    (table build) and traced values (kernels) alike."""
    return k_off <= q_off + block_q - 1


def _window_block_live(q_off, k_off, block_k, window):
    """Block-level liveness under a sliding window: the k tile holds a
    key no more than ``window - 1`` positions behind the q tile's
    first query (python ints at table build)."""
    return k_off + block_k - 1 > q_off - window


def _block_interior(q_off, k_off, block_q, block_k, causal, window,
                    causal_block=0):
    """Block-level: EVERY (query, key) of the tile lies on or under the
    diagonal (of ``causal_block`` blocks: :func:`_sees_up_to`) and,
    where there is a window, inside it, so neither compare of
    :func:`_tile_mask` can hide an element (python ints at table build).
    What only the data shows (key padding, segments) the kernel adds
    from scalars: see ``_fa_pair_kernel``."""
    if not causal:
        return True
    under = k_off + block_k - 1 <= _sees_up_to(q_off, causal_block)
    return under and (not window or q_off + block_q - 1 - k_off < window)


def _tile_mask(q_off, k_off, kv_len, causal, block_q, block_k,
               seg_q=None, seg_k=None, window=0, causal_block=0):
    """[block_q, block_k] element validity for one tile — THE shared
    masking helper for the forward kernel, both backward kernels and
    the packed variants: key-padding (``kv_len``), causal diagonal
    (token by token, or block by block with ``causal_block``:
    :func:`_sees_up_to`), (packed) segment-id equality with −1 =
    padding, and a sliding ``window`` (a query sees the ``window``
    newest keys up to itself; causal only).  ``seg_q`` is a column
    ``[block_q, 1]``, ``seg_k`` a row ``[1, block_k]``."""
    ki = k_off + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = ki < kv_len
    if causal:
        qi = q_off + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = jnp.logical_and(valid, _sees_up_to(qi, causal_block) >= ki)
        if window:
            valid = jnp.logical_and(valid, qi - ki < window)
    if seg_q is not None:
        valid = jnp.logical_and(valid, seg_q == seg_k)
        valid = jnp.logical_and(valid, seg_q >= 0)
    return valid


# ----------------------------------------------------------- pair tables
@functools.lru_cache(maxsize=None)
def _pair_tables(tq: int, tk: int, bq: int, bk: int, causal: bool,
                 slot: int = 0, window: int = 0, causal_block: int = 0):
    """Static block-sparse iteration tables.

    Returns ``(tab_q, tab_k)`` — int32 ``[5, n_pairs]`` arrays with
    rows ``(q_block, k_block, is_first, is_last, is_interior)`` —
    enumerating every
    causally-live (q-block, k-block) pair in q-major order (forward /
    dq kernels: the online-softmax / dq accumulators carry across one
    q block's pairs) and k-major order (dk/dv kernel: the dk/dv
    accumulators carry across one k block's pairs).  Blocks fully
    above the causal diagonal simply do not appear: at causal T=2048
    with 512-blocks that is 6 of 16 pairs gone — neither DMA'd nor
    visited.  Every q block (and, since causal requires Tq == Tk,
    every k block) keeps at least one pair, so outputs always flush.

    ``slot`` (packed layouts only): tokens per packed slot when the
    CALLER guarantees no segment crosses a slot boundary (the layer's
    [B, T] → [1, B·T] flatten: slot = T).  Block pairs in different
    slots are then statically dead and dropped from the table — the
    packed grid has exactly the padded grid's pair count instead of
    the full (B·nq)² cross product.  Only applied when slots are whole
    blocks (slot % bq == slot % bk == 0); 0 disables.

    ``window`` (causal only): blocks wholly behind the sliding window
    of the q block's first query are dropped like those above the
    diagonal.

    ``is_interior``: the diagonal and the window hide no element of
    the pair (:func:`_block_interior`); the forward kernel runs such a
    pair without a mask when its scalars show no padding and one
    segment.  At 7,168 tokens in blocks of 512 that is 91 of 105.

    ``causal_block`` (a divisor of ``bq``): the diagonal is one of blocks
    of positions (:func:`_sees_up_to`); a q tile's last query sees up to
    its own last position either way, so the live pairs are the same
    and only ``is_interior`` moves.
    """
    nq, nk = tq // bq, tk // bk
    if slot and (slot % bq or slot % bk):
        slot = 0                  # blocks straddle slots: hint unusable

    def build(q_major: bool):
        rows = [[], [], [], [], []]
        outer = range(nq) if q_major else range(nk)
        inner = range(nk) if q_major else range(nq)
        for a in outer:
            members = []
            for c in inner:
                j, s = (a, c) if q_major else (c, a)
                if causal and not _causal_block_live(
                        j * bq, s * bk, bq):
                    continue
                if slot and (j * bq) // slot != (s * bk) // slot:
                    continue
                if causal and window and not _window_block_live(
                        j * bq, s * bk, bk, window):
                    continue
                members.append((j, s))
            for t, (j, s) in enumerate(members):
                rows[0].append(j)
                rows[1].append(s)
                rows[2].append(1 if t == 0 else 0)
                rows[3].append(1 if t == len(members) - 1 else 0)
                rows[4].append(1 if _block_interior(
                    j * bq, s * bk, bq, bk, causal, window,
                    causal_block) else 0)
        # ptpu: lint-ok[PT-TRACE] python ints: the static table itself
        return np.asarray(rows, np.int32)

    return build(True), build(False)


def _length_windows(lengths, bsz: int, n_outer: int, bk: int):
    """``(lo, hi)`` int32 [B, n_outer] inclusive windows of live
    k-block indices per (batch row, q block) from valid-key lengths:
    the k/v index maps clamp into the window so blocks wholly inside
    the padding re-fetch the boundary block (a no-op DMA when the
    index repeats) instead of streaming dead data."""
    hi = jnp.maximum((lengths + bk - 1) // bk, 1) - 1       # [B]
    hi = jnp.broadcast_to(hi[:, None], (bsz, n_outer))
    lo = jnp.zeros((bsz, n_outer), jnp.int32)
    return lo, hi.astype(jnp.int32)


def _segment_windows(seg_outer, seg_inner, b_outer: int, b_inner: int):
    """``(lo, hi)`` int32 [B, n_outer] inclusive windows of inner
    blocks whose valid-segment range overlaps each outer block's.
    Relies on the packing contract (valid ids non-decreasing along the
    token axis, −1 padding anywhere) so each block's valid ids form an
    interval and blocks are ordered; an outer block with no valid
    token gets an empty (lo > hi) window."""
    bsz = seg_outer.shape[0]
    n_o = seg_outer.shape[1] // b_outer
    n_i = seg_inner.shape[1] // b_inner
    big = jnp.int32(2 ** 30)
    so = seg_outer.reshape(bsz, n_o, b_outer)
    si = seg_inner.reshape(bsz, n_i, b_inner)
    o_lo = jnp.min(jnp.where(so >= 0, so, big), axis=2)      # [B, n_o]
    o_hi = jnp.max(jnp.where(so >= 0, so, -big), axis=2)
    i_lo = jnp.min(jnp.where(si >= 0, si, big), axis=2)      # [B, n_i]
    i_hi = jnp.max(jnp.where(si >= 0, si, -big), axis=2)
    # inner block s overlaps outer block j iff the segment intervals
    # intersect; all-padding blocks (empty interval) never overlap, and
    # they may sit ANYWHERE between segments, so the window bounds come
    # from the live blocks' indices, not from counting "blocks before"
    live = jnp.logical_and(i_hi[:, None, :] >= o_lo[:, :, None],
                           i_lo[:, None, :] <= o_hi[:, :, None])
    idx = jnp.arange(n_i, dtype=jnp.int32)[None, None, :]
    lo = jnp.min(jnp.where(live, idx, n_i), axis=2)
    hi = jnp.max(jnp.where(live, idx, -1), axis=2)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _pair_live(tab_ref, lo_ref, hi_ref, len_ref, p, b, block_k):
    """Scalar liveness of pair ``p`` for batch row ``b``: inside the
    dynamic window AND the k block holds at least one valid key.
    Shared by the forward and dq kernels (the dk/dv kernel swaps the
    window roles — see ``_bwd_dkv_pair_kernel``)."""
    j = tab_ref[0, p]
    s = tab_ref[1, p]
    live = jnp.logical_and(s >= lo_ref[b, j], s <= hi_ref[b, j])
    return jnp.logical_and(live, s * block_k < len_ref[b])


def _win_clip(idx, lo, hi, n: int):
    """Clamp a block index into a dynamic [lo, hi] window and then the
    array bound (an empty lo > hi window would otherwise produce an
    out-of-range index for a pair that is compute-skipped anyway)."""
    return jnp.clip(jnp.clip(idx, lo, hi), 0, n - 1)


# ------------------------------------------------ pair-grid fwd kernel
#: lanes of a vector register: the forward kernel keeps a row's running
#: maximum and normalizer once in every lane, ``[block_q, 128]``.  As a
#: ``[block_q, 1]`` column each is as many registers with one lane in
#: 128 at work, and the dozen updates a step makes of them then cost
#: more than the 512 × 512 tile's own softmax (PERF.md §6, PR 32)
_LANES = 128


def _lanes(x, width: int):
    """``x`` [rows, 128], every lane of a row the same → [rows, width]."""
    reps, rem = divmod(width, _LANES)
    parts = [x if reps == 1 else pltpu.repeat(x, reps, axis=1)] \
        if reps else []
    if rem:
        parts.append(x[:, :rem])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _segment_uniform(segments, block: int):
    """int32 [B, n_blocks]: the segment id every token of the block
    bears, −1 where a block holds padding or more than one segment
    (minimum and maximum agree on a valid id, or the block is mixed)."""
    seg = segments.reshape(segments.shape[0], -1, block)
    lo, hi = seg.min(axis=2), seg.max(axis=2)
    return jnp.where(lo == hi, lo, -1).astype(jnp.int32)


#: what one forward step may hold in VMEM by :func:`_heads_per_step`'s
#: count, of the 16 MB Mosaic allows a kernel unasked
_FWD_VMEM_BUDGET = 12 << 20


def _heads_per_step(h: int, per_group: int, bq: int, bk: int, d: int,
                    d_v: int, itemsize: int) -> int:
    """Heads one grid step of the forward kernel takes.  A step costs
    the scalar core its index maps, liveness tests and DMA set-up
    whatever it computes: about a quarter of a 512 × 512 tile's time,
    shared by the heads of the step (PERF.md §6, PR 32).  The heads of
    a step lie in one batch row and, under grouped K/V heads, in one
    group (they then share the step's one K and V block); the count is
    the largest of 8, 4, 2 that divides so and fits the budget:
    operands and results double-buffered, the statistics and the
    accumulator, three tiles of intermediates."""
    for n in (8, 4, 2):
        if h % n or (per_group > 1 and per_group % n):
            continue
        kv = 1 if per_group > 1 else n
        vmem = (2 * itemsize * (n * bq + kv * bk) * (d + d_v)
                + 4 * n * bq * (2 * _LANES + d_v + 2 * 8)
                + 3 * 4 * bq * bk)
        if vmem <= _FWD_VMEM_BUDGET:
            return n
    return 1


def _fa_pair_kernel(*refs, scale, causal, block_q, block_k, n_heads,
                    packed, window=0, causal_block=0):
    """Grid (B·H / heads a step, n_pairs) over the q-major pair table:
    the online softmax carries in VMEM scratch across one q block's
    pairs, initialized at its first table entry and flushed at its
    last.  Dead pairs (no valid key in the window) skip the compute;
    their DMA was already skipped by the clamped index maps.  The
    leading axis of the q, o and scratch blocks is the step's heads
    (:func:`_heads_per_step`); k and v hold as many, or the one head
    the group shares.

    The step does for an element only what its block needs.  The two
    products take q, k and v **in the dtype they arrive in** (bfloat16
    from a bfloat16 pool, float32 from a float32 one) and accumulate in
    float32, as ``m``, ``l`` and the accumulator stay; the scale sits
    in the exponent (``m`` is the running maximum of the unscaled
    scores, ``scale`` > 0), so the query is multiplied as stored.  A
    pair that the table calls interior, whose keys all lie under the
    length and whose two blocks bear one valid segment, holds no
    hidden element: it runs without :func:`_tile_mask`."""
    (len_ref, lo_ref, hi_ref, tab_ref, uq_ref, uk_ref,
     q_ref, k_ref, v_ref, *refs) = refs
    if packed:
        sq_ref, sk_ref, o_ref, lse_ref, m_s, l_s, acc_s = refs
    else:
        o_ref, lse_ref, m_s, l_s, acc_s = refs
    heads, d_v = acc_s.shape[0], acc_s.shape[2]
    p = pl.program_id(1)
    b = pl.program_id(0) * heads // n_heads
    kv_len = len_ref[b]
    j = tab_ref[0, p]
    s_blk = tab_ref[1, p]
    q_off = j * block_q
    k_off = s_blk * block_k
    operand = jnp.promote_types(q_ref.dtype, k_ref.dtype)

    @pl.when(tab_ref[2, p] == 1)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    def _heads(body):
        # a loop, not an unrolled one: the step's ops are traced and
        # lowered once, whatever the heads (set-up time, PERF.md §6)
        jax.lax.fori_loop(0, heads, lambda hh, _: body(hh), None)

    def _step(masked: bool):
        valid = _tile_mask(
            q_off, k_off, kv_len, causal, block_q, block_k,
            sq_ref[0] if packed else None,
            sk_ref[0, 0] if packed else None, window,
            causal_block) if masked else None

        def one_head(hh):
            kh = hh if k_ref.shape[0] > 1 else 0
            vb = v_ref[kh]                              # [bk, Dv]
            s = jax.lax.dot_general(                    # q · kᵀ [bq, bk]
                q_ref[hh].astype(operand), k_ref[kh].astype(operand),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if masked:
                s = jnp.where(valid, s, NEG_INF)
            m_prev = m_s[hh]                            # [bq, 128]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # a fully-masked ROW inside a live block (packed: padding
            # queries sharing a block with valid ones) has m_new =
            # NEG_INF; exp(s − m_new) would be exp(0) = 1 and leak mass
            # — clamp the exponent base so those rows underflow to 0
            # instead (the flush then emits exact zeros)
            m_base = jnp.maximum(m_new, NEG_INF / 2)
            pexp = jnp.exp((s - _lanes(m_base, block_k)) * scale)
            alpha = jnp.exp((m_prev - m_base) * scale)
            m_s[hh] = m_new  # ptpu: lint-ok[PT-TRACE] a Pallas ref store
            # ptpu: lint-ok[PT-TRACE] a Pallas ref store
            l_s[hh] = l_s[hh] * alpha + pexp.sum(axis=-1, keepdims=True)
            # ptpu: lint-ok[PT-TRACE] a Pallas ref store
            acc_s[hh] = acc_s[hh] * _lanes(alpha, d_v) \
                + jax.lax.dot_general(
                    pexp.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        _heads(one_head)

    live = _pair_live(tab_ref, lo_ref, hi_ref, len_ref, p, b, block_k)
    seg = uq_ref[b, j]
    interior = jnp.logical_and(
        jnp.logical_and(tab_ref[4, p] == 1, k_off + block_k <= kv_len),
        jnp.logical_and(seg >= 0, seg == uk_ref[b, s_blk]))
    pl.when(jnp.logical_and(live, interior))(lambda: _step(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(
        lambda: _step(True))

    @pl.when(tab_ref[3, p] == 1)
    def _flush():
        def one_head(hh):
            # guard fully-masked rows (query past a zero-length
            # sequence / padding segment): l = 0 → emit 0 not NaN, and
            # an lse well away from NEG_INF so the backward's
            # p = exp(s − lse) underflows to 0 instead of
            # exp(NEG_INF − NEG_INF) = 1 leaking gradients
            dead = l_s[hh] == 0.0
            l_safe = jnp.where(dead, 1.0, l_s[hh])
            o_ref[hh] = (acc_s[hh] / _lanes(l_safe, d_v)
                         ).astype(o_ref.dtype)
            # the backward kernels read lse in the scaled scores' units
            lse = jnp.where(dead, NEG_INF / 2,
                            m_s[hh] * scale + jnp.log(l_safe))
            # lse block is (·, 8, bq) purely for TPU tiling (last two
            # dims must be (8k, 128k) or match the array); row 0
            # carries the data
            lse_ref[hh] = jnp.broadcast_to(lse[:, 0][None, :],
                                           (8, block_q))

        _heads(one_head)


def _tiling_ok(tq: int, tk: int, bq: int, bk: int) -> bool:
    """Mosaic block constraints: the lse block's last dim (bq) must be a
    multiple of 128 or equal Tq; the k/v block's penultimate dim (bk)
    must be a multiple of 8 or equal Tk.  Checked on EVERY backend so
    interpret-mode tests exercise the same dispatch as real TPU."""
    ok_q = bq % 128 == 0 or bq == tq
    ok_k = bk % 8 == 0 or bk == tk
    return ok_q and ok_k


def packed_tileable(t_total: int, block_q: int, block_k: int) -> bool:
    """Would a packed (flattened, self-attention) layout of
    ``t_total`` tokens hit the Pallas kernels?  The layer pre-checks
    this and reverts an untileable flatten to the padded per-row
    lowering — the op-level dense fallback on a [1, B·T] axis would
    build an O((B·T)²) score matrix."""
    bq = _choose_block(t_total, block_q)
    bk = _choose_block(t_total, block_k)
    return _tiling_ok(t_total, t_total, bq, bk)


def _mask_scores(s, causal, lengths, segments=None, window=0,
                 causal_block=0):
    """Apply causal / key-padding / packed-segment masks to
    [B, H, Tq, Tk] scores — the dense-path twin of :func:`_tile_mask`
    (same semantics at full-matrix granularity)."""
    tq, tk = s.shape[-2], s.shape[-1]
    if causal:
        qi = jnp.arange(tq)[None, None, :, None]
        ki = jnp.arange(tk)[None, None, None, :]
        seen = _sees_up_to(qi, causal_block) >= ki
        if window:
            seen = jnp.logical_and(seen, qi - ki < window)
        s = jnp.where(seen, s, NEG_INF)
    if lengths is not None:
        valid = jnp.arange(tk)[None, :] < lengths[:, None]   # [B, Tk]
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    if segments is not None:
        sq = segments[:, None, :, None]                      # [B,1,Tq,1]
        sk = segments[:, None, None, :]
        s = jnp.where(jnp.logical_and(sq == sk, sq >= 0), s, NEG_INF)
    return s


def _dense_forward(q, k, v, lengths, causal, segments=None, window=0,
                   causal_block=0):
    """Fallback for shapes the kernel can't tile (and the exact
    unfused reference the kill switches restore): plain XLA attention,
    same (out, lse) contract so the shared backward rule applies.
    Grouped KV heads are repeated out to the query heads."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(a, q.shape[2] // a.shape[2], axis=2)
                for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = _mask_scores(s, causal, lengths, segments, window, causal_block)
    m = s.max(axis=-1)
    # fully-masked rows (query past a zero-length sequence): emit 0
    m_safe = jnp.maximum(m, NEG_INF / 2)
    l = jnp.exp(s - m_safe[..., None]).sum(axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    lse = m_safe + jnp.log(l_safe)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype), lse


def _record_attn_work(kernel, bh, tq, tk, bq, bk, d, causal, slot,
                      operands, results, window=0, d_v=None,
                      causal_block=0):
    """The work account of one flash kernel (``ops/kernels.py``): 4·d
    FLOPs per (query, key) position of the statically live blocks —
    QKᵀ and PV forward; dP and dQ, or dV and dK, backward (the scores
    a backward kernel recomputes are re-done work, not the op's).
    Where the values are ``d_v`` wide and not ``d``: 2·(d + d_v).  The
    forward pair kernels also tick ``kind=pairs`` and
    ``kind=pairs_interior``: the block pairs of the table, and those
    the diagonal and the window leave whole."""
    tab = _pair_tables(tq, tk, bq, bk, causal, slot, window,
                       causal_block)[0]
    n_pairs = tab.shape[1]
    # how often the forward pair kernel's unmasked body can engage
    more = dict(pairs=n_pairs, pairs_interior=int(tab[4].sum())) \
        if kernel in (K.FLASH_FWD, K.FLASH_FWD_PACKED) else {}
    K.record_kernel_work(
        kernel, 2.0 * (d + (d if d_v is None else d_v)) * bq * bk
        * n_pairs * bh, operands, results, **more)


def _heads_first(a, b, t, h, d):
    """[B, T, H, D] → [B·H, T, D] so one grid row owns one head."""
    return a.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _fa_forward_sparse(q, k, v, lengths, causal, bq, bk,
                       segments=None, slot=0, window=0, causal_block=0):
    """Pair-table (block-sparse) forward: the call's work account, then
    :func:`_fa_sparse_call`.  With grouped KV heads (``k``/``v`` hold
    G < H heads) a grid row's k/v blocks are its group's: no copy of K
    or V is made.  The values may be another width than the queries
    and keys (``v``'s last axis): the result has the values' width."""
    b, tq, h, d = q.shape
    tk, g, d_v = k.shape[1], k.shape[2], v.shape[3]
    arr = jax.ShapeDtypeStruct
    operands = [arr((b * h, tq, d), q.dtype), arr((b * g, tk, d), k.dtype),
                arr((b * g, tk, d_v), v.dtype)]
    if segments is not None:
        operands += [arr((b, tq), jnp.int32)] * 2       # q's and k's ids
    _record_attn_work(
        K.FLASH_FWD if segments is None else K.FLASH_FWD_PACKED, b * h,
        tq, tk, bq, bk, d, causal, slot, operands,
        [arr((b * h, tq, d_v), q.dtype), arr((b * h, 8, tq), jnp.float32)],
        window, d_v, causal_block)
    return _fa_sparse_call(q, k, v, lengths, segments, causal=causal,
                           bq=bq, bk=bk, slot=slot, window=window,
                           causal_block=causal_block)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "slot",
                                             "window", "causal_block"))
def _fa_sparse_call(q, k, v, lengths, segments, *, causal, bq, bk, slot,
                    window, causal_block=0):
    """The forward pair kernel's call: grid (B·H / n, n_pairs), n heads
    a step (:func:`_heads_per_step`).  Jitted on its own: the layers of
    a decoder call it with one set of shapes, and the kernel is traced
    and lowered once for all of them, not once a layer (a serve cell's
    four prefill programs hold 20 to 96 such calls, traced anew in
    every process: PERF.md §6, PR 32)."""
    b, tq, h, d = q.shape
    tk, g, d_v = k.shape[1], k.shape[2], v.shape[3]
    scale = 1.0 / np.sqrt(d)
    qh = _heads_first(q, b, tq, h, d)
    kh = _heads_first(k, b, tk, g, d)
    vh = _heads_first(v, b, tk, g, d_v)
    nq, nk = tq // bq, tk // bk
    tab = jnp.asarray(_pair_tables(tq, tk, bq, bk, causal, slot,
                                   window, causal_block)[0])
    n_pairs = tab.shape[1]
    if segments is None:
        lo, hi = _length_windows(lengths, b, nq, bk)
        # one segment, no padding query: every block is uniform
        useg_q = jnp.zeros((b, nq), jnp.int32)
        useg_k = jnp.zeros((b, nk), jnp.int32)
    else:
        lo, hi = _segment_windows(segments, segments, bq, bk)
        useg_q = _segment_uniform(segments, bq)
        useg_k = _segment_uniform(segments, bk)
    per_group = h // g
    n = _heads_per_step(h, per_group, bq, bk, d, d_v, q.dtype.itemsize)
    n_kv = 1 if per_group > 1 else n

    # grid row i holds heads i·n … i·n + n − 1 of batch row i·n // h
    def q_idx(i, p, ln, lo_, hi_, tb, *_):
        return (i, tb[0, p], 0)

    def kv_idx(i, p, ln, lo_, hi_, tb, *_):
        j, first = tb[0, p], i * n
        row = i if g == h else \
            (first // h) * g + (first % h) // per_group
        return (row, _win_clip(tb[1, p], lo_[first // h, j],
                               hi_[first // h, j], nk), 0)

    def sq_idx(i, p, ln, lo_, hi_, tb, *_):
        return (i * n // h, tb[0, p], 0)

    def sk_idx(i, p, ln, lo_, hi_, tb, *_):
        j, row = tb[0, p], i * n // h
        return (row, _win_clip(tb[1, p], lo_[row, j], hi_[row, j], nk),
                0, 0)

    in_specs = [
        pl.BlockSpec((n, bq, d), q_idx),
        pl.BlockSpec((n_kv, bk, d), kv_idx),
        pl.BlockSpec((n_kv, bk, d_v), kv_idx),
    ]
    operands = [qh, kh, vh]
    if segments is not None:
        # the queries' ids as a column, the keys' along the lanes: the
        # mask compares them as they lie, and a step's key ids come in
        # one contiguous copy (a block of its own array dims: any bk)
        seg = segments.astype(jnp.int32)
        in_specs += [pl.BlockSpec((1, bq, 1), sq_idx),
                     pl.BlockSpec((1, 1, 1, bk), sk_idx)]
        operands += [seg.reshape(b, tq, 1), seg.reshape(b, nk, 1, bk)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(b * h // n, n_pairs),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n, bq, d_v), q_idx),
            pl.BlockSpec((n, 8, bq),
                         lambda i, p, ln, lo_, hi_, tb, *_:
                         (i, 0, tb[0, p])),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, bq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((n, bq, _LANES), jnp.float32),   # normalizer
            pltpu.VMEM((n, bq, d_v), jnp.float32),      # accumulator
        ],
    )
    kernel = functools.partial(
        _fa_pair_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, n_heads=h, packed=segments is not None,
        window=window, causal_block=causal_block)
    out_shape = [
        jax.ShapeDtypeStruct((b * h, tq, d_v), q.dtype),
        jax.ShapeDtypeStruct((b * h, 8, tq), jnp.float32),
    ]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.FLASH_FWD if segments is None else K.FLASH_FWD_PACKED,
    )(lengths.astype(jnp.int32), lo, hi, tab, useg_q, useg_k, *operands)
    out = out.reshape(b, h, tq, d_v).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :].reshape(b, h, tq)
    return out, lse


# --------------------------------------------------- legacy full grid
def _fa_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s,
               acc_s, *, scale, causal, block_q, block_k, n_kblocks,
               n_heads):
    """Legacy grid (B·H, q_blocks, k_blocks); k innermost so the
    scratch accumulators carry the online softmax across k steps.
    Every k/v block is DMA'd; ``pl.when`` skips only the compute —
    kept byte-for-byte behind ``--flash_block_sparse=false``."""
    i_k = pl.program_id(2)
    kv_len = len_ref[pl.program_id(0) // n_heads]

    @pl.when(i_k == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q_off = pl.program_id(1) * block_q
    k_off = i_k * block_k

    def _step():
        q = q_ref[0].astype(jnp.float32) * scale        # [bq, D]
        kb = k_ref[0]                                   # [bk, D]
        vb = v_ref[0]
        s = q @ kb.astype(jnp.float32).T                # [bq, bk]
        valid = _tile_mask(q_off, k_off, kv_len, causal, block_q,
                           block_k)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_s[:]
        l_prev = l_s[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_s[:] = m_new
        l_s[:] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[:] = acc_s[:] * alpha + p @ vb.astype(jnp.float32)

    # skip k blocks with no valid key: fully above the causal diagonal
    # or fully inside the padding (compute only — the DMA already ran)
    live = k_off < kv_len
    if causal:
        live = jnp.logical_and(live,
                               _causal_block_live(q_off, k_off, block_q))
    pl.when(live)(_step)

    @pl.when(i_k == n_kblocks - 1)
    def _flush():
        l_safe = jnp.where(l_s[:] == 0.0, 1.0, l_s[:])
        m_safe = jnp.maximum(m_s[:], NEG_INF / 2)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_safe + jnp.log(l_safe))[:, 0][None, :], (8, block_q))


def _fa_forward_grid(q, k, v, lengths, causal, bq, bk):
    """Legacy full-grid forward (``--flash_block_sparse=false``)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    qh = _heads_first(q, b, tq, h, d)
    kh = _heads_first(k, b, tk, h, d)
    vh = _heads_first(v, b, tk, h, d)
    n_kblocks = tk // bk
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk,
                               n_kblocks=n_kblocks, n_heads=h)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, tq // bq, n_kblocks),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, s, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, j, 0)),
            pl.BlockSpec((1, 8, bq), lambda i, j, s, *_: (i, 0, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # running normalizer
            pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        jax.ShapeDtypeStruct((b * h, 8, tq), jnp.float32),
    ]
    _record_attn_work(K.FLASH_FWD_GRID, b * h, tq, tk, bq, bk, d, causal,
                      0, (qh, kh, vh), out_shape)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.FLASH_FWD_GRID,
    )(lengths.astype(jnp.int32), qh, kh, vh)
    out = out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    lse = lse[:, 0, :].reshape(b, h, tq)
    return out, lse


def _block_sparse() -> bool:
    from ..utils import FLAGS

    return bool(FLAGS.flash_block_sparse)


def _flash_enabled() -> bool:
    from ..utils import FLAGS

    return bool(FLAGS.flash_kernel)


def _fa_forward(q, k, v, lengths, causal, block_q, block_k,
                segments=None, slot=0, window=0, causal_block=0):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    enforce(not window or causal,
            "a sliding window is a causal mask's: pass causal=True")
    enforce(causal_block <= 1 or (causal and not window and causal_block
                                  & (causal_block - 1) == 0),
            f"causal_block {causal_block}: a power of two, causal, and "
            "no window beside it")
    enforce(k.shape[2] == h or (segments is not None
                                and h % k.shape[2] == 0),
            f"K/V heads {k.shape[2]} must equal the {h} query heads "
            "(the packed entry also takes a divisor: grouped KV heads)")
    enforce(k.shape[3] == d and (v.shape[3] == d or segments is not None),
            f"q/k widths {d}/{k.shape[3]} must agree, and the values' "
            f"{v.shape[3]} too (the packed entry alone takes another)")
    if causal:
        # a causal mask is only meaningful on a shared timeline
        enforce(tq == tk,
                f"causal attention needs Tq == Tk, got {tq}/{tk}")
    bq = _choose_block(tq, block_q)
    bk = _choose_block(tk, block_k)
    enforce(bq % max(causal_block, 1) == 0,
            f"a query tile of {bq} cuts blocks of {causal_block}")
    if lengths is None:
        lengths = jnp.full((b,), tk, jnp.int32)
    packed = segments is not None
    if packed:
        enforce(tq == tk, "packed attention is self-attention: one "
                          f"segment table, Tq == Tk, got {tq}/{tk}")
    if not _flash_enabled():
        record_attention_dispatch(
            "dense", "kill_switch:flash_kernel")
        return _dense_forward(q, k, v, lengths, causal, segments, window,
                              causal_block)
    if not _tiling_ok(tq, tk, bq, bk):
        reason = "untileable shape (lse/kv block constraints)"
        record_attention_dispatch("dense", reason)
        _warn_dense_fallback(reason, tq, tk, bq, bk)
        return _dense_forward(q, k, v, lengths, causal, segments, window,
                              causal_block)
    if _block_sparse():
        reason = ""
        if packed and slot and (slot % bq or slot % bk) \
                and (tq > bq or tk > bk):
            # the slot hint can only drop cross-slot pairs when slots
            # are whole blocks; otherwise the grid keeps the full
            # cross product (windows still skip the compute + DMA,
            # but every pair is a scheduled step — O(B²) grid growth).
            # A single-block grid (the server's B·T ≤ 512 prefill
            # buckets) has no cross product to drop: not a fallback.
            reason = "slot hint unusable (blocks straddle slots)"
            warn_once(
                f"flash_attention_packed_slot:{slot}:{bq}x{bk}",
                "flash_attention_packed: slot hint %d unusable with "
                "blocks %d/%d (not whole blocks per slot); the pair "
                "table keeps the full cross product — prefer blocks "
                "dividing the slot width", slot, bq, bk, logger=_log)
        record_attention_dispatch("packed" if packed
                                   else "block_sparse", reason)
        return _fa_forward_sparse(q, k, v, lengths, causal, bq, bk,
                                  segments, slot, window, causal_block)
    if packed:
        # the legacy grid has no segment plumbing: exact dense fallback
        record_attention_dispatch(
            "dense", "kill_switch:flash_block_sparse(packed)")
        return _dense_forward(q, k, v, lengths, causal, segments, window,
                              causal_block)
    record_attention_dispatch("legacy_grid",
                               "kill_switch:flash_block_sparse")
    return _fa_forward_grid(q, k, v, lengths, causal, bq, bk)


# ------------------------------------------------------ backward kernels
def _recompute_block(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     q_off, k_off, kv_len, scale, causal, block_q,
                     block_k, seg_q=None, seg_k=None):
    """Rebuild one (q-block, k-block) softmax tile from the saved
    logsumexp and return (p, ds, q, kb, do) in f32 — shared by the dq
    and dk/dv kernels (legacy AND pair-grid) so their masking/scaling
    can never diverge from the forward's :func:`_tile_mask`."""
    q = q_ref[0].astype(jnp.float32)
    kb = k_ref[0].astype(jnp.float32)
    vb = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]         # [bq, 1]
    delta = delta_ref[0, 0].astype(jnp.float32)[:, None]
    s = (q @ kb.T) * scale
    valid = _tile_mask(q_off, k_off, kv_len, causal, block_q, block_k,
                       seg_q, seg_k)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    ds = p * (do @ vb.T - delta)
    return p, ds, q, kb, do


def _bwd_live(q_off, k_off, kv_len, causal, block_q):
    """Legacy-grid skip condition shared by both backward kernels: a
    block with no valid key (padding tail or fully above the causal
    diagonal)."""
    live = k_off < kv_len
    if causal:
        live = jnp.logical_and(live,
                               _causal_block_live(q_off, k_off, block_q))
    return live


def _bwd_dq_pair_kernel(*refs, scale, causal, block_q, block_k,
                        n_heads, packed):
    """Grid (B·H, n_pairs) over the q-major pair table: accumulate dq
    for one q block across its (causally-live) k pairs."""
    if packed:
        (len_ref, lo_ref, hi_ref, tab_ref, q_ref, k_ref, v_ref, do_ref,
         lse_ref, delta_ref, sq_ref, sk_ref, dq_ref, acc_s) = refs
    else:
        (len_ref, lo_ref, hi_ref, tab_ref, q_ref, k_ref, v_ref, do_ref,
         lse_ref, delta_ref, dq_ref, acc_s) = refs
        sq_ref = sk_ref = None
    p = pl.program_id(1)
    b = pl.program_id(0) // n_heads
    kv_len = len_ref[b]
    q_off = tab_ref[0, p] * block_q
    k_off = tab_ref[1, p] * block_k

    @pl.when(tab_ref[2, p] == 1)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    def _step():
        _p, ds, _q, kb, _do = _recompute_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_off,
            k_off, kv_len, scale, causal, block_q, block_k,
            None if sq_ref is None else sq_ref[0],
            None if sk_ref is None else sk_ref[0, :, 0][None, :])
        acc_s[:] = acc_s[:] + ds @ kb * scale

    pl.when(_pair_live(tab_ref, lo_ref, hi_ref, len_ref, p, b,
                       block_k))(_step)

    @pl.when(tab_ref[3, p] == 1)
    def _flush():
        dq_ref[0] = acc_s[:].astype(dq_ref.dtype)


def _bwd_dkv_pair_kernel(*refs, scale, causal, block_q, block_k,
                         n_heads, packed):
    """Grid (B·H, n_pairs) over the k-major pair table: accumulate
    dk/dv for one k block across its (causally-live) q pairs.  The
    dynamic window here runs over q blocks (packed segments); the
    key-padding liveness keeps the k-block-vs-length test."""
    if packed:
        (len_ref, lo_ref, hi_ref, tab_ref, q_ref, k_ref, v_ref, do_ref,
         lse_ref, delta_ref, sq_ref, sk_ref, dk_ref, dv_ref, dk_s,
         dv_s) = refs
    else:
        (len_ref, lo_ref, hi_ref, tab_ref, q_ref, k_ref, v_ref, do_ref,
         lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s) = refs
        sq_ref = sk_ref = None
    p = pl.program_id(1)
    b = pl.program_id(0) // n_heads
    kv_len = len_ref[b]
    j = tab_ref[0, p]
    s_blk = tab_ref[1, p]
    q_off = j * block_q
    k_off = s_blk * block_k

    @pl.when(tab_ref[2, p] == 1)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _step():
        pw, ds, q, _kb, do = _recompute_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_off,
            k_off, kv_len, scale, causal, block_q, block_k,
            None if sq_ref is None else sq_ref[0],
            None if sk_ref is None else sk_ref[0, :, 0][None, :])
        dv_s[:] = dv_s[:] + pw.T @ do
        dk_s[:] = dk_s[:] + ds.T @ q * scale

    live = jnp.logical_and(j >= lo_ref[b, s_blk], j <= hi_ref[b, s_blk])
    live = jnp.logical_and(live, k_off < kv_len)
    pl.when(live)(_step)

    @pl.when(tab_ref[3, p] == 1)
    def _flush():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd_residual_streams(q, k, v, out, do, lse):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    qh = _heads_first(q, b, tq, h, d)
    kh = _heads_first(k, b, tk, h, d)
    vh = _heads_first(v, b, tk, h, d)
    doh = _heads_first(do, b, tq, h, d)
    # delta_i = Σ_d dO_i·O_i (softmax-backward row term), [BH, 1, T]
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       out.astype(jnp.float32)).reshape(b * h, 1, tq)
    lse3 = lse.reshape(b * h, 1, tq)
    return qh, kh, vh, doh, delta, lse3


def _fa_backward_sparse(q, k, v, lengths, out, lse, do, causal, bq, bk,
                        segments=None, slot=0):
    """Pair-table (block-sparse) backward: two kernels over the shared
    tables — dq over the q-major order, dk/dv over the k-major order —
    so the backward traffic shrinks by exactly the forward's skip
    fraction."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    qh, kh, vh, doh, delta, lse3 = _bwd_residual_streams(
        q, k, v, out, do, lse)
    lengths = lengths.astype(jnp.int32)
    nq, nk = tq // bq, tk // bk
    tab_q, tab_k = _pair_tables(tq, tk, bq, bk, causal, slot)
    tab_q = jnp.asarray(tab_q)
    tab_k = jnp.asarray(tab_k)
    if segments is None:
        lo_q, hi_q = _length_windows(lengths, b, nq, bk)
        lo_k = jnp.zeros((b, nk), jnp.int32)
        hi_k = jnp.full((b, nk), nq - 1, jnp.int32)
    else:
        lo_q, hi_q = _segment_windows(segments, segments, bq, bk)
        lo_k, hi_k = _segment_windows(segments, segments, bk, bq)
    nh = h
    packed = segments is not None

    def q_idx(i, p, ln, lo_, hi_, tb):
        return (i, tb[0, p], 0)

    def kv_idx(i, p, ln, lo_, hi_, tb):
        j = tb[0, p]
        return (i, _win_clip(tb[1, p], lo_[i // nh, j],
                             hi_[i // nh, j], nk), 0)

    def row_idx(i, p, ln, lo_, hi_, tb):
        return (i, 0, tb[0, p])

    def sq_idx(i, p, ln, lo_, hi_, tb):
        return (i // nh, tb[0, p], 0)

    def sk_idx(i, p, ln, lo_, hi_, tb):
        j = tb[0, p]
        return (i // nh, _win_clip(tb[1, p], lo_[i // nh, j],
                                   hi_[i // nh, j], nk), 0)

    common = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )
    in_specs = [
        pl.BlockSpec((1, bq, d), q_idx),
        pl.BlockSpec((1, bk, d), kv_idx),
        pl.BlockSpec((1, bk, d), kv_idx),
        pl.BlockSpec((1, bq, d), q_idx),
        pl.BlockSpec((1, 1, bq), row_idx),
        pl.BlockSpec((1, 1, bq), row_idx),
    ]
    operands = [qh, kh, vh, doh, lse3, delta]
    if packed:
        seg3 = segments.astype(jnp.int32).reshape(b, tq, 1)
        in_specs += [pl.BlockSpec((1, bq, 1), sq_idx),
                     pl.BlockSpec((1, bk, 1), sk_idx)]
        operands += [seg3, seg3]
    dq_shape = [jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32)]
    dkv_shape = [jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32)] * 2
    _record_attn_work(K.FLASH_BWD_DQ, b * h, tq, tk, bq, bk, d, causal,
                      slot, operands, dq_shape)
    _record_attn_work(K.FLASH_BWD_DKV, b * h, tq, tk, bq, bk, d, causal,
                      slot, operands, dkv_shape)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_pair_kernel, scale=scale,
                          causal=causal, block_q=bq, block_k=bk,
                          n_heads=h, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b * h, int(tab_q.shape[1])),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, bq, d), q_idx)],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=dq_shape,
        name=K.FLASH_BWD_DQ,
        **common,
    )(lengths, lo_q, hi_q, tab_q, *operands)[0]

    # k-major order: q/do/lse/delta stream per pair (their q-block index
    # is the table's, window-clamped in packed mode); k/v/dk/dv are the
    # per-k-block residents
    def q_idx2(i, p, ln, lo_, hi_, tb):
        s_ = tb[1, p]
        return (i, _win_clip(tb[0, p], lo_[i // nh, s_],
                             hi_[i // nh, s_], nq), 0)

    def kv_idx2(i, p, ln, lo_, hi_, tb):
        return (i, tb[1, p], 0)

    def row_idx2(i, p, ln, lo_, hi_, tb):
        s_ = tb[1, p]
        return (i, 0, _win_clip(tb[0, p], lo_[i // nh, s_],
                                hi_[i // nh, s_], nq))

    def sq_idx2(i, p, ln, lo_, hi_, tb):
        s_ = tb[1, p]
        return (i // nh, _win_clip(tb[0, p], lo_[i // nh, s_],
                                   hi_[i // nh, s_], nq), 0)

    def sk_idx2(i, p, ln, lo_, hi_, tb):
        return (i // nh, tb[1, p], 0)

    in_specs2 = [
        pl.BlockSpec((1, bq, d), q_idx2),
        pl.BlockSpec((1, bk, d), kv_idx2),
        pl.BlockSpec((1, bk, d), kv_idx2),
        pl.BlockSpec((1, bq, d), q_idx2),
        pl.BlockSpec((1, 1, bq), row_idx2),
        pl.BlockSpec((1, 1, bq), row_idx2),
    ]
    operands2 = [qh, kh, vh, doh, lse3, delta]
    if packed:
        seg3 = segments.astype(jnp.int32).reshape(b, tq, 1)
        in_specs2 += [pl.BlockSpec((1, bq, 1), sq_idx2),
                      pl.BlockSpec((1, bk, 1), sk_idx2)]
        operands2 += [seg3, seg3]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_pair_kernel, scale=scale,
                          causal=causal, block_q=bq, block_k=bk,
                          n_heads=h, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b * h, int(tab_k.shape[1])),
            in_specs=in_specs2,
            out_specs=[
                pl.BlockSpec((1, bk, d), kv_idx2),
                pl.BlockSpec((1, bk, d), kv_idx2),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=dkv_shape,
        name=K.FLASH_BWD_DKV,
        **common,
    )(lengths, lo_k, hi_k, tab_k, *operands2)

    unpack_q = lambda a: a.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    unpack_k = lambda a: a.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    return (unpack_q(dq).astype(q.dtype), unpack_k(dk).astype(k.dtype),
            unpack_k(dv).astype(v.dtype))


def _bwd_dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_s, *, scale, causal, block_q,
                   block_k, n_kblocks, n_heads):
    """Legacy grid (B·H, q_blocks, k_blocks), k innermost: accumulate
    dq for one q block while k/v stream through VMEM."""
    i_k = pl.program_id(2)
    kv_len = len_ref[pl.program_id(0) // n_heads]

    @pl.when(i_k == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)

    q_off = pl.program_id(1) * block_q
    k_off = i_k * block_k

    def _step():
        _p, ds, _q, kb, _do = _recompute_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_off,
            k_off, kv_len, scale, causal, block_q, block_k)
        acc_s[:] = acc_s[:] + ds @ kb * scale

    pl.when(_bwd_live(q_off, k_off, kv_len, causal, block_q))(_step)

    @pl.when(i_k == n_kblocks - 1)
    def _flush():
        dq_ref[0] = acc_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale,
                    causal, block_q, block_k, n_qblocks, n_heads):
    """Legacy grid (B·H, k_blocks, q_blocks), q innermost: accumulate
    dk/dv for one k block while q/do stream through VMEM."""
    i_q = pl.program_id(2)
    kv_len = len_ref[pl.program_id(0) // n_heads]

    @pl.when(i_q == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    k_off = pl.program_id(1) * block_k
    q_off = i_q * block_q

    def _step():
        p, ds, q, _kb, do = _recompute_block(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_off,
            k_off, kv_len, scale, causal, block_q, block_k)
        dv_s[:] = dv_s[:] + p.T @ do
        dk_s[:] = dk_s[:] + ds.T @ q * scale

    pl.when(_bwd_live(q_off, k_off, kv_len, causal, block_q))(_step)

    @pl.when(i_q == n_qblocks - 1)
    def _flush():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _fa_backward_pallas(q, k, v, lengths, out, lse, do, causal, bq, bk):
    """Legacy blockwise backward (``--flash_block_sparse=false``):
    (dq, dk, dv) without a [T, T] score matrix in HBM, full grid."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / np.sqrt(d)
    qh, kh, vh, doh, delta, lse3 = _bwd_residual_streams(
        q, k, v, out, do, lse)
    lengths = lengths.astype(jnp.int32)

    common = dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
    )
    operands = (qh, kh, vh, doh, lse3, delta)
    dq_shape = [jax.ShapeDtypeStruct((b * h, tq, d), jnp.float32)]
    dkv_shape = [jax.ShapeDtypeStruct((b * h, tk, d), jnp.float32)] * 2
    _record_attn_work(K.FLASH_BWD_DQ_GRID, b * h, tq, tk, bq, bk, d,
                      causal, 0, operands, dq_shape)
    _record_attn_work(K.FLASH_BWD_DKV_GRID, b * h, tq, tk, bq, bk, d,
                      causal, 0, operands, dkv_shape)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_kblocks=tk // bk,
                          n_heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, tq // bq, tk // bk),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, j, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, s, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, s, 0)),
                pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, j, 0)),
                pl.BlockSpec((1, 1, bq), lambda i, j, s, *_: (i, 0, j)),
                pl.BlockSpec((1, 1, bq), lambda i, j, s, *_: (i, 0, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=dq_shape,
        name=K.FLASH_BWD_DQ_GRID,
        **common,
    )(lengths, *operands)[0]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_qblocks=tq // bq,
                          n_heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, tk // bk, tq // bq),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, s, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, j, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, j, 0)),
                pl.BlockSpec((1, bq, d), lambda i, j, s, *_: (i, s, 0)),
                pl.BlockSpec((1, 1, bq), lambda i, j, s, *_: (i, 0, s)),
                pl.BlockSpec((1, 1, bq), lambda i, j, s, *_: (i, 0, s)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, j, 0)),
                pl.BlockSpec((1, bk, d), lambda i, j, s, *_: (i, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
        ),
        out_shape=dkv_shape,
        name=K.FLASH_BWD_DKV_GRID,
        **common,
    )(lengths, *operands)

    unpack_q = lambda a: a.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    unpack_k = lambda a: a.reshape(b, h, tk, d).transpose(0, 2, 1, 3)
    return (unpack_q(dq).astype(q.dtype), unpack_k(dk).astype(k.dtype),
            unpack_k(dv).astype(v.dtype))


def _dense_backward(q, k, v, lengths, out, lse, do, causal,
                    segments=None):
    """Dense einsum backward — the exact composition the kill switches
    and untileable shapes fall back to."""
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    of = out.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    s = _mask_scores(s, causal, lengths, segments)
    p = jnp.exp(s - lse[:, :, :, None])                 # softmax weights
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
    # delta_i = Σ_d dO_i·O_i (the softmax-backward row term)
    delta = jnp.einsum("bqhd,bqhd->bhq", dof, of)
    ds = p * (dp - delta[:, :, :, None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def _fa_backward(q, k, v, lengths, out, lse, do, causal, block_q,
                 block_k, segments=None, slot=0):
    """Backward dispatch — mirrors :func:`_fa_forward` exactly (same
    flags, same tiling gate) so one compiled program's forward and
    backward always take matching paths."""
    tq, tk = q.shape[1], k.shape[1]
    bq = _choose_block(tq, block_q)
    bk = _choose_block(tk, block_k)
    if _flash_enabled() and _tiling_ok(tq, tk, bq, bk):
        if _block_sparse():
            return _fa_backward_sparse(q, k, v, lengths, out, lse, do,
                                       causal, bq, bk, segments, slot)
        if segments is None:
            return _fa_backward_pallas(q, k, v, lengths, out, lse, do,
                                       causal, bq, bk)
    return _dense_backward(q, k, v, lengths, out, lse, do, causal,
                           segments)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention(q, k, v, lengths=None, causal: bool = False,
                    block_q: int = 512, block_k: int = 512):
    """softmax(q·kᵀ/√d)·v without materializing [T,T] scores in HBM.

    q, k, v: ``[B, T, H, D]``; returns ``[B, T, H, D]`` in q's dtype.
    ``lengths``: optional int32 [B] valid key lengths for padded batches
    — keys at or past the length are masked out of the softmax, and
    (block-sparse path) k/v blocks wholly past the length are neither
    DMA'd nor visited.
    """
    out, _lse = _fa_forward(q, k, v, lengths, causal, block_q, block_k)
    return out


def _fa_fwd_rule(q, k, v, lengths, causal, block_q, block_k):
    out, lse = _fa_forward(q, k, v, lengths, causal, block_q, block_k)
    return out, (q, k, v, lengths, out, lse)


def _fa_bwd_rule(causal, block_q, block_k, res, do):
    q, k, v, lengths, out, lse = res
    if lengths is None:
        lengths = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
    dq, dk, dv = _fa_backward(q, k, v, lengths, out, lse, do, causal,
                              block_q, block_k)
    return dq, dk, dv, None


flash_attention.defvjp(_fa_fwd_rule, _fa_bwd_rule)


# ------------------------------------------------------ sequence packing
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def flash_attention_packed(q, k, v, segments, causal: bool = False,
                           block_q: int = 512, block_k: int = 512,
                           slot: int = 0, window: int = 0,
                           causal_block: int = 0):
    """Packed (ragged-batch) attention: tokens attend only within
    their segment.

    q: ``[B, T_total, H, D]``, k, v: ``[B, T_total, G, D]`` with G = H
    or a divisor of it (grouped KV heads: query head h attends KV head
    ``h // (H // G)``) — mixed-length sequences share one packed token
    axis; ``segments``: int32 ``[B, T_total]`` per-token
    segment ids, **non-decreasing** over valid tokens with ``-1``
    marking padding (the packing contract — the dynamic block windows
    rely on it).  Padding tokens produce zero output and zero grads;
    cross-segment and padding blocks are neither DMA'd nor visited on
    the block-sparse path.  ``causal`` applies within segments (packed
    positions are globally ordered, so the global diagonal is the
    per-segment diagonal).  ``slot``: optional static slot width when
    the caller guarantees no segment crosses a slot boundary — pairs
    across slots leave the iteration space entirely (see
    :func:`_pair_tables`).  ``window`` (causal only): a query sees the
    ``window`` newest keys of its segment up to itself; blocks wholly
    behind it leave the iteration space too.  ``causal_block`` (causal
    only, a power of two): **block-causal** — a query sees every key of
    its own block of ``causal_block`` positions and of the blocks before
    (:func:`_sees_up_to`), blocks counted from position 0 of the packed
    axis, so a segment that starts off a block boundary shares its
    first block with the one before (the serving prefill's rows start at
    multiples of their padded width, a multiple of the block).  ``v`` may be
    ``[B, T_total, G, Dv]`` with ``Dv`` other than ``D`` (latent
    attention expands keys of 192 and values of 128 lanes a head): the
    result is ``[B, T_total, H, Dv]``.  Grouped heads, a window and
    unequal widths and blocks are the serving prefill's: forward only
    (their backward raises).
    """
    out, _lse = _fa_forward(q, k, v, None, causal, block_q, block_k,
                            segments=segments, slot=slot, window=window,
                            causal_block=causal_block)
    return out


def _fa_packed_fwd_rule(q, k, v, segments, causal, block_q, block_k,
                        slot, window, causal_block):
    out, lse = _fa_forward(q, k, v, None, causal, block_q, block_k,
                           segments=segments, slot=slot, window=window,
                           causal_block=causal_block)
    return out, (q, k, v, segments, out, lse)


def _fa_packed_bwd_rule(causal, block_q, block_k, slot, window,
                        causal_block, res, do):
    q, k, v, segments, out, lse = res
    enforce(not window and causal_block <= 1 and k.shape[2] == q.shape[2]
            and v.shape[3] == q.shape[3],
            "flash_attention_packed: no backward for a sliding window, "
            "blocks, grouped KV heads or values of another width yet "
            "(serving is forward only)")
    lengths = jnp.full((q.shape[0],), k.shape[1], jnp.int32)
    dq, dk, dv = _fa_backward(q, k, v, lengths, out, lse, do, causal,
                              block_q, block_k, segments=segments,
                              slot=slot)
    return dq, dk, dv, None


flash_attention_packed.defvjp(_fa_packed_fwd_rule, _fa_packed_bwd_rule)


def segments_from_lengths(lengths, batch: int, t: int):
    """Per-token segment ids for a padded ``[B, T]`` batch flattened to
    one packed ``[1, B·T]`` row: valid tokens of row i get id ``i``,
    padding gets ``-1`` (ids non-decreasing — the packing contract)."""
    pos = jnp.arange(t, dtype=jnp.int32)[None, :]            # [1, T]
    row = jnp.arange(batch, dtype=jnp.int32)[:, None]        # [B, 1]
    seg = jnp.where(pos < lengths[:, None], row, -1)         # [B, T]
    return seg.reshape(1, batch * t)


# --------------------------------------------------- paged-KV decode
#: positions one loop step of a decode kernel attends at most: 8 pages of
#: 64 tokens are one [512, W] operand of both products
DECODE_STEP_TOKENS = 512
#: VMEM a decode kernel may hold (its double buffers, query tile,
#: accumulator and one step's scores): half of what Mosaic gives a kernel
#: on a v5e unasked (16 MiB)
DECODE_VMEM_BUDGET = 8 << 20


def _pages_per_step(page: int, token_bytes: int, rows: int,
                    fixed_bytes: int, n_pages_max: int) -> int:
    """Pages a decode kernel takes a loop step, from what the call can
    see: the largest power of two ``c`` with ``c·page`` ≤
    :data:`DECODE_STEP_TOKENS`, at most the page table's width, that
    fits :data:`DECODE_VMEM_BUDGET`: the double buffer
    (``2·c·page·token_bytes``, ``token_bytes`` what a cached token holds
    in the pools the kernel reads), one step's float32 scores, mask and
    weights for ``rows`` queries-times-heads, and ``fixed_bytes`` (the
    query tile and the accumulator).  The step's bookkeeping (one mask,
    one max / exp / sum, one accumulator update) is paid once for the
    ``c`` pages.  8 pages of 64 for the routed and the latent pools (bf16
    rows of 512 and 640 lanes), 8 of 16 for the dense pool's f32 rows of
    2,048 (16 would hold 9.0 MB)."""
    def fits(c):
        span = c * page
        return (c <= n_pages_max and span <= DECODE_STEP_TOKENS
                and 2 * span * token_bytes + 3 * 4 * rows * span
                + fixed_bytes <= DECODE_VMEM_BUDGET)

    chunk = 1
    while fits(2 * chunk):
        chunk *= 2
    return chunk


def _decode_kernel(len_ref, pidx_ref, live_ref, q_ref, k_hbm, v_hbm,
                   o_ref, kbuf, vbuf, sem, qe_s, m_s, l_s, acc_s, *,
                   scale, page, chunk, t_q, n_heads, kv_heads, d,
                   n_pages_max, window, block=0):
    """One invocation; a loop step takes ``chunk`` pages of one row, all
    heads in it, so the time follows the K/V that is live, not the
    table's width.  The pools stay in HBM as they are stored
    (``[P, page, G·D]``); each live page of the chunk comes by its own
    DMA, K and V, into one half of a double buffer ``[chunk·page, G·D]``
    (the next chunk, the next live row's first at a row's end, in flight
    meanwhile).  All heads go through one product: the queries are laid
    out block-diagonally (``[Tq·H, G·D]``, head h's D lanes in its row),
    so ``qe @ kbufᵀ`` is every head's scores ``[Tq·H, chunk·page]`` and
    the diagonal blocks of ``p @ vbuf`` every head's output.  Operands
    in the pool's dtype, the scale on the float32 scores,
    ``m``/``l``/acc in float32.  A page slot of a chunk past the row's
    used pages is not fetched (table slots there are never read, let
    alone dereferenced); what an earlier step left in the buffer (zeros
    at first) meets a weight of 0.

    Grouped KV heads (``kv_heads`` < ``n_heads``): the pool's rows are
    ``kv_heads·D`` wide and query head h lies in the D lanes of KV head
    ``h // (n_heads // kv_heads)``, so the same two products give every
    head its group's scores and output; the flush picks each group's
    heads out of its lanes (``o_ref`` is then ``[B, Tq, H, D]``).
    ``window`` > 0: a query sees only the ``window`` newest positions
    up to its own, and a row's walk starts at the first page that holds
    one of them, so the pages behind the window are not read.
    Query t sits ``Tq - 1 - t`` before the newest position and sees up
    to itself, or, with ``block`` B > 0, up to the end of its own block
    of B positions (blocks counted from position 0: a diffusion step's
    block sees itself whole), never past the row's length."""
    n_rows, span = q_ref.shape[0], chunk * page
    rep = n_heads // kv_heads
    hd = kbuf.shape[-1]

    def used_pages(b):
        kv_len = len_ref[jnp.minimum(b, n_rows - 1)]
        return jnp.minimum((kv_len + page - 1) // page, n_pages_max)

    def first_page(b):
        """The first page row ``b``'s earliest query can see."""
        if not window:
            return 0
        kv_len = len_ref[jnp.minimum(b, n_rows - 1)]
        return jnp.maximum(kv_len - t_q - window + 1, 0) // page

    def each_live_page(b, first, slot, act):
        """``act`` on the K and the V DMA of every page of row ``b``'s
        chunk that starts at page ``first``; ``b == n_rows`` means no
        row (``live_ref`` says so)."""
        @pl.when(b < n_rows)
        def _():
            def _page(i, _):
                pg = pidx_ref[b, first + i]
                at = pl.ds(pl.multiple_of(i * page, page), page)
                act(pltpu.make_async_copy(
                    k_hbm.at[pg], kbuf.at[slot, at], sem.at[0, slot, i]))
                act(pltpu.make_async_copy(
                    v_hbm.at[pg], vbuf.at[slot, at], sem.at[1, slot, i]))
                return 0

            # a loop, not ``chunk`` unrolled predicates: the decode step
            # traces this body once a layer (set-up time)
            jax.lax.fori_loop(
                0, jnp.clip(used_pages(b) - first, 0, chunk), _page, 0)

    rows = t_q * n_heads
    group = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hd), 0) // rep
    lanes = jax.lax.broadcasted_iota(jnp.int32, (n_heads, hd), 1)
    own = (lanes >= group * d) & (lanes < (group + 1) * d)   # [H, G·D]
    ki = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 1)
    # query t of the tile sits t_q - 1 - t positions before the newest
    # and sees up to itself
    back = 0 if t_q == 1 or block else t_q - 1 - jax.lax.broadcasted_iota(
        jnp.int32, (rows, 1), 0) // n_heads

    def _row(b, n):
        """``n`` counts the chunks attended so far: its parity is the
        half of the buffers the current chunk lies in."""
        kv_len = len_ref[b]
        used, first = used_pages(b), first_page(b)
        n_chunks = (jnp.maximum(used - first, 0) + chunk - 1) // chunk
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        for t in range(t_q):
            if rep == 1:
                qt = q_ref[b, pl.ds(t, 1), :]            # [1, H·D]
            else:
                # [H, D] → each head's D lanes under every KV head
                qt = jnp.concatenate([q_ref[b, t]] * kv_heads, axis=1)
                if hd > qt.shape[1]:
                    qt = jnp.pad(qt, ((0, 0), (0, hd - qt.shape[1])))
            qe_s[pl.ds(t * n_heads, n_heads), :] = jnp.where(
                own, qt.astype(qe_s.dtype), 0)
        # a query attends every key at or before the newest it sees
        # (itself: ``back``, or its block's end: ``ends``), which also
        # masks the last page's slots past the row's length and the
        # chunk's slots that were not fetched
        newest = jnp.minimum(kv_len, used * page) - 1
        ends = None
        if block:
            pos = kv_len - t_q + jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) // n_heads
            ends = jnp.minimum(newest, pos | (block - 1))

        def _chunk(j, n):
            slot = n % 2
            last = j + 1 == n_chunks
            nxt = live_ref[b + 1]
            each_live_page(jnp.where(last, nxt, b),
                           jnp.where(last, first_page(nxt),
                                     first + (j + 1) * chunk),
                           1 - slot, lambda cp: cp.start())
            each_live_page(b, first + j * chunk, slot,
                           lambda cp: cp.wait())
            s = jax.lax.dot_general(
                qe_s[...], kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [Tq·H, span]
            at = (newest - back if ends is None else ends) \
                - (first + j * chunk) * page
            seen = ki <= at             # ki: positions within the chunk
            if window:
                seen = seen & (ki > at + (kv_len - 1 - newest) - window)
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # fully-masked queries (0 < length < Tq: the leading rows
            # of a speculative/chunked tile sit at negative positions)
            # have m_new = NEG_INF; clamp the exponent base so
            # exp(s − m) underflows to 0 instead of
            # exp(−inf − (−inf)) = 1 leaking V mass — same guard as
            # _fa_pair_kernel; the flush's l_safe emits zeros
            m_base = jnp.maximum(m_new, NEG_INF / 2)
            pexp = jnp.exp(s - m_base)
            alpha = jnp.exp(m_prev - m_base)
            m_s[...] = m_new
            l_s[...] = l_s[...] * alpha + pexp.sum(axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                pexp.astype(vbuf.dtype), vbuf[slot],
                preferred_element_type=jnp.float32)
            return n + 1

        n = jax.lax.fori_loop(0, n_chunks, _chunk, n)
        l_all = l_s[...]
        o_all = acc_s[...] / jnp.where(l_all == 0.0, 1.0, l_all)
        for t in range(t_q):              # a row of length 0: zeros
            o = o_all[t * n_heads:(t + 1) * n_heads]
            if rep == 1:
                o_ref[b, pl.ds(t, 1), :] = jnp.where(own, o, 0.0).sum(
                    axis=0, keepdims=True).astype(o_ref.dtype)
            else:
                for g in range(kv_heads):
                    o_ref[b, t, pl.ds(g * rep, rep), :] = o[
                        g * rep:(g + 1) * rep,
                        g * d:(g + 1) * d].astype(o_ref.dtype)
        return n

    # a slot never fetched holds what was there: a NaN times 0 is NaN in
    # p @ vbuf (in the scores the mask replaces it)
    vbuf[...] = jnp.zeros_like(vbuf)
    each_live_page(live_ref[0], first_page(live_ref[0]), 0,
                   lambda cp: cp.start())
    jax.lax.fori_loop(0, n_rows, _row, 0)


def _next_live_row(lengths):
    """``live[i]``, int32 ``[B + 1]``: the first row at or after ``i``
    that holds cached tokens, ``B`` for none — where a decode kernel's
    prefetch goes at a row's end."""
    b = lengths.shape[0]
    at = jnp.arange(b, dtype=jnp.int32)
    return jnp.append(jax.lax.cummin(jnp.where(lengths > 0, at, b),
                                     reverse=True), b).astype(jnp.int32)


def paged_decode_attention(q, k_pages, v_pages, page_indices, lengths,
                           window: int = 0, name=None, block: int = 0):
    """Decode-step attention over a block-paged KV cache.

    - ``q``: ``[B, Tq, H, D]`` — the row's newest ``Tq`` tokens (Tq is
      small: 1 for plain decode, the block's length for a diffusion
      step, >1 for speculative or chunked steps);
    - ``k_pages`` / ``v_pages``: the physical page pools shared by
      every row, ``[P, page_size, G·D]`` — one lane-dense row a token,
      as the server stores them, read where they lie — or
      ``[P, page_size, G, D]``, which costs the reshape (on a TPU with
      D < 128 a relayout of the pool).  ``G`` KV heads divide the ``H``
      query heads: head h attends KV head ``h // (H // G)``;
    - ``page_indices``: int32 ``[B, max_pages]`` per-row page table
      (entries past the row's used pages are ignored: never read);
    - ``lengths``: int32 ``[B]`` valid cached tokens per row — the
      query tile occupies positions ``length - Tq … length - 1``, so
      the current step's K/V must already be written to the pages;
      query t sees every position up to its own, the tile's ragged
      causal tail;
    - ``window``: 0, or the number of newest positions (its own
      included) a query sees; pages wholly behind it are not read;
    - ``block``: 0, or a power of two B: each query sees every position
      up to the end of its own block of B positions instead (blocks
      counted from position 0: ``pos | (B - 1)``; a diffusion step's
      block sees itself whole, over the blocks before it), never past
      the row's length; no window beside it.  A tile of B queries ending
      on a block's end sees up to the tile's end; a tile of 2B that
      starts on a block's start holds a block and the next, the first
      never seeing the second.  Its work is counted under
      ``K.BLOCK_DECODE``, the name its caller gives it;
    - ``name``: the kernel's name in a device trace
      (``K.PAGED_DECODE``, ``K.BLOCK_DECODE``), or None for the
      instruction name the call inherits (see the call below).

    Returns ``[B, Tq, H, D]``.  Inference-only (no custom VJP): this is
    the serving decode primitive (ROADMAP item 1) exercised standalone.
    """
    b, t_q, h, d = q.shape
    page = k_pages.shape[1]
    gd = math.prod(k_pages.shape[2:])
    g = gd // d
    enforce(k_pages.shape[2:] in ((g, d), (gd,)) and g >= 1
            and h % g == 0,
            f"page pool {k_pages.shape} is neither [P, page, G, {d}] "
            f"nor [P, page, G·{d}] with G dividing the {h} query heads")
    enforce(v_pages.shape == k_pages.shape,
            "k_pages and v_pages shapes differ: "
            f"{k_pages.shape} vs {v_pages.shape}")
    enforce(page_indices.shape[0] == b and lengths.shape == (b,),
            f"page_indices/lengths batch mismatch: "
            f"{page_indices.shape}/{lengths.shape} vs B={b}")
    enforce(not (block and window), "a block's tile takes no window")
    enforce(block == 0 or (block > 1 and block & (block - 1) == 0),
            f"block {block}: 0, or a power of two of 2 or more")
    n_pages_max = page_indices.shape[1]
    record_attention_dispatch("decode")
    width, isz = gd + -gd % 128, k_pages.dtype.itemsize
    chunk = _pages_per_step(page, 2 * width * isz, t_q * h,
                            t_q * h * width * (isz + 4), n_pages_max)
    gauge("paged_decode_pages_per_step",
          "pages one loop step of the paged decode kernel takes, as "
          "_pages_per_step chose from the call's shapes (trace-time)"
          ).set(chunk, page=str(page), width=str(width),
                dtype=k_pages.dtype.name)
    # the op at the table's capacity (what is live is the caller's to
    # say: the serve loop's span carries live_pages / attended_tokens)
    reach = b * n_pages_max * page
    kv = jax.ShapeDtypeStruct((reach, gd), k_pages.dtype)
    K.record_kernel_work(K.BLOCK_DECODE if block else K.PAGED_DECODE,
                         4.0 * t_q * h * d * reach, (q, kv, kv), (q,))
    return _paged_decode(q, k_pages, v_pages, page_indices, lengths,
                         int(window), chunk, name, int(block))


def _paged_decode(q, k_pages, v_pages, page_indices, lengths, window,
                  chunk, name=None, block=0):
    """:func:`paged_decode_attention`'s call at ``chunk`` pages a loop
    step (its rule's; ``chip_smoke.py`` times the others)."""
    lengths = lengths.astype(jnp.int32)
    # a named call is traced where it stands, under its layer's scope
    # (ops/scopes.py); the unnamed one has no scope to keep
    call = _decode_call if name is None else _decode_pallas
    return call(
        lengths, page_indices.astype(jnp.int32), _next_live_row(lengths),
        q, k_pages, v_pages, window=window, chunk=chunk, name=name,
        block=block)


def _decode_pallas(lengths, page_indices, live, q, k_pages, v_pages, *,
                   window, chunk, name=None, block=0):
    """The ``pallas_call`` of :func:`_decode_kernel`: int32 ``lengths``,
    page table and next-live-row list first (scalar-prefetched), the
    pools left in HBM."""
    b, t_q, h, d = q.shape
    page, n_pages_max = k_pages.shape[1], page_indices.shape[1]
    gd = math.prod(k_pages.shape[2:])
    g = gd // d
    # a DMA cannot cut a row inside a 128-lane tile: rows narrower than
    # whole tiles (toy sizes) are padded out, a copy that real widths
    # (G·D a multiple of 128) never make
    pad = -gd % 128
    width = gd + pad

    def rows(a):
        a = a.reshape(*a.shape[:2], -1)
        return jnp.pad(a, ((0, 0), (0, 0), (0, pad))) if pad else a
    grouped = g != h
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / np.sqrt(d),
                          page=page, chunk=chunk, t_q=t_q, n_heads=h,
                          kv_heads=g, d=d, n_pages_max=n_pages_max,
                          window=window, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[vmem, hbm, hbm],
            out_specs=[vmem],
            scratch_shapes=[
                pltpu.VMEM((2, chunk * page, width), k_pages.dtype),
                pltpu.VMEM((2, chunk * page, width), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2, chunk)),
                pltpu.VMEM((t_q * h, width), k_pages.dtype),
                pltpu.VMEM((t_q * h, 1), jnp.float32),
                pltpu.VMEM((t_q * h, 1), jnp.float32),
                pltpu.VMEM((t_q * h, width), jnp.float32),
            ],
        ),
        # one [H·D] row a query where every head has its own K/V (the
        # lanes the products leave it in); [H, D] where heads share
        out_shape=[jax.ShapeDtypeStruct(
            (b, t_q, h, d) if grouped else (b, t_q, width), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        # The default plan's caller (serving/model.py) passes no name:
        # the accepted benchmark metric paged_decode_roofline.serve
        # finds its kernel as ``%_lambda_.N = f32[B,1,H·D] …
        # custom-call(s32[…``, the instruction name the call inherits
        # from the jax.jit(lambda …) around it, until the benchmark PR
        # that repoints the metric (PERF.md §7).  A caller that passes
        # K.PAGED_DECODE reads ``%paged_decode.N`` in a trace.  The
        # work depends on run-time lengths: the serve loop's
        # serve_decode_step span carries live_tokens / live_pages /
        # attended_tokens.
        name=name,
    )(lengths, page_indices, live,
      q if grouped else rows(q), rows(k_pages), rows(v_pages))[0]
    return out if grouped else out[..., :gd].reshape(b, t_q, h, d)


#: The unnamed kernel's call, jitted on its own: the layers of a decode
#: step call it with one set of shapes, and the kernel is traced and
#: lowered once for all of them, not once a layer (24 times in the dense
#: serve cell's step, in every process: set-up time, as
#: ``_fa_sparse_call``).  **A lambda**, because an unnamed call takes its
#: instruction name from the innermost jit around it, and the accepted
#: metric finds the default plan's kernel as ``%_lambda_``
#: (``chip_smoke.py::decode_step_checks`` holds it).
_decode_call = jax.jit(
    lambda *a, **kw: _decode_pallas(*a, **kw),
    static_argnames=("window", "chunk", "name", "block"))


def paged_decode_reference(q, k_pages, v_pages, page_indices, lengths,
                           window: int = 0, block: int = 0):
    """Dense one-step reference for :func:`paged_decode_attention`
    (tests; also the numerics contract): gather each row's pages into
    a contiguous [B, max_pages·page, G, D] cache, give every query head
    its KV head, and run the dense masked attention."""
    b, t_q, h, d = q.shape
    page = k_pages.shape[1]
    n_max = page_indices.shape[1]
    gk = k_pages[page_indices.reshape(-1)].reshape(
        b, n_max * page, -1, d)
    gv = v_pages[page_indices.reshape(-1)].reshape(
        b, n_max * page, -1, d)
    rep = h // gk.shape[2]
    gk, gv = jnp.repeat(gk, rep, axis=2), jnp.repeat(gv, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   gk.astype(jnp.float32)) / np.sqrt(d)
    ki = jnp.arange(n_max * page, dtype=jnp.int32)
    qpos = (lengths[:, None] - t_q
            + jnp.arange(t_q, dtype=jnp.int32)[None, :])     # [B, Tq]
    if block:                     # up to the end of the query's block
        qpos = jnp.minimum(qpos | (block - 1), lengths[:, None] - 1)
    valid = ki[None, None, :] <= qpos[:, :, None]            # [B,Tq,K]
    if window:
        valid &= ki[None, None, :] > qpos[:, :, None] - window
    s = jnp.where(valid[:, None, :, :], s, NEG_INF)
    m = jnp.maximum(s.max(axis=-1, keepdims=True), NEG_INF / 2)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, gv.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_row_write(pages, new, page_indices, start_positions, counts):
    """Scatter new tokens' rows into their rows' physical pages of ONE
    pool — the pool-maintenance half of the paged-decode contract
    ("the current step's rows must already be written to the pages").

    - ``pages``: the pool, ``[P, page, W]`` (one lane-dense row a
      token, as the server stores it) or ``[P, page, H, D]``; returned
      in the shape it came in;
    - ``new``: ``[B, Tn, …]`` — each row's newest ``Tn`` tokens
      (``Tn`` = padded prompt length at prefill, 1 per decode step),
      whatever is behind the token axis flattened to the pool's row;
    - ``page_indices``: int32 ``[B, max_pages]`` per-row page table;
    - ``start_positions``: int32 ``[B]`` absolute position of each
      row's FIRST new token (token ``j`` of row ``b`` lands at
      ``start_positions[b] + j``);
    - ``counts``: int32 ``[B]`` valid new tokens per row — tokens at or
      past the count (prompt padding; inactive batch slots via
      ``counts == 0``) are dropped, not written.

    Pure jnp scatter (one ``.at[].set``, out-of-range destinations
    dropped), which XLA does in place on a pool that the jitted caller
    donates, as the server's steps do (``serving/model.py``).  They
    pass every layer's pool at once, ``[L·P, page, W]``, with layer
    ``i``'s page table offset by ``i·P``: a dropped token then aims
    past the last layer.
    """
    n_pages, page = pages.shape[:2]
    b, t_n = new.shape[0], new.shape[1]
    enforce(page_indices.shape[0] == b
            and start_positions.shape == (b,) and counts.shape == (b,),
            f"paged_row_write batch mismatch: page_indices "
            f"{page_indices.shape}, start_positions "
            f"{start_positions.shape}, counts {counts.shape} vs B={b}")
    pos = start_positions.astype(jnp.int32)[:, None] \
        + jnp.arange(t_n, dtype=jnp.int32)[None, :]          # [B, Tn]
    slot = jnp.clip(pos // page, 0, page_indices.shape[1] - 1)
    phys = jnp.take_along_axis(page_indices.astype(jnp.int32), slot,
                               axis=1)                       # [B, Tn]
    dest = phys * page + pos % page
    valid = (jnp.arange(t_n, dtype=jnp.int32)[None, :]
             < counts.astype(jnp.int32)[:, None]) & (pos >= 0)
    # invalid tokens aim past the pool; mode="drop" discards them
    dest = jnp.where(valid, dest, n_pages * page).reshape(-1)
    # one lane-dense row a token: for a pool stored [P, page, W] the
    # reshapes are free and the scatter writes whole rows in place
    flat = pages.reshape(n_pages * page, -1).at[dest].set(
        new.reshape(b * t_n, -1).astype(pages.dtype), mode="drop")
    return flat.reshape(pages.shape)


def paged_kv_write(k_pages, v_pages, k_new, v_new, page_indices,
                   start_positions, counts):
    """:func:`paged_row_write` for a K pool and a V pool of one shape:
    ``k_new`` / ``v_new`` ``[B, Tn, H, D]`` into ``k_pages`` /
    ``v_pages``.  Returns the updated ``(k_pages, v_pages)``."""
    enforce(v_new.shape == k_new.shape,
            f"k_new/v_new shapes differ: {k_new.shape} vs {v_new.shape}")
    return (paged_row_write(k_pages, k_new, page_indices, start_positions,
                            counts),
            paged_row_write(v_pages, v_new, page_indices, start_positions,
                            counts))


# ------------------------------------------------- a chunk of a prompt
#: keys a grid step of the chunk kernel takes at most: whole pages
CHUNK_KEYS = 512


def _chunk_kernel(at_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
                  scale, block_q, block_k):
    """Grid (head blocks, query blocks, key blocks), keys innermost: a
    query block's online softmax carries in VMEM scratch across the key
    blocks, as :func:`_fa_pair_kernel`'s does, its heads looped in the
    step over one K and V block (a group's, or each head's own).  The
    query at row t of the chunk sits at ``at[0] + t`` and sees every
    key at or before it, never one at or past ``at[0] + at[1]``; a key
    block past a query block's newest key is neither computed nor,
    by the clamped index map, fetched."""
    qb, kb = pl.program_id(1), pl.program_id(2)
    start, n = at_ref[0], at_ref[1]
    q_off = start + qb * block_q
    newest = jnp.minimum(q_off + block_q, start + n) - 1
    heads, d_v = acc_s.shape[0], acc_s.shape[2]

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(kb * block_k <= newest)
    def _step():
        qi = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k),
                                              0)
        ki = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        seen = (ki <= qi) & (ki < start + n)

        def one_head(hh, _):
            kh = hh if k_ref.shape[0] > 1 else 0
            s = jax.lax.dot_general(
                q_ref[hh], k_ref[kh], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(seen, s, NEG_INF)
            m_prev = m_s[hh]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            m_base = jnp.maximum(m_new, NEG_INF / 2)
            p = jnp.exp((s - _lanes(m_base, block_k)) * scale)
            alpha = jnp.exp((m_prev - m_base) * scale)
            m_s[hh] = m_new
            l_s[hh] = l_s[hh] * alpha + p.sum(axis=-1, keepdims=True)
            acc_s[hh] = acc_s[hh] * _lanes(alpha, d_v) + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[kh], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return 0

        jax.lax.fori_loop(0, heads, one_head, 0)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _flush():
        def one_head(hh, _):
            l = l_s[hh]
            o_ref[hh] = (acc_s[hh] / _lanes(jnp.where(l == 0.0, 1.0, l), d_v)
                         ).astype(o_ref.dtype)
            return 0

        jax.lax.fori_loop(0, heads, one_head, 0)


def chunk_prefill_attention(q, k_pages, v_pages, page_indices, at):
    """Attention of a chunk of one prompt that continues its prefill:
    every position of its row before the chunk is in the pages already,
    and the chunk's own K/V are written there first.

    - ``q``: ``[C, H, D]``, the chunk's queries at positions ``at[0]``
      … ``at[0] + C − 1``, the first ``at[1]`` of them the prompt's
      (those past it see what a query at their place would, and are the
      caller's to drop);
    - ``k_pages`` / ``v_pages``: ``[P, page, G·D]`` as the server stores
      them; ``page_indices``: int32 ``[max_pages]``, the row's table;
    - ``at``: int32 ``[2]``, (the chunk's start, its real positions).

    The row's pages are gathered whole, ``[G, max_pages·page, D]`` (8.5
    MB a layer at 16,640 positions of one K/V head of 128 in bf16), and
    go through :func:`_chunk_kernel` in blocks of :data:`CHUNK_KEYS`
    keys: query t sees position p iff p ≤ at[0] + t and p < at[0] +
    at[1].  Returns ``[C, H, D]`` in q's dtype.  Its work is counted at
    the table's capacity, 4·C·H·D FLOPs a position (what is live is the
    caller's span's to state)."""
    c, h, d = q.shape
    page, n_pages = k_pages.shape[1], page_indices.shape[0]
    gd = math.prod(k_pages.shape[2:])
    g = gd // d
    enforce(g >= 1 and h % g == 0 and v_pages.shape == k_pages.shape,
            f"page pools {k_pages.shape}/{v_pages.shape}: G·{d} lanes a "
            f"row with G dividing the {h} query heads")
    per = max(1, min(CHUNK_KEYS // page, n_pages))   # pages a key block
    blocks = -(-n_pages // per)
    # the pad's pages lie past every length: masked, never a NaN source
    table = jnp.pad(page_indices.astype(jnp.int32), (0, blocks * per
                                                     - n_pages))
    bk, tk = per * page, blocks * per * page
    k, v = (a[table].reshape(tk, g, d).transpose(1, 0, 2)
            for a in (k_pages, v_pages))                    # [G, Tk, D]
    bq = _choose_block(c, 512)
    rep = h // g
    n = _heads_per_step(h, rep, bq, bk, d, d, q.dtype.itemsize)
    n_kv = 1 if rep > 1 else n
    K.record_kernel_work(K.CHUNK_PREFILL, 4.0 * c * h * d * tk, (q, k, v),
                         (q,))

    def kv_idx(i, j, kb, at_ref):
        last = (jnp.minimum(at_ref[0] + (j + 1) * bq,
                            at_ref[0] + at_ref[1]) - 1) // bk
        return (i if rep == 1 else i * n // rep, jnp.minimum(kb, last), 0)

    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=1.0 / np.sqrt(d),
                          block_q=bq, block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // n, c // bq, blocks),
            in_specs=[pl.BlockSpec((n, bq, d), lambda i, j, kb, _: (i, j, 0)),
                      pl.BlockSpec((n_kv, bk, d), kv_idx),
                      pl.BlockSpec((n_kv, bk, d), kv_idx)],
            out_specs=pl.BlockSpec((n, bq, d), lambda i, j, kb, _: (i, j, 0)),
            scratch_shapes=[pltpu.VMEM((n, bq, _LANES), jnp.float32),
                            pltpu.VMEM((n, bq, _LANES), jnp.float32),
                            pltpu.VMEM((n, bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((h, c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.CHUNK_PREFILL,
    )(at.astype(jnp.int32), q.transpose(1, 0, 2), k, v)
    return out.transpose(1, 0, 2)


# ------------------------------------------------- latent-cache decode
def _latent_decode_kernel(len_ref, pidx_ref, live_ref, q_ref, pages_hbm,
                          o_ref, buf, sem, m_s, l_s, acc_s, *, scale,
                          page, chunk, v_width, n_pages_max):
    """One invocation; a loop step takes ``chunk`` pages of one row: the
    live ones come each by its own DMA into one half of a double buffer
    ``[chunk·page, W]`` (the next chunk — the next live row's first, at
    a row's end — in flight meanwhile), and that buffer is the step's
    keys (all ``W`` lanes) and its values (the first ``v_width``): **a
    page is fetched once**.  Every head's query is a row of ``q``
    ``[H, W]``, so ``q @ bufᵀ`` is every head's scores ``[H,
    chunk·page]`` and ``p @ buf[:, :v_width]`` every head's output.
    Operands in the pool's dtype, ``m``/``l``/acc in float32.  A page
    slot of a chunk past the row's used pages is not fetched; what an
    earlier step left there (zeros at first) meets a weight of 0."""
    n_rows, span = q_ref.shape[0], chunk * page

    def used_pages(b):
        kv_len = len_ref[jnp.minimum(b, n_rows - 1)]
        return jnp.minimum((kv_len + page - 1) // page, n_pages_max)

    def each_live_page(b, first, slot, act):
        """``act`` on the DMA of every page of row ``b``'s chunk that
        starts at page ``first``; ``b == n_rows`` means no row."""
        @pl.when(b < n_rows)
        def _():
            used = used_pages(b)
            for i in range(chunk):
                @pl.when(first + i < used)
                def _():
                    act(pltpu.make_async_copy(
                        pages_hbm.at[pidx_ref[b, first + i]],
                        buf.at[slot, pl.ds(i * page, page)],
                        sem.at[slot, i]))

    ki = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[1], span), 1)

    def _row(b, n):
        """``n`` counts the chunks attended so far: its parity is the
        half of the buffer the current chunk lies in."""
        kv_len = len_ref[b]
        n_chunks = (used_pages(b) + chunk - 1) // chunk
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        q = q_ref[b]                                     # [H, W]

        def _chunk(j, n):
            slot = n % 2
            last = j + 1 == n_chunks
            each_live_page(jnp.where(last, live_ref[b + 1], b),
                           jnp.where(last, 0, (j + 1) * chunk), 1 - slot,
                           lambda cp: cp.start())
            each_live_page(b, j * chunk, slot, lambda cp: cp.wait())
            rows = buf[slot]                             # [span, W]
            s = jax.lax.dot_general(
                q, rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            # the one query sits at position kv_len - 1 and sees every
            # key up to itself, which also masks what lies past the
            # row's length in its last page and in the slots not fetched
            s = jnp.where(j * span + ki < kv_len, s, NEG_INF)
            m_prev = m_s[...]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_s[...] = m_new
            l_s[...] = l_s[...] * alpha + pexp.sum(axis=-1, keepdims=True)
            acc_s[...] = acc_s[...] * alpha + jnp.dot(
                pexp.astype(rows.dtype), rows[:, :v_width],
                preferred_element_type=jnp.float32)
            return n + 1

        n = jax.lax.fori_loop(0, n_chunks, _chunk, n)
        l_safe = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])   # length 0
        o_ref[b] = (acc_s[...] / l_safe).astype(o_ref.dtype)
        return n

    buf[...] = jnp.zeros_like(buf)
    each_live_page(live_ref[0], 0, 0, lambda cp: cp.start())
    jax.lax.fori_loop(0, n_rows, _row, 0)


def latent_decode_attention(q, pages, page_indices, lengths, v_width: int,
                            scale: float):
    """Decode-step attention over a paged **latent** cache: one
    compressed row a token serves every query head, as its key and, in
    its first ``v_width`` lanes, as its value (multi-head latent
    attention with the key and value up-projections absorbed into the
    query and the output, ``serving/model.py``).

    - ``q``: ``[B, H, W]`` — each head's absorbed query, laid out as the
      cache rows are, in the pool's dtype;
    - ``pages``: the pool ``[P, page_size, W]``, read where it lies;
    - ``page_indices``: int32 ``[B, max_pages]``; ``lengths``: int32
      ``[B]`` cached tokens a row (the fed token's row already written;
      0: the row reads nothing and gets zeros);
    - ``scale``: the scores' factor (the width the query and key had
      before absorption, not ``W``).

    Returns ``softmax(scale · q·rowsᵀ) · rows[:, :v_width]``,
    ``[B, H, v_width]`` float32.  Inference only."""
    b, h, w = q.shape
    page = pages.shape[1]
    enforce(pages.ndim == 3 and pages.shape[2] == w and 0 < v_width <= w,
            f"latent pool {pages.shape} is not [P, page, {w}] or the "
            f"value width {v_width} is not within the row")
    enforce(page_indices.shape[0] == b and lengths.shape == (b,),
            f"page_indices/lengths batch mismatch: "
            f"{page_indices.shape}/{lengths.shape} vs B={b}")
    n_pages_max = page_indices.shape[1]
    isz = pages.dtype.itemsize
    chunk = _pages_per_step(page, w * isz, h, h * (w * isz + v_width * 4),
                            n_pages_max)
    record_attention_dispatch("latent_decode")
    lengths = lengths.astype(jnp.int32)
    live = _next_live_row(lengths)
    out_shape = jax.ShapeDtypeStruct((b, h, v_width), jnp.float32)
    # the op at the table's capacity (what is live is the caller's to
    # say: the serve loop's span carries attended_tokens)
    reach = b * n_pages_max * page
    K.record_kernel_work(
        K.LATENT_DECODE, 2.0 * h * (w + v_width) * reach,
        (q, jax.ShapeDtypeStruct((reach, w), pages.dtype)), (out_shape,))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=float(scale),
                          page=page, chunk=chunk, v_width=int(v_width),
                          n_pages_max=n_pages_max),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[vmem, hbm],
            out_specs=[vmem],
            scratch_shapes=[
                pltpu.VMEM((2, chunk * page, w), pages.dtype),
                pltpu.SemaphoreType.DMA((2, chunk)),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, v_width), jnp.float32),
            ],
        ),
        out_shape=[out_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.LATENT_DECODE,
    )(lengths, page_indices.astype(jnp.int32), live, q, pages)[0]


def latent_decode_reference(q, pages, page_indices, lengths, v_width: int,
                            scale: float):
    """Dense reference for :func:`latent_decode_attention` (tests; the
    numerics contract): gather each row's pages into one contiguous
    cache and attend it, float32 throughout."""
    b, h, w = q.shape
    page, n_max = pages.shape[1], page_indices.shape[1]
    rows = pages[page_indices.reshape(-1)].reshape(
        b, n_max * page, w).astype(jnp.float32)
    seen = jnp.arange(n_max * page)[None, :] < lengths[:, None]  # [B, K]
    # what lies past a row's length is never read (whatever it holds)
    rows = jnp.where(seen[:, :, None], rows, 0.0)
    s = jnp.einsum("bhw,bkw->bhk", q.astype(jnp.float32), rows) * scale
    s = jnp.where(seen[:, None, :], s, NEG_INF)
    m = jnp.maximum(s.max(axis=-1, keepdims=True), NEG_INF / 2)
    p = jnp.where(seen[:, None, :], jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhk,bkw->bhw", p, rows[..., :v_width])
