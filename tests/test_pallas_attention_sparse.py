"""Block-sparse / packed / paged-decode attention equivalence suite.

Round 19 turned ``ops/pallas_attention.py`` from masked-but-fetched
into truly block-sparse (scalar-prefetched pair tables + windowed DMA).
These tests pin every new path against the dense reference the kill
switches restore:

- block-skip (pair-grid) forward + gradients ≡ dense across causal /
  key-padding / rectangular / zero-length / block-boundary-length
  cases, and ≡ the legacy full grid it replaced;
- packed (segment-id) forward + gradients ≡ per-row dense attention on
  valid tokens, exact zeros on padding, layer-level kill switches in
  both directions;
- the paged-KV decode primitive ≡ a one-step dense reference over a
  partially-filled paged cache, with the page table actually driving
  the gather;
- the static pair tables: causal skip fraction, fwd/bwd same pair set
  (the single-shared-masking-helper contract);
- ``attention_dispatch_total{path,reason}`` trace-time counter pins.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.utils import FLAGS, PaddleTpuError


@pytest.fixture
def attn_flags():
    """Restore the attention dispatch flags after each test."""
    saved = {f: FLAGS.get(f) for f in
             ("flash_kernel", "flash_block_sparse", "attention_packing")}
    yield
    for f, v in saved.items():
        FLAGS.set(f, v)


def _qkv(rng, b, t, h=2, d=16, scale=0.5):
    return tuple(jnp.asarray(rng.randn(b, t, h, d).astype(np.float32))
                 * scale for _ in range(3))


def _grads(fn, q, k, v, cot):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                    argnums=(0, 1, 2))(q, k, v)


def _dense_grads(q, k, v, lengths, causal, cot, segments=None):
    """Gradients through the exact dense composition (flash off)."""
    old = FLAGS.flash_kernel
    FLAGS.set("flash_kernel", False)
    try:
        if segments is None:
            fn = lambda *a: pa.flash_attention(*a, lengths, causal,
                                               128, 16)
        else:
            fn = lambda *a: pa.flash_attention_packed(*a, segments,
                                                      causal, 128, 16)
        return _grads(fn, q, k, v, cot)
    finally:
        FLAGS.set("flash_kernel", old)


# --------------------------------------------------------- block-skip
# lengths hit a zero row, a block-boundary row (64 = 4 full k blocks of
# 16), an off-boundary row and a full row — the cases where a windowed
# DMA clamp could diverge from the mask
LENGTH_CASES = [256, 93, 64, 0]


@pytest.mark.parametrize("causal", [False, True])
def test_block_sparse_matches_dense_padded(causal, rng):
    B, T = 4, 256
    q, k, v = _qkv(rng, B, T)
    lengths = jnp.asarray(LENGTH_CASES, jnp.int32)
    cot = jnp.asarray(rng.randn(*q.shape).astype(np.float32))

    out = pa.flash_attention(q, k, v, lengths, causal, 128, 16)
    ref, _ = pa._dense_forward(q, k, v, lengths, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g = _grads(lambda *a: pa.flash_attention(*a, lengths, causal,
                                             128, 16), q, k, v, cot)
    gd = _dense_grads(q, k, v, lengths, causal, cot)
    for a, b in zip(g, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)
    # zero-length row: zero output, zero dk/dv for its keys
    assert np.abs(np.asarray(out)[3]).max() == 0.0
    assert np.abs(np.asarray(g[1])[3]).max() == 0.0
    assert np.abs(np.asarray(g[2])[3]).max() == 0.0


@pytest.mark.parametrize("causal", [False, True])
def test_block_sparse_matches_legacy_grid(causal, rng, attn_flags):
    """The compacted pair grid computes exactly what the legacy full
    grid computed — the --flash_block_sparse kill switch is a perf
    knob, never a numerics knob."""
    B, T = 2, 256
    q, k, v = _qkv(rng, B, T)
    lengths = jnp.asarray([256, 100], jnp.int32)
    cot = jnp.asarray(rng.randn(*q.shape).astype(np.float32))
    fn = lambda *a: pa.flash_attention(*a, lengths, causal, 128, 16)

    out_sparse = fn(q, k, v)
    g_sparse = _grads(fn, q, k, v, cot)
    FLAGS.set("flash_block_sparse", False)
    out_legacy = fn(q, k, v)
    g_legacy = _grads(fn, q, k, v, cot)
    np.testing.assert_allclose(np.asarray(out_sparse),
                               np.asarray(out_legacy),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(g_sparse, g_legacy):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_block_sparse_rectangular_cross(rng):
    """Tq != Tk (cross-attention shapes) on the pair grid."""
    B, TQ, TK = 2, 128, 256
    q = jnp.asarray(rng.randn(B, TQ, 2, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(B, TK, 2, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(B, TK, 2, 16).astype(np.float32))
    lengths = jnp.asarray([256, 70], jnp.int32)
    cot = jnp.asarray(rng.randn(*q.shape).astype(np.float32))
    out = pa.flash_attention(q, k, v, lengths, False, 128, 16)
    ref, _ = pa._dense_forward(q, k, v, lengths, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    g = _grads(lambda *a: pa.flash_attention(*a, lengths, False,
                                             128, 16), q, k, v, cot)
    gd = _dense_grads(q, k, v, lengths, False, cot)
    for a, b in zip(g, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_causal_tq_ne_tk_raises_paddle_error(rng):
    """Satellite: the old bare ``assert`` (vanishes under python -O) is
    now a PaddleTpuError naming the offending shapes."""
    q = jnp.zeros((1, 32, 1, 8), jnp.float32)
    k = jnp.zeros((1, 64, 1, 8), jnp.float32)
    with pytest.raises(PaddleTpuError, match="32/64"):
        pa.flash_attention(q, k, k, None, True, 32, 32)


def test_kill_switches_and_dispatch_counter(rng, attn_flags):
    """Every dispatch path ticks its own counter series, and the kill
    switches actually change the path (both directions)."""
    B, T = 2, 256
    q, k, v = _qkv(rng, B, T)

    def flat():
        return observe.REGISTRY.flat(kinds=("counter",))

    pa.flash_attention(q, k, v, None, True, 128, 16)
    assert flat()[
        'attention_dispatch_total{path="block_sparse",reason=""}'] >= 1
    FLAGS.set("flash_block_sparse", False)
    pa.flash_attention(q, k, v, None, True, 128, 16)
    assert flat()[
        'attention_dispatch_total{path="legacy_grid",'
        'reason="kill_switch:flash_block_sparse"}'] >= 1
    FLAGS.set("flash_kernel", False)
    pa.flash_attention(q, k, v, None, True, 128, 16)
    assert flat()[
        'attention_dispatch_total{path="dense",'
        'reason="kill_switch:flash_kernel"}'] >= 1
    FLAGS.set("flash_kernel", True)
    FLAGS.set("flash_block_sparse", True)
    # untileable shape → dense with the untileable reason
    qs = jnp.zeros((1, 48, 1, 8), jnp.float32)
    pa.flash_attention(qs, qs, qs, None, False, 16, 12)
    assert any(k_.startswith('attention_dispatch_total{path="dense",'
                             'reason="untileable')
               for k_ in flat())


# -------------------------------------------------------- pair tables
def test_pair_tables_causal_skip_fraction():
    """Causal tables enumerate exactly the at-or-below-diagonal block
    pairs — at T=2048 with 512 blocks that is 10 of 16 (the committed
    roofline delta's arithmetic) — and the fwd (q-major) and bwd
    (k-major) tables contain the SAME pair set, so forward and
    backward sparsity cannot diverge."""
    tab_q, tab_k = pa._pair_tables(2048, 2048, 512, 512, True)
    assert tab_q.shape == (5, 10) and tab_k.shape == (5, 10)
    pairs_q = set(zip(tab_q[0].tolist(), tab_q[1].tolist()))
    pairs_k = set(zip(tab_k[0].tolist(), tab_k[1].tolist()))
    assert pairs_q == pairs_k
    assert pairs_q == {(j, s) for j in range(4) for s in range(4)
                       if s <= j}
    # every q block flushes exactly once in the q-major order; every k
    # block flushes exactly once in the k-major order
    assert tab_q[3].sum() == 4 and tab_q[2].sum() == 4
    assert tab_k[3].sum() == 4 and tab_k[2].sum() == 4
    # non-causal: full grid, no pairs dropped
    full_q, _ = pa._pair_tables(2048, 2048, 512, 512, False)
    assert full_q.shape == (5, 16) and full_q[4].all()


def test_segment_windows_skip_interleaved_padding():
    """Padding-only blocks BETWEEN segments must not shift the window
    (regression: counting 'blocks entirely before' treated the empty
    sentinel range as before-everything)."""
    lengths = jnp.asarray([100, 64, 30], jnp.int32)
    seg = pa.segments_from_lengths(lengths, 3, 128)
    lo, hi = pa._segment_windows(seg, seg, 128, 16)
    # q blocks align with rows at bq=128: row 0 spans k blocks 0..6
    # (100 tokens / 16), row 1 blocks 8..11, row 2 blocks 16..17
    assert np.asarray(lo).tolist() == [[0, 8, 16]]
    assert np.asarray(hi).tolist() == [[6, 11, 17]]


# ------------------------------------------------------------- packed
def _np_attention(q, k, v, causal, window):
    """One row's attention in numpy, float64: q [T, H, D], k and v
    [T, G, D] (query head h attends K/V head h // (H/G)); ``window`` > 0
    hides keys ``window`` or more positions behind the query."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k, dtype=np.float64) / np.sqrt(d)
    behind = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = np.ones((t, t), bool)
    if causal:
        seen &= behind >= 0
    if window:
        seen &= behind < window
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("causal,kv_heads,window", [
    (False, 2, 0), (True, 2, 0),
    # grouped K/V heads (4 query heads over 2, over 1) and a sliding
    # window that cuts inside a k block (40 = 2.5 blocks of 16) and on
    # a block's edge (32): the serving prefill's, forward only
    (False, 1, 0), (True, 1, 0), (True, 2, 40), (True, 1, 40),
    (True, 4, 32)])
def test_packed_matches_per_row_dense(causal, kv_heads, window, rng):
    """Packed kernel over one [1, B·T] token axis ≡ per-row dense
    attention on every valid token; padding tokens emit exact zeros
    and receive exact-zero gradients."""
    D = 16
    H = 2 if (kv_heads, window) == (2, 0) else 4
    lens = [100, 64, 30]          # boundary (64 = 4·16) + odd + short
    B, T = 3, 128
    x = [rng.randn(B, T, n, D).astype(np.float32)
         for n in (H, kv_heads, kv_heads)]
    q, k, v = (jnp.asarray(a.reshape(1, B * T, -1, D)) for a in x)
    seg = pa.segments_from_lengths(jnp.asarray(lens, jnp.int32), B, T)
    out = np.asarray(pa.flash_attention_packed(q, k, v, seg, causal,
                                               128, 16, 0, window))
    out = out.reshape(B, T, H, D)
    for i, l in enumerate(lens):
        ref = _np_attention(x[0][i, :l], x[1][i, :l], x[2][i, :l],
                            causal, window)
        np.testing.assert_allclose(out[i, :l], ref, rtol=2e-4, atol=2e-5)
        assert np.abs(out[i, l:]).max() == 0.0
    cot = jnp.asarray(rng.randn(1, B * T, H, D).astype(np.float32))
    packed = lambda *a: pa.flash_attention_packed(
        *a, seg, causal, 128, 16, 0, window)
    if kv_heads != H or window:
        with pytest.raises(PaddleTpuError, match="no backward"):
            _grads(packed, q, k, v, cot)
        return
    g = _grads(packed, q, k, v, cot)
    gd = _dense_grads(q, k, v, None, causal, cot, segments=seg)
    segn = np.asarray(seg).reshape(B * T)
    for a, b in zip(g, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)
        assert np.abs(np.asarray(a)[0, segn < 0]).max() == 0.0


def _np_block_causal(q, k, v, b):
    """:func:`_np_attention` under a block-causal mask: query i sees key
    j iff ⌊j/b⌋ <= ⌊i/b⌋."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("qhd,khd->hqk", q, k, dtype=np.float64) / np.sqrt(d)
    blk = np.arange(t) // b
    s = np.where((blk[None, :] <= blk[:, None])[None], s, -np.inf)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("kv_heads,block_k", [(2, 16), (1, 16), (2, 128)])
def test_packed_block_causal_matches_per_row_dense(kv_heads, block_k, rng):
    """The block-causal mode of the packed kernel over one [1, B·T] axis
    (rows of 128, blocks of 4 counted from each row's start, lengths
    that end mid-block and on a tile's edge) ≡ per-row dense attention
    under the block mask, and ≡ the dense path of the kernel's own
    dispatch; padding emits exact zeros; no backward."""
    D, H, B, T = 16, 4, 3, 128
    lens = [101, 64, 30]
    x = [rng.randn(B, T, n, D).astype(np.float32)
         for n in (H, kv_heads, kv_heads)]
    q, k, v = (jnp.asarray(a.reshape(1, B * T, -1, D)) for a in x)
    seg = pa.segments_from_lengths(jnp.asarray(lens, jnp.int32), B, T)
    out = np.asarray(pa.flash_attention_packed(
        q, k, v, seg, True, 128, block_k, T, causal_block=4))
    dense, _ = pa._dense_forward(q, k, v, jnp.full((1,), B * T, jnp.int32),
                                 True, seg, causal_block=4)
    np.testing.assert_allclose(out, np.asarray(dense), rtol=2e-4, atol=2e-5)
    out = out.reshape(B, T, H, D)
    for i, n in enumerate(lens):
        ref = _np_block_causal(x[0][i, :n], x[1][i, :n], x[2][i, :n], 4)
        np.testing.assert_allclose(out[i, :n], ref, rtol=2e-4, atol=2e-5)
        assert np.abs(out[i, n:]).max() == 0.0
    # a block's first query sees its whole block, unlike a causal tile's
    causal = np.asarray(pa.flash_attention_packed(
        q, k, v, seg, True, 128, block_k, T)).reshape(B, T, H, D)
    assert np.abs(causal[0, 0] - out[0, 0]).max() > 1e-3
    np.testing.assert_allclose(causal[0, 3], out[0, 3], rtol=2e-4,
                               atol=2e-5)
    with pytest.raises(PaddleTpuError, match="no backward"):
        _grads(lambda *a: pa.flash_attention_packed(
            *a, seg, True, 128, block_k, T, 0, 4), q, k, v,
            jnp.ones((1, B * T, H, D)))


def test_block_causal_pair_tables_keep_the_causal_pairs():
    """Blocks of 4 inside tiles of 16: the live pairs are the causal
    table's; a pair is interior once its keys end within the q tile's
    first block, so the diagonal pairs stay masked."""
    causal = pa._pair_tables(64, 64, 16, 16, True)[0]
    block = pa._pair_tables(64, 64, 16, 16, True, causal_block=4)[0]
    np.testing.assert_array_equal(causal[:4], block[:4])
    assert causal[4].sum() == block[4].sum() == 6
    assert pa._pair_tables(64, 64, 16, 4, True, causal_block=4)[0][4].sum() \
        == pa._pair_tables(64, 64, 16, 4, True)[0][4].sum() + 4


def test_packed_window_at_the_served_shape(rng):
    """The routed decoder's longest prefill as the server launches it:
    one row of 6144 tokens in blocks of 512, 2 query heads over 1 K/V
    head of 128, window 2048, ``slot`` = the row.  Sampled queries —
    inside the window, at its edge, on block edges and at the row's
    end — equal the windowed attention over their keys."""
    T, H, D, W = 6144, 2, 128, 2048
    q = rng.randn(1, T, H, D).astype(np.float32)
    k, v = (rng.randn(1, T, 1, D).astype(np.float32) for _ in range(2))
    seg = pa.segments_from_lengths(jnp.asarray([T], jnp.int32), 1, T)
    out = np.asarray(pa.flash_attention_packed(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), seg, causal=True,
        slot=T, window=W))
    for i in (0, 511, 512, 2047, 2048, 2049, 2560, 4095, 4096, 6143):
        lo = max(0, i - W + 1)
        s = np.einsum("hd,kd->hk", q[0, i].astype(np.float64),
                      k[0, lo:i + 1, 0]) / np.sqrt(D)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        np.testing.assert_allclose(out[0, i], p @ v[0, lo:i + 1, 0],
                                   rtol=2e-4, atol=2e-5)


def test_window_drops_the_pairs_behind_it():
    """The static pair table of a causal, windowed layer holds only
    the blocks a query of the q block can see: at 6144 tokens in
    blocks of 512 under a window of 2048, 12 diagonal blocks and at
    most 4 behind each (the fifth holds only keys 2048 or more behind
    the block's first query)."""
    full = pa._pair_tables(6144, 6144, 512, 512, True)[0].shape[1]
    near = pa._pair_tables(6144, 6144, 512, 512, True, 0, 2048)[0].shape[1]
    assert full == 12 * 13 // 2
    assert near == sum(min(j + 1, 5) for j in range(12))
    # the dense fallback masks the same pairs
    s = pa._mask_scores(jnp.zeros((1, 1, 8, 8)), True, None, None, 3)
    assert (np.asarray(s[0, 0]) == 0).sum() == 3 * 8 - 3


def test_packed_layer_kill_switch_both_directions(rng, attn_flags):
    """Layer plumbing: packed=True equals the padded lowering on valid
    tokens; --attention_packing=false makes the packed layer EXACTLY
    the padded layer (byte-for-byte same path)."""
    from layer_grad_util import build_single_layer_net
    from paddle_tpu.core.sequence import pad_batch

    lens = [100, 64, 30]
    sb = pad_batch([rng.randn(l, 12).astype(np.float32) for l in lens],
                   max_len=128)
    mk = lambda packed: build_single_layer_net(
        "scaled_dot_product_attention", size=16, input_sizes=[12],
        with_bias=True, attrs={"num_heads": 4, "causal": True,
                               "block_q": 128, "block_k": 16,
                               "packed": packed})
    net_pad, net_pk = mk(False), mk(True)
    params = net_pad.init_params(seed=2)
    o_pad = np.asarray(net_pad.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    o_pk = np.asarray(net_pk.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    for i, l in enumerate(lens):
        np.testing.assert_allclose(o_pk[i, :l], o_pad[i, :l],
                                   rtol=2e-4, atol=2e-5)
    FLAGS.set("attention_packing", False)
    o_off = np.asarray(net_pk.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    np.testing.assert_array_equal(o_off, o_pad)
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="unpacked",'
                'reason="kill_switch:attention_packing"}'] >= 1
    FLAGS.set("attention_packing", True)
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="packed",reason=""}'] \
        >= 1


def test_packed_zero_length_row(rng):
    """A zero-length sequence inside a packed batch contributes nothing
    and breaks nothing."""
    lens = [60, 0, 31]
    B, T, H, D = 3, 64, 2, 16
    x = [rng.randn(B, T, H, D).astype(np.float32) for _ in range(3)]
    q, k, v = (jnp.asarray(a.reshape(1, B * T, H, D)) for a in x)
    seg = pa.segments_from_lengths(jnp.asarray(lens, jnp.int32), B, T)
    out = np.asarray(pa.flash_attention_packed(q, k, v, seg, False,
                                               128, 16))
    out = out.reshape(B, T, H, D)
    ref = np.asarray(pa._dense_forward(
        jnp.asarray(x[0]), jnp.asarray(x[1]), jnp.asarray(x[2]),
        jnp.asarray(lens, jnp.int32), False)[0])
    assert np.abs(out[1]).max() == 0.0
    for i, l in enumerate(lens):
        np.testing.assert_allclose(out[i, :l], ref[i, :l],
                                   rtol=2e-4, atol=2e-5)


# ------------------------------------------------------------- decode
def _decode_case(case, rng):
    """(H, D, pools, page table, lengths, the table the reference may
    gather through) of one named decode case.  Pages no row uses hold
    NaN where the case plants table entries that must never be
    dereferenced: a fetched page shows in the output even under p = 0."""
    H, D, P, page = 2, 16, 10, 16
    G = window = 0                    # G: K/V heads (0: one a query head)
    poison = ()
    if case == "gqa":
        # 4 query heads over 2 K/V heads: the pool's rows are G·D wide
        H, G = 4, 2
        pidx = [[2, 0, 4, 7], [5, 1, 3, 8], [9, 6, 2, 0]]
        lengths = [55, 32, 7]
    elif case in ("window", "gqa_window"):
        # a window of 20 over pages of 16: rows whose first pages lie
        # wholly behind it (never fetched: they hold NaN), a row the
        # window covers whole, and one that ends on a page's edge
        H, G = (4, 1) if case == "gqa_window" else (2, 0)
        window = 20
        pidx = [[8, 9, 4, 7], [5, 1, 3, 9], [6, 0, 0, 0], [0, 2, 0, 0]]
        lengths = [55, 48, 7, 32]
        poison = (8, 9)
    elif case == "gqa_serve":
        # the routed decoder's row: 32 heads over 4 K/V heads of 128,
        # pages of 64 and a window of 128
        H, G, D, P, page, window = 32, 4, 128, 8, 64, 128
        pidx = [[7, 2, 5, 1], [3, 6, 0, 0], [4, 0, 0, 0]]
        lengths = [250, 128, 3]
        poison = (7,)
    elif case == "base":
        # mid-page, page-boundary, and single-page fills
        pidx = [[2, 0, 4, 7], [5, 1, 3, 8], [9, 6, 2, 0]]
        lengths = [55, 32, 7]
    elif case in ("wide_scratch", "wide_garbage"):
        # the server's table: 128 slots, 3 in use, the rest the
        # scratch page or whatever was there
        fill = 0 if case == "wide_scratch" else 10 ** 6
        pidx = [[4, 2, 7] + [fill] * 125, [5, 1, 3] + [-5] * 125]
        lengths = [41, 48]
        if case == "wide_garbage":
            poison = (0, P - 1)       # where a clamped fetch would land
    elif case == "page_boundary":
        pidx = [[3, 5, 1, 0], [2, 4, 0, 0], [6, 0, 0, 0]]
        lengths = [48, 32, 16]        # every row ends on a page's edge
    elif case == "inactive_rows":
        # padded batch slots: length 1 over the scratch page, beside
        # live rows (serving/model.py's klen)
        pidx = [[0] * 6, [4, 2, 7, 9, 0, 0], [0] * 6, [5, 1, 0, 0, 0, 0]]
        lengths = [1, 60, 1, 20]
    elif case == "hd2048":
        # the serve cell's row: 32 heads of 64, lane-dense 2048
        H, D, P = 32, 64, 8
        pidx = [[6, 2, 5, 0], [1, 3, 0, 0]]
        lengths = [37, 32]
    else:
        raise AssertionError(case)
    kpg = rng.randn(P, page, G or H, D).astype(np.float32)
    vpg = rng.randn(P, page, G or H, D).astype(np.float32)
    for pg in poison:
        kpg[pg] = vpg[pg] = np.nan
    pidx = np.asarray(pidx, np.int32)
    used = -(-np.asarray(lengths) // page)
    # under a window the walk starts at the first page the row's
    # earliest query (of up to 4) can see
    first = np.maximum(np.asarray(lengths) - 4 - window + 1, 0) // page \
        if window else np.zeros_like(used)
    slot = np.arange(pidx.shape[1])[None, :]
    live = (slot >= first[:, None]) & (slot < used[:, None])
    assert not np.isin(pidx[live], poison).any()
    # the reference gathers every slot of the table before it masks:
    # give it a live page wherever the kernel must not look
    safe = np.where(live, pidx, pidx[np.arange(len(used)), first][:, None])
    return (H, D, jnp.asarray(kpg), jnp.asarray(vpg), jnp.asarray(pidx),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(safe), window)


@pytest.mark.parametrize("t_q", [4, 8])
@pytest.mark.parametrize("case", ["base", "wide_garbage", "page_boundary",
                                  "inactive_rows", "gqa", "hd2048"])
def test_paged_decode_block_mode_matches_dense_reference(case, t_q, rng):
    """The block mode in blocks of 4, over a tile of 4 queries or of 8
    (a diffusion step's tile of 2B: where its length, start + 8, puts
    its start on a block's start, it holds a finished block and the
    next, and the first never sees the second): each query sees every
    position up to the end of its own block of 4 (counted from position
    0), never past the row's length, through the same pages as the
    causal tail and under the same guards; a tile that ends on a block's
    end sees up to it whole.  The dense reference's block mode is the
    plain attention over the positions each query sees; every row as
    long as the tile is held to it query by query."""
    H, D, kpg, vpg, pidx, lengths, safe, _ = _decode_case(case, rng)
    q = jnp.asarray(rng.randn(pidx.shape[0], t_q, H, D).astype(np.float32))
    out = np.asarray(pa.paged_decode_attention(q, kpg, vpg, pidx, lengths,
                                               block=4))
    ref = np.asarray(pa.paged_decode_reference(q, kpg, vpg, safe, lengths,
                                               block=4))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
    tail = np.asarray(pa.paged_decode_attention(q, kpg, vpg, pidx, lengths))
    held = 0
    for row, n in enumerate(np.asarray(lengths).tolist()):
        if n < t_q:
            continue
        keys = lambda pool: np.asarray(pool)[np.asarray(safe)[row]].reshape(
            -1, *pool.shape[2:])[:n].reshape(n, -1, D)
        kr, vr = (np.repeat(keys(pool), H // keys(pool).shape[1], axis=1)
                  for pool in (kpg, vpg))
        sc = np.einsum("qhd,khd->hqk", np.asarray(q[row]), kr,
                       dtype=np.float64) / np.sqrt(D)
        pos = n - t_q + np.arange(t_q)
        sees = np.minimum(pos | 3, n - 1)
        if t_q == 8 and n % 4 == 0:       # a finished block and the next
            assert (sees[:4] == n - 5).all() and (sees[4:] == n - 1).all()
        sc = np.where(np.arange(n)[None, None, :] <= sees[None, :, None],
                      sc, -np.inf)
        w = np.exp(sc - sc.max(axis=-1, keepdims=True))
        dense = np.einsum("hqk,khd->qhd", w / w.sum(axis=-1, keepdims=True),
                          vr)
        np.testing.assert_allclose(out[row], dense, rtol=2e-4, atol=2e-5)
        # the causal tail: the same where a query's block ends at itself
        for t in range(t_q):
            if sees[t] == pos[t]:
                np.testing.assert_allclose(tail[row, t], out[row, t],
                                           rtol=2e-4, atol=2e-5)
            else:
                assert np.abs(tail[row, t] - out[row, t]).max() > 1e-3
        held += 1
    assert held
    with pytest.raises(PaddleTpuError, match="no window"):
        pa.paged_decode_attention(q, kpg, vpg, pidx, lengths, window=8,
                                  block=4)
    with pytest.raises(PaddleTpuError, match="power of two"):
        pa.paged_decode_attention(q, kpg, vpg, pidx, lengths, block=3)


@pytest.mark.parametrize("t_q", [1, 4])
@pytest.mark.parametrize("case", ["base", "wide_scratch", "wide_garbage",
                                  "page_boundary", "inactive_rows",
                                  "hd2048", "gqa", "window",
                                  "gqa_window", "gqa_serve"])
def test_paged_decode_matches_dense_reference(case, t_q, rng):
    """The decode primitive over a partially-filled paged cache equals
    the dense one-step reference: per-row lengths (mid-page fills,
    fills that end on a page's edge), per-row page tables far wider
    than the pages in use whose dead slots are never dereferenced,
    inactive rows beside live ones, small-Tq causal tail, toy and
    lane-dense head widths; query heads that share K/V heads, and a
    sliding window whose pages behind it are never fetched."""
    H, D, kpg, vpg, pidx, lengths, safe, window = _decode_case(case, rng)
    q = jnp.asarray(rng.randn(pidx.shape[0], t_q, H, D).astype(np.float32))
    out = pa.paged_decode_attention(q, kpg, vpg, pidx, lengths, window)
    ref = pa.paged_decode_reference(q, kpg, vpg, safe, lengths, window)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="decode",reason=""}'] \
        >= 1


def _pages_a_step(k_pages, d):
    """What the call's gauge says ``_pages_per_step`` chose."""
    width = int(np.prod(k_pages.shape[2:]))
    return observe.REGISTRY.find("paged_decode_pages_per_step").value(
        page=str(k_pages.shape[1]), width=str(width + -width % 128),
        dtype=k_pages.dtype.name)


#: name → (query heads, K/V heads, head lanes, page, table slots, pool
#: dtype, window, the chunk the rule must give).  The chunk is forced
#: through what the rule sees: the page size (512 tokens a step at
#: most) and the table's width
CHUNK_CASES = {
    "page512": (2, 2, 16, 512, 4, "float32", 0, 1),
    "page256": (2, 2, 16, 256, 8, "float32", 0, 2),
    "table3": (2, 2, 16, 16, 3, "float32", 0, 2),
    "page16": (2, 2, 16, 16, 12, "float32", 0, 8),
    # the walk starts at the window's first page, wherever in a chunk
    # of a walk from page 0 that falls
    "window": (4, 2, 16, 16, 12, "float32", 40, 8),
    # the served rows: the dense pool's, the routed decoders'
    "dense": (32, 32, 64, 16, 24, "float32", 0, 8),
    "trinity": (32, 4, 128, 64, 12, "bfloat16", 0, 8),
    "lfm2": (32, 8, 64, 64, 12, "bfloat16", 0, 8),
}


@pytest.mark.parametrize("t_q", [1, 4])
@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_paged_decode_takes_several_pages_a_step(case, t_q, rng):
    """Rows whose lengths lie at, one under and one over a chunk's edge,
    a row shorter than a chunk beside a row of several, and rows of
    length 0 and 1 between them (the prefetch crosses rows), at 1, 2
    and 8 pages a loop step.  Every page no row may read holds NaN and
    every table slot past a row's used pages an index outside the pool:
    a fetched page shows in the output even under a weight of 0."""
    H, G, D, page, slots, dtype, window, chunk = CHUNK_CASES[case]
    edge = chunk * page
    lengths = np.minimum([edge, 0, edge - 1, 1, edge + 1, 0, 3 * edge - 5,
                          min(7, page)], slots * page)
    B = len(lengths)
    used = -(-lengths // page)
    first = np.maximum(lengths - 4 - window + 1, 0) // page if window \
        else np.zeros_like(used)
    slot = np.arange(slots)[None, :]
    live = (slot >= first[:, None]) & (slot < used[:, None])
    P = int(live.sum()) + 2
    pidx = np.full((B, slots), 10 ** 6, np.int32)
    pidx[:, 1::2] = -5
    pidx[live] = rng.permutation(P - 2) + 1     # pages 0 and P-1: no row's
    kpg, vpg = (rng.randn(P, page, G, D).astype(np.float32)
                for _ in range(2))
    kpg[[0, P - 1]] = vpg[[0, P - 1]] = np.nan
    # the reference gathers every slot before it masks: give it a live
    # page (any finite one for a row with none) where the kernel must
    # not look
    safe = np.where(live, pidx, np.where(
        used > first, pidx[np.arange(B), np.minimum(first, slots - 1)],
        1)[:, None])
    kpg, vpg = jnp.asarray(kpg, dtype), jnp.asarray(vpg, dtype)
    q = jnp.asarray(rng.randn(B, t_q, H, D).astype(np.float32))
    lengths = jnp.asarray(lengths, jnp.int32)
    out = pa.paged_decode_attention(q, kpg, vpg, jnp.asarray(pidx), lengths,
                                    window)
    assert _pages_a_step(kpg, D) == chunk
    ref = pa.paged_decode_reference(q, kpg, vpg, jnp.asarray(safe), lengths,
                                    window)
    assert np.isfinite(np.asarray(out)).all()
    assert not np.asarray(out[1]).any()         # length 0: zeros
    # bfloat16 pools: the query and the weights are rounded to the
    # pool's dtype before their products, as every configuration states
    tol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" \
        else dict(rtol=0, atol=3e-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)


def test_paged_decode_bf16_makes_no_float32_copy_of_a_page():
    """Operands as the pool stores them: nowhere in the kernel's body is
    a page's worth of bfloat16 (or more) converted to float32; the
    products take the buffers as they are and accumulate in float32."""
    B, H, G, D, P, page, slots = 4, 32, 4, 128, 16, 64, 8
    args = (jnp.zeros((B, 1, H, D)), jnp.zeros((P, page, G * D), "bfloat16"),
            jnp.zeros((P, page, G * D), "bfloat16"),
            jnp.zeros((B, slots), jnp.int32), jnp.ones((B,), jnp.int32))
    closed = jax.make_jaxpr(pa.paged_decode_attention)(*args)

    def walk(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "convert_element_type" \
                    and eqn.params["new_dtype"] == jnp.float32 \
                    and eqn.invars[0].aval.dtype == jnp.bfloat16:
                found.append(tuple(eqn.invars[0].aval.shape))
            if eqn.primitive.name == "dot_general":
                found.append(tuple(str(v.aval.dtype) for v in eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, found)
        return found

    found = walk(closed.jaxpr, [])
    casts = [f for f in found if isinstance(f[0], int)]
    assert not [c for c in casts if int(np.prod(c)) >= page * G * D], casts
    assert [f for f in found if f == ("bfloat16", "bfloat16")] \
        == [("bfloat16", "bfloat16")] * 2, found


#: the serve cells' decode calls: (B, Tq, H, D), K/V heads, page, dtype,
#: table slots, window — and the latent cell's, whose kernel asks the
#: same rule
CELL_CALLS = {
    "dense": ((16, 1, 32, 64), 32, 16, "float32", 128, 0),
    "trinity": ((16, 1, 32, 128), 4, 64, "bfloat16", 128, 2048),
    "lfm2": ((16, 1, 32, 64), 8, 64, "bfloat16", 128, 0),
    "jamba": ((16, 1, 20, 128), 1, 64, "bfloat16", 260, 0),
    "latent": ((16, 32, 640), 0, 64, "bfloat16", 128, 0),
    # the block mode: 16 rows of a tile of 8 queries in blocks of 4
    # (SDAR's: a block and the one it commits)
    "sdar": ((16, 8, 32, 128), 4, 64, "bfloat16", 64, 0),
}


@pytest.fixture(scope="module")
def one_v5e():
    """A described (not attached) v5e chip to compile for."""
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever says "no libtpu here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell", sorted(CELL_CALLS))
def test_the_rule_gives_8_pages_at_the_cells_shapes_and_mosaic_takes_them(
        cell, one_v5e, monkeypatch):
    """``_pages_per_step`` at what each serve cell's decode call shows
    it: 8 pages a step (512 tokens of bf16 rows; the dense pool's f32
    pages of 2,048 lanes by the VMEM budget, where 16 would not fit),
    and the kernel at that chunk compiles for a v5e: Mosaic takes the
    buffers, the 3-D semaphore array and the products as written."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    q_shape, g, page, dtype, slots, window = CELL_CALLS[cell]
    isz = jnp.dtype(dtype).itemsize
    if cell == "latent":
        b, h, w = q_shape
        assert pa._pages_per_step(page, w * isz, h,
                                  h * (w * isz + 512 * 4), slots) == 8
        shapes = [(q_shape, dtype), ((2048, page, w), dtype)]
        call = lambda q, pages, t, n: pa.latent_decode_attention(
            q, pages, t, n, 512, 0.07)
    else:
        b, t_q, h, d = q_shape
        w = g * d
        rule = lambda: pa._pages_per_step(
            page, 2 * w * isz, t_q * h, t_q * h * w * (isz + 4), slots)
        assert rule() == 8
        # what bounds it: the budget for the dense pool, the 512 tokens
        # for the routed ones
        monkeypatch.setattr(pa, "DECODE_VMEM_BUDGET", 64 << 20)
        assert rule() == (32 if cell == "dense" else 8)
        monkeypatch.undo()
        shapes = [(q_shape, "float32"), ((2048, page, w), dtype),
                  ((2048, page, w), dtype)]
        call = lambda q, k, v, t, n: pa.paged_decode_attention(
            q, k, v, t, n, window=window, block=4 if cell == "sdar" else 0,
            name="block_decode" if cell == "sdar" else "paged_decode")
    monkeypatch.setattr(pa, "pallas_interpret", lambda: False)
    shapes += [((b, slots), "int32"), ((b,), "int32")]
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(t), sharding=one_v5e)
            for s, t in shapes]
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = jax.jit(call).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert "tpu_custom_call" in text


def test_the_scan_kernel_at_the_jamba_cells_widths_compiles(one_v5e,
                                                            monkeypatch):
    """``ops/pallas_ssm.py``'s kernel at the longest prompt of
    ``jamba_serve_closed_c12``: 16,384 positions of 5,120 channels and
    16 state numbers, compiled for a v5e: Mosaic takes the [N, 512]
    state, the [N, 8] tiles of B and C and their lane slices."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from paddle_tpu.ops import pallas_ssm

    monkeypatch.setattr(pallas_ssm, "pallas_interpret", lambda: False)
    t, ch, n = 16384, 5120, 16
    shapes = [(1, t, ch), (1, t, ch), (n, ch), (1, t, n), (1, t, n),
              (ch,), (1, n, ch)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_v5e)
            for s in shapes]
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = jax.jit(lambda *a: pallas_ssm.selective_scan(
            *a, impl="pallas")).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert "tpu_custom_call" in text and "ssm_scan" in text


def test_the_chunk_kernel_at_the_jamba_cells_widths_compiles(one_v5e,
                                                             monkeypatch):
    """``chunk_prefill_attention`` as ``jamba_serve_closed_c12``'s chunk
    launch calls it: 512 queries of 20 heads over one K/V head of 128 in
    bf16, a pool of 4,096 pages of 64 a layer (two layers end to end),
    a table of 260 pages, compiled for a v5e."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(pa, "pallas_interpret", lambda: False)
    shapes = [((512, 20, 128), "bfloat16"), ((8192, 64, 128), "bfloat16"),
              ((8192, 64, 128), "bfloat16"), ((260,), "int32"),
              ((2,), "int32")]
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(t), sharding=one_v5e)
            for s, t in shapes]
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        text = jax.jit(pa.chunk_prefill_attention).lower(
            *args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert "tpu_custom_call" in text and "chunk_prefill" in text


@pytest.mark.parametrize("kv_heads,start,n", [
    (1, 0, 8), (1, 12, 5), (4, 20, 8), (2, 33, 11), (1, 61, 3)])
def test_the_chunk_kernel_is_the_dense_reference(kv_heads, start, n, rng):
    """A chunk of 8 (16 where it ends off a page) queries of 4 heads
    over 1, 2 or 4 K/V heads, at starts on and off a page and a block of
    keys, with padding past its real queries: each real query sees every
    position of its row up to its own through the page table, as the
    dense reference of a tile of ``n`` queries ending at start + n."""
    h, d, page, n_pages, pool = 4, 16, 4, 20, 48
    c = 16 if n > 8 else 8
    kp, vp = (jnp.asarray(rng.randn(pool, page, kv_heads * d)
                          .astype(np.float32)) for _ in range(2))
    table = jnp.asarray(rng.permutation(pool)[:n_pages].astype(np.int32))
    q = jnp.asarray(rng.randn(c, h, d).astype(np.float32))
    got = pa.chunk_prefill_attention(q, kp, vp, table,
                                     jnp.array([start, n], jnp.int32))
    want = pa.paged_decode_reference(q[None, :n], kp, vp, table[None],
                                     jnp.array([start + n]))[0]
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("hw,ch", [(56, 64), (28, 128), (14, 256),
                                   (7, 512)])
def test_the_fused_conv_kernels_at_resnet50s_stage_shapes_compile(
        hw, ch, one_v5e, monkeypatch):
    """``ops/pallas_conv.py``'s ``conv_bn_fwd`` and ``conv_bn_fwd_bwd``
    at a batch of 128 at each of ResNet-50's four 3×3 stage shapes, in
    the tile ``_conv_tile`` picks there, compiled for a v5e: Mosaic
    takes the padded copy's shifted slices, the taps stacked on the
    lanes, bands of rows and several images a step.  This file keeps the
    repository's compiles for a described chip in one place."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from paddle_tpu.ops import pallas_conv

    monkeypatch.setattr(pallas_conv, "pallas_interpret", lambda: False)
    img = jax.ShapeDtypeStruct((128, hw, hw, ch), jnp.bfloat16,
                               sharding=one_v5e)
    w = jax.ShapeDtypeStruct((3, 3, ch, ch), jnp.bfloat16, sharding=one_v5e)
    ci = jax.ShapeDtypeStruct((8, ch), jnp.float32, sharding=one_v5e)
    calls = [(lambda z, ci, w: pallas_conv._fwd_call(
                  z, ci, w, jnp.bfloat16, True), (img, ci, w)),
             (lambda g, z, ci, w: pallas_conv._fwd_bwd_call(
                  g, z, ci, w, True), (img, img, ci, w))]
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        texts = [jax.jit(f).lower(*a).compile().as_text() for f, a in calls]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    for text, name in zip(texts, ("conv_bn_fwd", "conv_bn_fwd_bwd")):
        assert "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("kind", ["base", "gqa"])
@pytest.mark.parametrize("t_q", [1, 4])
def test_paged_decode_lane_dense_pool_is_the_same_pool(t_q, kind, rng):
    """The server stores a token as one [G·D] row (``new_pools``): the
    kernel and ``paged_kv_write`` take that pool as they take
    [P, page, G, D], and give the same numbers — at the K/V heads'
    width where query heads share them."""
    H, D, kpg, vpg, pidx, lengths, _, _ = _decode_case(kind, rng)
    G = kpg.shape[2]
    B, P, page = pidx.shape[0], kpg.shape[0], kpg.shape[1]
    q = jnp.asarray(rng.randn(B, t_q, H, D).astype(np.float32))
    k_new, v_new = (jnp.asarray(rng.randn(B, t_q, G, D)
                                .astype(np.float32)) for _ in range(2))
    counts = jnp.full((B,), t_q, jnp.int32)
    kp4, vp4 = pa.paged_kv_write(kpg, vpg, k_new, v_new, pidx,
                                 lengths - t_q, counts)
    kp3, vp3 = pa.paged_kv_write(
        kpg.reshape(P, page, G * D), vpg.reshape(P, page, G * D),
        k_new, v_new, pidx, lengths - t_q, counts)
    assert kp3.shape == (P, page, G * D) and kp4.shape == kpg.shape
    np.testing.assert_array_equal(np.asarray(kp3).reshape(kp4.shape),
                                  np.asarray(kp4))
    np.testing.assert_array_equal(np.asarray(vp3).reshape(vp4.shape),
                                  np.asarray(vp4))
    np.testing.assert_array_equal(
        np.asarray(pa.paged_decode_attention(q, kp3, vp3, pidx, lengths)),
        np.asarray(pa.paged_decode_attention(q, kp4, vp4, pidx, lengths)))


def _pool_sized_eqns(jaxpr, floor, found):
    """(primitive, shapes) of every equation outside a kernel's body
    that takes an operand of ``floor`` elements or more."""
    for eqn in jaxpr.eqns:
        big = [tuple(v.aval.shape) for v in eqn.invars
               if hasattr(v.aval, "shape")
               and int(np.prod(v.aval.shape)) >= floor]
        if big:
            found.append((eqn.primitive.name, big))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pool_sized_eqns(sub, floor, found)
    return found


@pytest.mark.parametrize("rank", [3, 4])
def test_paged_decode_takes_the_pool_as_it_is_stored(rank):
    """No transpose, copy, pad or gather of a pool-sized operand
    anywhere in the wrapper: a lane-dense pool goes to the kernel
    untouched, [P, page, H, D] through one reshape (which the TPU has
    to pay for as a relayout: why the server stores the first)."""
    B, t_q, H, D, P, page, slots = 4, 1, 4, 32, 24, 16, 8
    pool = (P, page, H * D) if rank == 3 else (P, page, H, D)
    args = (jnp.zeros((B, t_q, H, D)), jnp.zeros(pool), jnp.zeros(pool),
            jnp.zeros((B, slots), jnp.int32), jnp.ones((B,), jnp.int32))
    closed = jax.make_jaxpr(pa.paged_decode_attention)(*args)
    found = _pool_sized_eqns(closed.jaxpr, P * page * H * D, [])
    names = [name for name, _ in found]
    assert names.count("pallas_call") == 1, found
    # the call is a jit of its own (one trace for a step's layers): the
    # pools pass through it, and XLA inlines it
    rest = [n for n in names if n not in ("pallas_call", "jit")]
    assert rest == ([] if rank == 3 else ["reshape", "reshape"]), found


def test_paged_decode_fully_masked_rows_emit_zeros(rng):
    """0 < length < Tq (speculative/chunked decode on a near-empty
    row): the leading query rows sit at negative positions and are
    fully masked — they must emit exact zeros like the reference, not
    an exp(−inf − (−inf)) = 1 average of V (regression: the decode
    kernel lacked the pair kernel's exponent-base clamp)."""
    B, t_q, H, D = 2, 4, 2, 16
    P, page = 6, 16
    kpg = jnp.asarray(rng.randn(P, page, H, D).astype(np.float32))
    vpg = jnp.asarray(rng.randn(P, page, H, D).astype(np.float32))
    pidx = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lengths = jnp.asarray([2, 12], jnp.int32)   # row 0: 2 of 4 queries live
    q = jnp.asarray(rng.randn(B, t_q, H, D).astype(np.float32))
    out = pa.paged_decode_attention(q, kpg, vpg, pidx, lengths)
    ref = pa.paged_decode_reference(q, kpg, vpg, pidx, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    # row 0's queries 0..1 are at positions −2/−1: exact zeros
    assert np.abs(np.asarray(out)[0, :2]).max() == 0.0


def test_packed_layer_block_sparse_kill_switch_reverts_to_padded(
        rng, attn_flags):
    """--flash_block_sparse=false on a packed layer reverts to the
    padded per-row lowering (regression: the op-level fallback built a
    dense [1, B·T]² score matrix — O((B·T)²) memory at bench scale)."""
    from layer_grad_util import build_single_layer_net
    from paddle_tpu.core.sequence import pad_batch

    lens = [100, 30]
    sb = pad_batch([rng.randn(l, 12).astype(np.float32) for l in lens],
                   max_len=128)
    mk = lambda packed: build_single_layer_net(
        "scaled_dot_product_attention", size=16, input_sizes=[12],
        attrs={"num_heads": 4, "block_q": 128, "block_k": 16,
               "packed": packed})
    net_pad, net_pk = mk(False), mk(True)
    params = net_pad.init_params(seed=2)
    FLAGS.set("flash_block_sparse", False)
    o_pad = np.asarray(net_pad.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    o_pk = np.asarray(net_pk.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    np.testing.assert_array_equal(o_pk, o_pad)   # same (legacy) path
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="unpacked",'
                'reason="kill_switch:flash_block_sparse(packed)"}'] >= 1
    # no packed series, no dense fallback ticked for the packed layer
    assert 'attention_dispatch_total{path="packed",reason=""}' \
        not in flat


def test_packed_layer_untileable_flatten_reverts_to_padded(rng):
    """A flatten whose blocks miss the Pallas tiling gate must revert
    to the padded per-row lowering at the LAYER (regression: the
    op-level fallback would run dense attention over the flattened
    [1, B·T] axis — an O((B·T)²) score matrix at scale)."""
    from layer_grad_util import build_single_layer_net
    from paddle_tpu.core.sequence import pad_batch

    # T=500, B=4: flat total 2000, _choose_block(2000, 500) = 500 —
    # neither %128 nor the full axis → untileable
    sb = pad_batch([rng.randn(l, 12).astype(np.float32)
                    for l in (500, 300, 200, 100)], max_len=500)
    mk = lambda packed: build_single_layer_net(
        "scaled_dot_product_attention", size=16, input_sizes=[12],
        attrs={"num_heads": 4, "packed": packed})
    net_pad, net_pk = mk(False), mk(True)
    params = net_pad.init_params(seed=2)
    o_pad = np.asarray(net_pad.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    o_pk = np.asarray(net_pk.forward(
        params, {"in0": sb}, is_training=False)[0]["test"].data)
    np.testing.assert_array_equal(o_pk, o_pad)   # same path entirely
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="unpacked",'
                'reason="untileable(packed flatten)"}'] >= 1
    assert 'attention_dispatch_total{path="packed",reason=""}' \
        not in flat


def test_packed_slot_hint_degradation_is_recorded(rng):
    """A slot width that is not a whole number of blocks cannot drop
    cross-slot pairs; the degradation must be visible (dispatch reason
    + one-time warning), not silent."""
    T, H, D = 256, 2, 16
    q = jnp.asarray(rng.randn(1, T, H, D).astype(np.float32))
    seg = pa.segments_from_lengths(jnp.asarray([100, 80], jnp.int32),
                                   2, 128)
    pa.flash_attention_packed(q, q, q, seg, False, 128, 16, 100)
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['attention_dispatch_total{path="packed",reason="slot '
                'hint unusable (blocks straddle slots)"}'] >= 1


def test_paged_decode_page_table_drives_gather(rng):
    """Permuting physical pages while permuting the table the same way
    must not change the result — the scalar-prefetched indices really
    address the pages."""
    B, H, D = 1, 2, 16
    P, page, n_max = 6, 16, 3
    kpg = rng.randn(P, page, H, D).astype(np.float32)
    vpg = rng.randn(P, page, H, D).astype(np.float32)
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    lengths = jnp.asarray([40], jnp.int32)
    pidx = np.asarray([[1, 3, 5]], np.int32)
    out1 = pa.paged_decode_attention(
        q, jnp.asarray(kpg), jnp.asarray(vpg), jnp.asarray(pidx),
        lengths)
    perm = np.asarray([4, 0, 3, 2, 5, 1])      # old page p → slot
    inv = np.argsort(perm)
    out2 = pa.paged_decode_attention(
        q, jnp.asarray(kpg[inv]), jnp.asarray(vpg[inv]),
        jnp.asarray(perm[pidx]), lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-5, atol=1e-6)


def test_paged_decode_ignores_stale_pages(rng):
    """Cache slots past the row's length — including whole unused table
    entries — must not influence the output."""
    B, H, D = 1, 2, 8
    P, page = 4, 16
    kpg = rng.randn(P, page, H, D).astype(np.float32)
    vpg = rng.randn(P, page, H, D).astype(np.float32)
    q = jnp.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    pidx = jnp.asarray([[0, 1, 2]], jnp.int32)
    lengths = jnp.asarray([20], jnp.int32)     # page 1 half full
    out1 = pa.paged_decode_attention(
        q, jnp.asarray(kpg), jnp.asarray(vpg), pidx, lengths)
    kpg2, vpg2 = kpg.copy(), vpg.copy()
    kpg2[1, 4:] = 99.0                          # beyond length
    vpg2[1, 4:] = -99.0
    kpg2[2] = 77.0                              # wholly-unused page
    vpg2[2] = -77.0
    kpg2[3] = 55.0                              # not in the table
    out2 = pa.paged_decode_attention(
        q, jnp.asarray(kpg2), jnp.asarray(vpg2), pidx, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-7)


# ------------------------------------- operands as stored, interior blocks
def _p_rounded_reference(q, k, v, seg, window, p_dtype):
    """The dense forward with the kernel's one rounding made explicit:
    the unnormalised probabilities go through ``p_dtype`` before P·V,
    the normalizer sums them unrounded, everything else is float32."""
    h, g = q.shape[2], k.shape[2]
    kf, vf = (jnp.repeat(a.astype(jnp.float32), h // g, axis=2)
              for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kf) \
        / np.sqrt(q.shape[-1])
    s = pa._mask_scores(s, True, None, seg, window)
    p = jnp.exp(s - jnp.maximum(s.max(axis=-1, keepdims=True),
                                pa.NEG_INF / 2))
    l = p.sum(axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   p.astype(p_dtype).astype(jnp.float32), vf)
    l = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (o / l).astype(q.dtype)


def _served_case(shape, dtype, rng):
    """The two served prefill shapes cut small, one row of 512 tokens
    in blocks of 128 (10 block pairs, 6 of them interior without a
    window): ``latent`` has keys of 192 lanes and values of 128 with a
    K/V head a query head, ``routed`` 32 query heads over 4 K/V heads of
    128 under a window that ends inside a block."""
    t = 512
    h, g, d, d_v, window = ((4, 4, 192, 128, 0) if shape == "latent"
                            else (32, 4, 128, 128, 200))
    q = jnp.asarray(rng.randn(1, t, h, d), dtype)
    k = jnp.asarray(rng.randn(1, t, g, d), dtype)
    v = jnp.asarray(rng.randn(1, t, g, d_v), dtype)
    seg = pa.segments_from_lengths(jnp.asarray([t], jnp.int32), 1, t)
    return q, k, v, seg, window


def _mean_gap(a, b):
    a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
    return float(np.abs(a - b).mean() / np.abs(b).mean())


@pytest.mark.parametrize("shape", ["latent", "routed"])
def test_packed_bf16_operands_at_the_served_shapes(shape, rng):
    """bfloat16 q, k and v go into both products as they are stored:
    against the dense forward on the same bfloat16 inputs the kernel
    reads under a tolerance that a path with float32 probabilities
    passes ten times under and one with fp8 probabilities fails — the
    one new rounding is the probabilities' to bfloat16."""
    q, k, v, seg, window = _served_case(shape, jnp.bfloat16, rng)
    want, _ = pa._dense_forward(q, k, v, None, True, seg, window)
    got = pa.flash_attention_packed(q, k, v, seg, True, 128, 128, 512,
                                    window)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    tol = 4e-3      # the kernel reads 1.3e-3, fp8 probabilities 2.1e-2
    assert _mean_gap(got, want) < tol
    f32_p = _p_rounded_reference(q, k, v, seg, window, jnp.float32)
    fp8_p = _p_rounded_reference(q, k, v, seg, window, jnp.float8_e4m3fn)
    assert _mean_gap(f32_p, want) < tol / 10
    assert _mean_gap(fp8_p, want) > tol


@pytest.mark.parametrize("shape", ["latent", "routed"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_packed_lse_is_in_the_scaled_scores_units(shape, dtype, rng):
    """The scale sits in the exponent, over the running maximum of the
    unscaled scores; the logsumexp that leaves the kernel is still the
    scaled scores' (what the backward kernels subtract), and float32
    inputs give what they gave, to the file's tolerance.  1/√192 is no
    power of two: a wrong unit shows."""
    q, k, v, seg, window = _served_case(shape, jnp.dtype(dtype), rng)
    out, lse = pa._fa_forward(q, k, v, None, True, 128, 128, seg, 512,
                              window)
    want, want_lse = pa._dense_forward(q, k, v, None, True, seg, window)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-4, atol=2e-4)
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def _block_case(case, rng):
    """A block pair wholly under the diagonal that still hides an
    element, in blocks of 128: (entry, q, k, v, lengths, segments,
    window)."""
    t, h, d = 512, 2, 16
    q, k, v = _qkv(rng, 1, t, h, d)
    seg = np.zeros((1, t), np.int32)
    lengths, window = None, 0
    if case == "segment_boundary":
        seg[0, 200:] = 1              # inside k block 1, under q block 2
    elif case == "padding_in_the_middle":
        seg[0, 150:170] = -1          # k block 1 holds 20 padding tokens
    elif case == "segment_change_on_a_block_edge":
        seg[0, 256:] = 1              # uniform blocks of two segments
    elif case == "key_past_kv_len":
        lengths = jnp.asarray([200], jnp.int32)    # ends inside k block 1
    elif case == "window_ends_inside":
        window = 200                  # q 511 no longer sees keys 256..311
    else:
        raise AssertionError(case)
    return q, k, v, lengths, None if lengths is not None \
        else jnp.asarray(seg), window


@pytest.mark.parametrize("case", [
    "segment_boundary", "padding_in_the_middle",
    "segment_change_on_a_block_edge", "key_past_kv_len",
    "window_ends_inside"])
def test_a_block_under_the_diagonal_that_hides_an_element_is_masked(
        case, rng):
    """Only a pair that the table calls interior AND whose scalars show
    one valid segment and no key past the length runs without the mask:
    each of these lies wholly under the diagonal, holds an element that
    must not be seen, and equals the dense forward."""
    q, k, v, lengths, seg, window = _block_case(case, rng)
    if seg is None:
        got = pa.flash_attention(q, k, v, lengths, True, 128, 128)
    else:
        got = pa.flash_attention_packed(q, k, v, seg, True, 128, 128, 0,
                                        window)
    want, _ = pa._dense_forward(q, k, v, lengths, True, seg, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    if seg is not None:
        # which blocks the scalars call uniform: none that holds a
        # boundary or padding
        uniform = np.asarray(pa._segment_uniform(seg, 128))[0].tolist()
        assert uniform == {
            "segment_boundary": [0, -1, 1, 1],
            "padding_in_the_middle": [0, -1, 0, 0],
            "segment_change_on_a_block_edge": [0, 0, 1, 1],
            "window_ends_inside": [0, 0, 0, 0]}[case]


@pytest.mark.parametrize("t,window,pairs,interior", [
    (7168, 0, 105, 91), (6144, 0, 78, 66), (4096, 0, 36, 28),
    (2048, 0, 10, 6), (6144, 2048, 50, 30)])
def test_pair_table_counts_the_interior_blocks(t, window, pairs, interior):
    """The table's fifth row at the served lengths in blocks of 512:
    every pair off the diagonal is interior without a window; under
    Trinity's window of 2,048 a full row of 5 pairs keeps 3 (the
    diagonal block and the one the window ends in stay masked)."""
    tab = pa._pair_tables(t, t, 512, 512, True, t, window)[0]
    assert tab.shape == (5, pairs) and int(tab[4].sum()) == interior
    if window:
        last_row = tab[:, tab[0] == t // 512 - 1]
        assert last_row.shape[1] == 5 and int(last_row[4].sum()) == 3
    # no table entry on the diagonal is interior
    assert not tab[4][tab[0] == tab[1]].any()


def test_forward_work_counter_says_how_often_the_unmasked_body_engages(rng):
    """``pallas_kernel_work_total`` gains ``pairs`` and
    ``pairs_interior`` for the forward pair kernels, trace-time."""
    from paddle_tpu.ops import kernels as K

    def work(kernel):
        rows = observe.REGISTRY.find("pallas_kernel_work_total")
        return {s["labels"]["kind"]: s["value"]
                for s in (rows.samples() if rows is not None else ())
                if s["labels"]["kernel"] == kernel}

    q, k, v, seg, _ = _served_case("latent", jnp.bfloat16, rng)
    before = work(K.FLASH_FWD_PACKED)
    pa.flash_attention_packed(q, k, v, seg, True, 128, 128, 512)
    after = work(K.FLASH_FWD_PACKED)
    assert after["pairs"] - before.get("pairs", 0.0) == 10
    assert after["pairs_interior"] - before.get("pairs_interior", 0.0) == 6
    assert "pairs" not in work(K.FLASH_BWD_DQ)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_bf16_gradients_match_dense(causal, rng):
    """The trainer's pairing: a forward that multiplies bfloat16
    operands and emits the scaled logsumexp, a backward that recomputes
    the same scores from float32 casts of the same bfloat16 numbers."""
    B, T = 2, 256
    q, k, v = (a.astype(jnp.bfloat16) for a in _qkv(rng, B, T))
    lengths = jnp.asarray([256, 93], jnp.int32)
    cot = jnp.asarray(rng.randn(*q.shape), jnp.bfloat16)
    fn = lambda *a: pa.flash_attention(*a, lengths, causal, 128, 16)
    out = fn(q, k, v)
    ref, _ = pa._dense_forward(q, k, v, lengths, causal)
    assert out.dtype == jnp.bfloat16
    assert _mean_gap(out, ref) < 4e-3
    g = _grads(fn, q, k, v, cot)
    gd = _dense_grads(q, k, v, lengths, causal, cot)
    for a, b in zip(g, gd):
        assert a.dtype == jnp.bfloat16
        assert _mean_gap(a, b) < 3e-3
