"""Hidden-blocked fused RNN tier ≡ the lax.scan path (round 8).

The blocked kernels (``ops/pallas_lstm.py`` / ``ops/pallas_gru.py``,
grid (T, H/Hb) streaming weight column blocks) must be numerically
interchangeable with the scan implementation at the shapes the old
H ≤ 512 gate rejected — forward, gradients through x / w_ih / w_hh /
bias, length-masked tails — for both LSTM and GRU, in interpret mode
(the same dispatch used on hardware).  Also pins the two-tier
``fused_tier`` resolution (the baseline's b=128/h=1280 row must land
on ``fused_blocked``) and the ``--fused_rnn_hblock`` kill switch in
both directions.

Lane budget: each equivalence test compares outputs AND all grads from
ONE ``value_and_grad(has_aux=True)`` program per path, so the quick
lane pays the minimum number of fresh compiles; the H=1280 width (the
baseline row, 4× the work) and the extra-coverage variants (peepholes/
boot state, bf16 policy, reversed GRU) ride the slow lane.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.ops import pallas_gru, pallas_lstm, recurrent_ops
from paddle_tpu.utils import FLAGS

B, T, D = 8, 5, 16

# H=640 is the smallest blocked-tier shape (5 hidden blocks) and runs
# in the quick lane; H=1280 is the baseline row's width, slow lane.
HS = [640, pytest.param(1280, marks=pytest.mark.slow)]


@pytest.fixture
def rng():
    return np.random.RandomState(7)


@pytest.fixture
def hblock_on():
    FLAGS.set("fused_rnn_hblock", True)
    yield
    FLAGS.set("fused_rnn_hblock", True)


def _inputs(rng, h, n_gates):
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32)) * 0.3
    # length-masked tails: force a one-step row and a full row so the
    # (1-m) passthrough is exercised on both ends
    lens = np.clip(rng.randint(1, T + 1, size=(B,)), 1, T)
    lens[0], lens[1] = 1, T
    seq = SequenceBatch(x, jnp.asarray(lens, jnp.int32))
    w_ih = jnp.asarray(rng.randn(D, n_gates * h).astype(np.float32)) * 0.2
    w_hh = jnp.asarray(rng.randn(h, n_gates * h).astype(np.float32)) * 0.05
    bias = jnp.asarray(rng.randn(n_gates * h).astype(np.float32)) * 0.1
    return seq, w_ih, w_hh, bias


def _assert_close(got, want, rtol, atol):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=atol)


# ------------------------------------------------------- equivalence
@pytest.mark.parametrize("h", HS)
def test_blocked_lstm_matches_scan(rng, h, monkeypatch, hblock_on):
    """Forward outputs, final states, and grads wrt x/w_ih/w_hh/bias in
    one program per path."""
    seq, w_ih, w_hh, bias = _inputs(rng, h, 4)
    cot = jnp.asarray(rng.randn(B, T, h).astype(np.float32))
    cot_h = jnp.asarray(rng.randn(B, h).astype(np.float32))
    cot_c = jnp.asarray(rng.randn(B, h).astype(np.float32))

    def loss(x, wi, w, b):
        out, final = recurrent_ops.lstm_sequence(
            SequenceBatch(x, seq.length), wi, w, b)
        # touch the hidden sequence AND both final states so the
        # dc_seq cotangent pathway is exercised
        l = (jnp.sum(out.data * cot) + jnp.sum(final.h * cot_h)
             + jnp.sum(final.c * cot_c))
        return l, (out.data, final.h, final.c)

    assert pallas_lstm.fused_tier(B, h) == "fused_blocked"
    args = (seq.data, w_ih, w_hh, bias)
    run = jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True)
    (_, fwd_b), g_blocked = run(*args)
    # masked tail really is zeroed (row 0 has length 1)
    assert (np.asarray(fwd_b[0])[0, 1:] == 0).all()
    monkeypatch.setattr(pallas_lstm, "fused_ok", lambda *_: False)
    (_, fwd_s), g_scan = run(*args)
    _assert_close(fwd_b, fwd_s, rtol=2e-5, atol=2e-5)
    _assert_close(g_blocked, g_scan, rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("h", HS)
def test_blocked_gru_matches_scan(rng, h, monkeypatch, hblock_on):
    seq, w_ih, w_hh, bias = _inputs(rng, h, 3)
    cot = jnp.asarray(rng.randn(B, T, h).astype(np.float32))
    cot_h = jnp.asarray(rng.randn(B, h).astype(np.float32))
    h0 = jnp.asarray(rng.randn(B, h).astype(np.float32)) * 0.2

    def loss(x, wi, w, b, h0_):
        out, final = recurrent_ops.gru_sequence(
            SequenceBatch(x, seq.length), wi, w, b, h0=h0_)
        l = jnp.sum(out.data * cot) + jnp.sum(final * cot_h)
        return l, (out.data, final)

    assert pallas_gru.fused_tier(B, h) == "fused_blocked"
    args = (seq.data, w_ih, w_hh, bias, h0)
    run = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    (_, fwd_b), g_blocked = run(*args)
    assert (np.asarray(fwd_b[0])[0, 1:] == 0).all()
    monkeypatch.setattr(pallas_gru, "fused_ok", lambda *_: False)
    (_, fwd_s), g_scan = run(*args)
    _assert_close(fwd_b, fwd_s, rtol=2e-5, atol=2e-5)
    _assert_close(g_blocked, g_scan, rtol=3e-4, atol=3e-5)


@pytest.mark.slow
def test_blocked_lstm_peepholes_and_boot_state(rng, monkeypatch,
                                               hblock_on):
    """Peephole weights stream per-block through the kernels and their
    grads come off the dgates residue; boot states feed the VMEM
    scratch init."""
    h = 640
    rngs = np.random.RandomState(11)
    xw = jnp.asarray(rngs.randn(B, T, 4 * h).astype(np.float32)) * 0.3
    lens = np.clip(rngs.randint(1, T + 1, size=(B,)), 1, T)
    seq = SequenceBatch(xw, jnp.asarray(lens, jnp.int32))
    w_hh = jnp.asarray(rngs.randn(h, 4 * h).astype(np.float32)) * 0.05
    checks = [jnp.asarray(rngs.randn(h).astype(np.float32)) * 0.1
              for _ in range(3)]
    h0 = jnp.asarray(rngs.randn(B, h).astype(np.float32)) * 0.2
    c0 = jnp.asarray(rngs.randn(B, h).astype(np.float32)) * 0.2
    cot = jnp.asarray(rngs.randn(B, T, h).astype(np.float32))

    def loss(ci, cf, co, h0_, c0_):
        out, _ = recurrent_ops.lstm_sequence(
            seq, None, w_hh, None, ci, cf, co, h0=h0_, c0=c0_)
        return jnp.sum(out.data * cot)

    args = (*checks, h0, c0)
    g_blocked = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    monkeypatch.setattr(pallas_lstm, "fused_ok", lambda *_: False)
    g_scan = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    _assert_close(g_blocked, g_scan, rtol=3e-4, atol=3e-5)


@pytest.mark.slow
def test_blocked_gru_reverse_matches_scan(rng, monkeypatch, hblock_on):
    seq, w_ih, w_hh, bias = _inputs(rng, 640, 3)

    def run():
        out, final = recurrent_ops.gru_sequence(seq, w_ih, w_hh, bias,
                                                reverse=True)
        return np.asarray(out.data), np.asarray(final)

    got = run()
    monkeypatch.setattr(pallas_gru, "fused_ok", lambda *_: False)
    want = run()
    _assert_close(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_blocked_lstm_under_bf16_policy(rng, monkeypatch, hblock_on):
    """Production bf16 policy at a blocked shape: the kernel computes
    f32 internally, so agreement with the bf16 scan is within bf16
    rounding."""
    FLAGS.set("bf16_activations", True)
    try:
        seq, w_ih, w_hh, bias = _inputs(rng, 640, 4)

        def run():
            out, final = recurrent_ops.lstm_sequence(seq, w_ih, w_hh,
                                                     bias)
            return np.asarray(out.data, np.float32)

        got = run()
        monkeypatch.setattr(pallas_lstm, "fused_ok", lambda *_: False)
        want = run()
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)
    finally:
        FLAGS.set("bf16_activations", False)


# ---------------------------------------------------- tier resolution
def test_tier_resolution(hblock_on):
    # single-block fast path for h <= 512 …
    assert pallas_lstm.fused_tier(8, 128) == "fused"
    assert pallas_lstm.fused_tier(128, 512) == "fused"
    # … while the batch fits: Mosaic compiled (256, 512) and (512, 256)
    # and refused (512, 512) and (1024, 512) on the v5e (PR 21 sweep)
    assert pallas_lstm.fused_tier(256, 512) == "fused"
    assert pallas_lstm.fused_tier(512, 256) == "fused"
    assert pallas_lstm.fused_tier(512, 512) is None
    assert pallas_lstm.fused_tier(1024, 512) is None
    assert pallas_gru.fused_tier(256, 512) == "fused"
    assert pallas_gru.fused_tier(1024, 512) is None
    # the baseline's big-hidden row lands on the blocked tier
    assert pallas_lstm.fused_tier(128, 1280) == "fused_blocked"
    assert pallas_lstm.fused_tier(128, 2048) == "fused_blocked"
    assert pallas_lstm.fused_tier(8, 640) == "fused_blocked"
    assert pallas_gru.fused_tier(128, 1280) == "fused_blocked"
    # off-tile shapes still fall through to the scan path
    assert pallas_lstm.fused_tier(7, 1280) is None       # B % 8
    assert pallas_lstm.fused_tier(8, 1216) is None       # H % 128
    assert pallas_lstm.fused_tier(128, 8192) is None     # VMEM budget
    assert pallas_lstm.fused_ok(128, 1280)
    assert not pallas_lstm.fused_ok(7, 1280)


def test_kill_switch_restores_round7_gate(hblock_on):
    """--fused_rnn_hblock=false must reproduce the old H <= 512 gate
    exactly: blocked shapes fall to scan, the fast tier is untouched."""
    FLAGS.set("fused_rnn_hblock", False)
    try:
        for h in (640, 1024, 1280, 2048):
            assert pallas_lstm.fused_tier(128, h) is None
            assert not pallas_lstm.fused_ok(128, h)
            assert pallas_gru.fused_tier(128, h) is None
        assert pallas_lstm.fused_tier(128, 512) == "fused"
        assert pallas_lstm.fused_tier(8, 128) == "fused"
        assert pallas_gru.fused_tier(128, 512) == "fused"
    finally:
        FLAGS.set("fused_rnn_hblock", True)


def test_kill_switch_dispatch_both_directions(rng, monkeypatch,
                                              hblock_on):
    """Flag on: the blocked entry point actually runs for H=640.
    Flag off: it must NOT run (scan path), and the results agree."""
    seq, w_ih, w_hh, bias = _inputs(rng, 640, 4)
    calls = []
    real = pallas_lstm.lstm_fused_sequence_blocked
    monkeypatch.setattr(
        pallas_lstm, "lstm_fused_sequence_blocked",
        lambda *a, **k: (calls.append(1), real(*a, **k))[1])

    out_on, _ = recurrent_ops.lstm_sequence(seq, w_ih, w_hh, bias)
    assert calls, "flag on: H=640 must dispatch to the blocked kernel"

    calls.clear()
    FLAGS.set("fused_rnn_hblock", False)
    try:
        out_off, _ = recurrent_ops.lstm_sequence(seq, w_ih, w_hh, bias)
    finally:
        FLAGS.set("fused_rnn_hblock", True)
    assert not calls, "flag off: the blocked kernel must not run"
    np.testing.assert_allclose(np.asarray(out_on.data),
                               np.asarray(out_off.data),
                               rtol=2e-5, atol=2e-5)
