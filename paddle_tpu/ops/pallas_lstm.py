"""Fused LSTM sequence as Pallas TPU kernels.

The reference hand-wrote exactly this kernel tier in CUDA
(``paddle/cuda/src/hl_cuda_lstm.cu`` / ``hl_lstm_ops.cuh``: one kernel
per step fusing the gate elementwise math, state kept in registers) —
SURVEY §7 names the fused lstm step as the Pallas candidate for the
latency-bound regime.  This module goes further than the reference: the
ENTIRE time loop runs inside one kernel launch, with h/c carried in VMEM
scratch across a sequential grid over T and the recurrent weight matrix
resident in VMEM, so XLA's per-scan-step fixed costs (loop bookkeeping,
HBM round-trips for the carry) disappear.

Forward kernel (grid = (T,)): per step, gates = xw_t + h @ w_hh (MXU),
peepholes + sigmoid/tanh gate math (VPU), length-masked state keep —
writes the kept state sequences H, C and the activated gates (backward
residual).

Backward kernel (grid = (T,), reversed block maps): standard BPTT with
dh/dc carries and the dW_hh / peephole-grad accumulators in VMEM f32
scratch, one (dgates @ w_hhᵀ) + one (h_prevᵀ @ dgates) MXU matmul per
step.

Layouts are time-major ([T, B, ·]) so each grid step addresses one
contiguous block.  Shapes that don't tile (B % 8, H % 128) or non-default
activations dispatch to the ``lax.scan`` path in
:mod:`paddle_tpu.ops.recurrent_ops` — same contract, same results.
On non-TPU backends the kernels run in Pallas interpret mode so CPU
tests exercise the exact dispatch used on hardware.

Round 8 adds the **hidden-blocked tier** for 512 < H (the baseline's
own hidden=1280 row used to fall off this kernel onto the scan path):
grid (T, H/Hb) with Hb = 128, each inner step streaming one
[H, 4Hb] column block of w_hh through a double-buffered VMEM pipeline
— the flash-attention / ``hl_cuda_lstm.cu`` large-weight treatment —
while the full [B, H] h/c state carries in scratch across both grid
dimensions.  The backward mirrors it; its dW_hh is a separate
constant-block kernel (grid (nb, T), time innermost) so no [H, 4H]
tensor is ever VMEM-resident.  ``fused_tier`` picks the tier;
``--fused_rnn_hblock=false`` kills the blocked tier (round-6 one-flag
revert contract).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from ..core.device import pallas_interpret
from . import kernels as K


# Hidden-block width of the blocked tier.  128 = one lane tile, the
# smallest width that keeps every streamed weight block MXU-shaped; it
# also makes the blocked-tier shape gate coincide with the lane-tiling
# gate (H % 128), so any lane-tileable H > 512 is a blocking candidate.
HBLOCK = 128

# Budget for the dominant VMEM residents of the blocked kernels, kept
# under the 16 MB scoped-vmem window with headroom for Mosaic's own
# spills.  See _blocked_vmem_bytes for the arithmetic.
_BLOCKED_VMEM_CAP = 14 * 1024 * 1024


def _blocked_vmem_bytes(b: int, h: int, n_gates: int) -> int:
    """Dominant VMEM residents of the hidden-blocked kernels, in bytes:
    up to five full-width [B, H] f32 state/accumulator scratches (the
    backward's dh/dc carries plus the cross-block recurrent-pullback
    accumulator) and the double-buffered streamed weight column block
    [H, n_gates·HBLOCK] f32.  At the baseline row (b=128, h=1280,
    LSTM): 5·128·1280·4 ≈ 3.3 MB + 2·1280·512·4 ≈ 5.2 MB ≈ 8.5 MB —
    comfortably inside the cap, where the round-7 single-block kernel
    needed 2×26 MB for the resident w_hh + dW_hh pair."""
    state = 5 * b * h * 4
    w_block_stream = 2 * h * n_gates * HBLOCK * 4
    return state + w_block_stream


# Cap on _single_block_vmem_bytes, set from what Mosaic (libtpu 0.0.34,
# v5e) did with 35 shapes of these kernels and the conv family inside
# a jitted program (chip run, PR 21): every shape whose estimate was
# ≤ 30.5 MiB compiled — (128..256, 512), (512, 256), (2048, 128) here —
# and every one ≥ 38 MiB was refused ("Ran out of memory in memory
# space vmem … Scoped allocation with size 16.02M and limit 16.00M" at
# (512, 512); 20.00M at (1024, 512)).  The estimate is not Mosaic's own
# sum — that is not observable — so the cap sits below the lowest
# refusal with margin.
_SINGLE_BLOCK_VMEM_CAP = 28 * 1024 * 1024


def _single_block_vmem_bytes(b: int, h: int, n_gates: int) -> int:
    """VMEM blocks of the single-block BACKWARD kernel (the larger of
    the pair), in bytes: per-step streamed blocks (the gate residue in
    and dxw out, [B, n_gates·H]; the [B, H] state/cotangent streams)
    double-buffered by the Pallas pipeline, plus the constant-index
    residents held once (w_hh and the dW_hh accumulator [H, n_gates·H],
    the boot-state cotangents) and the carry scratches."""
    f32 = 4
    n_bh = 5 if n_gates == 4 else 2        # H/C/dy streams: LSTM, GRU
    n_carry = 2 if n_gates == 4 else 1     # (h, c) or h
    streamed = (2 * n_gates * h + n_bh * h) * b * f32
    resident = 2 * n_gates * h * h * f32 + n_carry * b * h * f32
    scratch = n_carry * b * h * f32
    return 2 * streamed + resident + scratch


def fused_tier(b: int, h: int, n_gates: int = 4):
    """Two-tier Mosaic dispatch predicate, checked on every backend so
    interpret-mode tests exercise the hardware dispatch.

    - ``"fused"`` (h ≤ 512 and the single-block VMEM estimate under its
      cap): the round-5 single-block kernels — w_hh [H, 4H] f32 fully
      VMEM-resident (4 MB at H=512) plus the same-shape dW_hh
      accumulator.  The estimate grows with the batch: (128, 512) and
      (256, 512) compile, (512, 512) does not (see
      ``_SINGLE_BLOCK_VMEM_CAP``).
    - ``"fused_blocked"`` (512 < h, h % HBLOCK == 0, VMEM estimate
      under cap): the round-8 hidden-blocked kernels — grid (T, H/Hb)
      streams [H, n_gates·Hb] weight column blocks while the full
      [B, H] state carries live in VMEM scratch, so no [H, n_gates·H]
      tensor is ever resident.  ``--fused_rnn_hblock=false`` disables
      this tier, restoring the round-7 h ≤ 512 gate byte-for-byte.
    - ``None``: the ``lax.scan`` path (dispatch site logs a one-time
      structured warning per shape).
    """
    if b % 8 or h % 128:
        return None
    if h <= 512:
        if _single_block_vmem_bytes(b, h, n_gates) > _SINGLE_BLOCK_VMEM_CAP:
            return None
        return "fused"
    from ..utils import FLAGS

    if not FLAGS.fused_rnn_hblock:
        return None
    if h % HBLOCK or _blocked_vmem_bytes(b, h, n_gates) > _BLOCKED_VMEM_CAP:
        return None
    return "fused_blocked"


def fused_ok(b: int, h: int) -> bool:
    """True when either fused tier serves (b, h) — the dispatch kill
    point tests monkeypatch to force the scan reference path."""
    return fused_tier(b, h) is not None


# ------------------------------------------------- block-gate layout
def _to_gate_blocks(a, h: int, n_gates: int, hb: int = HBLOCK):
    """Permute a gate-major last axis (g0|g1|…, each H wide) into the
    block-major layout the blocked kernels stream: block j holds
    [g0_j|g1_j|…] (n_gates·hb columns), so a BlockSpec column block j
    of the permuted array carries every gate's slice of hidden block j
    contiguously.  Pure reshape/transpose — XLA does it in one pass and
    autodiff transposes it for free around the custom_vjp core."""
    nb = h // hb
    lead = a.shape[:-1]
    return a.reshape(*lead, n_gates, nb, hb).swapaxes(-3, -2) \
            .reshape(*lead, n_gates * h)


def _from_gate_blocks(a, h: int, n_gates: int, hb: int = HBLOCK):
    """Inverse of :func:`_to_gate_blocks`."""
    nb = h // hb
    lead = a.shape[:-1]
    return a.reshape(*lead, nb, n_gates, hb).swapaxes(-3, -2) \
            .reshape(*lead, n_gates * h)


def _sig(x):
    return jax.nn.sigmoid(x)


# --------------------------------------------------------------- forward
def _fwd_kernel(xw_ref, m_ref, whh_ref, ck_ref, h0_ref, c0_ref,
                hseq_ref, cseq_ref, gates_ref, h_s, c_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[:] = h0_ref[...].astype(jnp.float32)
        c_s[:] = c0_ref[...].astype(jnp.float32)

    h_prev = h_s[:]                                     # [B, H] f32
    c_prev = c_s[:]
    hd = h_prev.shape[-1]
    xw = xw_ref[0].astype(jnp.float32)                  # [B, 4H]
    gates = xw + h_prev @ whh_ref[...].astype(jnp.float32)
    pre_i = gates[:, :hd]
    pre_f = gates[:, hd:2 * hd]
    pre_c = gates[:, 2 * hd:3 * hd]
    pre_o = gates[:, 3 * hd:]
    # peepholes (row 0 = check_i, 1 = check_f, 2 = check_o)
    ck = ck_ref[...].astype(jnp.float32)                # [8, H]
    i = _sig(pre_i + c_prev * ck[0])
    f = _sig(pre_f + c_prev * ck[1])
    g = jnp.tanh(pre_c)
    c = f * c_prev + i * g
    o = _sig(pre_o + c * ck[2])
    h = o * jnp.tanh(c)

    m = m_ref[0, 0].astype(jnp.float32)[:, None]        # [B, 1]
    h_keep = m * h + (1.0 - m) * h_prev
    c_keep = m * c + (1.0 - m) * c_prev
    h_s[:] = h_keep
    c_s[:] = c_keep
    hseq_ref[0] = h_keep.astype(hseq_ref.dtype)
    cseq_ref[0] = c_keep.astype(cseq_ref.dtype)
    gates_ref[0] = jnp.concatenate([i, f, g, o],
                                   axis=-1).astype(gates_ref.dtype)


def _fwd_call(xw, mask, w_hh, checks, h0, c0):
    t, b, hd4 = xw.shape
    hd = hd4 // 4
    return pl.pallas_call(
        _fwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, hd4), lambda i: (i, 0, 0)),   # xw
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0)),     # mask
            pl.BlockSpec((hd, hd4), lambda i: (0, 0)),        # w_hh
            pl.BlockSpec((8, hd), lambda i: (0, 0)),          # checks
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # h0
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # c0
        ],
        out_specs=[
            pl.BlockSpec((1, b, hd), lambda i: (i, 0, 0)),    # H
            pl.BlockSpec((1, b, hd), lambda i: (i, 0, 0)),    # C
            pl.BlockSpec((1, b, hd4), lambda i: (i, 0, 0)),   # gates
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd4), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),                 # h carry
            pltpu.VMEM((b, hd), jnp.float32),                 # c carry
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.LSTM_FWD,
    )(xw, mask, w_hh, checks, h0, c0)


# -------------------------------------------------------------- backward
def _bwd_kernel(gates_ref, hprev_ref, cprev_ref, c_ref, m_ref, whh_ref,
                ck_ref, dy_ref, dyc_ref, dxw_ref, dwhh_ref, dck_ref,
                dh0_ref, dc0_ref, dh_s, dc_s, *, t_total):
    """Grid step i visits t = T-1-i (the block index maps reverse time).
    hprev/cprev blocks carry H_{t-1}/C_{t-1} (the wrapper passes the
    state sequences shifted by one with h0/c0 prepended).  dy/dyc are
    the external cotangents on the kept sequences H_t/C_t; they join the
    recurrent carries BEFORE the masked split, so the (1−m) passthrough
    forwards them to earlier steps exactly like the forward keep."""
    i_rev = pl.program_id(0)

    @pl.when(i_rev == 0)
    def _init():
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = jnp.zeros_like(dc_s)
        # dW/dck accumulate directly in their (constant-block) output
        # refs — a second VMEM copy as scratch would overflow the 16 MB
        # scoped-vmem budget at H=512
        dwhh_ref[...] = jnp.zeros_like(dwhh_ref)
        dck_ref[...] = jnp.zeros_like(dck_ref)

    hd = dh_s.shape[-1]
    gates = gates_ref[0].astype(jnp.float32)
    g_i = gates[:, :hd]
    g_f = gates[:, hd:2 * hd]
    g_g = gates[:, 2 * hd:3 * hd]
    g_o = gates[:, 3 * hd:]
    h_prev = hprev_ref[0].astype(jnp.float32)
    c_prev = cprev_ref[0].astype(jnp.float32)
    c = c_ref[0].astype(jnp.float32)
    ck = ck_ref[...].astype(jnp.float32)
    m = m_ref[0, 0].astype(jnp.float32)[:, None]

    tanh_c = jnp.tanh(c)
    # total cotangents on the kept states H_t / C_t
    dh_tot = dy_ref[0].astype(jnp.float32) + dh_s[:]
    dc_tot = dyc_ref[0].astype(jnp.float32) + dc_s[:]
    dh = m * dh_tot                                     # raw-h share
    do_pre = dh * tanh_c * g_o * (1.0 - g_o)
    dc = m * dc_tot + dh * g_o * (1.0 - tanh_c * tanh_c) \
        + do_pre * ck[2]                                # raw-c share
    di_pre = dc * g_g * g_i * (1.0 - g_i)
    df_pre = dc * c_prev * g_f * (1.0 - g_f)
    dg_pre = dc * g_i * (1.0 - g_g * g_g)
    dgates = jnp.concatenate([di_pre, df_pre, dg_pre, do_pre], axis=-1)

    dh_prev = dgates @ whh_ref[...].astype(jnp.float32).T
    dc_prev = dc * g_f + di_pre * ck[0] + df_pre * ck[1]

    dh_s[:] = (1.0 - m) * dh_tot + dh_prev
    dc_s[:] = (1.0 - m) * dc_tot + dc_prev
    dwhh_ref[...] = dwhh_ref[...] + h_prev.T @ dgates
    dck_ref[0] = dck_ref[0] + jnp.sum(di_pre * c_prev, axis=0)
    dck_ref[1] = dck_ref[1] + jnp.sum(df_pre * c_prev, axis=0)
    dck_ref[2] = dck_ref[2] + jnp.sum(do_pre * c, axis=0)
    dxw_ref[0] = dgates.astype(dxw_ref.dtype)

    @pl.when(i_rev == t_total - 1)
    def _flush():
        dh0_ref[...] = dh_s[:].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_s[:].astype(dc0_ref.dtype)


def _bwd_call(gates, h_prev_seq, c_prev_seq, c_seq, mask, w_hh, checks,
              dy, dyc):
    t, b, hd4 = gates.shape
    hd = hd4 // 4
    rev3 = lambda i: (t - 1 - i, 0, 0)
    kernel = functools.partial(_bwd_kernel, t_total=t)
    return pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, hd4), rev3),                  # gates
            pl.BlockSpec((1, b, hd), rev3),                   # H_{t-1}
            pl.BlockSpec((1, b, hd), rev3),                   # C_{t-1}
            pl.BlockSpec((1, b, hd), rev3),                   # C_t
            pl.BlockSpec((1, 1, b), lambda i: (t - 1 - i, 0, 0)),  # mask
            pl.BlockSpec((hd, hd4), lambda i: (0, 0)),        # w_hh
            pl.BlockSpec((8, hd), lambda i: (0, 0)),          # checks
            pl.BlockSpec((1, b, hd), rev3),                   # dy (dH)
            pl.BlockSpec((1, b, hd), rev3),                   # dyc (dC)
        ],
        out_specs=[
            pl.BlockSpec((1, b, hd4), rev3),                  # dxw
            pl.BlockSpec((hd, hd4), lambda i: (0, 0)),        # dw_hh
            pl.BlockSpec((8, hd), lambda i: (0, 0)),          # dchecks
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # dh0
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # dc0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd4), jnp.float32),
            jax.ShapeDtypeStruct((hd, hd4), jnp.float32),
            jax.ShapeDtypeStruct((8, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),                 # dh carry
            pltpu.VMEM((b, hd), jnp.float32),                 # dc carry
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.LSTM_BWD,
    )(gates, h_prev_seq, c_prev_seq, c_seq, mask, w_hh, checks, dy, dyc)


# ------------------------------------------------------------ custom vjp
@jax.custom_vjp
def _lstm_core(xw, mask, w_hh, checks, h0, c0):
    """xw [T, B, 4H] (input projection + bias already applied), mask
    [T, B], w_hh [H, 4H], checks [8, H] (rows 0..2 = peephole i/f/o,
    rest zero), h0/c0 [B, H].  Returns kept-state sequences
    (H [T, B, Hd], C [T, B, Hd]) in f32."""
    h_seq, c_seq, _gates = _fwd_call(xw, mask, w_hh, checks, h0, c0)
    return h_seq, c_seq


def _lstm_core_fwd(xw, mask, w_hh, checks, h0, c0):
    h_seq, c_seq, gates = _fwd_call(xw, mask, w_hh, checks, h0, c0)
    return (h_seq, c_seq), (gates, h_seq, c_seq, mask, w_hh, checks,
                            h0, c0)


def _lstm_core_bwd(res, cts):
    gates, h_seq, c_seq, mask, w_hh, checks, h0, c0 = res
    dh_seq, dc_seq = cts
    # state sequences shifted one step back, boot state prepended
    h_prev_seq = jnp.concatenate([h0[None].astype(h_seq.dtype),
                                  h_seq[:-1]], axis=0)
    c_prev_seq = jnp.concatenate([c0[None].astype(c_seq.dtype),
                                  c_seq[:-1]], axis=0)
    dxw, dw_hh, dck, dh0, dc0 = _bwd_call(
        gates, h_prev_seq, c_prev_seq, c_seq, mask, w_hh, checks,
        dh_seq, dc_seq)
    # mask was cast to xw's dtype in the wrapper, so it carries the
    # input dtype for the cotangent cast
    return (dxw.astype(mask.dtype), jnp.zeros_like(mask), dw_hh,
            dck, dh0, dc0)


_lstm_core.defvjp(_lstm_core_fwd, _lstm_core_bwd)


def lstm_fused_sequence(xw, mask, w_hh, check_i, check_f, check_o,
                        h0, c0):
    """Batch-major wrapper: xw [B, T, 4H] pre-projected (+bias), mask
    [B, T]; returns (y [B, T, H] masked hidden outputs, cy [B, T, H]
    masked cell outputs, final_h [B, H], final_c [B, H]) in f32 —
    callers cast per their dtype policy.  XLA dead-code-eliminates the
    cy mask-multiply when the caller drops it.
    """
    b, t, hd4 = xw.shape
    hd = hd4 // 4
    checks = jnp.zeros((8, hd), jnp.float32)
    if check_i is not None:
        checks = checks.at[0].set(check_i.astype(jnp.float32))
        checks = checks.at[1].set(check_f.astype(jnp.float32))
    if check_o is not None:
        checks = checks.at[2].set(check_o.astype(jnp.float32))
    h0 = jnp.zeros((b, hd), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    c0 = jnp.zeros((b, hd), jnp.float32) if c0 is None \
        else c0.astype(jnp.float32)
    h_seq, c_seq = _lstm_core(
        jnp.moveaxis(xw, 1, 0),
        jnp.moveaxis(mask, 1, 0).astype(xw.dtype)[:, None, :],
        w_hh.astype(jnp.float32), checks, h0, c0)
    m = mask.astype(jnp.float32)[:, :, None]
    y = jnp.moveaxis(h_seq, 0, 1) * m
    cy = jnp.moveaxis(c_seq, 0, 1) * m
    return y, cy, h_seq[-1], c_seq[-1]


# =================================================================
# Hidden-blocked tier (512 < H): grid (T, H/Hb) streams weight column
# blocks instead of keeping w_hh resident.  The full [B, H] h/c state
# (0.7 MB f32 at b=128/H=1280 — cheap) carries in VMEM scratch across
# BOTH grid dimensions; per inner step the MXU sees one
# [B, H] @ [H, 4Hb] matmul against the streamed block.  All dynamic
# scratch column offsets are j·Hb with Hb = 128, i.e. lane-tile
# aligned — the Mosaic-friendly dynamic-slice case.
# =================================================================
def _fwd_kernel_blocked(xw_ref, m_ref, whh_ref, ck_ref, h0_ref, c0_ref,
                        hseq_ref, cseq_ref, gates_ref,
                        h_s, c_s, hn_s, cn_s, *, nb, hb):
    """Grid (T, nb), hidden blocks innermost.  Every block of step t
    reads the step-(t-1) state from h_s/c_s and writes its kept slice
    into the staging scratches hn_s/cn_s; the last block commits the
    staged state so no block of step t ever sees a partial update.
    xw/whh/gates are in block-gate layout (see _to_gate_blocks)."""
    t = pl.program_id(0)
    j = pl.program_id(1)
    col = j * hb

    @pl.when((t == 0) & (j == 0))
    def _init():
        h_s[:] = h0_ref[...].astype(jnp.float32)
        c_s[:] = c0_ref[...].astype(jnp.float32)

    h_prev = h_s[:]                                     # [B, H] f32
    h_prev_blk = h_s[:, pl.ds(col, hb)]                 # [B, Hb]
    c_prev_blk = c_s[:, pl.ds(col, hb)]
    xw = xw_ref[0].astype(jnp.float32)                  # [B, 4Hb]
    gates = xw + h_prev @ whh_ref[...].astype(jnp.float32)
    pre_i = gates[:, :hb]
    pre_f = gates[:, hb:2 * hb]
    pre_c = gates[:, 2 * hb:3 * hb]
    pre_o = gates[:, 3 * hb:]
    ck = ck_ref[...].astype(jnp.float32)                # [8, Hb]
    i = _sig(pre_i + c_prev_blk * ck[0])
    f = _sig(pre_f + c_prev_blk * ck[1])
    g = jnp.tanh(pre_c)
    c = f * c_prev_blk + i * g
    o = _sig(pre_o + c * ck[2])
    h = o * jnp.tanh(c)

    m = m_ref[0, 0].astype(jnp.float32)[:, None]        # [B, 1]
    h_keep = m * h + (1.0 - m) * h_prev_blk
    c_keep = m * c + (1.0 - m) * c_prev_blk
    hn_s[:, pl.ds(col, hb)] = h_keep
    cn_s[:, pl.ds(col, hb)] = c_keep
    hseq_ref[0] = h_keep.astype(hseq_ref.dtype)
    cseq_ref[0] = c_keep.astype(cseq_ref.dtype)
    gates_ref[0] = jnp.concatenate([i, f, g, o],
                                   axis=-1).astype(gates_ref.dtype)

    @pl.when(j == nb - 1)
    def _commit():
        h_s[:] = hn_s[:]
        c_s[:] = cn_s[:]


def _fwd_call_blocked(xw, mask, w_hh, checks, h0, c0, hb=HBLOCK):
    t, b, hd4 = xw.shape
    hd = hd4 // 4
    nb = hd // hb
    kernel = functools.partial(_fwd_kernel_blocked, nb=nb, hb=hb)
    return pl.pallas_call(
        kernel,
        grid=(t, nb),
        in_specs=[
            pl.BlockSpec((1, b, 4 * hb), lambda i, j: (i, 0, j)),  # xw
            pl.BlockSpec((1, 1, b), lambda i, j: (i, 0, 0)),       # mask
            pl.BlockSpec((hd, 4 * hb), lambda i, j: (0, j)),       # w_hh
            pl.BlockSpec((8, hb), lambda i, j: (0, j)),            # checks
            pl.BlockSpec((b, hd), lambda i, j: (0, 0)),            # h0
            pl.BlockSpec((b, hd), lambda i, j: (0, 0)),            # c0
        ],
        out_specs=[
            pl.BlockSpec((1, b, hb), lambda i, j: (i, 0, j)),      # H
            pl.BlockSpec((1, b, hb), lambda i, j: (i, 0, j)),      # C
            pl.BlockSpec((1, b, 4 * hb), lambda i, j: (i, 0, j)),  # gates
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd4), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),                # h carry
            pltpu.VMEM((b, hd), jnp.float32),                # c carry
            pltpu.VMEM((b, hd), jnp.float32),                # h staging
            pltpu.VMEM((b, hd), jnp.float32),                # c staging
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.LSTM_FWD_BLOCKED,
    )(xw, mask, w_hh, checks, h0, c0)


def _bwd_kernel_blocked(gates_ref, cprev_ref, c_ref, m_ref, whh_ref,
                        ck_ref, dy_ref, dyc_ref,
                        dxw_ref, dh0_ref, dc0_ref,
                        dh_s, dc_s, dacc_s, dcn_s, *, t_total, nb, hb):
    """Reversed-time BPTT, grid (T, nb).  The gate math is elementwise
    in the hidden index, so each block computes its own dgates slice
    from the carried dh_s/dc_s; the one cross-block coupling — the
    recurrent pullback dgates @ w_hhᵀ, full [B, H] wide — accumulates
    over the inner block loop in dacc_s, and the last block commits the
    next step's carries.  The weight gradient does NOT ride along: a
    revisited [H, 4Hb] dW block would flush/refill per step, so dW_hh
    runs as its own constant-block kernel (_dw_call_blocked) over the
    dgates residue this kernel writes out as dxw."""
    i_rev = pl.program_id(0)
    j = pl.program_id(1)
    col = j * hb

    @pl.when((i_rev == 0) & (j == 0))
    def _init():
        dh_s[:] = jnp.zeros_like(dh_s)
        dc_s[:] = jnp.zeros_like(dc_s)

    @pl.when(j == 0)
    def _zero_acc():
        dacc_s[:] = jnp.zeros_like(dacc_s)

    gates = gates_ref[0].astype(jnp.float32)            # [B, 4Hb]
    g_i = gates[:, :hb]
    g_f = gates[:, hb:2 * hb]
    g_g = gates[:, 2 * hb:3 * hb]
    g_o = gates[:, 3 * hb:]
    c_prev = cprev_ref[0].astype(jnp.float32)           # [B, Hb]
    c = c_ref[0].astype(jnp.float32)
    ck = ck_ref[...].astype(jnp.float32)                # [8, Hb]
    m = m_ref[0, 0].astype(jnp.float32)[:, None]

    tanh_c = jnp.tanh(c)
    dh_tot = dy_ref[0].astype(jnp.float32) + dh_s[:, pl.ds(col, hb)]
    dc_tot = dyc_ref[0].astype(jnp.float32) + dc_s[:, pl.ds(col, hb)]
    dh = m * dh_tot                                     # raw-h share
    do_pre = dh * tanh_c * g_o * (1.0 - g_o)
    dc = m * dc_tot + dh * g_o * (1.0 - tanh_c * tanh_c) \
        + do_pre * ck[2]                                # raw-c share
    di_pre = dc * g_g * g_i * (1.0 - g_i)
    df_pre = dc * c_prev * g_f * (1.0 - g_f)
    dg_pre = dc * g_i * (1.0 - g_g * g_g)
    dgates = jnp.concatenate([di_pre, df_pre, dg_pre, do_pre], axis=-1)

    # cross-block recurrent pullback: every gate block contributes a
    # full-width [B, H] term
    dacc_s[:] = dacc_s[:] + dgates @ whh_ref[...].astype(jnp.float32).T
    # block-local pieces join the accumulator at this block's columns
    dacc_s[:, pl.ds(col, hb)] = dacc_s[:, pl.ds(col, hb)] \
        + (1.0 - m) * dh_tot
    dc_prev = dc * g_f + di_pre * ck[0] + df_pre * ck[1]
    dcn_s[:, pl.ds(col, hb)] = (1.0 - m) * dc_tot + dc_prev
    dxw_ref[0] = dgates.astype(dxw_ref.dtype)

    @pl.when(j == nb - 1)
    def _commit():
        dh_s[:] = dacc_s[:]
        dc_s[:] = dcn_s[:]

    @pl.when((i_rev == t_total - 1) & (j == nb - 1))
    def _flush():
        dh0_ref[...] = dacc_s[:].astype(dh0_ref.dtype)
        dc0_ref[...] = dcn_s[:].astype(dc0_ref.dtype)


def _bwd_call_blocked(gates, c_prev_seq, c_seq, mask, w_hh, checks,
                      dy, dyc, hb=HBLOCK):
    t, b, hd4 = gates.shape
    hd = hd4 // 4
    nb = hd // hb
    rev_blk = lambda i, j: (t - 1 - i, 0, j)
    kernel = functools.partial(_bwd_kernel_blocked, t_total=t, nb=nb,
                               hb=hb)
    return pl.pallas_call(
        kernel,
        grid=(t, nb),
        in_specs=[
            pl.BlockSpec((1, b, 4 * hb), rev_blk),                # gates
            pl.BlockSpec((1, b, hb), rev_blk),                    # C_{t-1}
            pl.BlockSpec((1, b, hb), rev_blk),                    # C_t
            pl.BlockSpec((1, 1, b), lambda i, j: (t - 1 - i, 0, 0)),
            pl.BlockSpec((hd, 4 * hb), lambda i, j: (0, j)),      # w_hh
            pl.BlockSpec((8, hb), lambda i, j: (0, j)),           # checks
            pl.BlockSpec((1, b, hb), rev_blk),                    # dy
            pl.BlockSpec((1, b, hb), rev_blk),                    # dyc
        ],
        out_specs=[
            pl.BlockSpec((1, b, 4 * hb), rev_blk),                # dxw
            pl.BlockSpec((b, hd), lambda i, j: (0, 0)),           # dh0
            pl.BlockSpec((b, hd), lambda i, j: (0, 0)),           # dc0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd4), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),               # dh carry
            pltpu.VMEM((b, hd), jnp.float32),               # dc carry
            pltpu.VMEM((b, hd), jnp.float32),               # dh accum
            pltpu.VMEM((b, hd), jnp.float32),               # dc staging
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.LSTM_BWD_BLOCKED,
    )(gates, c_prev_seq, c_seq, mask, w_hh, checks, dy, dyc)


def _dw_kernel_blocked(hprev_ref, dgates_ref, dwhh_ref):
    """Grid (nb, T), time innermost: dW block j stays resident in its
    output ref across the whole T loop (the round-7 constant-block
    pattern — the block index map ignores the inner grid dim), so the
    only VMEM-resident weight-gradient tensor is one [H, 4Hb] block,
    never the full [H, 4H] accumulator."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        dwhh_ref[...] = jnp.zeros_like(dwhh_ref)

    h_prev = hprev_ref[0].astype(jnp.float32)           # [B, H]
    dgates = dgates_ref[0].astype(jnp.float32)          # [B, 4Hb]
    dwhh_ref[...] = dwhh_ref[...] + h_prev.T @ dgates


def _dw_call_blocked(h_prev_seq, dgates, hb=HBLOCK):
    t, b, hd4 = dgates.shape
    hd = hd4 // 4
    nb = hd // hb
    return pl.pallas_call(
        _dw_kernel_blocked,
        grid=(nb, t),
        in_specs=[
            pl.BlockSpec((1, b, hd), lambda j, i: (i, 0, 0)),     # H_{t-1}
            pl.BlockSpec((1, b, 4 * hb), lambda j, i: (i, 0, j)),  # dgates
        ],
        out_specs=pl.BlockSpec((hd, 4 * hb), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((hd, hd4), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.LSTM_DW_BLOCKED,
    )(h_prev_seq, dgates)


@jax.custom_vjp
def _lstm_core_blocked(xw, mask, w_hh, checks, h0, c0):
    """Blocked-tier core: same contract as :func:`_lstm_core` except xw
    [T, B, 4H] and w_hh [H, 4H] arrive in block-gate layout (the
    wrapper permutes; autodiff transposes the permute around this
    boundary).  Returns kept-state sequences in natural layout."""
    h_seq, c_seq, _gates = _fwd_call_blocked(xw, mask, w_hh, checks,
                                             h0, c0)
    return h_seq, c_seq


def _lstm_core_blocked_fwd(xw, mask, w_hh, checks, h0, c0):
    h_seq, c_seq, gates = _fwd_call_blocked(xw, mask, w_hh, checks,
                                            h0, c0)
    return (h_seq, c_seq), (gates, h_seq, c_seq, mask, w_hh, checks,
                            h0, c0)


def _lstm_core_blocked_bwd(res, cts):
    gates, h_seq, c_seq, mask, w_hh, checks, h0, c0 = res
    dh_seq, dc_seq = cts
    hd = h_seq.shape[-1]
    h_prev_seq = jnp.concatenate([h0[None].astype(h_seq.dtype),
                                  h_seq[:-1]], axis=0)
    c_prev_seq = jnp.concatenate([c0[None].astype(c_seq.dtype),
                                  c_seq[:-1]], axis=0)
    dxw, dh0, dc0 = _bwd_call_blocked(
        gates, c_prev_seq, c_seq, mask, w_hh, checks, dh_seq, dc_seq)
    dw_hh = _dw_call_blocked(h_prev_seq, dxw)
    # peephole grads are an O(H) reduction over residues already in
    # HBM (the dgates residue is dxw) — plain XLA, no VMEM pressure
    dxw_n = _from_gate_blocks(dxw, hd, 4)
    dck = jnp.zeros((8, hd), jnp.float32)
    dck = dck.at[0].set(jnp.sum(dxw_n[..., :hd] * c_prev_seq,
                                axis=(0, 1)))
    dck = dck.at[1].set(jnp.sum(dxw_n[..., hd:2 * hd] * c_prev_seq,
                                axis=(0, 1)))
    dck = dck.at[2].set(jnp.sum(dxw_n[..., 3 * hd:] * c_seq,
                                axis=(0, 1)))
    return (dxw.astype(mask.dtype), jnp.zeros_like(mask), dw_hh,
            dck, dh0, dc0)


_lstm_core_blocked.defvjp(_lstm_core_blocked_fwd, _lstm_core_blocked_bwd)


def lstm_fused_sequence_blocked(xw, mask, w_hh, check_i, check_f,
                                check_o, h0, c0):
    """Blocked-tier entry — same batch-major contract as
    :func:`lstm_fused_sequence`, dispatched by
    ``fused_tier(b, h) == "fused_blocked"``."""
    b, t, hd4 = xw.shape
    hd = hd4 // 4
    checks = jnp.zeros((8, hd), jnp.float32)
    if check_i is not None:
        checks = checks.at[0].set(check_i.astype(jnp.float32))
        checks = checks.at[1].set(check_f.astype(jnp.float32))
    if check_o is not None:
        checks = checks.at[2].set(check_o.astype(jnp.float32))
    h0 = jnp.zeros((b, hd), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    c0 = jnp.zeros((b, hd), jnp.float32) if c0 is None \
        else c0.astype(jnp.float32)
    xw_blk = _to_gate_blocks(jnp.moveaxis(xw, 1, 0), hd, 4)
    whh_blk = _to_gate_blocks(w_hh.astype(jnp.float32), hd, 4)
    h_seq, c_seq = _lstm_core_blocked(
        xw_blk,
        jnp.moveaxis(mask, 1, 0).astype(xw.dtype)[:, None, :],
        whh_blk, checks, h0, c0)
    m = mask.astype(jnp.float32)[:, :, None]
    y = jnp.moveaxis(h_seq, 0, 1) * m
    cy = jnp.moveaxis(c_seq, 0, 1) * m
    return y, cy, h_seq[-1], c_seq[-1]
