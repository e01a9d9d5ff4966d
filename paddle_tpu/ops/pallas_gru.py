"""Fused GRU sequence as Pallas TPU kernels.

Companion to :mod:`paddle_tpu.ops.pallas_lstm` — the second half of the
``hl_cuda_lstm.cu`` / ``hl_cuda_gru`` kernel tier SURVEY §7 names.  The
whole time loop runs in one launch: h carried in VMEM f32 scratch, both
recurrent weights (w_gates [H, 2H], w_cand [H, H]) resident, per step
two MXU matmuls (gate and candidate projections) plus the sigmoid/tanh
gate math on the VPU, with the length-masked keep.  Backward is a
reversed-grid BPTT kernel accumulating dW directly in constant-block
output refs.  Gate layout (u, r, c) and the update rule
``h' = u·h + (1−u)·c`` match ``recurrent_ops.gru_sequence`` exactly —
equivalence is pinned by ``tests/test_pallas_gru.py``.

Same dispatch contract as the LSTM kernel: default activations and
tileable shapes only; anything else takes the ``lax.scan`` path.

Round 8 adds the hidden-blocked tier for 512 < H (see pallas_lstm.py
for the scheme): because the candidate projection needs the full reset
gate first, each time step runs as TWO phases over the inner grid dim
— grid (T, 2·H/Hb), gate blocks then candidate blocks — with w_gates
and w_cand streamed as column blocks and min/max-pinned index maps so
each weight stream moves exactly its own bytes per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import pallas_interpret
from . import kernels as K
from .pallas_lstm import (HBLOCK, _from_gate_blocks, _to_gate_blocks,
                          fused_tier as _lstm_fused_tier)


def fused_tier(b: int, h: int):
    """Same two-tier dispatch as the LSTM kernel, with the GRU's gate
    width (3H: u|r gates 2H + candidate H) in the streamed-block VMEM
    estimate."""
    return _lstm_fused_tier(b, h, n_gates=3)


def fused_ok(b: int, h: int) -> bool:
    """True when either fused tier serves (b, h) — the dispatch kill
    point tests monkeypatch to force the scan reference path."""
    return fused_tier(b, h) is not None


def _sig(x):
    return jax.nn.sigmoid(x)


# --------------------------------------------------------------- forward
def _fwd_kernel(xw_ref, m_ref, wg_ref, wc_ref, h0_ref, hseq_ref,
                gates_ref, h_s):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        h_s[:] = h0_ref[...].astype(jnp.float32)

    h_prev = h_s[:]                                     # [B, H] f32
    hd = h_prev.shape[-1]
    xw = xw_ref[0].astype(jnp.float32)                  # [B, 3H]
    xu = xw[:, :hd]
    xr = xw[:, hd:2 * hd]
    xc = xw[:, 2 * hd:]
    g = h_prev @ wg_ref[...].astype(jnp.float32)        # [B, 2H]
    u = _sig(xu + g[:, :hd])
    r = _sig(xr + g[:, hd:])
    c = jnp.tanh(xc + (r * h_prev) @ wc_ref[...].astype(jnp.float32))
    h_new = u * h_prev + (1.0 - u) * c

    m = m_ref[0, 0].astype(jnp.float32)[:, None]        # [B, 1]
    h_keep = m * h_new + (1.0 - m) * h_prev
    h_s[:] = h_keep
    hseq_ref[0] = h_keep.astype(hseq_ref.dtype)
    gates_ref[0] = jnp.concatenate([u, r, c],
                                   axis=-1).astype(gates_ref.dtype)


def _fwd_call(xw, mask, w_gates, w_cand, h0):
    t, b, hd3 = xw.shape
    hd = hd3 // 3
    return pl.pallas_call(
        _fwd_kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, hd3), lambda i: (i, 0, 0)),   # xw
            pl.BlockSpec((1, 1, b), lambda i: (i, 0, 0)),     # mask
            pl.BlockSpec((hd, 2 * hd), lambda i: (0, 0)),     # w_gates
            pl.BlockSpec((hd, hd), lambda i: (0, 0)),         # w_cand
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # h0
        ],
        out_specs=[
            pl.BlockSpec((1, b, hd), lambda i: (i, 0, 0)),    # H
            pl.BlockSpec((1, b, hd3), lambda i: (i, 0, 0)),   # gates
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd3), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, hd), jnp.float32)],    # h carry
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.GRU_FWD,
    )(xw, mask, w_gates, w_cand, h0)


# -------------------------------------------------------------- backward
def _bwd_kernel(gates_ref, hprev_ref, m_ref, wg_ref, wc_ref, dy_ref,
                dxw_ref, dwg_ref, dwc_ref, dh0_ref, dh_s, *, t_total):
    """Grid step i visits t = T-1-i.  dy is the external cotangent on
    the kept H_t; it joins the carry BEFORE the masked split so the
    (1−m) passthrough mirrors the forward keep."""
    i_rev = pl.program_id(0)

    @pl.when(i_rev == 0)
    def _init():
        dh_s[:] = jnp.zeros_like(dh_s)
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwc_ref[...] = jnp.zeros_like(dwc_ref)

    hd = dh_s.shape[-1]
    gates = gates_ref[0].astype(jnp.float32)
    u = gates[:, :hd]
    r = gates[:, hd:2 * hd]
    c = gates[:, 2 * hd:]
    h_prev = hprev_ref[0].astype(jnp.float32)
    m = m_ref[0, 0].astype(jnp.float32)[:, None]

    dh_tot = dy_ref[0].astype(jnp.float32) + dh_s[:]
    dh_new = m * dh_tot                                 # raw-h' share
    du_pre = dh_new * (h_prev - c) * u * (1.0 - u)
    dc_pre = dh_new * (1.0 - u) * (1.0 - c * c)
    drh = dc_pre @ wc_ref[...].astype(jnp.float32).T    # d(r·h_prev)
    dr_pre = drh * h_prev * r * (1.0 - r)
    dg = jnp.concatenate([du_pre, dr_pre], axis=-1)     # [B, 2H]

    dh_prev = (dh_new * u + drh * r
               + dg @ wg_ref[...].astype(jnp.float32).T)
    dh_s[:] = (1.0 - m) * dh_tot + dh_prev
    dwg_ref[...] = dwg_ref[...] + h_prev.T @ dg
    dwc_ref[...] = dwc_ref[...] + (r * h_prev).T @ dc_pre
    dxw_ref[0] = jnp.concatenate([du_pre, dr_pre, dc_pre],
                                 axis=-1).astype(dxw_ref.dtype)

    @pl.when(i_rev == t_total - 1)
    def _flush():
        dh0_ref[...] = dh_s[:].astype(dh0_ref.dtype)


def _bwd_call(gates, h_prev_seq, mask, w_gates, w_cand, dy):
    t, b, hd3 = gates.shape
    hd = hd3 // 3
    rev3 = lambda i: (t - 1 - i, 0, 0)
    kernel = functools.partial(_bwd_kernel, t_total=t)
    return pl.pallas_call(
        kernel,
        grid=(t,),
        in_specs=[
            pl.BlockSpec((1, b, hd3), rev3),                  # gates
            pl.BlockSpec((1, b, hd), rev3),                   # H_{t-1}
            pl.BlockSpec((1, 1, b), lambda i: (t - 1 - i, 0, 0)),  # mask
            pl.BlockSpec((hd, 2 * hd), lambda i: (0, 0)),     # w_gates
            pl.BlockSpec((hd, hd), lambda i: (0, 0)),         # w_cand
            pl.BlockSpec((1, b, hd), rev3),                   # dy (dH)
        ],
        out_specs=[
            pl.BlockSpec((1, b, hd3), rev3),                  # dxw
            pl.BlockSpec((hd, 2 * hd), lambda i: (0, 0)),     # dw_gates
            pl.BlockSpec((hd, hd), lambda i: (0, 0)),         # dw_cand
            pl.BlockSpec((b, hd), lambda i: (0, 0)),          # dh0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd3), jnp.float32),
            jax.ShapeDtypeStruct((hd, 2 * hd), jnp.float32),
            jax.ShapeDtypeStruct((hd, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b, hd), jnp.float32)],    # dh carry
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name=K.GRU_BWD,
    )(gates, h_prev_seq, mask, w_gates, w_cand, dy)


# ------------------------------------------------------------ custom vjp
@jax.custom_vjp
def _gru_core(xw, mask, w_gates, w_cand, h0):
    """xw [T, B, 3H] (input projection + bias applied), mask [T, 1, B],
    w_gates [H, 2H], w_cand [H, H], h0 [B, H].  Returns the kept state
    sequence H [T, B, Hd] in f32."""
    h_seq, _gates = _fwd_call(xw, mask, w_gates, w_cand, h0)
    return h_seq


def _gru_core_fwd(xw, mask, w_gates, w_cand, h0):
    h_seq, gates = _fwd_call(xw, mask, w_gates, w_cand, h0)
    return h_seq, (gates, h_seq, mask, w_gates, w_cand, h0)


def _gru_core_bwd(res, dh_seq):
    gates, h_seq, mask, w_gates, w_cand, h0 = res
    h_prev_seq = jnp.concatenate([h0[None].astype(h_seq.dtype),
                                  h_seq[:-1]], axis=0)
    dxw, dwg, dwc, dh0 = _bwd_call(gates, h_prev_seq, mask, w_gates,
                                   w_cand, dh_seq)
    return (dxw.astype(mask.dtype), jnp.zeros_like(mask), dwg, dwc, dh0)


_gru_core.defvjp(_gru_core_fwd, _gru_core_bwd)


def gru_fused_sequence(xw, mask, w_gates, w_cand, h0):
    """Batch-major wrapper: xw [B, T, 3H] pre-projected (+bias), mask
    [B, T]; returns (y [B, T, H] masked hidden outputs, final_h [B, H])
    in f32 — callers cast per their dtype policy."""
    b, t, hd3 = xw.shape
    hd = hd3 // 3
    h0 = jnp.zeros((b, hd), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    h_seq = _gru_core(
        jnp.moveaxis(xw, 1, 0),
        jnp.moveaxis(mask, 1, 0).astype(xw.dtype)[:, None, :],
        w_gates.astype(jnp.float32), w_cand.astype(jnp.float32), h0)
    y = jnp.moveaxis(h_seq, 0, 1) * mask.astype(jnp.float32)[:, :, None]
    return y, h_seq[-1]


# =================================================================
# Hidden-blocked tier (512 < H) — see pallas_lstm.py for the general
# scheme.  The GRU adds a wrinkle the LSTM doesn't have: the candidate
# projection (r·h_prev) @ w_cand needs the FULL reset gate r before any
# candidate block can run, so one time step is TWO phases over the
# inner grid dim: grid (T, 2·nb), steps p < nb compute gate blocks
# (u_j, r_j) and stage r·h_prev, steps p ≥ nb stream w_cand column
# blocks and finish candidate/update math.  The min/max index-map
# pinning keeps each weight's stream at exactly its own bytes per step
# (w_gates holds its last block through phase 2, w_cand holds block 0
# through phase 1 — an unchanged block index fetches nothing).
# =================================================================
def _fwd_kernel_blocked(xur_ref, xc_ref, m_ref, wg_ref, wc_ref, h0_ref,
                        hseq_ref, urseq_ref, cseq_ref,
                        h_s, u_s, rh_s, hn_s, *, nb, hb):
    """xur/wg/urseq are in block-gate layout (block j = [u_j|r_j]);
    xc/wc/hseq/cseq are natural (w_cand column blocks are already
    contiguous)."""
    t = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when((t == 0) & (p == 0))
    def _init():
        h_s[:] = h0_ref[...].astype(jnp.float32)

    @pl.when(p < nb)
    def _phase_gates():
        col = p * hb
        h_prev = h_s[:]                                 # [B, H] f32
        h_prev_blk = h_s[:, pl.ds(col, hb)]
        xur = xur_ref[0].astype(jnp.float32)            # [B, 2Hb]
        g = h_prev @ wg_ref[...].astype(jnp.float32)    # [B, 2Hb]
        u = _sig(xur[:, :hb] + g[:, :hb])
        r = _sig(xur[:, hb:] + g[:, hb:])
        u_s[:, pl.ds(col, hb)] = u
        rh_s[:, pl.ds(col, hb)] = r * h_prev_blk
        urseq_ref[0] = jnp.concatenate([u, r],
                                       axis=-1).astype(urseq_ref.dtype)

    @pl.when(p >= nb)
    def _phase_cand():
        col = (p - nb) * hb
        h_prev_blk = h_s[:, pl.ds(col, hb)]
        u = u_s[:, pl.ds(col, hb)]
        xc = xc_ref[0].astype(jnp.float32)              # [B, Hb]
        c = jnp.tanh(xc + rh_s[:] @ wc_ref[...].astype(jnp.float32))
        h_new = u * h_prev_blk + (1.0 - u) * c
        m = m_ref[0, 0].astype(jnp.float32)[:, None]
        h_keep = m * h_new + (1.0 - m) * h_prev_blk
        hn_s[:, pl.ds(col, hb)] = h_keep
        hseq_ref[0] = h_keep.astype(hseq_ref.dtype)
        cseq_ref[0] = c.astype(cseq_ref.dtype)

    @pl.when(p == 2 * nb - 1)
    def _commit():
        h_s[:] = hn_s[:]


def _fwd_call_blocked(xur, xc, mask, w_gates, w_cand, h0, hb=HBLOCK):
    t, b, hd = xc.shape
    nb = hd // hb
    kernel = functools.partial(_fwd_kernel_blocked, nb=nb, hb=hb)
    ph1 = lambda i, p: (i, 0, jnp.minimum(p, nb - 1))       # gate phase
    ph2 = lambda i, p: (i, 0, jnp.maximum(p - nb, 0))       # cand phase
    return pl.pallas_call(
        kernel,
        grid=(t, 2 * nb),
        in_specs=[
            pl.BlockSpec((1, b, 2 * hb), ph1),              # xur blk
            pl.BlockSpec((1, b, hb), ph2),                  # xc blk
            pl.BlockSpec((1, 1, b), lambda i, p: (i, 0, 0)),  # mask
            pl.BlockSpec((hd, 2 * hb),
                         lambda i, p: (0, jnp.minimum(p, nb - 1))),
            pl.BlockSpec((hd, hb),
                         lambda i, p: (0, jnp.maximum(p - nb, 0))),
            pl.BlockSpec((b, hd), lambda i, p: (0, 0)),     # h0
        ],
        out_specs=[
            pl.BlockSpec((1, b, hb), ph2),                  # H
            pl.BlockSpec((1, b, 2 * hb), ph1),              # u|r gates
            pl.BlockSpec((1, b, hb), ph2),                  # candidate
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, 2 * hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),               # h carry
            pltpu.VMEM((b, hd), jnp.float32),               # u staging
            pltpu.VMEM((b, hd), jnp.float32),               # r·h staging
            pltpu.VMEM((b, hd), jnp.float32),               # h staging
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.GRU_FWD_BLOCKED,
    )(xur, xc, mask, w_gates, w_cand, h0)


def _bwd_kernel_blocked(ur_ref, c_ref, hprev_ref, m_ref, wg_ref, wc_ref,
                        dy_ref, dxur_ref, dxc_ref, dh0_ref,
                        dh_s, du_s, drh_s, dacc_s, *, t_total, nb, hb):
    """Reversed-time BPTT with the forward's two phases mirrored:
    phase A (p < nb) forms du_pre/dc_pre per block and accumulates the
    full-width d(r·h_prev) = Σ_j dc_pre_j @ w_cand_jᵀ; phase B needs
    that complete sum to split dr_pre per block, then accumulates the
    gate pullback into the next dh carry.  dW_gates/dW_cand run as the
    separate constant-block kernel over the residues written here."""
    i_rev = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when((i_rev == 0) & (p == 0))
    def _init():
        dh_s[:] = jnp.zeros_like(dh_s)

    @pl.when(p == 0)
    def _zero_acc():
        drh_s[:] = jnp.zeros_like(drh_s)
        dacc_s[:] = jnp.zeros_like(dacc_s)

    m = m_ref[0, 0].astype(jnp.float32)[:, None]

    @pl.when(p < nb)
    def _phase_a():
        col = p * hb
        ur = ur_ref[0].astype(jnp.float32)              # [B, 2Hb]
        u = ur[:, :hb]
        c = c_ref[0].astype(jnp.float32)                # [B, Hb]
        h_prev_blk = hprev_ref[0].astype(jnp.float32)
        dh_tot = dy_ref[0].astype(jnp.float32) + dh_s[:, pl.ds(col, hb)]
        dh_new = m * dh_tot                             # raw-h' share
        du_pre = dh_new * (h_prev_blk - c) * u * (1.0 - u)
        dc_pre = dh_new * (1.0 - u) * (1.0 - c * c)
        du_s[:, pl.ds(col, hb)] = du_pre
        drh_s[:] = drh_s[:] \
            + dc_pre @ wc_ref[...].astype(jnp.float32).T
        dxc_ref[0] = dc_pre.astype(dxc_ref.dtype)

    @pl.when(p >= nb)
    def _phase_b():
        col = (p - nb) * hb
        ur = ur_ref[0].astype(jnp.float32)
        u = ur[:, :hb]
        r = ur[:, hb:]
        h_prev_blk = hprev_ref[0].astype(jnp.float32)
        dh_tot = dy_ref[0].astype(jnp.float32) + dh_s[:, pl.ds(col, hb)]
        dh_new = m * dh_tot
        drh = drh_s[:, pl.ds(col, hb)]                  # complete sum
        dr_pre = drh * h_prev_blk * r * (1.0 - r)
        du_pre = du_s[:, pl.ds(col, hb)]
        dg = jnp.concatenate([du_pre, dr_pre], axis=-1)  # [B, 2Hb]
        dacc_s[:] = dacc_s[:] + dg @ wg_ref[...].astype(jnp.float32).T
        dacc_s[:, pl.ds(col, hb)] = dacc_s[:, pl.ds(col, hb)] \
            + (1.0 - m) * dh_tot + dh_new * u + drh * r
        dxur_ref[0] = dg.astype(dxur_ref.dtype)

    @pl.when(p == 2 * nb - 1)
    def _commit():
        dh_s[:] = dacc_s[:]

    @pl.when((i_rev == t_total - 1) & (p == 2 * nb - 1))
    def _flush():
        dh0_ref[...] = dacc_s[:].astype(dh0_ref.dtype)


def _bwd_call_blocked(ur_seq, c_seq, h_prev_seq, mask, w_gates, w_cand,
                      dy, hb=HBLOCK):
    t, b, hd = c_seq.shape
    nb = hd // hb
    kernel = functools.partial(_bwd_kernel_blocked, t_total=t, nb=nb,
                               hb=hb)
    rev = lambda i: t - 1 - i
    # both phases address hidden block p mod nb (phase A: p, phase B:
    # p−nb — same residue)
    both = lambda i, p: (rev(i), 0, p % nb)
    ph_a = lambda i, p: (rev(i), 0, jnp.minimum(p, nb - 1))
    ph_b = lambda i, p: (rev(i), 0, jnp.maximum(p - nb, 0))
    return pl.pallas_call(
        kernel,
        grid=(t, 2 * nb),
        in_specs=[
            pl.BlockSpec((1, b, 2 * hb), both),             # u|r gates
            pl.BlockSpec((1, b, hb), ph_a),                 # candidate
            pl.BlockSpec((1, b, hb), both),                 # H_{t-1}
            pl.BlockSpec((1, 1, b), lambda i, p: (rev(i), 0, 0)),
            pl.BlockSpec((hd, 2 * hb),
                         lambda i, p: (0, jnp.maximum(p - nb, 0))),
            pl.BlockSpec((hd, hb),
                         lambda i, p: (0, jnp.minimum(p, nb - 1))),
            pl.BlockSpec((1, b, hb), both),                 # dy
        ],
        out_specs=[
            pl.BlockSpec((1, b, 2 * hb), ph_b),             # dxur
            pl.BlockSpec((1, b, hb), ph_a),                 # dxc
            pl.BlockSpec((b, hd), lambda i, p: (0, 0)),     # dh0
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b, 2 * hd), jnp.float32),
            jax.ShapeDtypeStruct((t, b, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, hd), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((b, hd), jnp.float32),               # dh carry
            pltpu.VMEM((b, hd), jnp.float32),               # du staging
            pltpu.VMEM((b, hd), jnp.float32),               # drh accum
            pltpu.VMEM((b, hd), jnp.float32),               # dh accum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.GRU_BWD_BLOCKED,
    )(ur_seq, c_seq, h_prev_seq, mask, w_gates, w_cand, dy)


def _dw_kernel_blocked(hprev_ref, rh_ref, dg_ref, dcp_ref,
                       dwg_ref, dwc_ref):
    """Grid (nb, T), time innermost: both weight-gradient blocks stay
    resident in their output refs across the T loop (round-7 constant-
    block pattern), so at most [H, 3Hb] of weight gradient is ever in
    VMEM."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        dwg_ref[...] = jnp.zeros_like(dwg_ref)
        dwc_ref[...] = jnp.zeros_like(dwc_ref)

    h_prev = hprev_ref[0].astype(jnp.float32)           # [B, H]
    rh = rh_ref[0].astype(jnp.float32)                  # [B, H]
    dg = dg_ref[0].astype(jnp.float32)                  # [B, 2Hb]
    dcp = dcp_ref[0].astype(jnp.float32)                # [B, Hb]
    dwg_ref[...] = dwg_ref[...] + h_prev.T @ dg
    dwc_ref[...] = dwc_ref[...] + rh.T @ dcp


def _dw_call_blocked(h_prev_seq, rh_seq, dg_seq, dcp_seq, hb=HBLOCK):
    t, b, hd = h_prev_seq.shape
    nb = hd // hb
    return pl.pallas_call(
        _dw_kernel_blocked,
        grid=(nb, t),
        in_specs=[
            pl.BlockSpec((1, b, hd), lambda j, i: (i, 0, 0)),  # H_{t-1}
            pl.BlockSpec((1, b, hd), lambda j, i: (i, 0, 0)),  # r·h
            pl.BlockSpec((1, b, 2 * hb), lambda j, i: (i, 0, j)),  # dg
            pl.BlockSpec((1, b, hb), lambda j, i: (i, 0, j)),  # dc_pre
        ],
        out_specs=[
            pl.BlockSpec((hd, 2 * hb), lambda j, i: (0, j)),   # dw_gates
            pl.BlockSpec((hd, hb), lambda j, i: (0, j)),       # dw_cand
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hd, 2 * hd), jnp.float32),
            jax.ShapeDtypeStruct((hd, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.GRU_DW_BLOCKED,
    )(h_prev_seq, rh_seq, dg_seq, dcp_seq)


@jax.custom_vjp
def _gru_core_blocked(xur, xc, mask, w_gates, w_cand, h0):
    """Blocked-tier core: xur [T, B, 2H] and w_gates [H, 2H] arrive in
    block-gate layout, xc [T, B, H] / w_cand [H, H] natural (the
    wrapper splits and permutes; autodiff transposes the permutes
    around this boundary).  Returns the kept state sequence H
    [T, B, Hd] in f32."""
    h_seq, _ur, _c = _fwd_call_blocked(xur, xc, mask, w_gates, w_cand,
                                       h0)
    return h_seq


def _gru_core_blocked_fwd(xur, xc, mask, w_gates, w_cand, h0):
    h_seq, ur, c = _fwd_call_blocked(xur, xc, mask, w_gates, w_cand, h0)
    return h_seq, (ur, c, h_seq, mask, w_gates, w_cand, h0)


def _gru_core_blocked_bwd(res, dh_seq):
    ur, c, h_seq, mask, w_gates, w_cand, h0 = res
    hd = h_seq.shape[-1]
    h_prev_seq = jnp.concatenate([h0[None].astype(h_seq.dtype),
                                  h_seq[:-1]], axis=0)
    dxur, dxc, dh0 = _bwd_call_blocked(
        ur, c, h_prev_seq, mask, w_gates, w_cand, dh_seq)
    # r·h_prev for the w_cand gradient, recovered from the gate residue
    # (one XLA pass; the dW kernel streams it full-width per step)
    r_seq = _from_gate_blocks(ur, hd, 2)[..., hd:]
    dwg, dwc = _dw_call_blocked(h_prev_seq, r_seq * h_prev_seq,
                                dxur, dxc)
    return (dxur.astype(mask.dtype), dxc.astype(mask.dtype),
            jnp.zeros_like(mask), dwg, dwc, dh0)


_gru_core_blocked.defvjp(_gru_core_blocked_fwd, _gru_core_blocked_bwd)


def gru_fused_sequence_blocked(xw, mask, w_gates, w_cand, h0):
    """Blocked-tier entry — same batch-major contract as
    :func:`gru_fused_sequence`, dispatched by
    ``fused_tier(b, h) == "fused_blocked"``."""
    b, t, hd3 = xw.shape
    hd = hd3 // 3
    h0 = jnp.zeros((b, hd), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    xw_t = jnp.moveaxis(xw, 1, 0)
    xur_blk = _to_gate_blocks(xw_t[..., :2 * hd], hd, 2)
    xc = xw_t[..., 2 * hd:]
    wg_blk = _to_gate_blocks(w_gates.astype(jnp.float32), hd, 2)
    h_seq = _gru_core_blocked(
        xur_blk, xc,
        jnp.moveaxis(mask, 1, 0).astype(xw.dtype)[:, None, :],
        wg_blk, w_cand.astype(jnp.float32), h0)
    y = jnp.moveaxis(h_seq, 0, 1) * mask.astype(jnp.float32)[:, :, None]
    return y, h_seq[-1]
