"""Mamba-1's selective scan: the recurrence of a state-space mixer over
a whole prompt, as one Pallas kernel.

For one sequence of T positions and C channels, each channel keeps N
numbers of state.  With ``delta`` Δ [T, C], the input ``u`` [T, C],
``a`` A [N, C] (negative: A = −exp(A_log), channels on the lanes),
``b`` B and ``c`` C [T, N] and ``d`` D [C]::

    h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ u_t) ⊗ B_t,  h_{−1} = h0
    y_t = Σ_n h_t[n] ⊙ C_t[n] + D ⊙ u_t

all in float32.  A position with Δ = 0 leaves ``h`` exactly as it was
(exp(0) = 1, Δ·u = 0), so a row padded past its length with Δ = 0 ends
holding the state after its own last token.

:func:`selective_scan` is the whole prompt: on the chip the kernel
(``name=ssm_scan``), whose grid runs over rows and blocks of channels
(parallel) and over chunks of time (in order), the state of its block
carried in VMEM from one chunk to the next and in registers from one
position to the next, so nothing T×C×N wide reaches HBM (at T =
16,384, C = 5,120, N = 16 it would be 5.4 GB).  Channels lie on the
lanes and N on the sublanes: a block's state is ``[N, 1024]``
float32, sixteen vregs at N = 16.  B and C reach the kernel eight
positions to an ``[N, 8]`` tile, so a position's B is a column that
broadcasts across the lanes.  Off the chip a ``lax.scan`` over positions computes the
same (:func:`_scan_xla`), and :func:`ssm_step` is one position, which
the scan and the decode step both take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.device import is_tpu, pallas_interpret
from . import kernels as K

#: positions a grid step: the state crosses from one chunk to the next
#: in VMEM scratch
CHUNK = 256
#: channels a grid step at most (a whole multiple of 128 lanes that
#: divides C; C itself where it is narrower).  On the chip at 5,120
#: channels (PERF.md §6, PR 37): 16,384 positions take 7.59 / 5.12 /
#: 4.57 ms at 256 / 512 / 1024, whatever the chunk (256 or 512)
CHANNELS = 1024
#: positions whose B and C arrive as one [N, GROUP] tile
GROUP = 8


def ssm_step(h, u, delta, a, b, c, d):
    """One position of every row: ``h`` [R, N, C], ``u`` and ``delta``
    [R, C], ``b`` and ``c`` [R, N] → (the new state, ``y`` [R, C]).
    Elementwise and summed in float32 (no matrix unit)."""
    h = jnp.exp(delta[:, None, :] * a) * h \
        + (delta * u)[:, None, :] * b[:, :, None]
    return h, jnp.sum(h * c[:, :, None], axis=1) + d * u


def _scan_xla(u, delta, a, b, c, d, h0):
    """The scan as ``lax.scan`` over positions (off the chip)."""
    def one(h, xs):
        u_t, dt_t, b_t, c_t = xs
        return ssm_step(h, u_t, dt_t, a, b_t, c_t, d)

    first = lambda x: jnp.moveaxis(x, 1, 0)
    h, y = jax.lax.scan(one, h0, tuple(map(first, (u, delta, b, c))))
    return jnp.moveaxis(y, 0, 1), h


def _channel_block(ch: int) -> int:
    if ch <= CHANNELS:
        return ch
    return next((w for w in range(CHANNELS, 0, -128) if ch % w == 0), ch)


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
                 y_ref, h_ref, carry):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        carry[...] = h0_ref[0]

    a = a_ref[...]                                   # [N, bc]
    d = d_ref[...]                                   # [1, bc]

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        dt = dt_ref[0, pl.ds(t0, GROUP), :]          # [GROUP, bc]
        u = u_ref[0, pl.ds(t0, GROUP), :]
        bt, ct = b_ref[0, g], c_ref[0, g]            # [N, GROUP]
        du = dt * u
        rows = []
        for s in range(GROUP):
            h = jnp.exp(dt[s:s + 1] * a) * h + du[s:s + 1] * bt[:, s:s + 1]
            rows.append(jnp.sum(h * ct[:, s:s + 1], axis=0, keepdims=True))
        y_ref[0, pl.ds(t0, GROUP), :] = jnp.concatenate(rows, axis=0) \
            + d * u
        return h

    h = jax.lax.fori_loop(0, dt_ref.shape[1] // GROUP, group, carry[...])
    carry[...] = h

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        h_ref[0] = h


def _scan_pallas(u, delta, a, b, c, d, h0):
    rows, t, ch = u.shape
    n = a.shape[0]
    tc = min(CHUNK, -(-t // GROUP) * GROUP)
    tp = -(-t // tc) * tc
    if tp != t:
        # Δ = 0 past the end leaves the state as it was
        pad = lambda x: jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
        u, delta, b, c = map(pad, (u, delta, b, c))
    # B and C eight positions to an [N, 8] tile: a position's column
    tiles = lambda x: x.reshape(rows, tp // GROUP, GROUP, n).swapaxes(2, 3)
    bc = _channel_block(ch)
    seq = pl.BlockSpec((1, tc, bc), lambda i, j, k: (i, k, j))
    col = pl.BlockSpec((1, tc // GROUP, n, GROUP),
                       lambda i, j, k: (i, k, 0, 0))
    state = pl.BlockSpec((1, n, bc), lambda i, j, k: (i, 0, j))
    y, h = pl.pallas_call(
        _scan_kernel,
        grid=(rows, ch // bc, tp // tc),
        in_specs=[seq, seq, pl.BlockSpec((n, bc), lambda i, j, k: (0, j)),
                  col, col, pl.BlockSpec((1, bc), lambda i, j, k: (0, j)),
                  state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((rows, tp, ch), jnp.float32),
                   jax.ShapeDtypeStruct((rows, n, ch), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name=K.SSM_SCAN,
    )(u, delta, a, tiles(b), tiles(c), d.reshape(1, ch), h0)
    return y[:, :t], h


def selective_scan(u, delta, a, b, c, d, h0, *, impl=None):
    """The scan over whole rows: ``u``, ``delta`` [R, T, C], ``a``
    [N, C], ``b``, ``c`` [R, T, N], ``d`` [C], ``h0`` [R, N, C] → (``y``
    [R, T, C], the state after position T − 1 [R, N, C]), float32.
    ``impl``: ``pallas`` (the kernel; interpreted off the chip) or
    ``xla``; by default the kernel on the chip and ``xla`` elsewhere.
    Counts the kernel's work: 7 FLOPs a (position, channel, state
    number), the exp as one, and 3 a (position, channel); operands and
    results once."""
    f32 = lambda x: x.astype(jnp.float32)
    u, delta, a, b, c, d, h0 = map(f32, (u, delta, a, b, c, d, h0))
    if (impl or ("pallas" if is_tpu() else "xla")) == "xla":
        return _scan_xla(u, delta, a, b, c, d, h0)
    rows, t, ch = u.shape
    n = a.shape[0]
    K.record_kernel_work(
        K.SSM_SCAN, rows * t * ch * (7.0 * n + 3.0),
        (u, delta, a, b, c, d, h0),
        (jax.ShapeDtypeStruct(u.shape, jnp.float32), h0))
    return _scan_pallas(u, delta, a, b, c, d, h0)
