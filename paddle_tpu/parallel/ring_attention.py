"""Long-context attention: ring attention and Ulysses-style all-to-all
sequence parallelism over the device mesh.

The reference (pre-transformer) handles long sequences only by
variable-length batching (SURVEY §5 long-context); this framework makes
sequence/context parallelism first-class for TPU scale:

- :func:`ring_attention` — q/k/v sharded on the sequence dim over a mesh
  axis; each step computes a flash-style streaming block (running max +
  log-sum-exp accumulation) against the resident k/v shard, then rotates
  k/v around the ring with ``lax.ppermute`` so comms ride ICI and overlap
  with the matmuls.  Memory per chip is O(T/P); exact (not approximate).
- :func:`ulysses_attention` — ``all_to_all`` re-shards from sequence-
  parallel to head-parallel, runs dense local attention, and re-shards
  back (DeepSpeed-Ulysses pattern); cheaper for moderate T with many
  heads.

Both are pure jax and run under ``shard_map`` on any mesh — tested on the
8-device CPU mesh, identical math on a TPU pod slice.  For the
single-chip hot path, :func:`paddle_tpu.ops.pallas_attention.
flash_attention` is the Pallas kernel version of the same blockwise
math (8.4× the dense formulation at T=2048 on v5e); the ring/Ulysses
bodies keep the pure-jax formulation because their backward
differentiates through the scan, which Pallas calls do not support
without a ring-level custom VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import enforce

NEG_INF = -1e30


def _as_varying(x, axis_name):
    """Type a replicated value as device-varying over ``axis_name`` so a
    scan carry matches its (idx-dependent) updated value under
    shard_map."""
    return lax.pcast(x, (axis_name,), to="varying")


def _block_attn(q, k, v, m_prev, l_prev, o_prev, mask):
    """One flash-attention block update.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; mask: [Tq, Tk] additive or None.
    Carries the running max ``m``, normalizer ``l`` and unnormalized
    output ``o`` (all fp32).
    """
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # ptpu: lint-ok[PT-DTYPE] fp32-by-design: flash-attention scores
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / jnp.sqrt(q.shape[-1])
    if mask is not None:
        scores = scores + mask[None, None, :, :]
    m_cur = jnp.max(scores, axis=-1)                       # [B, H, Tq]
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (max = -inf)
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    l_cur = jnp.sum(p, axis=-1)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                      jnp.exp(m_prev - m_safe))
    l_new = alpha * l_prev + l_cur
    # ptpu: lint-ok[PT-DTYPE] fp32-by-design flash-attention accumulator
    o_new = alpha[..., None] * o_prev + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vf)
    return m_new, l_new, o_new


def _finalize(m, l, o, dtype):
    out = o / jnp.maximum(l, 1e-20)[..., None]             # [B, H, Tq, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(dtype)  # [B, Tq, H, D]


def _local_ring(q, k, v, axis_name: str, causal: bool):
    """Per-shard body under shard_map: q/k/v are the local sequence
    blocks [B, Tl, H, D]."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, tl, h, d = q.shape
    # initial carries must be typed as device-varying for the scan carry
    # to match the (idx-dependent) updated values under shard_map
    m0 = _as_varying(jnp.full((b, h, tl), NEG_INF, jnp.float32),
                     axis_name)
    l0 = _as_varying(jnp.zeros((b, h, tl), jnp.float32), axis_name)
    o0 = _as_varying(jnp.zeros((b, h, tl, d), jnp.float32), axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    pos_q = idx * tl + jnp.arange(tl)

    def step(carry, r):
        k_r, v_r, m, l, o = carry
        # k_r currently holds the block of ring-source (idx - r) mod n
        src = (idx - r) % n
        if causal:
            pos_k = src * tl + jnp.arange(tl)
            mask = jnp.where(pos_q[:, None] >= pos_k[None, :], 0.0,
                             NEG_INF)
        else:
            mask = None
        m, l, o = _block_attn(q, k_r, v_r, m, l, o, mask)
        k_r = lax.ppermute(k_r, axis_name, perm)
        v_r = lax.ppermute(v_r, axis_name, perm)
        return (k_r, v_r, m, l, o), None

    (k_f, v_f, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0),
                                      jnp.arange(n))
    return _finalize(m, l, o, q.dtype)


def ring_attention(q, k, v, mesh: Mesh, axis: str = "data",
                   causal: bool = False):
    """Exact attention over sequences sharded on ``axis``.

    q/k/v: [B, T, H, D] with T divisible by the axis size.  Returns
    [B, T, H, D] with the same sharding.
    """
    enforce(q.shape[1] % mesh.shape[axis] == 0,
            f"T={q.shape[1]} not divisible by mesh axis {axis}")
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(_local_ring, axis_name=axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _local_ulysses(q, k, v, axis_name: str, causal: bool, t_total: int):
    """all_to_all: [B, T/P, H, D] → [B, T, H/P, D], dense attention,
    back."""
    def seq2head(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    mask = None
    if causal:
        pos = jnp.arange(t_total)
        mask = jnp.where(pos[:, None] >= pos[None, :], 0.0, NEG_INF)
    b, t, h, d = qh.shape
    m0 = jnp.full((b, h, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    m, l, o = _block_attn(qh, kh, vh, m0, l0, o0, mask)
    return head2seq(_finalize(m, l, o, q.dtype))


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "data",
                      causal: bool = False):
    """Sequence-parallel attention via head re-sharding (all-to-all).

    Heads must be divisible by the axis size.
    """
    p = mesh.shape[axis]
    enforce(q.shape[2] % p == 0,
            f"H={q.shape[2]} not divisible by mesh axis {axis}")
    enforce(q.shape[1] % p == 0,
            f"T={q.shape[1]} not divisible by mesh axis {axis}")
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(_local_ulysses, axis_name=axis, causal=causal,
                          t_total=q.shape[1]),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def full_attention(q, k, v, causal: bool = False):
    """Single-device reference: softmax(q·kᵀ/√d)·v."""
    # ptpu: lint-ok[PT-DTYPE] fp32-by-design reference implementation
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(q.shape[-1])
    if causal:
        t = q.shape[1]
        mask = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :],
                         0.0, NEG_INF)
        scores = scores + mask[None, None]
    w = jax.nn.softmax(scores, axis=-1)
    # ptpu: lint-ok[PT-DTYPE] fp32-by-design reference implementation
    out = jnp.einsum("bhqk,bkhd->bhqd", w, v.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
