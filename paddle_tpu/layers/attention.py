"""Attention-family layers backed by the Pallas flash-attention kernel.

The reference pattern for a hand kernel is kernel → layer → config
(``paddle/cuda/src/hl_cuda_lstm.cu`` → ``LstmLayer`` → DSL
``lstmemory``); this module is the same wiring for the repo's flash
attention (:mod:`paddle_tpu.ops.pallas_attention`): the kernel is
reachable from a config file via ``scaled_dot_product_attention`` /
``multi_head_attention``, with ``layer_norm`` and ``position_embedding``
alongside so a full transformer block can be declared in the v1/v2 DSL.

These three types go beyond the 2017 reference's layer set (it predates
transformers) — they are the TPU-era counterpart of what ``lstmemory``
was to its era: the hot-path sequence mixer, hand-kernelled.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..config.model_config import ParameterConfig
from ..core.device import batch_local, local_rows
from ..core.dtypes import current_policy, record_op_precision
from ..core.sequence import SequenceBatch, like, value_of
from ..ops.pallas_attention import (flash_attention,
                                    flash_attention_packed,
                                    packed_tileable,
                                    record_attention_dispatch,
                                    segments_from_lengths)
from ..utils import enforce
from .base import Layer, register_layer


def _seq_parts(x):
    """(data [B, T, D], lengths [B] or None) from a layer input."""
    if isinstance(x, SequenceBatch):
        return x.data, x.length
    return value_of(x), None


@register_layer("scaled_dot_product_attention", "multi_head_attention",
                "flash_attention")
class MultiHeadAttentionLayer(Layer):
    """Multi-head scaled-dot-product attention over padded sequences.

    One input = self-attention (a packed [D_in, 3·size] q/k/v projection
    — one MXU matmul instead of three); three inputs = (query, key,
    value) cross-attention with per-input projections.  An output
    projection ``_{name}.wo`` [size, size] merges the heads; bias (if
    any) is added after it.  Attrs: ``num_heads`` (must divide size),
    ``causal``, ``block_q``/``block_k`` (Pallas tile sizes).

    Padded keys are masked inside the kernel via the scalar-prefetched
    lengths of the key sequence; queries keep their own lengths on the
    output SequenceBatch.

    ``packed=True`` (self-attention only): the padded batch is packed
    into ONE ``[1, B·T]`` token axis with per-token segment ids derived
    from the sequence lengths, and attention runs through
    :func:`flash_attention_packed` — padding and cross-sequence blocks
    do zero work (block-sparse path: not even DMA).  Padding positions
    of the output are exact zeros (they were arbitrary garbage on the
    padded path; both are masked downstream).  The
    ``--attention_packing=false`` kill switch makes the layer ignore
    the attr and run the exact padded per-row lowering.
    """

    def param_specs(self):
        size = self.conf.size
        heads = self.conf.attrs.get("num_heads", 1)
        enforce(size % heads == 0,
                f"attention size {size} not divisible by num_heads {heads}")
        ins = self.conf.inputs
        enforce(len(ins) in (1, 3),
                "attention takes 1 input (self) or 3 (q, k, v), got "
                f"{len(ins)}")
        specs = []
        if len(ins) == 1:
            din = self.model.find_size(ins[0].input_layer_name)
            specs.append(self._weight_spec(0, (din, 3 * size),
                                           initial_smart=True))
        else:
            for i, inp in enumerate(ins):
                din = self.model.find_size(inp.input_layer_name)
                specs.append(self._weight_spec(i, (din, size),
                                               initial_smart=True))
        specs.append(ParameterConfig(
            name=f"_{self.name}.wo", size=size * size, dims=[size, size],
            initial_smart=True))
        if self.conf.with_bias:
            specs.append(self._bias_spec((size,)))
        return specs

    def forward(self, params, inputs, ctx):
        size = self.conf.size
        heads = self.conf.attrs.get("num_heads", 1)
        dh = size // heads
        # policy compute dtype for the projections AND the kernel's
        # q/k/v: without the explicit cast a bf16 activation against an
        # fp32 weight silently PROMOTES the matmul to fp32 (jnp
        # promotion), so the fused tier never saw bf16 inputs.  The
        # flash kernel accumulates in f32 internally regardless.
        pol = current_policy()
        record_op_precision("attention")
        cd = pol.compute_dtype
        if len(inputs) == 1:
            x, q_len = _seq_parts(inputs[0])
            qkv = x.astype(cd) @ params[self.weight_name(0)].astype(cd)
            q, k, v = jnp.split(qkv, 3, axis=-1)   # [B, T, 3·size]
            kv_len = q_len
        else:
            xq, q_len = _seq_parts(inputs[0])
            xk, kv_len = _seq_parts(inputs[1])
            xv, v_len = _seq_parts(inputs[2])
            del v_len  # value lengths follow the key sequence
            q = xq.astype(cd) @ params[self.weight_name(0)].astype(cd)
            k = xk.astype(cd) @ params[self.weight_name(1)].astype(cd)
            v = xv.astype(cd) @ params[self.weight_name(2)].astype(cd)

        b, tq = q.shape[0], q.shape[1]
        tk = k.shape[1]
        causal = bool(self.conf.attrs.get("causal", False))
        block_q = int(self.conf.attrs.get("block_q", 512))
        block_k = int(self.conf.attrs.get("block_k", 512))
        packed = bool(self.conf.attrs.get("packed", False))
        # packed blocks clamp to the slot width (one row's T) so the
        # static cross-row compaction stays usable when T < block
        pbq, pbk = min(block_q, tq), min(block_k, tq)
        if packed:
            from ..utils import FLAGS
            enforce(len(inputs) == 1,
                    "packed attention requires self-attention "
                    f"(1 input), layer {self.name} has {len(inputs)}")
            if not FLAGS.attention_packing:
                # kill switch: ignore the attr, run the exact padded
                # per-row lowering below
                record_attention_dispatch(
                    "unpacked", "kill_switch:attention_packing")
                packed = False
            elif not FLAGS.flash_block_sparse or not FLAGS.flash_kernel:
                # the packed kernel IS the block-sparse pair grid; with
                # it (or the flash kernel) disabled, the honest revert
                # is the padded per-row lowering — the op-level dense
                # fallback over the flattened [1, B·T] axis would build
                # an O((B·T)²) score matrix
                flag = "flash_kernel" if not FLAGS.flash_kernel \
                    else "flash_block_sparse"
                record_attention_dispatch(
                    "unpacked", f"kill_switch:{flag}(packed)")
                packed = False
            elif not packed_tileable(local_rows(b) * tq, pbq, pbk):
                # the flattened axis would miss the Pallas tiling gate
                # and the op-level dense fallback on [1, B·T] builds an
                # O((B·T)²) score matrix — the padded per-row lowering
                # is the honest fallback here too
                record_attention_dispatch(
                    "unpacked", "untileable(packed flatten)")
                packed = False

        # the kernel call, over whatever rows it is handed: under a
        # multi-device mesh batch_local gives each device its own rows
        # (a packed flatten then packs one shard's rows, never across)
        def attend(q, k, v, kv_len):
            nb = q.shape[0]
            if packed:
                lengths = kv_len if kv_len is not None \
                    else jnp.full((nb,), tq, jnp.int32)
                seg = segments_from_lengths(lengths, nb, tq)
                pack = lambda a: a.reshape(1, nb * tq, heads, dh)
                # slot = T: rows occupy fixed T-token slots in the flat
                # layout, so cross-row block pairs are statically dead
                # and leave the kernel's iteration space entirely
                # (blocks clamped to the slot width above keep the hint
                # usable)
                o = flash_attention_packed(
                    pack(q), pack(k), pack(v), seg, causal, pbq, pbk, tq)
            else:
                split = lambda a, t: a.reshape(nb, t, heads, dh)
                o = flash_attention(
                    split(q, tq), split(k, tk), split(v, tk), kv_len,
                    causal, block_q, block_k)
            return o.reshape(nb, tq, size)

        out = batch_local(attend, (q, k, v, kv_len),
                          batch_in=(True, True, True, True),
                          batch_out=True)
        out = out @ params[f"_{self.name}.wo"].astype(cd)
        out = out.astype(pol.output_dtype)
        if self.conf.with_bias:
            out = out + params[self.bias_name()].astype(out.dtype)
        out = like(inputs[0], out) if isinstance(inputs[0], SequenceBatch) \
            else out
        return self.finalize(out, ctx)


@register_layer("layer_norm")
class LayerNormLayer(Layer):
    """Per-position layer normalization with learned gain/bias.

    Normalizes the last (feature) dim of [B, ..., size]; gain is the
    weight of input 0, bias the layer bias (on unless bias_attr=False).
    """

    def param_specs(self):
        specs = [self._weight_spec(0, (self.conf.size,), initial_mean=1.0,
                                   initial_std=0.0)]
        if self.conf.with_bias:
            specs.append(self._bias_spec((self.conf.size,)))
        return specs

    def forward(self, params, inputs, ctx):
        x = value_of(inputs[0])
        eps = self.conf.attrs.get("epsilon", 1e-5)
        xf = x.astype(jnp.float32)
        mu = xf.mean(axis=-1, keepdims=True)
        var = jnp.square(xf - mu).mean(axis=-1, keepdims=True)
        y = (xf - mu) / jnp.sqrt(var + eps)
        y = y * params[self.weight_name(0)]
        if self.conf.with_bias:
            y = y + params[self.bias_name()]
        return self.finalize(like(inputs[0], y.astype(x.dtype)), ctx)


@register_layer("position_embedding")
class PositionEmbeddingLayer(Layer):
    """Adds a learned [max_len, size] position table to a sequence input
    (sliced to the batch's T, so bucketed batches share one parameter)."""

    def param_specs(self):
        max_len = self.conf.attrs["max_len"]
        return [self._weight_spec(0, (max_len, self.conf.size),
                                  initial_std=0.01)]

    def forward(self, params, inputs, ctx):
        x = value_of(inputs[0])
        table = params[self.weight_name(0)]
        t = x.shape[1]
        enforce(t <= table.shape[0],
                f"sequence length {t} exceeds position_embedding max_len "
                f"{table.shape[0]}")
        out = x + table[:t][None, :, :].astype(x.dtype)
        return self.finalize(like(inputs[0], out), ctx)
