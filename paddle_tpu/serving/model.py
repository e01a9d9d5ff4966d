"""Decoder-only transformer for the serving stack — the model half of
the continuous-batching server (``serving/server.py``).

One decoder: what a layer computes is read from the **layer plan** of
:class:`DecoderConfig` (window, full or latent attention or a gated
short convolution, rotary positions, q/k norms, an output gate, four
norms a block; a GELU, SwiGLU or routed-expert feed-forward), query
heads may share K/V heads, and the weights and the caches are kept in
float32 or bfloat16.  The empty plan is the dense block this module
started as.  A plan of ``latent`` layers caches ONE compressed row a
token and layer in one pool where the others cache per-head K and V in
two; the pools hold the layers that attend and no others.  A ``conv``
or ``mamba`` layer caches nothing a token: it keeps a fixed-size state
a **sequence**, whatever its length (``conv_taps - 1`` rows of its
convolution's input, and a ``mamba`` layer its scan's state besides),
in caches beside the pools, in the sequence's **slot**: a step takes
each row's slot as an input (where its caller names none, the number of
the row's first page), and a slot holds one sequence from its prefill
to its end (:meth:`DecoderModel.new_pools` says what the plan needs,
and the steps take and donate whatever it gave).

The two entry points mirror the two serving kernels from PR 14/15:

- :meth:`DecoderModel.prefill` runs a batch of mixed-length prompts in
  ONE ``flash_attention_packed`` launch per layer ([B, T] rows flattened
  to one packed [1, B*T] row with ``segments_from_lengths``), writes
  every prompt token's K/V into the rows' KV pages via
  ``paged_kv_write``, and returns each row's first generated token;
- :meth:`DecoderModel.decode` advances a fixed-width decode batch one
  token with ``paged_decode_attention`` over the shared page pool —
  inactive (padded) slots carry a scratch page table, zero write count,
  and length 1, so the kernel touches no memory the slot does not own;
- :meth:`DecoderModel.block_step` is decode's counterpart for a model
  that generates by **diffusion over blocks** (``block_length`` > 0):
  each row's block of positions, masked where nothing is revealed yet,
  goes through the layers at once, writes its K/V at the block's place
  and attends its own block whole and every block before it; the step
  reveals the row's most confident masked positions on the device.  A
  row whose block is finished **commits** it in the same pass as the
  next block's first step: its tile holds the finished block, whose K/V
  it writes once more from its final ids, and the next block, all
  masked, under the block-causal mask.  Its prefill is block-causal;
- :meth:`DecoderModel.step_chunk` continues a prompt's prefill by one
  chunk of :data:`CHUNK` positions where every layer of the plan can
  (:func:`continues`: mamba layers from the state in the sequence's
  slot, full attention over the row's pages), with a decode step's rows
  riding in the same launch, one token each: every row goes through a
  layer's products at once, so the weights are read once for both.
  :meth:`DecoderModel.prefill` runs a prompt longer than a chunk so.

Both update the pools **in place**: a step donates the stacked
pools (:class:`KVPool` holds the one reference to each) and every layer
writes its rows into, and reads its pages out of, the stack as it lies
in memory, at the layer's page offset — no copy, slice or write-back of
a pool or of a layer's share of one (PERF.md §6, PR 28).

Each has a **launch** half (``launch_prefill`` / ``launch_decode``:
queue the program, return at once) and a **collect** half (wait, bring
back the token ids and the routed counts, nothing else); ``prefill`` and
``decode`` are the two in one.  A decode launch can take its rows' ids
from the decode launch before it while those are still on the device,
which is what lets the server keep one launch queued ahead of the host
(PERF.md §6, PR 30).  The logits stay a device array in every result.

Batch invariance is a load-bearing property, not an accident: every
per-row computation (matmuls, RMS norms, attention — the decode
kernel walks a row's own pages in the row's own order — ``argmax``
sampling) is row-independent and runs in the same
within-row reduction order regardless of batch width, which is what
lets the ``--serve_continuous`` kill switch promise byte-for-byte
identical tokens between batched-continuous and sequential
single-request serving (pinned in ``tests/test_serving_server.py``).

Artifacts: :func:`export_decoder` writes the version-2 weights-only
int8 layout of ``serving/export.py`` (same ``weights.npz`` schema, no
StableHLO module — the decode loop is live code) with
``"kind": "decoder"`` in the manifest; :meth:`DecoderModel.from_artifact`
loads it through the shared ``loader.read_manifest`` /
``loader.load_weight_entries`` path, int8 dequantization included.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..layers.beam_search import eos_frozen_logits
from ..observe.trace import span as _span
from ..ops import kernels as K
from ..ops import scopes as S
from ..ops.pallas_attention import (chunk_prefill_attention,
                                    flash_attention_packed,
                                    latent_decode_attention,
                                    paged_decode_attention, paged_kv_write,
                                    paged_row_write, segments_from_lengths)
from ..ops.pallas_moe import (SCORES, routed_experts, weight_einsum,
                              weight_matmul)
from ..ops.pallas_ssm import selective_scan, ssm_step
from ..utils import enforce
from . import export as _export
from . import loader as _loader


class DecoderConfig(NamedTuple):
    """Shape of the served decoder (all sizes static — one compiled
    prefill per (B, T) bucket, one compiled decode step per batch
    width).

    ``plan`` is the **layer plan**, the one selector of what a layer
    computes: one entry a layer, ``"<attention>/<feed-forward>"``, each
    side a ``+``-joined set of words.

    - attention: ``full``, ``window`` (a query sees the ``window``
      newest positions up to its own), ``latent`` or ``conv`` (both
      below), then any of ``rope`` (rotary positions on q and k at
      ``rope_theta``: lane j turns with lane j + D/2, or with
      ``rope_interleave`` lane 2j with lane 2j + 1),
      ``qknorm`` (RMS norm of every q and k head over ``head_dim``),
      ``gate`` (the attention output times ``sigmoid(x·Wg)`` before
      ``wo``) and ``postnorm`` (each sub-block's output is RMS-normed
      before it joins the residual stream: four norms a block);
    - feed-forward: ``gelu`` (``w1``, ``w2``, tanh-GELU), ``swiglu``
      (``w_gate``, ``w_up``, ``w_down`` of width ``ffn``) or ``routed``
      (``experts`` SwiGLU experts of width ``expert_ffn``, ``top_k`` a
      token by sigmoid score, ``ops/pallas_moe.py``), the last with
      ``shared`` for one more expert that every token passes.

    ``latent`` is multi-head latent attention, full and causal, in every
    layer of a plan or in none.  The query comes through a norm at rank
    ``q_rank``; keys and values come from ONE row a token, ``kv_rank``
    normed numbers ``c`` and a key part of ``rope_dim`` lanes that all
    heads share and that alone carries the position (``rope``).  A head
    has ``nope_dim`` + ``rope_dim`` query/key lanes and ``v_dim`` value
    lanes; the cache holds the row, never per-head K/V.  Prefill expands
    K and V per head from the row (``w_ukv``) and runs the packed
    kernel; decode folds ``w_ukv`` into the query and the output and
    attends the rows themselves (``ops/pallas_attention.py::
    latent_decode_attention``): the same function of the same weights.

    ``conv`` is a gated short convolution in the place of attention,
    alone on its side of the entry and mixed freely with ``full`` and
    ``window`` layers.  ``in_proj`` gives a token three vectors B, C, u
    of ``dim`` lanes (in this order); z = B ⊙ u; c_t = Σ_j taps[:, j] ⊙
    z_{t - (conv_taps - 1) + j}, causal and per lane, with zeros before
    a sequence's own start; the layer adds (C ⊙ c)·``out_proj``.  What
    it keeps of a sequence is the newest ``conv_taps - 1`` z, in the
    storage dtype (prefill writes them, a decode step rolls them), and
    the sum is float32 over z as stored.

    ``mamba`` is Mamba-1's selective state-space mixer, alone on its
    side like ``conv``.  ``in_proj`` gives u and z of ``ssm_inner``
    lanes (in this order); u' = silu(causal depthwise convolution of u
    over ``conv_taps`` + ``conv_bias``); ``x_proj`` of u' gives δ, B, C
    (``dt_rank``, ``ssm_state``, ``ssm_state`` lanes), each RMS-normed
    with a gain (``dt_norm``, ``b_norm``, ``c_norm``); Δ =
    softplus(δ·``dt_proj`` + ``dt_bias``), A = −exp(``A_log``), and the
    scan h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ u'_t) ⊗ B_t, y_t =
    h_t·C_t + D ⊙ u'_t runs in float32 (``ops/pallas_ssm.py``); the
    layer adds (y ⊙ silu(z))·``out_proj``.  What it keeps of a sequence
    is the newest ``conv_taps - 1`` u as stored and ``h``
    [``ssm_state``, ``ssm_inner``] in float32.

    The empty plan is the default one, ``full/gelu`` in every layer
    (with ``pos_embed`` the decoder this module started as).  ``heads``
    query heads share ``kv_heads`` K/V heads of ``head_dim`` (0: one
    K/V head a query head, ``dim // heads`` wide).  ``storage`` is the
    dtype of the matrices, the embedding and the K/V pool; matrix
    products take operands in it and accumulate in float32, and norms,
    router scores, softmax and the residual stream stay float32.
    ``tied_head``: the logits are the final norm's output times the
    embedding's transpose, and there is no ``lm_head``.
    ``route_score``: how a routed layer scores its experts, ``sigmoid``
    or ``softmax`` over all of them (``ops/pallas_moe.py::route``).

    ``block_length`` > 0 (a power of two) makes the model generate by
    diffusion over blocks of that many positions, counted from position
    0: position i sees position j iff ⌊j/B⌋ ≤ ⌊i/B⌋, in the prefill and
    in every step; the logits at a position predict the token AT it.
    A block starts with its positions masked (the embedding row
    ``mask_id``; which positions are masked is tracked by position, an
    id of −1, never by id) but for prompt ids it carries, and each of
    ``denoise_steps`` steps reveals a share of the masked positions
    (:func:`reveal_schedule`): those whose top softmax probability is
    highest, each set to its argmax.  Every layer is ``full`` with per-
    head K/V.  0 is generation one token a row a step: every other
    configuration."""
    vocab: int
    dim: int
    heads: int
    layers: int
    ffn: int
    max_context: int = 256
    eos_id: int = 1
    plan: Tuple[str, ...] = ()
    kv_heads: int = 0
    head_dim: int = 0
    window: int = 0
    experts: int = 0
    top_k: int = 0
    expert_ffn: int = 0
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    embed_scale: float = 1.0
    pos_embed: bool = True
    storage: str = "float32"
    rope_interleave: bool = False
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    conv_taps: int = 3
    ssm_inner: int = 0
    ssm_state: int = 0
    dt_rank: int = 0
    tied_head: bool = False
    route_score: str = "sigmoid"
    block_length: int = 0
    denoise_steps: int = 0
    mask_id: int = -1


KINDS = frozenset({"full", "window", "latent", "conv", "mamba"})
#: the mixers that keep a fixed-size state a sequence in its slot
STATE_KINDS = ("conv", "mamba")
ATTENTION_WORDS = KINDS | {"rope", "qknorm", "gate", "postnorm"}
FFN_WORDS = frozenset({"gelu", "swiglu", "routed", "shared"})

#: prompt positions a chunk launch takes, where the plan can continue a
#: prefill from what a sequence holds (:func:`continues`).  Every
#: decoding row rides in each chunk launch, one token a row, so the
#: weights a decode step reads alone are read once for both.  From the
#: Jamba cell's closed loop (PERF.md §6): a request costs the
#: device 0.383 s of prefill and 0.166 s of decode; with decode riding,
#: the device spends its time on chunks, ≈ 2.3–2.5 requests/s × 9,830
#: prompt tokens ÷ c launches a second, and the output is the 9–11
#: rows carried times that.  c = 512 gives ≈ 45 launches/s, room for
#: 400–490 tokens/s; c = 1,024 caps it at ≈ 23 × 11 = 253, under the
#: 291 of whole-prompt launches; c = 256 puts a launch's products at
#: the chip's ridge (197 T / 819 G ≈ 240 FLOP a weight byte, a product
#: of c rows doing c) and its 7.4 ms of weight reads would stop hiding
#: under the compute.
CHUNK = 512


def continues(cfg: DecoderConfig) -> bool:
    """Can every layer of the plan continue a prefill from what a
    sequence already holds (its pages, its slot)?  A ``mamba`` layer
    (its scan's state and the convolution's newest inputs) and a
    ``full`` layer with per-head K/V can; so can the dense feed-forward,
    row by row.  A window, a latent row, a conv or routed layer and a
    model that generates by blocks are not taught to."""
    return not cfg.block_length and all(
        attn & KINDS <= {"full", "mamba"} and ffn & {"gelu", "swiglu"}
        and "routed" not in ffn for attn, ffn in layer_plan(cfg))


@functools.lru_cache(maxsize=None)
def layer_plan(cfg: DecoderConfig
               ) -> Tuple[Tuple[frozenset, frozenset], ...]:
    """``cfg.plan`` parsed and checked: one (attention words,
    feed-forward words) pair a layer."""
    plan = cfg.plan or ("full/gelu",) * cfg.layers
    enforce(len(plan) == cfg.layers,
            f"the layer plan has {len(plan)} entries for {cfg.layers} "
            "layers")
    out = []
    for entry in plan:
        attn, _, ffn = entry.partition("/")
        attn, ffn = frozenset(attn.split("+")), frozenset(ffn.split("+"))
        enforce(attn <= ATTENTION_WORDS and len(attn & KINDS) == 1
                and ("latent" not in attn or attn <= {"latent", "rope"})
                and all(k not in attn or attn == {k} for k in STATE_KINDS),
                f"plan entry {entry!r}: attention is full, window or "
                f"latent[+rope], then any of {sorted(ATTENTION_WORDS)}; "
                "or conv alone, or mamba alone")
        enforce(ffn <= FFN_WORDS
                and len(ffn & {"gelu", "swiglu", "routed"}) == 1
                and ("shared" not in ffn or "routed" in ffn),
                f"plan entry {entry!r}: the feed-forward is gelu, swiglu "
                "or routed[+shared]")
        enforce("window" not in attn or cfg.window > 0,
                f"plan entry {entry!r} needs window > 0")
        enforce("routed" not in ffn
                or (cfg.experts >= cfg.top_k >= 1 and cfg.expert_ffn > 0),
                f"plan entry {entry!r} needs experts >= top_k >= 1 and "
                "expert_ffn > 0")
        out.append((attn, ffn))
    enforce(all(attn.isdisjoint(STATE_KINDS) for attn, _ in out)
            or cfg.conv_taps >= 2,
            "a conv or mamba layer needs conv_taps >= 2")
    enforce(all("mamba" not in attn for attn, _ in out)
            or min(cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank) > 0,
            "a mamba layer needs ssm_inner, ssm_state and dt_rank > 0")
    latent = sum("latent" in attn for attn, _ in out)
    enforce(latent in (0, cfg.layers),
            "a plan is latent in every layer or in none: one kind of "
            "cache row a model")
    enforce(not latent or min(cfg.q_rank, cfg.kv_rank, cfg.nope_dim,
                              cfg.v_dim) > 0 and cfg.rope_dim % 2 == 0,
            "a latent plan needs q_rank, kv_rank, nope_dim, v_dim > 0 "
            "and an even rope_dim")
    if cfg.head_dim == 0 and not latent:
        enforce(cfg.dim % cfg.heads == 0,
                f"dim {cfg.dim} not divisible by heads {cfg.heads}")
    enforce(cfg.heads % kv_heads(cfg) == 0,
            f"{cfg.heads} query heads do not divide over "
            f"{kv_heads(cfg)} K/V heads")
    enforce(cfg.storage in ("float32", "bfloat16"),
            f"storage {cfg.storage!r} is neither float32 nor bfloat16")
    enforce(cfg.route_score in SCORES,
            f"route_score {cfg.route_score!r} is none of {sorted(SCORES)}")
    b = cfg.block_length
    enforce(b == 0 or (b >= 2 and b & (b - 1) == 0
                       and cfg.denoise_steps >= 1
                       and 0 <= cfg.mask_id < cfg.vocab
                       and all(attn & KINDS == {"full"} for attn, _ in out)),
            f"block_length {b}: a power of two of 2 or more, with "
            "denoise_steps >= 1, a mask_id in the vocabulary and full "
            "attention in every layer")
    return tuple(out)


def kv_heads(cfg: DecoderConfig) -> int:
    return cfg.kv_heads or cfg.heads


def head_dim(cfg: DecoderConfig) -> int:
    return cfg.head_dim or cfg.dim // cfg.heads


def leaf_shapes(cfg: DecoderConfig) -> Dict[str, Tuple[int, ...]]:
    """Every weight of the decoder by its artifact name → its shape:
    ``embed``, ``pos_embed`` (if ``cfg.pos_embed``), ``ln_f``,
    ``lm_head`` (unless ``cfg.tied_head``) and per layer
    ``l{i}.{ln1,ln2,wq,wk,wv,wo}`` plus what its plan entry adds: ``w1 w2`` (gelu) | ``w_gate w_up w_down``
    (swiglu) | ``router router_bias e_gate e_up e_down`` (routed) and
    ``s_gate s_up s_down`` (shared); ``qn kn`` (qknorm), ``wg`` (gate),
    ``ln1p ln2p`` (postnorm).  A ``latent`` layer has ``w_dq q_ln w_uq
    w_dkv kv_ln w_ukv`` in the place of ``wq wk wv``, and its ``wo``
    takes ``heads · v_dim``; a ``conv`` layer has ``in_proj conv
    out_proj`` in the place of all four, a ``mamba`` layer ``in_proj
    conv conv_bias x_proj dt_norm b_norm c_norm dt_proj dt_bias A_log D
    out_proj``."""
    d, h, g = cfg.dim, cfg.heads, kv_heads(cfg)
    dh = 0 if cfg.kv_rank else head_dim(cfg)
    e, f = cfg.experts, cfg.expert_ffn
    out: Dict[str, Tuple[int, ...]] = {"embed": (cfg.vocab, d)}
    if cfg.pos_embed:
        out["pos_embed"] = (cfg.max_context, d)
    out["ln_f"] = (d,)
    if not cfg.tied_head:
        out["lm_head"] = (d, cfg.vocab)
    for i, (attn, ffn) in enumerate(layer_plan(cfg)):
        leaves = {"ln1": (d,), "ln2": (d,)}
        if "latent" in attn:
            r, dr = cfg.kv_rank, cfg.rope_dim
            leaves.update(
                w_dq=(d, cfg.q_rank), q_ln=(cfg.q_rank,),
                w_uq=(cfg.q_rank, h * (cfg.nope_dim + dr)),
                w_dkv=(d, r + dr), kv_ln=(r,),
                w_ukv=(r, h * (cfg.nope_dim + cfg.v_dim)),
                wo=(h * cfg.v_dim, d))
        elif "conv" in attn:
            leaves.update(in_proj=(d, 3 * d), conv=(d, cfg.conv_taps),
                          out_proj=(d, d))
        elif "mamba" in attn:
            di, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
            leaves.update(
                in_proj=(d, 2 * di), conv=(di, cfg.conv_taps),
                conv_bias=(di,), x_proj=(di, r + 2 * n), dt_norm=(r,),
                b_norm=(n,), c_norm=(n,), dt_proj=(r, di), dt_bias=(di,),
                A_log=(di, n), D=(di,), out_proj=(di, d))
        else:
            leaves.update(wq=(d, h * dh), wk=(d, g * dh), wv=(d, g * dh),
                          wo=(h * dh, d))
        if "gelu" in ffn:
            leaves.update(w1=(d, cfg.ffn), w2=(cfg.ffn, d))
        if "swiglu" in ffn:
            leaves.update(w_gate=(d, cfg.ffn), w_up=(d, cfg.ffn),
                          w_down=(cfg.ffn, d))
        if "routed" in ffn:
            leaves.update(router=(d, e), router_bias=(e,),
                          e_gate=(e, d, f), e_up=(e, d, f),
                          e_down=(e, f, d))
        if "shared" in ffn:
            leaves.update(s_gate=(d, f), s_up=(d, f), s_down=(f, d))
        if "qknorm" in attn:
            leaves.update(qn=(dh,), kn=(dh,))
        if "gate" in attn:
            leaves["wg"] = (d, h * dh)
        if "postnorm" in attn:
            leaves.update(ln1p=(d,), ln2p=(d,))
        for name, shape in leaves.items():
            out[f"l{i}.{name}"] = shape
    return out


def _stored_as(name: str, shape, cfg: DecoderConfig) -> str:
    """The dtype a leaf is kept in on the device: ``cfg.storage`` for
    the matrices and the embeddings; float32 for gains, the router and
    its bias (a score that rounds differently picks another expert), a
    conv or mamba layer's taps (its sum is float32) and a mamba layer's
    ``A_log`` (its scan is)."""
    if len(shape) < 2 or name.endswith((".router", ".conv", ".A_log")):
        return "float32"
    return cfg.storage


def init_decoder_params(cfg: DecoderConfig, seed: int = 0
                        ) -> Dict[str, np.ndarray]:
    """Random fp32 decoder weights (scaled normal init) under the
    artifact's names (:func:`leaf_shapes`): matrices N(0, 1/fan_in),
    the embedding N(0, 1), positions N(0, 0.02²), gains 1, the router's
    selection bias N(0, 0.1²), a conv layer's taps N(0, 1/taps); a mamba
    layer as Mamba inits it: ``A_log`` = log(1..N) a channel, ``D`` 1,
    ``dt_bias`` = softplus⁻¹(Δ₀) with Δ₀ log-uniform in [0.001, 0.1],
    ``conv_bias`` N(0, 0.1²)."""
    rng = np.random.default_rng(seed)
    p: Dict[str, np.ndarray] = {}
    for name, shape in leaf_shapes(cfg).items():
        if name.endswith(".dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), shape))
            p[name] = (dt + np.log(-np.expm1(-dt))).astype(np.float32)
            continue
        if name.endswith(".A_log"):
            p[name] = np.broadcast_to(np.log(np.arange(1, shape[1] + 1)),
                                      shape).astype(np.float32)
            continue
        if len(shape) == 1:
            p[name] = (0.1 * rng.standard_normal(shape)
                       if name.endswith(("router_bias", "conv_bias"))
                       else np.ones(shape)).astype(np.float32)
            continue
        z = rng.standard_normal(shape)
        if name == "embed":      # N(0, 1), rounded as it always was
            p[name] = (z / np.sqrt(cfg.vocab)).astype(np.float32) \
                * np.float32(np.sqrt(cfg.vocab))
        elif name == "pos_embed":
            p[name] = (0.02 * z).astype(np.float32)
        elif name.endswith(".conv"):
            p[name] = (z / np.sqrt(shape[-1])).astype(np.float32)
        else:
            p[name] = (z / np.sqrt(shape[-2])).astype(np.float32)
    return p


def _rms(x, g, eps=1e-6):
    return (x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)) * g


def _rope(x, pos, theta: float, interleave: bool = False):
    """Rotary positions: ``x`` [B, T, N, D] at ``pos`` [B, T]; lane
    j < D/2 turns with lane j + D/2 by ``pos · theta^(-2j/D)``.
    ``interleave``: the pairs that turn are lanes 2j and 2j + 1.  They
    are first brought to j and j + D/2 and stay there: a fixed
    permutation of the lanes, the same for a query and its key, which
    no score sees."""
    half = x.shape[-1] // 2
    if interleave:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, :, None, None] * inv    # [B,T,1,D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _embed(params, tokens, pos, cfg: DecoderConfig):
    """Token ids [B, T] at positions ``pos`` [B, T] → the float32
    residual stream [B, T, dim]."""
    x = params["embed"][tokens].astype(jnp.float32)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    if cfg.pos_embed:
        x = x + params["pos_embed"][
            jnp.clip(pos, 0, cfg.max_context - 1)].astype(jnp.float32)
    return x


def _qkv(x, pos, params, i, cfg: DecoderConfig, attn):
    """The layer's attention inputs from the residual stream: q
    [B, T, H, D], k and v [B, T, G, D], and the output gate [B, T, H·D]
    or None."""
    b, t, _ = x.shape
    h, g, dh = cfg.heads, kv_heads(cfg), head_dim(cfg)
    with jax.named_scope(S.MIXER_NORM.format(i)):
        xn = _rms(x, params[f"l{i}.ln1"], cfg.norm_eps)
    with jax.named_scope(S.QKV.format(i)):
        q = weight_matmul(xn, params[f"l{i}.wq"]).reshape(b, t, h, dh)
        k = weight_matmul(xn, params[f"l{i}.wk"]).reshape(b, t, g, dh)
        v = weight_matmul(xn, params[f"l{i}.wv"]).reshape(b, t, g, dh)
        if "qknorm" in attn:
            q = _rms(q, params[f"l{i}.qn"], cfg.norm_eps)
            k = _rms(k, params[f"l{i}.kn"], cfg.norm_eps)
        if "rope" in attn:
            q, k = (_rope(a, pos, cfg.rope_theta, cfg.rope_interleave)
                    for a in (q, k))
        gate = jax.nn.sigmoid(weight_matmul(xn, params[f"l{i}.wg"])) \
            if "gate" in attn else None
    return q, k, v, gate


def latent_row_width(cfg: DecoderConfig) -> int:
    """Lanes of a latent cache row: ``kv_rank + rope_dim`` numbers in
    whole tiles of 128 lanes (576 lie in 640).  The chip lays the array
    out so whatever width is declared, and a page's DMA cannot cut a row
    inside a tile; the lanes behind the numbers hold zeros."""
    return -(-(cfg.kv_rank + cfg.rope_dim) // 128) * 128


def _lanes(x, width: int):
    """``x`` with zeros behind its last axis up to ``width`` lanes."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent_qrow(x, pos, params, i, cfg: DecoderConfig, attn):
    """A latent layer's attention inputs from the stream: the position-
    free query part q_n [B, T, H, nope_dim], the rotated part q_r
    [B, T, H, rope_dim], and the token's **cache row** [B, T, W]: the
    normed latent ``c``, the one rotated key part every head shares, and
    zeros up to :func:`latent_row_width`, in the storage dtype."""
    b, t, _ = x.shape
    p = lambda leaf: params[f"l{i}.{leaf}"]
    r, dn = cfg.kv_rank, cfg.nope_dim
    with jax.named_scope(S.MIXER_NORM.format(i)):
        a = _rms(x, p("ln1"), cfg.norm_eps)
    with jax.named_scope(S.QKV.format(i)):
        cq = _rms(weight_matmul(a, p("w_dq")), p("q_ln"), cfg.norm_eps)
        q = weight_matmul(cq, p("w_uq")).reshape(b, t, cfg.heads, -1)
        ckr = weight_matmul(a, p("w_dkv"))
        c = _rms(ckr[..., :r], p("kv_ln"), cfg.norm_eps)
        q_r, k_r = q[..., dn:], ckr[:, :, None, r:]
        if "rope" in attn:
            q_r, k_r = (_rope(a, pos, cfg.rope_theta, cfg.rope_interleave)
                        for a in (q_r, k_r))
        row = _lanes(jnp.concatenate([c, k_r[:, :, 0]], axis=-1),
                     latent_row_width(cfg))
        return q[..., :dn], q_r, row.astype(cfg.storage)


def _latent_scale(cfg: DecoderConfig) -> float:
    return 1.0 / float(np.sqrt(cfg.nope_dim + cfg.rope_dim))


def _latent_up(params, i, cfg: DecoderConfig):
    """``w_ukv`` by head: [kv_rank, H, nope_dim + v_dim] (a free
    reshape); its leading ``nope_dim`` columns a head make keys of the
    latent, the others values."""
    return params[f"l{i}.w_ukv"].reshape(cfg.kv_rank, cfg.heads, -1)


def _latent_expanded(q_n, q_r, row, params, i, cfg: DecoderConfig,
                     segments, slot):
    """Attention of a whole prompt in the **expanded** form: every
    head's keys [k_n ; k_r] and values are made from the cache rows and
    go through the packed kernel (keys 192 wide, values 128, at the
    published sizes).  [B, T, …] → [B, T, H·v_dim] float32."""
    b, t, h, dn = q_n.shape
    r = cfg.kv_rank
    kv = weight_einsum("btr,rhn->bthn", row[..., :r],
                       _latent_up(params, i, cfg))
    k_r = jnp.broadcast_to(row[:, :, None, r:r + cfg.rope_dim],
                           (b, t, h, cfg.rope_dim))
    # the kernel multiplies what the pool stores
    q = jnp.concatenate([q_n, q_r], axis=-1).astype(row.dtype)
    k = jnp.concatenate([kv[..., :dn].astype(row.dtype), k_r], axis=-1)
    v = kv[..., dn:].astype(row.dtype)
    o = flash_attention_packed(
        q.reshape(1, b * t, h, -1), k.reshape(1, b * t, h, -1),
        v.reshape(1, b * t, h, -1), segments, causal=True, slot=slot)
    return o.reshape(b, t, h * cfg.v_dim).astype(jnp.float32)


def _latent_absorbed(q_n, q_r, pool, table, klen, params, i,
                     cfg: DecoderConfig):
    """Attention of one new token a row in the **absorbed** form: the
    key up-projection goes into the query (q̃_h = q_n,h·W_uk,hᵀ, as wide
    as the latent), the kernel attends the cache rows themselves, which
    are keys and, in their leading lanes, values, and the value
    up-projection goes onto what comes out.  [B, 1, …] → [B, 1,
    H·v_dim] float32."""
    b, _, h, dn = q_n.shape
    r = cfg.kv_rank
    up = _latent_up(params, i, cfg)
    q_abs = weight_einsum("bhn,rhn->bhr", q_n[:, 0], up[..., :dn])
    q = _lanes(jnp.concatenate([q_abs, q_r[:, 0]], axis=-1), pool.shape[-1])
    o = latent_decode_attention(q.astype(pool.dtype), pool, table, klen, r,
                                _latent_scale(cfg))
    o = weight_einsum("bhr,rhv->bhv", o, up[..., dn:])
    return o.reshape(b, 1, h * cfg.v_dim)


def _conv_gates(x, params, i, cfg: DecoderConfig):
    """A conv layer's inputs from the stream: z = B ⊙ u as the state
    stores it (the storage dtype, which prefill's sum and a later
    decode step's then both read) and the gate C, float32; B, C, u are
    the thirds of ``in_proj``'s result in this order."""
    xn = _rms(x, params[f"l{i}.ln1"], cfg.norm_eps)
    b, c, u = jnp.split(weight_matmul(xn, params[f"l{i}.in_proj"]), 3,
                        axis=-1)
    return (b * u).astype(cfg.storage), c


def _conv_window(window, taps):
    """Σ_j taps[:, j] ⊙ window[j] in float32: ``window`` holds the
    ``conv_taps`` z that end at the token, oldest first."""
    return sum(taps[:, j] * z.astype(jnp.float32)
               for j, z in enumerate(window))


def _conv_out(x, gate, c, params, i):
    return x + weight_matmul(gate * c, params[f"l{i}.out_proj"])


def _causal_windows(z, taps: int):
    """``z`` [B, T, W] with ``taps - 1`` zeros before each row's start
    and the ``taps`` shifted views whose sum is a causal convolution:
    (padded z, [the view ending at each position, oldest first])."""
    t = z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return zp, [zp[:, j:j + t] for j in range(taps)]


def _newest(zp, lengths, taps: int):
    """The ``taps - 1`` newest rows of each prompt in the padded ``zp``
    (zeros where the prompt is shorter): what a sequence keeps."""
    at = lengths[:, None] + jnp.arange(taps - 1)[None, :]
    return jnp.take_along_axis(zp, at[:, :, None], axis=1)


def _keep(state, slot, rows, value):
    """``value`` written whole into the ``slot`` [B] of each row where
    ``rows`` [B] holds; the other rows write nothing (a slot needs no
    clearing: a prefill writes all of it)."""
    return state.at[jnp.where(rows, slot, state.shape[0])].set(
        value.astype(state.dtype), mode="drop")


def _conv_prefill(x, held, slot, lengths, params, i, cfg: DecoderConfig):
    """A conv layer over whole prompts ``x`` [B, T, dim]: every
    position's sum in one pass over the row, zeros before its start
    (a row is one sequence, so no sum reaches into another).  What a
    sequence keeps is its newest ``conv_taps - 1`` z, written whole into
    its ``slot`` [B] of the state ``held``.  → (stream, state)."""
    z, gate = _conv_gates(x, params, i, cfg)
    zp, views = _causal_windows(z, cfg.conv_taps)
    conv = _conv_window(views, params[f"l{i}.conv"])
    held = _keep(held, slot, lengths > 0, _newest(zp, lengths, cfg.conv_taps))
    return _conv_out(x, gate, conv, params, i), held


def _conv_decode(x, held, slot, active, params, i, cfg: DecoderConfig):
    """A conv layer over one new token a row, ``x`` [B, 1, dim]: the
    row's state is read at its ``slot``, rolled by the token's z and
    written back where it lay; an idle row reads the scratch slot and
    writes nothing.  → (stream, state)."""
    z, gate = _conv_gates(x[:, 0], params, i, cfg)
    window = jnp.concatenate([held[slot], z[:, None]], axis=1)
    held = _keep(held, slot, active, window[:, 1:])
    conv = _conv_window(jnp.moveaxis(window, 1, 0), params[f"l{i}.conv"])
    return _conv_out(x, gate[:, None], conv[:, None], params, i), held


def _mamba_in(x, params, i, cfg: DecoderConfig):
    """A mamba layer's inputs from the stream: u as the state stores it
    (the storage dtype, which prefill's convolution and a later decode
    step's both read) and the gate z, float32."""
    xn = _rms(x, params[f"l{i}.ln1"], cfg.norm_eps)
    u, z = jnp.split(weight_matmul(xn, params[f"l{i}.in_proj"]), 2, axis=-1)
    return u.astype(cfg.storage), z


def _mamba_conv(views, params, i):
    """u' = silu(Σ_j taps[:, j] ⊙ u_{…j} + bias), float32."""
    return jax.nn.silu(_conv_window(views, params[f"l{i}.conv"])
                       + params[f"l{i}.conv_bias"])


def _mamba_dbc(uc, params, i, cfg: DecoderConfig):
    """``x_proj`` of u' → (Δ over ``ssm_inner`` lanes, B, C): δ, B and C
    each RMS-normed with its gain, Δ = softplus(δ·dt_proj + dt_bias)."""
    p = lambda leaf: params[f"l{i}.{leaf}"]
    r, n = cfg.dt_rank, cfg.ssm_state
    dbc = weight_matmul(uc, p("x_proj"))
    rms = lambda a, g: _rms(a, p(g), cfg.norm_eps)
    delta = rms(dbc[..., :r], "dt_norm")
    b, c = rms(dbc[..., r:r + n], "b_norm"), rms(dbc[..., r + n:], "c_norm")
    return jax.nn.softplus(weight_matmul(delta, p("dt_proj"))
                           + p("dt_bias")), b, c


def _mamba_a(params, i):
    """A = −exp(A_log), [ssm_state, ssm_inner]: channels on the lanes."""
    return -jnp.exp(params[f"l{i}.A_log"]).T


def _mamba_out(x, y, z, params, i):
    return x + weight_matmul(y * jax.nn.silu(z), params[f"l{i}.out_proj"])


def _mamba_prefill(x, win, hs, slot, lengths, params, i,
                   cfg: DecoderConfig):
    """A mamba layer over whole prompts ``x`` [B, T, dim]: the
    convolution over each row with zeros before its start, then the
    scan from a zero state with Δ = 0 past each row's length, so the
    state the scan ends in is the one after the row's own last token.
    The newest ``conv_taps - 1`` u and that state go whole into the
    row's ``slot`` of ``win`` and ``hs``.  → (stream, win, hs)."""
    b, t, _ = x.shape
    u, z = _mamba_in(x, params, i, cfg)
    up, views = _causal_windows(u, cfg.conv_taps)
    uc = _mamba_conv(views, params, i)
    delta, bm, cm = _mamba_dbc(uc, params, i, cfg)
    inside = jnp.arange(t)[None, :, None] < lengths[:, None, None]
    h0 = jnp.zeros((b, cfg.ssm_state, cfg.ssm_inner), jnp.float32)
    y, h = selective_scan(uc, jnp.where(inside, delta, 0.0),
                          _mamba_a(params, i), bm, cm,
                          params[f"l{i}.D"], h0)
    # what the slot keeps is taken before the next layer starts: left to
    # the scheduler, every layer's [T, ssm_inner] u stayed live until
    # the state's writes at the step's end (at T = 16,384 and 26
    # layers, 4.4 GB of the prefill's scratch)
    x, kept, h = jax.lax.optimization_barrier(
        (_mamba_out(x, y, z, params, i), _newest(up, lengths, cfg.conv_taps),
         h))
    rows = lengths > 0
    return x, _keep(win, slot, rows, kept), _keep(hs, slot, rows, h)


def _mamba_decode(x, win, hs, slot, active, params, i, cfg: DecoderConfig):
    """A mamba layer over one new token a row, ``x`` [B, 1, dim]: the
    row's window and state are read at its ``slot``, advanced by the
    token and written back where they lay; an idle row reads the
    scratch slot and writes nothing.  → (stream, win, hs)."""
    u, z = _mamba_in(x[:, 0], params, i, cfg)
    window = jnp.concatenate([win[slot], u[:, None]], axis=1)
    uc = _mamba_conv(jnp.moveaxis(window, 1, 0), params, i)
    delta, bm, cm = _mamba_dbc(uc, params, i, cfg)
    h, y = ssm_step(hs[slot], uc, delta, _mamba_a(params, i), bm, cm,
                    params[f"l{i}.D"])
    win = _keep(win, slot, active, window[:, 1:])
    hs = _keep(hs, slot, active, h)
    return _mamba_out(x, y[:, None], z[:, None], params, i), win, hs


def _mamba_chunk(x, win, hs, slot, at, slots, active, params, i,
                 cfg: DecoderConfig):
    """A mamba layer over a chunk launch's stream ``x`` [1, C + W, dim]:
    C positions of one prompt from ``at[0]`` (``at[1]`` of them real),
    whose sequence lies in ``slot``, then W rows one new token each, as
    :func:`_mamba_decode` takes them (``slots``, ``active``).
    ``in_proj`` and ``out_proj`` take every row at once (``x_proj`` and
    ``dt_proj``, a few MB, take each kind apart: no copy of the chunk's
    rows is made for them).  The chunk's convolution starts
    from the newest ``conv_taps - 1`` u its slot holds and its scan from
    the slot's state, both zeros where the chunk starts the prompt; what
    the chunk ends in goes back into the slot, as :func:`_mamba_prefill`
    leaves it.  → (stream, win, hs)."""
    c, taps = x.shape[1] - slots.shape[0], cfg.conv_taps
    first = at[0] == 0
    u, z = _mamba_in(x, params, i, cfg)
    up = jnp.concatenate(
        [jnp.where(first, jnp.zeros_like(win[0]), win[slot])[None],
         u[:, :c]], axis=1)
    window = jnp.concatenate([win[slots], u[0, c:, None]], axis=1)
    uc = _mamba_conv([up[:, j:j + c] for j in range(taps)], params, i)
    uc_rows = _mamba_conv(jnp.moveaxis(window, 1, 0), params, i)
    delta, bm, cm = _mamba_dbc(uc, params, i, cfg)
    a, d = _mamba_a(params, i), params[f"l{i}.D"]
    inside = jnp.arange(c)[None, :, None] < at[1]
    h0 = jnp.where(first, jnp.zeros_like(hs[0]), hs[slot])[None]
    y, h = selective_scan(uc, jnp.where(inside, delta, 0.0), a, bm, cm, d,
                          h0)
    dr, br, cr = _mamba_dbc(uc_rows, params, i, cfg)
    h_rows, y_rows = ssm_step(hs[slots], uc_rows, dr, a, br, cr, d)
    y = jnp.concatenate([y, y_rows[None]], axis=1)
    x, kept, h = jax.lax.optimization_barrier(
        (_mamba_out(x, y, z, params, i), _newest(up, at[1:], taps), h))
    # the chunk's slot first, then the rows': one scatter a state
    every = jnp.concatenate([slot[None], slots])
    rows = jnp.concatenate([jnp.ones((1,), bool), active])
    win = _keep(win, every, rows, jnp.concatenate([kept, window[:, 1:]]))
    hs = _keep(hs, every, rows, jnp.concatenate([h, h_rows]))
    return x, win, hs


def _head(x, params, cfg: DecoderConfig):
    """The final norm and the logits [B, V], float32: times the
    embedding's transpose where the head is tied."""
    x = _rms(x, params["ln_f"], cfg.norm_eps)
    if cfg.tied_head:
        return weight_einsum("bd,vd->bv", x, params["embed"])
    return weight_matmul(x, params["lm_head"])


def _attend_out(x, o, gate, params, i, cfg: DecoderConfig, attn):
    """The attention result ``o`` [B, T, H·D] back into the stream."""
    if gate is not None:
        o = o * gate
    y = weight_matmul(o, params[f"l{i}.wo"])
    if "postnorm" in attn:
        y = _rms(y, params[f"l{i}.ln1p"], cfg.norm_eps)
    return x + y


def _swiglu(m, w_gate, w_up, w_down):
    return weight_matmul(jax.nn.silu(weight_matmul(m, w_gate))
                         * weight_matmul(m, w_up), w_down)


def _ffn(x, valid, params, i, cfg: DecoderConfig, attn, ffn):
    """The layer's feed-forward on the stream ``x`` [B, T, dim];
    ``valid`` [B, T] marks the tokens that are real (a routed layer
    sends the others nowhere).  → (stream, the routed layer's tokens
    per expert [E] or None)."""
    p = lambda leaf: params[f"l{i}.{leaf}"]
    sizes = None
    with jax.named_scope(S.FFN.format(i)):
        with jax.named_scope(S.FFN_NORM):
            m = _rms(x, p("ln2"), cfg.norm_eps)
        if "gelu" in ffn:
            with jax.named_scope(S.DENSE):
                y = weight_matmul(jax.nn.gelu(weight_matmul(m, p("w1"))),
                                  p("w2"))
        elif "swiglu" in ffn:
            with jax.named_scope(S.DENSE):
                y = _swiglu(m, p("w_gate"), p("w_up"), p("w_down"))
        else:
            b, t, d = m.shape
            y, sizes = routed_experts(
                m.reshape(b * t, d), p("router"), p("router_bias"),
                p("e_gate"), p("e_up"), p("e_down"), top_k=cfg.top_k,
                route_scale=cfg.route_scale, valid=valid.reshape(-1),
                score=cfg.route_score)
            y = y.reshape(b, t, d)
            if "shared" in ffn:
                with jax.named_scope(S.SHARED):
                    y = y + _swiglu(m, p("s_gate"), p("s_up"), p("s_down"))
        if "postnorm" in attn:
            with jax.named_scope(S.FFN_NORM):
                y = _rms(y, p("ln2p"), cfg.norm_eps)
        return x + y, sizes


def _route_counts(sizes):
    """What a step's routed layers did, two integers that ride back
    with its tokens: experts with at least one token, summed over the
    layers, and the most tokens on one expert of any layer.  No routed
    layer: nothing."""
    if not sizes:
        return jnp.zeros((0,), jnp.int32)
    per = jnp.stack(sizes)                                   # [Lr, E]
    return jnp.stack([(per > 0).sum(), per.max()]).astype(jnp.int32)


def _layers_end_to_end(pool):
    """The stacked pool ``[L, P, page, G·D]`` as one pool of ``L·P``
    pages (a free reshape): layer ``i``'s page ``p`` is page
    ``i·P + p``, so a page table offset by ``i·P`` addresses the
    layer's share and ``paged_kv_write``'s "past the pool" for a
    dropped row lies past the last layer, never in the next one.  A
    state ``[L, slots, …]`` is viewed the same way: layer ``i``'s slot
    ``s`` is ``i·S + s``."""
    return pool.reshape(pool.shape[0] * pool.shape[1], *pool.shape[2:])


def _kv_and_state(pools, cfg: DecoderConfig):
    """A step's caches as :meth:`DecoderModel.new_pools` orders them →
    (their shapes, the K/V or latent pools layers end to end, the
    states layers end to end: a dict by what keeps them, ``conv``
    [held] and ``mamba`` [window, h], each as the plan has such
    layers)."""
    shapes = [pool.shape for pool in pools]
    flat = [_layers_end_to_end(pool) for pool in pools]
    n = n_kv_pools(cfg)
    states = {}
    for (kind, _, _), state in zip(state_shapes(cfg), flat[n:]):
        states.setdefault(kind, []).append(state)
    return shapes, flat[:n], states


def _as_stored(shapes, pools, states):
    """The step's caches back in the shapes and the order they came."""
    pools = [*pools, *(a for kept in states.values() for a in kept)]
    return tuple(pool.reshape(shape) for pool, shape in zip(pools, shapes))


def _slot_count(shapes, cfg: DecoderConfig) -> int:
    """Slots of the step's states (0 where the plan keeps none)."""
    n = n_kv_pools(cfg)
    return shapes[n][1] if len(shapes) > n else 0


def _prefill_impl(params, pools, tokens, lengths, page_indices, slots,
                  cfg: DecoderConfig):
    """[B, T] padded prompts → ([B] first generated tokens, [B, V]
    logits, the updated pools).  Packed causal attention (block-causal
    where the model has a ``block_length``: its prompts then are whole
    blocks, and the ids and logits of the last position are no
    prediction): the batch is ONE [1, B*T] row; segment ids keep rows
    from attending across each other and mask padding outright.
    ``slots`` [B]: where each row's sequence keeps its state (conv and
    mamba layers)."""
    b, t = tokens.shape
    h, g, dh = cfg.heads, kv_heads(cfg), head_dim(cfg)
    # a row of the packed axis starts at a multiple of t: its blocks are
    # the packed axis's
    enforce(t % max(cfg.block_length, 1) == 0,
            f"a prefill of {t} positions cuts blocks of {cfg.block_length}")
    with jax.named_scope(S.EMBED):
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :],
                               (b, t))
        x = _embed(params, tokens, pos, cfg)
        segments = segments_from_lengths(lengths, b, t)
        valid = pos < lengths[:, None]
        zero = jnp.zeros((b,), jnp.int32)
    with jax.named_scope(S.CACHE_LAYOUT):
        shapes, pools, states = _kv_and_state(pools, cfg)
    n_places, n_slots = shapes[0][1], _slot_count(shapes, cfg)
    a = c = m = 0        # the layer's index among its kind: its share
    for i, (attn, ffn) in enumerate(layer_plan(cfg)):
        if "conv" in attn:
            with jax.named_scope(S.CONV.format(i)):
                x, *states["conv"] = _conv_prefill(
                    x, *states["conv"], slots + c * n_slots, lengths,
                    params, i, cfg)
            c += 1
        elif "mamba" in attn:
            with jax.named_scope(S.SSM.format(i)):
                x, *states["mamba"] = _mamba_prefill(
                    x, *states["mamba"], slots + m * n_slots, lengths,
                    params, i, cfg)
            m += 1
        else:
            table = page_indices + a * n_places
            a += 1
            # the decode contract: a token's rows must be in the pages
            # before any later step queries them — write the whole
            # prompt now
            if "latent" in attn:
                q_n, q_r, row = _latent_qrow(x, pos, params, i, cfg, attn)
                with jax.named_scope(S.CACHE_WRITE.format(i)):
                    pools = [paged_row_write(pools[0], row, table, zero,
                                             lengths)]
                with jax.named_scope(S.ATTEND.format(i)):
                    o, gate = _latent_expanded(q_n, q_r, row, params, i,
                                               cfg, segments, t), None
            else:
                q, k, v, gate = _qkv(x, pos, params, i, cfg, attn)
                with jax.named_scope(S.CACHE_WRITE.format(i)):
                    pools = paged_kv_write(*pools, k, v, table, zero,
                                           lengths)
                with jax.named_scope(S.ATTEND.format(i)):
                    # the kernel multiplies what the pool stores
                    q, k, v = (m.astype(pools[0].dtype) for m in (q, k, v))
                    o = flash_attention_packed(
                        q.reshape(1, b * t, h, dh),
                        k.reshape(1, b * t, g, dh),
                        v.reshape(1, b * t, g, dh), segments, causal=True,
                        slot=t,
                        window=cfg.window if "window" in attn else 0,
                        causal_block=cfg.block_length)
                    o = o.reshape(b, t, h * dh).astype(jnp.float32)
            with jax.named_scope(S.MIXER_OUT.format(i)):
                x = _attend_out(x, o, gate, params, i, cfg, attn)
        x, _ = _ffn(x, valid, params, i, cfg, attn, ffn)
    with jax.named_scope(S.HEAD):
        last = jnp.take_along_axis(
            x, jnp.clip(lengths - 1, 0, t - 1)[:, None, None], axis=1)[:, 0]
        logits = _head(last, params, cfg)
        active = lengths > 0
        nxt = jnp.argmax(eos_frozen_logits(logits, active, cfg.eos_id), -1)
        nxt = nxt.astype(jnp.int32)
    with jax.named_scope(S.CACHE_LAYOUT):
        return nxt, logits, *_as_stored(shapes, pools, states)


def _decode_impl(params, pools, tokens, page_indices, lengths, active,
                 slots, cfg: DecoderConfig):
    """One decode step for a fixed-width batch.  ``lengths`` INCLUDE the
    token being fed (its position is ``lengths - 1``); ``active`` masks
    padded slots — their write count is zero and their kernel length
    clamps to 1 over the scratch page, so padding can neither write nor
    read real pool state; ``slots`` [B] says where each row's state lies
    (an idle row's: the scratch slot).  The first result is the [B] next
    tokens,
    followed by :func:`_route_counts`' integers where the plan has
    routed layers; then the logits and the updated pools."""
    b = tokens.shape[0]
    with jax.named_scope(S.EMBED):
        pos = jnp.clip(lengths - 1, 0, cfg.max_context - 1)[:, None]
        x = _embed(params, tokens[:, None], pos, cfg)
        counts = active.astype(jnp.int32)
        klen = jnp.where(active, lengths, 1).astype(jnp.int32)
    # the default plan's kernel keeps the name its call inherits (see
    # ops/pallas_attention.py::_decode_call): the ``%_lambda_`` of the
    # jits around it, held only under no scope at all, so there
    # ``attend`` is empty and the call stands bare; a planned decoder's
    # reads %paged_decode under any scope
    name = K.PAGED_DECODE if cfg.plan else None
    sizes = []
    with jax.named_scope(S.CACHE_LAYOUT):
        shapes, pools, states = _kv_and_state(pools, cfg)
    n_places, n_slots = shapes[0][1], _slot_count(shapes, cfg)
    a = c = m = 0
    for i, (attn, ffn) in enumerate(layer_plan(cfg)):
        if "conv" in attn:
            with jax.named_scope(S.CONV.format(i)):
                x, *states["conv"] = _conv_decode(
                    x, *states["conv"], slots + c * n_slots, active,
                    params, i, cfg)
            c += 1
        elif "mamba" in attn:
            with jax.named_scope(S.SSM.format(i)):
                x, *states["mamba"] = _mamba_decode(
                    x, *states["mamba"], slots + m * n_slots, active,
                    params, i, cfg)
            m += 1
        else:
            table = page_indices + a * n_places
            a += 1
            # the whole stack as stored, [L·P, page, W], stays in HBM:
            # the kernel DMAs the rows' live pages of this layer out of
            # it, nothing else, before the next layer's rows are written
            if "latent" in attn:
                q_n, q_r, row = _latent_qrow(x, pos, params, i, cfg, attn)
                with jax.named_scope(S.CACHE_WRITE.format(i)):
                    pools = [paged_row_write(pools[0], row, table,
                                             lengths - 1, counts)]
                with jax.named_scope(S.ATTEND.format(i)):
                    o, gate = _latent_absorbed(q_n, q_r, pools[0], table,
                                               klen, params, i, cfg), None
            else:
                q, k, v, gate = _qkv(x, pos, params, i, cfg, attn)
                with jax.named_scope(S.CACHE_WRITE.format(i)):
                    pools = paged_kv_write(*pools, k, v, table, lengths - 1,
                                           counts)
                with jax.named_scope(S.ATTEND.format(i)) if cfg.plan \
                        else contextlib.nullcontext():
                    o = paged_decode_attention(
                        q, *pools, table, klen,
                        window=cfg.window if "window" in attn else 0,
                        name=name).reshape(b, 1, -1)
            with jax.named_scope(S.MIXER_OUT.format(i)):
                x = _attend_out(x, o, gate, params, i, cfg, attn)
        x, routed = _ffn(x, active[:, None], params, i, cfg, attn, ffn)
        if routed is not None:
            sizes.append(routed)
    with jax.named_scope(S.HEAD):
        logits = _head(x[:, 0], params, cfg)
        nxt = jnp.argmax(eos_frozen_logits(logits, active, cfg.eos_id), -1)
        ids = jnp.concatenate([nxt.astype(jnp.int32), _route_counts(sizes)])
    with jax.named_scope(S.CACHE_LAYOUT):
        return ids, logits, *_as_stored(shapes, pools, states)


def _chunk_impl(params, pools, chunk, at, table, slot, tokens, page_indices,
                lengths, active, slots, cfg: DecoderConfig):
    """One chunk launch of a plan that :func:`continues`: ``chunk`` [C]
    ids of one prompt at positions ``at[0]`` … (``at[1]`` of them real,
    the rest padding), its row's page table ``table`` [max_pages] and
    ``slot``, beside a decode step's W rows (``tokens`` … ``slots``, as
    :func:`_decode_impl` takes them, idle rows included).  Every row
    goes through a layer's products as one [1, C + W] stream, so the
    weights are read once a launch; then the chunk continues its
    prefill (a mamba layer from its slot: :func:`_mamba_chunk`; a full
    layer writes the chunk's K/V and attends the row's pages through
    :func:`chunk_prefill_attention`) and the rows take their decode step
    (``_mamba_decode``'s arithmetic, the paged decode kernel).  → (the
    rows' [W] next tokens, [1] the token after the chunk's last real
    position (the first generated one where the chunk ends the prompt),
    the logits [W + 1, V] of both, the updated pools)."""
    c, w = chunk.shape[0], tokens.shape[0]
    h, g, dh = cfg.heads, kv_heads(cfg), head_dim(cfg)
    with jax.named_scope(S.EMBED):
        pos = jnp.concatenate([at[0] + jnp.arange(c, dtype=jnp.int32),
                               jnp.clip(lengths - 1, 0,
                                        cfg.max_context - 1)])[None]
        x = _embed(params, jnp.concatenate([chunk, tokens])[None], pos, cfg)
        counts = active.astype(jnp.int32)
        klen = jnp.where(active, lengths, 1).astype(jnp.int32)
        valid = jnp.concatenate([jnp.arange(c) < at[1], active])[None]
    # as _decode_impl names the rows' kernel
    name = K.PAGED_DECODE if cfg.plan else None
    with jax.named_scope(S.CACHE_LAYOUT):
        shapes, pools, states = _kv_and_state(pools, cfg)
    n_places, n_slots = shapes[0][1], _slot_count(shapes, cfg)
    a = m = 0
    for i, (attn, ffn) in enumerate(layer_plan(cfg)):
        if "mamba" in attn:
            with jax.named_scope(S.SSM.format(i)):
                x, *states["mamba"] = _mamba_chunk(
                    x, *states["mamba"], slot + m * n_slots, at,
                    slots + m * n_slots, active, params, i, cfg)
            m += 1
        else:
            mine, rows = table + a * n_places, page_indices + a * n_places
            a += 1
            q, k, v, gate = _qkv(x, pos, params, i, cfg, attn)
            with jax.named_scope(S.CACHE_WRITE.format(i)):
                pools = paged_kv_write(*pools, k[:, :c], v[:, :c],
                                       mine[None], at[:1], at[1:])
                pools = paged_kv_write(*pools, k[0, c:, None], v[0, c:, None],
                                       rows, lengths - 1, counts)
            with jax.named_scope(S.ATTEND.format(i)):
                o = chunk_prefill_attention(
                    q[0, :c].astype(pools[0].dtype), *pools, mine, at)
                o_rows = paged_decode_attention(
                    q[0, c:, None], *pools, rows, klen, name=name)
                o = jnp.concatenate([o.reshape(c, h * dh).astype(jnp.float32),
                                     o_rows.reshape(w, h * dh)])[None]
            with jax.named_scope(S.MIXER_OUT.format(i)):
                x = _attend_out(x, o, gate, params, i, cfg, attn)
        x, _ = _ffn(x, valid, params, i, cfg, attn, ffn)
    with jax.named_scope(S.HEAD):
        last = x[0, jnp.clip(at[1] - 1, 0, c - 1)][None]
        logits = _head(jnp.concatenate([x[0, c:], last]), params, cfg)
        live = jnp.concatenate([active, jnp.ones((1,), bool)])
        nxt = jnp.argmax(eos_frozen_logits(logits, live, cfg.eos_id), -1)
        nxt = nxt.astype(jnp.int32)
    with jax.named_scope(S.CACHE_LAYOUT):
        return nxt[:w], nxt[w:], logits, *_as_stored(shapes, pools, states)


def reveal_schedule(masked: int, steps: int) -> Tuple[int, ...]:
    """How many of a block's ``masked`` positions each of ``steps``
    denoising steps reveals: ⌊m/steps⌋ + (s < m mod steps) at step s, a
    step that would reveal none left out (4 over 2 steps: 2, 2; 3: 2, 1;
    1: 1)."""
    return tuple(n for n in (masked // steps + (s < masked % steps)
                             for s in range(steps)) if n)


def _reveal(block, logits, reveal):
    """(The block after a step, each position's confidence): of each
    row's masked positions (id < 0) the ``reveal`` [W] whose top softmax
    probability (float32) is highest, the lower position first on a
    tie, take their argmax; the rest stay as they are.  The confidence
    is that probability's log, float32 [W, B]."""
    conf = logits.max(axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [W, B]
    score = jnp.where(block < 0, conf, -jnp.inf)
    at = jnp.arange(block.shape[1])
    # how many of the row's positions rank before each position
    ahead = (score[:, None, :] > score[:, :, None]) | (
        (score[:, None, :] == score[:, :, None])
        & (at[None, None, :] < at[None, :, None]))
    chosen = (block < 0) & (ahead.sum(axis=-1) < reveal[:, None])
    return jnp.where(chosen, pred, block), conf


def _block_impl(params, pools, block, page_indices, starts, active, reveal,
                commit, cfg: DecoderConfig):
    """One diffusion step for a fixed-width batch of blocks: ``block``
    [W, B] ids (−1 where masked), each row's tile of 2B positions at
    ``starts`` [W] … + 2B, its live positions first.  A row whose
    ``commit`` [W] is set holds its finished block there and the next
    block, all masked, after it: one pass writes the finished block's
    K/V from its final ids and takes the next block's first denoising
    step.  Any other row holds its block and B dead positions, which
    write nothing and reach no expert; their queries read what lies
    there, and their outputs are dropped.  Every layer writes the live
    positions' K/V at their place and attends through the block mode of
    the paged kernel, every row's tile placed by its length, starts +
    2B (each query its own block whole and every block before it: the
    block-causal mask, so a finished block's queries do not see the
    block after it); ``reveal`` [W] positions of the row's
    current block (the second half of a committing row's tile, the
    first of any other) are revealed (:func:`_reveal`).  An ``active``
    row's pages and positions are its own; an idle row writes nothing
    and reads the scratch page.  → (the [W·B] current blocks after the
    step, their [W·B] confidences' float32 bits and
    :func:`_route_counts`' integers in one int32 vector, the logits
    [W, B, V] of the current blocks, the updated pools)."""
    w, bl = block.shape
    with jax.named_scope(S.EMBED):
        tile = jnp.concatenate([block, jnp.full_like(block, -1)], axis=1)
        pos = jnp.clip(starts[:, None] + jnp.arange(2 * bl)[None, :], 0,
                       cfg.max_context - 1)
        x = _embed(params, jnp.where(tile >= 0, tile, cfg.mask_id), pos, cfg)
        counts = jnp.where(active, jnp.where(commit, 2 * bl, bl),
                           0).astype(jnp.int32)
        # the kernel places a tile by its row's length: 2B from starts
        klen = jnp.where(active, starts + 2 * bl, 1).astype(jnp.int32)
        valid = jnp.arange(2 * bl)[None, :] < counts[:, None]
    sizes = []
    with jax.named_scope(S.CACHE_LAYOUT):
        shapes, pools, _ = _kv_and_state(pools, cfg)
    n_places = shapes[0][1]
    for i, (attn, ffn) in enumerate(layer_plan(cfg)):
        table = page_indices + i * n_places
        q, k, v, gate = _qkv(x, pos, params, i, cfg, attn)
        with jax.named_scope(S.CACHE_WRITE.format(i)):
            pools = paged_kv_write(*pools, k, v, table, starts, counts)
        with jax.named_scope(S.ATTEND.format(i)):
            o = paged_decode_attention(
                q, *pools, table, klen, name=K.BLOCK_DECODE,
                block=bl).reshape(w, 2 * bl, -1)
        with jax.named_scope(S.MIXER_OUT.format(i)):
            x = _attend_out(x, o, gate, params, i, cfg, attn)
        x, routed = _ffn(x, valid, params, i, cfg, attn, ffn)
        if routed is not None:
            sizes.append(routed)
    with jax.named_scope(S.HEAD):
        cur = jnp.where(commit[:, None, None], x[:, bl:], x[:, :bl])
        logits = _head(cur, params, cfg)
        after, conf = _reveal(jnp.where(commit[:, None], -1, block),
                              logits, reveal)
        ids = jnp.concatenate([
            after.reshape(-1),
            jax.lax.bitcast_convert_type(conf.reshape(-1), jnp.int32),
            _route_counts(sizes)])
    with jax.named_scope(S.CACHE_LAYOUT):
        return ids, logits, *_as_stored(shapes, pools, {})


def n_kv_pools(cfg: DecoderConfig) -> int:
    """Pools of cache rows a model of this plan keeps for the layers
    that attend: one of latent rows, or a K and a V pool."""
    return 1 if "latent" in layer_plan(cfg)[0][0] else 2


def layers_of(cfg: DecoderConfig, kind: str) -> int:
    """Layers of the plan whose mixer is ``kind`` (conv, mamba …)."""
    return sum(kind in attn for attn, _ in layer_plan(cfg))


def state_shapes(cfg: DecoderConfig):
    """[(kind, shape less the slots' axis, dtype)] of the states the plan
    keeps, in their order: the conv layers' window ``[layers, taps - 1,
    dim]``; the mamba layers' window ``[layers, taps - 1, ssm_inner]``,
    then their scan's state ``[layers, ssm_state, ssm_inner]`` in
    float32."""
    keep, out = cfg.conv_taps - 1, []
    conv, mamba = layers_of(cfg, "conv"), layers_of(cfg, "mamba")
    if conv:
        out.append(("conv", (conv, keep, cfg.dim), cfg.storage))
    if mamba:
        out += [("mamba", (mamba, keep, cfg.ssm_inner), cfg.storage),
                ("mamba", (mamba, cfg.ssm_state, cfg.ssm_inner), "float32")]
    return out


def n_pools(cfg: DecoderConfig) -> int:
    """Caches a step of this plan takes and donates: the pools of the
    layers that attend and, where the plan has conv or mamba layers,
    their states."""
    return n_kv_pools(cfg) + len(state_shapes(cfg))


@functools.lru_cache(maxsize=None)
def _jitted_steps(cfg: DecoderConfig):
    """One jitted (prefill, decode) pair PER CONFIG, shared by every
    :class:`DecoderModel` of that config.  Params are traced arguments
    (not closure constants), so two models with the same config hit
    the same executables — which is what makes a hot-swap
    (``serving/rollout.py``) actually zero-downtime: the swapped-in
    model rides every (B, T) bucket the serving process has already
    compiled instead of stalling the first post-flip requests behind
    a full recompile."""
    # static cfg via closure; jax caches one executable per
    # (B, T)/(B,) shape bucket.  The arguments are the params, the
    # plan's pools, then the step's inputs.  The pools are donated: the
    # step's scatters write into the buffers that came in, and the
    # arrays the caller passed are deleted (KVPool keeps the only
    # reference)
    # The last input of both is each row's slot
    n = n_pools(cfg)
    donated = tuple(range(1, 1 + n))
    prefill = jax.jit(
        lambda p, *a: _prefill_impl(p, a[:n], *a[n:], cfg),
        donate_argnums=donated)

    # a row's fed id is the host's ``tk`` or, where ``src`` >= 0, entry
    # ``src`` of ``prev``: the ids of the decode launch before this one,
    # still on the device (the host has not read them yet).  Both stay
    # lambdas: the default plan's unnamed kernel is found in a trace by
    # the ``%_lambda_`` it inherits (ops/pallas_attention.py::_decode_call)
    decode = jax.jit(
        lambda p, *a: _decode_impl(
            p, a[:n], _fed_ids(*a[n:n + 3]), *a[n + 3:], cfg),
        donate_argnums=donated)
    return prefill, decode


def _fed_ids(tokens, prev, src):
    with jax.named_scope(S.EMBED):
        return jnp.where(src >= 0, prev[jnp.maximum(src, 0)], tokens)


@functools.lru_cache(maxsize=None)
def _jitted_chunk(cfg: DecoderConfig):
    """The jitted :func:`_chunk_impl` of a config, shared as
    :func:`_jitted_steps`' pair is; it takes ``(params, *pools, chunk,
    at, table, slot, tokens, prev_ids, src, tables, lengths, active,
    slots)``, the rows fed as a decode step's are (``prev`` the rows'
    ids of the decode or chunk launch before), and donates the pools."""
    n = n_pools(cfg)
    return jax.jit(
        lambda p, *a: _chunk_impl(p, a[:n], *a[n:n + 4],
                                  _fed_ids(*a[n + 4:n + 7]), *a[n + 7:], cfg),
        donate_argnums=tuple(range(1, 1 + n)))


@functools.lru_cache(maxsize=None)
def _jitted_block_step(cfg: DecoderConfig):
    """The jitted :func:`_block_impl` of a config, shared as
    :func:`_jitted_steps`' pair is; it takes ``(params, *pools, blocks,
    prev_ids, src, tables, starts, active, reveal, commit)`` and donates
    the pools.  A row's block is the host's ``blocks`` row or, where
    ``src`` >= 0, block ``src`` of ``prev``: the blocks an earlier step
    left, still on the device."""
    n = n_pools(cfg)
    return jax.jit(
        lambda p, *a: _block_impl(p, a[:n], _fed_blocks(*a[n:n + 3]),
                                  *a[n + 3:], cfg),
        donate_argnums=tuple(range(1, 1 + n)))


def _fed_blocks(blocks, prev, src):
    w, bl = blocks.shape
    with jax.named_scope(S.EMBED):
        held = prev[:w * bl].reshape(w, bl)[jnp.maximum(src, 0)]
        return jnp.where((src >= 0)[:, None], held, blocks)


class KVPool:
    """One stacked cache on the device (K, V or latent rows, ``[L, P,
    page, W]``, or the conv layers' state, ``[L, places, taps - 1,
    dim]``), and the only reference to it.  A step donates
    ``array`` and puts its result back here
    (:meth:`DecoderModel.prefill` / ``decode``),
    so whoever holds the pool always holds the live buffer and a stale
    reference to a donated one cannot exist."""
    __slots__ = ("array",)

    def __init__(self, array: jax.Array):
        self.array = array

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.array.shape

    @property
    def dtype(self):
        return self.array.dtype

    def block_until_ready(self) -> "KVPool":
        self.array.block_until_ready()
        return self


class DecoderModel:
    """A loaded decoder + its jitted prefill/decode steps.

    Pools are owned by the caller (the server) and threaded through
    every call as :class:`KVPool` objects, which a step updates in
    place — the model never holds KV state, so one model instance
    serves any number of pools/replicas reentrantly.  One set of pools
    (:meth:`new_pools`) belongs to one thread at a time."""

    def __init__(self, params: Dict[str, Any], cfg: DecoderConfig):
        self.cfg = cfg
        self.plan = layer_plan(cfg)          # checks the config too
        self.routed_layers = sum("routed" in ffn for _, ffn in self.plan)
        self._window_layers = sum("window" in attn for attn, _ in self.plan)
        self.conv_layers = layers_of(cfg, "conv")
        self.mamba_layers = layers_of(cfg, "mamba")
        # the layers that keep a state a sequence in its slot
        self.state_layers = self.conv_layers + self.mamba_layers
        # the layers that attend: the pools hold these and no others
        self._cached_layers = cfg.layers - self.state_layers
        # slots of the states new_pools last gave (what a step that
        # names none is handed: _pools_of)
        self.slots = 0
        self.n_kv_pools = n_kv_pools(cfg)
        self.n_pools = n_pools(cfg)
        shapes = leaf_shapes(cfg)
        enforce(set(params) == set(shapes),
                "the weights are not the plan's: missing "
                f"{sorted(set(shapes) - set(params))}, unknown "
                f"{sorted(set(params) - set(shapes))}")
        # on the device once, each leaf in the dtype it is kept in
        # (float32 arrives, dequantized int8 artifacts too, and is
        # rounded there: the host never holds a second copy)
        self.params = {}
        for name, v in params.items():
            a = jax.device_put(np.asarray(v))
            enforce(a.shape == shapes[name],
                    f"weight {name}: shape {a.shape}, the plan's is "
                    f"{shapes[name]}")
            self.params[name] = a.astype(_stored_as(name, a.shape, cfg))
        self._prefill, self._decode = _jitted_steps(cfg)
        self._chunk = _jitted_chunk(cfg)
        # prompt positions a chunk launch takes (CHUNK), or 0 where the
        # plan cannot continue a prefill: every prompt one launch
        self.chunk = CHUNK if continues(cfg) else 0
        # rows of the decode batch a chunk launch carries (new_pools)
        self.width = 1
        self.block_length = cfg.block_length
        self._block = _jitted_block_step(cfg) if cfg.block_length else None

    # ----------------------------------------------------------- pools
    def new_pools(self, n_pages: int, page_size: int,
                  slots: Optional[int] = None,
                  width: int = 1) -> Tuple[KVPool, ...]:
        """The zeroed caches this plan needs, the caller's to keep and
        to hand to every step in this order: a K and a V pool, or the
        one pool of a latent plan, then the states of the conv and the
        mamba layers where the plan has such layers.  A pool is a
        :class:`KVPool` over ``[L, P, page, W]`` in the storage dtype,
        ``L`` the layers that attend (a conv or mamba layer has no
        share), one lane-dense row a token
        (``W`` = G·Dh, or :func:`latent_row_width`), the layout the
        decode kernels fetch pages in and ``paged_row_write`` scatters
        rows into.  (Stored ``[…, G, Dh]`` with Dh < 128 the TPU lays
        the page axis along the lanes, and every use of a layer's pool
        is a relayout copy of it: PERF.md §6, PR 26.)  A state is a
        :class:`KVPool` over ``[layers of its kind, slots, …]``
        (:func:`state_shapes`).  A sequence's state lies in the slot
        its caller gives its row in every step, so it follows the
        request wherever its row moves in the batch: ``slots`` of them
        (the server gives a mamba plan's sequences one each from
        admission to release, and keeps the last for idle rows); none
        given, one a page, where a step that names no slots finds a
        row's state at the number of its first page.  ``width``: the
        rows of the decode batch the pools serve, which a chunk launch
        carries beside its chunk (:meth:`step_chunk`; :meth:`prefill`
        runs a long prompt's chunks at that width too, so the server's
        chunk launches find the program compiled)."""
        shape = (self._cached_layers, n_pages, page_size, self._row_width())
        pools = tuple(KVPool(jnp.zeros(shape, self.cfg.storage))
                      for _ in range(self.n_kv_pools))
        self.slots = slots or n_pages
        self.width = width
        return pools + self._new_state(self.slots)

    def _new_state(self, slots: int) -> Tuple[KVPool, ...]:
        return tuple(KVPool(jnp.zeros((shape[0], slots, *shape[1:]), dtype))
                     for _, shape, dtype in state_shapes(self.cfg))

    def _row_width(self) -> int:
        return latent_row_width(self.cfg) if self.n_kv_pools == 1 \
            else kv_heads(self.cfg) * head_dim(self.cfg)

    def _pools_of(self, args):
        """A step's arguments, the pools first: → (the plan's pools,
        the rest).  A caller that names a K and a V pool where the plan
        has one latent pool names that one twice: its rows are both;
        one that names no states where the plan keeps them gets zeroed
        states of as many slots as :meth:`new_pools` last gave, the same
        program at the same shapes (the benchmark's warm-up is such a
        caller: PERF.md §7)."""
        n = next((i for i, a in enumerate(args)
                  if not isinstance(a, KVPool)), len(args))
        pools = tuple(dict.fromkeys(args[:n]))
        if len(pools) == self.n_kv_pools:
            pools += self._new_state(self.slots)
        enforce(len(pools) == self.n_pools,
                f"{len(pools)} pools handed to a step of a plan with "
                f"{self.n_pools} (see new_pools)")
        return pools, args[n:]

    # ------------------------------------------------- what a step reads
    def attended_tokens(self, lengths) -> int:
        """K/V positions a decode step over rows of these ``lengths``
        (the fed token included) must read, summed over the layers that
        attend: all of a row on a full layer, its ``window`` newest on
        a window layer, none on a conv layer."""
        full = sum(lengths)
        if not self._window_layers:
            return full * self._cached_layers
        return self._over_layers(
            full, sum(min(n, self.cfg.window) for n in lengths))

    def _over_layers(self, full: int, near: int) -> int:
        """A count that is ``near`` on a window layer, ``full`` on the
        others that attend, summed over the plan."""
        return near * self._window_layers \
            + full * (self._cached_layers - self._window_layers)

    def attn_pairs(self, prompts) -> int:
        """Visible (query, key) pairs of a prefill over prompts of these
        lengths, summed over the layers that attend: the causal
        triangle, of which a window layer counts what its window
        leaves, or the staircase of a block-causal mask."""
        b = self.cfg.block_length
        if b:       # block-causal: position i sees up to its block's end
            return self._cached_layers * sum(
                b * b * (n // b) * (n // b + 1) // 2 + (n % b) * n
                for n in prompts)
        full = sum(n * (n + 1) // 2 for n in prompts)
        if not self._window_layers:
            return full * self._cached_layers
        w = self.cfg.window
        return self._over_layers(full, sum(
            min(n, w) * (min(n, w) + 1) // 2 + max(n - w, 0) * w
            for n in prompts))

    def cache_bytes_per_token(self) -> int:
        """What one position holds in the pools over the layers that
        attend, as stored (a latent row in whole lane tiles)."""
        return self.n_kv_pools * self._cached_layers * self._row_width() \
            * jnp.dtype(self.cfg.storage).itemsize

    def state_bytes_per_sequence(self) -> int:
        """What a sequence holds in the conv and mamba layers' states,
        whatever its length."""
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for _, shape, dtype in state_shapes(self.cfg))

    def pages_behind_window(self, lengths, page_size: int) -> int:
        """Layer-pages (one layer's K and V of one page) that these
        rows hold wholly behind a window layer's window: allocated,
        never read again."""
        if not self._window_layers:
            return 0
        behind = sum(max(n - self.cfg.window, 0) // page_size
                     for n in lengths)
        return behind * self._window_layers

    # ----------------------------------------------------------- steps
    # Each step has a launch half, which queues the program on the
    # device and returns at once, and a collect half, which waits for
    # it and brings back the ids (and the routed counts): 64-72 bytes.
    # The logits stay on the device, the caller's to fetch.  ``prefill``
    # and ``decode`` are the two in one, and the launch half is the same
    # method told not to collect: the jitted call sits in ONE frame,
    # as deep under ``prefill`` as under ``launch_prefill``.  JAX writes
    # the Python traceback into every op's location, and one frame more
    # above the call cost a 24-layer program a second or more of
    # lowering on the chip's host (PERF.md §6, PR 30).
    def prefill(self, *args, slots=None, collect: bool = True):
        """``prefill(*pools, tokens, lengths, page_indices,
        slots=None)``: prompts
        in, first generated token out (plus the logits and the pools,
        the same objects, updated in place).  ``pools`` are
        :meth:`new_pools`' in their order; ``tokens`` [B, T] int32
        padded, ``lengths`` [B], ``page_indices`` [B, max_pages]
        physical page tables covering each prompt PLUS the tokens to be
        generated, ``slots`` [B] where each row's sequence keeps its
        state (none named: the number of its first page, the same
        program).  The
        pools hold the launch's result as soon as it is
        queued; ``collect=False`` (:meth:`launch_prefill`) returns
        there, with the launch for :meth:`collect_prefill`.  Where the
        plan :func:`continues` and ``tokens`` is wider than a chunk,
        each row's prompt runs as chunk launches in order, no row riding
        (:meth:`step_chunk`): the one program whatever the width."""
        pools, (tokens, lengths, page_indices) = self._pools_of(args)
        shape = np.shape(tokens)
        enforce(len(shape) == 2 and shape[1] <= self.cfg.max_context,
                f"prompt batch {shape} exceeds max_context "
                f"{self.cfg.max_context}")
        if 0 < self.chunk < shape[1]:
            nxt, logits = self._prefill_by_chunks(
                pools, tokens, lengths, page_indices, slots)
            if not collect:
                return nxt, logits
            return (*self.collect_prefill((nxt, logits)), *pools)
        with _span("prefill_dispatch"):       # host→device + launch
            nxt, logits, *arrays = self._prefill(
                self.params, *(p.array for p in pools),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(page_indices, jnp.int32),
                self._slots_of(slots, page_indices))
            for pool, array in zip(pools, arrays):
                pool.array = array
        if not collect:
            return nxt, logits
        return (*self.collect_prefill((nxt, logits)), *pools)

    launch_prefill = functools.partialmethod(prefill, collect=False)

    def _prefill_by_chunks(self, pools, tokens, lengths, page_indices,
                           slots):
        """:meth:`prefill`'s rows as chunk launches with :attr:`width`
        idle rows beside each.  → ([B] first tokens, [B, V] logits),
        device arrays."""
        tokens, tables = np.asarray(tokens), np.asarray(page_indices, np.int32)
        slots = np.asarray(self._slots_of(slots, tables))
        w, c = self.width, self.chunk
        idle = (np.zeros((w,), np.int32),
                np.zeros((w, tables.shape[1]), np.int32),
                np.ones((w,), np.int32), np.zeros((w,), bool))
        firsts, logits = [], []
        for row, length in enumerate(np.asarray(lengths).tolist()):
            for start in range(0, max(length, 1), c):
                n = min(c, length - start)
                chunk = np.zeros((c,), np.int32)
                chunk[:n] = tokens[row, start:start + n]
                _, first, out = self.launch_chunk(
                    *pools, chunk, np.array([start, n], np.int32),
                    tables[row], slots[row], *idle)
            firsts.append(first)
            logits.append(out[w:])
        return jnp.concatenate(firsts), jnp.concatenate(logits)

    def step_chunk(self, *args, slots=None, collect: bool = True):
        """``step_chunk(*pools, chunk, at, table, slot, tokens,
        page_indices, lengths, active[, prev, src], slots=None)``: one
        chunk launch of a plan that :func:`continues`.  ``chunk`` [C]
        int32 ids of one prompt from position ``at[0]``, ``at[1]`` of
        them real (C is :attr:`chunk`; the launch writes their K/V and
        its slot's state, which the next chunk continues from), ``table``
        [max_pages] and ``slot`` the prompt's row's; beside it the rows of
        a decode step, taken as :meth:`decode` takes them (``prev`` an
        earlier decode or chunk launch of the same width).  → (the rows'
        next tokens, on the host; the token after the chunk's last real
        position, the prompt's first generated token where the chunk
        ends it; the logits [W + 1, V], a device array, the chunk's
        last; the pools).  ``collect=False`` (:meth:`launch_chunk`)
        returns once the launch is queued, with the launch for
        :meth:`collect_chunk`."""
        pools, (chunk, at, table, slot, tokens, page_indices, lengths,
                active, *feed) = self._pools_of(args)
        prev, src = feed or (None, None)
        w = np.shape(tokens)[0]
        if prev is None:       # the program's shapes, fed by nobody
            ids = np.zeros((w,), np.int32)
            src = np.full((w,), -1, np.int32)
        else:
            ids = prev[0]
        with _span("prefill_dispatch"):       # host→device + launch
            ids, first, logits, *arrays = self._chunk(
                self.params, *(p.array for p in pools),
                jnp.asarray(chunk, jnp.int32), jnp.asarray(at, jnp.int32),
                jnp.asarray(table, jnp.int32), jnp.asarray(slot, jnp.int32),
                jnp.asarray(tokens, jnp.int32), jnp.asarray(ids, jnp.int32),
                jnp.asarray(src, jnp.int32),
                jnp.asarray(page_indices, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(active, bool),
                self._slots_of(slots, page_indices))
            for pool, array in zip(pools, arrays):
                pool.array = array
        if not collect:
            return ids, first, logits
        return (*self.collect_chunk((ids, first, logits)), *pools)

    launch_chunk = functools.partialmethod(step_chunk, collect=False)

    @staticmethod
    def collect_chunk(launch):
        """→ (the rows' next tokens and the chunk's token, on the host,
        in one fetch; the logits, a device array)."""
        ids, first, logits = launch
        with _span("prefill_fetch"):          # blocks on the device
            ids, first = jax.device_get((ids, first))
        return np.asarray(ids), int(first[0]), logits

    @staticmethod
    def collect_prefill(launch):
        """→ (each row's first generated token, on the host; the
        logits, a device array)."""
        nxt, logits = launch
        with _span("prefill_fetch"):          # blocks on the device
            nxt = np.asarray(nxt)
        return nxt, logits

    def decode(self, *args, slots=None, collect: bool = True):
        """``decode(*pools, tokens, page_indices, lengths, active[,
        prev, src], slots=None)``: one continuous-batching decode step
        over the page pool.  → (next tokens, logits, the pools (the same
        objects, updated in place), the routed counts of
        :meth:`collect_decode`).  ``prev`` is an earlier decode launch
        of the same width, collected or not, and ``src`` [B] says per
        row which of its ids the row is fed (−1: the host's
        ``tokens``); the same program runs with or without it, and with
        or without ``slots`` (as :meth:`prefill`'s).
        ``collect=False`` (:meth:`launch_decode`) returns once the step
        is queued, with the launch for :meth:`collect_decode`."""
        pools, (tokens, page_indices, lengths, active, *feed) = \
            self._pools_of(args)
        prev, src = feed or (None, None)
        b = np.shape(tokens)[0]
        if prev is None:       # the program's shapes, fed by nobody
            counts = 2 if self.routed_layers else 0    # _route_counts
            ids = np.zeros((b + counts,), np.int32)
            src = np.full((b,), -1, np.int32)
        else:
            ids = prev[0]
        with _span("decode_dispatch"):        # host→device + launch
            ids, logits, *arrays = self._decode(
                self.params, *(p.array for p in pools),
                jnp.asarray(tokens, jnp.int32), jnp.asarray(ids, jnp.int32),
                jnp.asarray(src, jnp.int32),
                jnp.asarray(page_indices, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(active, bool),
                self._slots_of(slots, page_indices))
            for pool, array in zip(pools, arrays):
                pool.array = array
        if not collect:
            return ids, logits
        nxt, logits, routed = self.collect_decode((ids, logits))
        return (nxt, logits, *pools, routed)

    launch_decode = functools.partialmethod(decode, collect=False)

    @staticmethod
    def _slots_of(slots, page_indices):
        """A step's slot input: the caller's, or each row's first page."""
        return jnp.asarray(np.asarray(page_indices)[:, 0] if slots is None
                           else slots, jnp.int32)

    @staticmethod
    def collect_decode(launch):
        """→ (next tokens, on the host; the logits, a device array;
        what the step's routed layers did: ``{"experts_hit",
        "expert_load_max"}``, :func:`_route_counts`, empty for a plan
        without routed layers).  The counts come back in the fetch that
        brings the tokens."""
        ids, logits = launch
        with _span("decode_fetch"):           # blocks on the device
            ids = np.asarray(ids)
        b = logits.shape[0]
        routed = dict(zip(("experts_hit", "expert_load_max"),
                          map(int, ids[b:])))
        return ids[:b], logits, routed

    def block_step(self, *args, collect: bool = True):
        """``block_step(*pools, blocks, page_indices, starts, active,
        reveal[, commit[, prev, src]])``: one diffusion step of a
        fixed-width batch of blocks (a model with a ``block_length``).
        ``blocks`` [W, B] int32 ids, −1 where a position is masked;
        ``starts`` [W] each row's first position (its block's, or the
        finished block's where the row commits); ``reveal`` [W]
        how many masked positions of the row's current block the step
        reveals; ``commit`` [W] (all false where not given) the rows
        whose ``blocks`` row is a finished block: the step writes its
        K/V from its final ids and takes the next block's first step,
        that block all masked, so its K/V are written at starts …
        starts + 2B where another row's are at starts … starts + B, into
        pages the table covers; every active row reads through starts +
        2B, so its table holds a page id (the scratch page past its own
        pages) that far (:func:`_block_impl`).  ``prev`` is an
        earlier block step of the same width, collected or not, and
        ``src`` [W] says per row which of its blocks the row is fed (−1:
        the host's ``blocks``).  → (the current blocks after the step
        [W, B], each position's confidence [W, B] (:func:`_reveal`), the
        logits [W, B, V], a device array, the pools, the routed counts
        as :meth:`collect_decode`'s); ``collect=False``
        (:meth:`launch_block_step`) returns once the step is queued."""
        pools, (blocks, page_indices, starts, active, reveal, *more) = \
            self._pools_of(args)
        enforce(len(more) in (0, 1, 3), "block_step takes commit, or "
                f"commit, prev and src, after reveal: {len(more)} more")
        w, bl = np.shape(blocks)
        commit, *feed = more or (np.zeros((w,), bool),)
        prev, src = feed or (None, None)
        if prev is None:       # the program's shapes, fed by nobody
            counts = 2 if self.routed_layers else 0
            ids = np.zeros((2 * w * bl + counts,), np.int32)
            src = np.full((w,), -1, np.int32)
        else:
            ids = prev[0]
        with _span("decode_dispatch"):        # host→device + launch
            ids, logits, *arrays = self._block(
                self.params, *(p.array for p in pools),
                jnp.asarray(blocks, jnp.int32), jnp.asarray(ids, jnp.int32),
                jnp.asarray(src, jnp.int32),
                jnp.asarray(page_indices, jnp.int32),
                jnp.asarray(starts, jnp.int32), jnp.asarray(active, bool),
                jnp.asarray(reveal, jnp.int32), jnp.asarray(commit, bool))
            for pool, array in zip(pools, arrays):
                pool.array = array
        if not collect:
            return ids, logits
        after, conf, logits, routed = self.collect_block_step((ids, logits))
        return (after, conf, logits, *pools, routed)

    launch_block_step = functools.partialmethod(block_step, collect=False)

    @staticmethod
    def collect_block_step(launch):
        """→ (the blocks after the step [W, B] and each position's
        confidence [W, B], on the host, in the one fetch; the logits, a
        device array; the routed counts, as :meth:`collect_decode`)."""
        ids, logits = launch
        with _span("decode_fetch"):           # blocks on the device
            ids = np.asarray(ids)
        w, bl = logits.shape[:2]
        n = w * bl
        routed = dict(zip(("experts_hit", "expert_load_max"),
                          map(int, ids[2 * n:])))
        return (ids[:n].reshape(w, bl), ids[n:2 * n].view(np.float32)
                .reshape(w, bl), logits, routed)

    # -------------------------------------------------------- artifacts
    @classmethod
    def from_artifact(cls, dirname: str, verify: bool = True
                      ) -> "DecoderModel":
        """Load an exported decoder artifact (int8 entries dequantized
        once at load through the shared loader path).  ``verify``
        re-hashes the payload against the manifest digests first —
        a torn artifact raises :class:`loader.TornArtifact` before any
        weight byte is interpreted."""
        manifest = _loader.read_manifest(dirname)
        if verify:
            _loader.verify_artifact(dirname, manifest)
        enforce(manifest.get("kind") == "decoder",
                f"{dirname}: not a decoder artifact "
                f"(kind={manifest.get('kind')!r}); ServedModel.load "
                "handles module artifacts")
        # JSON has no tuple: the plan comes back a list (a config must
        # hash); an artifact from before the plan has no such key and
        # loads as the default plan
        shape = dict(manifest["decoder"])
        shape["plan"] = tuple(shape.get("plan", ()))
        cfg = DecoderConfig(**shape)
        wsec = manifest["weights"]
        weights = _loader.load_weight_entries(dirname, wsec)
        params = {e["name"]: w
                  for e, w in zip(wsec["entries"], weights)}
        model = cls(params, cfg)
        # testing knob (export_decoder extra_meta): a seeded-slow
        # artifact carries debug_prefill_delay_ms in its manifest; the
        # server's _prefill sleeps it inside the TTFT stamp so a canary
        # bake has a deterministic latency regression to detect
        delay_ms = manifest.get("debug_prefill_delay_ms")
        if delay_ms:
            model.debug_prefill_delay_s = float(delay_ms) / 1e3
        return model


def export_decoder(params: Dict[str, Any], cfg: DecoderConfig,
                   dirname: str, quantize: Optional[str] = "int8",
                   dequant_dtype: str = "float32",
                   extra_meta: Optional[Dict[str, Any]] = None) -> str:
    """Write a decoder artifact: the version-2 weights layout of
    ``serving/export.py`` (int8 per-channel for ≥2-D floats when
    ``quantize="int8"``, raw otherwise) plus ``"kind": "decoder"`` and
    the :class:`DecoderConfig` in the manifest.  No StableHLO module —
    the paged decode loop is live code, not an exported graph.

    ``extra_meta`` lands verbatim in the manifest — the rollout
    pipeline records provenance there (``source_ckpt_digest``,
    ``source_ckpt``) so exactly-once export survives watcher restarts
    without any side-channel state file."""
    if quantize is None:
        store = {}
        entries = []
        for name in sorted(params):
            arr = np.asarray(params[name])
            store["w::" + name] = arr
            entries.append({"name": name, "shape": list(arr.shape),
                            "dtype": str(arr.dtype), "quantized": False,
                            "axis": None})
        scheme = "none"
    else:
        enforce(quantize == "int8",
                f"export_decoder: unknown quantize scheme {quantize!r}")
        store, entries = _export.quantize_weight_store(params, dequant_dtype)
        scheme = _export.QUANT_SCHEME
    os.makedirs(dirname, exist_ok=True)
    np.savez(os.path.join(dirname, _export.WEIGHTS_FILE), **store)
    manifest = {
        "format": _export.FORMAT_NAME,
        "version": _export.QUANT_FORMAT_VERSION,
        "kind": "decoder",
        "decoder": dict(cfg._asdict()),
        "weights": {
            "file": _export.WEIGHTS_FILE,
            "scheme": scheme,
            "dequant_dtype": dequant_dtype,
            "entries": entries,
        },
    }
    if extra_meta:
        for k, v in extra_meta.items():
            enforce(k not in manifest,
                    f"export_decoder: extra_meta key {k!r} collides with "
                    "a manifest field")
            manifest[k] = v
    _export.stamp_manifest(manifest, dirname, [_export.WEIGHTS_FILE])
    with open(os.path.join(dirname, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    return dirname
