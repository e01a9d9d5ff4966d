"""Plain reference of SDAR-30B-A3B-Chat's decoder (``sdar_moe``, a
Qwen3-MoE block) at its published widths, as
``configs/sdar-30b-a3b-serve.json`` cuts it in depth, and of its
generation by diffusion over blocks.

d = hidden, H query heads over G K/V heads of D, E experts, k a token,
blocks of B positions counted from position 0.  For a layer with input
x [T, d]:

- a = RMS(x; g1); q = a·Wq [T,H,D], k = a·Wk [T,G,D], v = a·Wv [T,G,D];
- q <- RoPE(RMS_D(q; gq)), k <- RoPE(RMS_D(k; gk)): the head norms over
  D first, then the rotation at the token's position (half-split: lane
  j turns with lane j + D/2 by pos · θ^(-2j/D));
- query head h attends K/V head h // (H/G); scores q·kT/sqrt(D); key j
  is visible to query i iff ⌊j/B⌋ <= ⌊i/B⌋ (block-causal);
  x <- x + (softmax(scores)·v)·Wo;
- m = RMS(x; g2); p = softmax(m·Wr) over all E experts in float32; S =
  the k largest of p; x <- x + sum_{e in S} (p_e / sum_S p) ·
  Expert_e(m), each (silu(m·Wgate_e) * (m·Wup_e))·Wdown_e.  No shared
  expert, no selection bias, no token dropped;
- x0 = Embed[token]; logits = RMS(x_L; gf)·Whead (untied); the logits
  at position i predict the token AT position i.

**A denoising step** of a block at positions s … s+B-1 is the block's
ids as they stood, the mask id where a position was masked, through the
same layers at its own positions: it sees the committed sequence before
s (blocks before its own, whose K/V were written from their final ids)
and itself whole, nothing else.  :func:`run` computes the committed
sequence and every step of a request in ONE pass a layer: its tokens
are the sequence followed by every step's block, and an explicit mask
gives each query what it sees (a sequence query: the block-causal
prefix; a step's query: the sequence before its block's start, and its
own step's block).

Float32, dense attention a block of queries at a time, **every expert
on every token** a group of experts at a time (weighted by the routing
weight, 0 for the experts not chosen), no cache, no paging; weights come
from the host one layer at a time and every request passes a layer
before the next is fetched.  The caller sets
``jax.default_matmul_precision("highest")``.  ``cast`` rounds the
operands of every matrix product the configuration states in bfloat16:
the identity for the reference, a lower precision for the control.  The
router's product is stated in float32 and is never cast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128            # rows of scores held at once: [H, 128, T]
EXPERT_GROUP = 8             # experts whose hidden layer is held at once
HIDDEN = -1e30               # a score no query sees (finite: a padding
#                              query that sees nothing stays finite)

LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
          "ffn_norm", "router", "experts_gate", "experts_up",
          "experts_down")


def block_length(sizes) -> int:
    return int(sizes["block_length"])


def param_spec(sizes):
    d, v = int(sizes["hidden_size"]), int(sizes["vocab_size"])
    h, g = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    dh = int(sizes["head_dim"])
    e, fe = int(sizes["num_experts"]), int(sizes["moe_intermediate_size"])
    # the embedding at the scale of every other matrix, under what the
    # first layer adds: a stream that is the token's embedding and little
    # else gives every masked position of every row one argmax, answers of
    # a few ids repeated, and a launch that reaches as many experts as the
    # seed happens to give those ids
    spec = {"tok_embed": ((v, d), "normal", 1.0 / math.sqrt(d)),
            "final_norm": ((d,), "gain", 0.1),
            "lm_head": ((d, v), "normal", 1.0 / math.sqrt(d))}
    mat = lambda a, b: ((a, b), "normal", 1.0 / math.sqrt(a))
    for i in range(int(sizes["num_hidden_layers"])):
        p = f"layers.{i}."
        spec[p + "attn_norm"] = ((d,), "gain", 0.1)
        spec[p + "ffn_norm"] = ((d,), "gain", 0.1)
        spec[p + "q_norm"] = ((dh,), "gain", 0.1)
        spec[p + "k_norm"] = ((dh,), "gain", 0.1)
        spec[p + "wq"] = mat(d, h * dh)
        spec[p + "wk"], spec[p + "wv"] = mat(d, g * dh), mat(d, g * dh)
        spec[p + "wo"] = mat(h * dh, d)
        spec[p + "router"] = mat(d, e)
        spec[p + "experts_gate"] = ((e, d, fe), "normal", 1 / math.sqrt(d))
        spec[p + "experts_up"] = ((e, d, fe), "normal", 1 / math.sqrt(d))
        spec[p + "experts_down"] = ((e, fe, d), "normal", 1 / math.sqrt(fe))
    return spec


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x [T, N, D] at positions ``pos`` [T], half-split rotation."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                     / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _sees(q, k, b):
    """[Tq, Tk] visibility from the tokens' descriptors (``pos``,
    ``seq``: a token of the committed sequence, ``real``: no padding,
    ``step``: which step's block a token is, −1 for the sequence)."""
    bq, bk = q["pos"][:, None] // b, k["pos"][None, :] // b
    prefix = k["seq"][None, :] & k["real"][None, :] & (
        (bk < bq) | (q["seq"][:, None] & (bk == bq)))
    own = ~k["seq"][None, :] & ~q["seq"][:, None] \
        & (q["step"][:, None] == k["step"][None, :])
    return prefix | own


def _attention(q, k, v, desc, b, cast):
    """q [T,H,D], k, v [T,G,D] of the tokens ``desc`` → [T, H·D]."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kc, vc = cast(k), cast(v)

    def block(args):
        qb, i0 = args                                   # [Bq,H,D], start
        rows = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i0, QUERY_BLOCK), desc)
        s = jnp.einsum("qhd,khd->hqk", cast(qb), kc) / math.sqrt(d)
        s = jnp.where(_sees(rows, desc, b)[None], s, HIDDEN)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), vc)

    nb = t // QUERY_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, QUERY_BLOCK, h, d),
                              jnp.arange(nb) * QUERY_BLOCK))
    return out.reshape(t, h * d)


def route_weights(m, router, top_k: int):
    """[T, E] routing weights: softmax over all experts in float32, the
    ``top_k`` largest kept and renormalised to sum 1, 0 elsewhere."""
    p = jax.nn.softmax(m @ router, axis=-1)
    picked, chosen = jax.lax.top_k(p, top_k)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None], chosen].set(
        picked / picked.sum(axis=1, keepdims=True))


def _routed(m, w, sizes, cast):
    weight = route_weights(m, w["router"], int(sizes["num_experts_per_tok"]))
    groups = weight.shape[1] // EXPERT_GROUP

    def group(y, args):
        wg, wu, wd, wt = args        # [Ge,d,f] [Ge,d,f] [Ge,f,d] [Ge,T]
        hid = jax.nn.silu(jnp.einsum("td,edf->etf", cast(m), cast(wg))) \
            * jnp.einsum("td,edf->etf", cast(m), cast(wu))
        out = jnp.einsum("etf,efd->etd", cast(hid), cast(wd))
        return y + jnp.einsum("etd,et->td", out, wt), None

    split = lambda a: a.reshape(groups, EXPERT_GROUP, *a.shape[1:])
    y, _ = jax.lax.scan(
        group, jnp.zeros_like(m),
        (split(w["experts_gate"]), split(w["experts_up"]),
         split(w["experts_down"]), split(weight.T)))
    return y


def _layer(x, w, desc, sizes, cast):
    """The tokens ``desc`` of one request [T, d] through one layer."""
    t, _ = x.shape
    h, g = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    dh, eps = int(sizes["head_dim"]), float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_theta"])
    mm = lambda a, b: cast(a) @ cast(b)
    a = _rms(x, w["attn_norm"], eps)
    q = _rope(_rms(mm(a, w["wq"]).reshape(t, h, dh), w["q_norm"], eps),
              desc["pos"], theta)
    k = _rope(_rms(mm(a, w["wk"]).reshape(t, g, dh), w["k_norm"], eps),
              desc["pos"], theta)
    v = mm(a, w["wv"]).reshape(t, g, dh)
    x = x + mm(_attention(q, k, v, desc, block_length(sizes), cast),
               w["wo"])
    return x + _routed(_rms(x, w["ffn_norm"], eps), w, sizes, cast)


def _pad(n: int) -> int:
    return -(-max(n, 1) // QUERY_BLOCK) * QUERY_BLOCK


def _tokens(req, mask_id: int):
    """One request's token list and descriptors: the committed sequence
    padded to whole query blocks, then every step's block."""
    seq = np.asarray(req["seq"], np.int64)
    starts = np.asarray(req.get("starts", ()), np.int64)
    blocks = np.asarray(req.get("blocks", np.zeros((0, 1))), np.int64)
    n, b = blocks.shape if blocks.size else (0, 1)
    ts, tb = _pad(len(seq)), _pad(n * b)
    ids = np.zeros(ts + tb, np.int64)
    ids[:len(seq)] = seq
    ids[ts:ts + n * b] = np.where(blocks < 0, mask_id, blocks).reshape(-1)
    pos = np.zeros(ts + tb, np.int64)
    pos[:ts] = np.arange(ts)
    pos[ts:ts + n * b] = (starts[:, None] + np.arange(b)).reshape(-1)
    real = np.zeros(ts + tb, bool)
    real[:len(seq)] = True
    real[ts:ts + n * b] = True
    step = np.full(ts + tb, -1, np.int64)
    step[ts:] = np.arange(tb) // b
    step[ts + n * b:] = n                 # padding: a step of its own
    desc = {"pos": jnp.asarray(pos, jnp.int32),
            "seq": jnp.asarray(np.arange(ts + tb) < ts),
            "real": jnp.asarray(real), "step": jnp.asarray(step, jnp.int32)}
    return ids, desc, ts


def run(weights, sizes, requests, cast=lambda a: a,
        reduce=lambda logits, i: logits):
    """``requests``: ``{"seq": committed ids [T], "at": positions of the
    sequence whose logits are wanted [K], "starts": each step's block
    start [N], "blocks": each step's block as it stood [N, B] (−1 where
    masked)}`` (``at`` or ``starts``/``blocks`` may be left out) → per
    request ``(logits at "at" [K, V], reduce(each step's logits
    [N, B, V], the request's index))``, ``reduce`` on the device."""
    mask_id = int(sizes["mask_token_id"])
    eps = float(sizes["rms_norm_eps"])
    b = block_length(sizes)
    built = [_tokens(r, mask_id) for r in requests]
    xs = [jnp.asarray(weights["tok_embed"][ids]) for ids, _, _ in built]
    layer = jax.jit(lambda x, w, desc: _layer(x, w, desc, sizes, cast))
    for i in range(int(sizes["num_hidden_layers"])):
        w = {k: jnp.asarray(weights[f"layers.{i}.{k}"]) for k in LEAVES}
        xs = [layer(x, w, desc) for x, (_, desc, _) in zip(xs, built)]
        del w
    # the head's weights are arguments: closed over, a jit would embed
    # them in the program as a constant of 1.2 GB
    head = jax.jit(lambda hid, g, m: cast(_rms(hid, g, eps)) @ cast(m))
    gf, wh = (jnp.asarray(weights[k]) for k in ("final_norm", "lm_head"))
    out = []
    for i, (x, r, (_, _, ts)) in enumerate(zip(xs, requests, built)):
        at = jnp.asarray(np.asarray(r.get("at", ()), np.int32))
        n = len(r.get("starts", ()))
        steps = head(x[ts:ts + n * b], gf, wh).reshape(n, b, -1)
        out.append((np.asarray(head(x[at], gf, wh)),
                    jax.device_get(reduce(steps, i))))
    return out


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [R, T] (whole sequences, no padding inside a block
    that a position sees), ``positions`` [R, N] → float32 logits
    [R, N, V] at each position, block-causal."""
    got = run(weights, sizes,
              [{"seq": tokens[r], "at": positions[r]}
               for r in range(tokens.shape[0])], cast)
    return np.stack([a for a, _ in got])
