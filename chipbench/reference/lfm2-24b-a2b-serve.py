"""Plain reference of LFM2-24B-A2B's decoder (``lfm2_moe``) at its
published widths, as ``configs/lfm2-24b-a2b-serve.json`` cuts it in
depth.

d = hidden, H query heads over G K/V heads of D = d / H, K =
``conv_L_cache`` taps, E experts, k a token.  Write rms(x; g) = x /
sqrt(mean(x²) + eps) ⊙ g.  For a block with input x [T, d]:

- h = x + mixer(rms(x; g_op)); x' = h + ffn(rms(h; g_ffn)): two norms a
  block, nothing else normed but the heads' q and k;
- **conv** mixer (``layer_types[i] == "conv"``): [B | C | u] = m·W_in,
  three thirds of 3d in this order; z = B ⊙ u; c_t = Σ_{j<K} w[:, j] ⊙
  z_{t-(K-1)+j}, a causal depthwise sum with z_s = 0 for s < 0;
  y = (C ⊙ c)·W_out.  No bias;
- **attention** mixer (``full_attention``): q = m·Wq [T,H,D], k = m·Wk,
  v = m·Wv [T,G,D]; q <- RoPE(rms_D(q; gq)), k <- RoPE(rms_D(k; gk))
  (per head over D, then the half-split rotation of all D lanes at the
  token's position, θ = ``rope_parameters.rope_theta``); query head h
  attends K/V head h // (H/G); scores q·kT/sqrt(D), causal, full;
  y = softmax(scores)·v·Wo.  No gate, no bias;
- dense FFN (the leading ``num_dense_layers``): (silu(m·Wgate) *
  (m·Wup))·Wdown;
- routed FFN: s = sigmoid(m·Wr) in float32; S = the k largest of s + b
  (``use_expert_bias``: the bias chooses, it does not weigh); w_e =
  ``routed_scaling_factor`` · s_e / (sum_{e in S} s_e + 1e-6)
  (``norm_topk_prob``); y = sum_{e in S} w_e · Expert_e(m), each a
  SiLU-gated MLP.  No shared expert, no token dropped;
- x0 = Embed[token]; logits = rms(x_L; gf)·Whead (a head of its own).

One teacher-forced forward over whole sequences in float32: the conv's
sum over the whole sequence at once (no state), dense attention a block
of queries at a time, **every expert on every token** a group of experts
at a time (weighted by w, which is 0 for the experts not chosen), no
cache, no paging, no sorting, one sequence after the other; weights come
from the host one layer at a time.  The caller sets
``jax.default_matmul_precision("highest")``.  ``cast`` rounds what the
configuration states in bfloat16: the operands of every matrix product
and z, which the served model keeps as its conv state; the identity for
the reference, a lower precision for the control.  The router's product
and the conv's sum are stated in float32 and are never cast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128            # rows of scores held at once: [H, 128, T]
EXPERT_GROUP = 8             # experts whose hidden layer is held at once

NORM_LEAVES = ("operator_norm", "ffn_norm")
CONV_LEAVES = ("in_proj", "conv", "out_proj")
ATTN_LEAVES = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTED_LEAVES = ("router", "router_bias", "experts_gate", "experts_up",
                 "experts_down")


def layer_kinds(sizes):
    """[(conv?, routed?)] of the layers that are run: the first
    ``num_dense_layers`` have the dense feed-forward."""
    types = sizes["layer_types"]
    assert len(types) == int(sizes["num_hidden_layers"])
    assert set(types) <= {"conv", "full_attention"}, types
    return [(t == "conv", i >= int(sizes["num_dense_layers"]))
            for i, t in enumerate(types)]


def head_dim(sizes) -> int:
    return int(sizes["hidden_size"]) // int(sizes["num_attention_heads"])


def param_spec(sizes):
    d, v = int(sizes["hidden_size"]), int(sizes["vocab_size"])
    h, g = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    dh, f = head_dim(sizes), int(sizes["intermediate_size"])
    e, fe = int(sizes["num_experts"]), int(sizes["moe_intermediate_size"])
    taps = int(sizes["conv_L_cache"])
    # no embedding scale in this family: drawn at 1 the stream is of
    # unit size, as what a layer adds to it is
    spec = {"tok_embed": ((v, d), "normal", 1.0),
            "final_norm": ((d,), "gain", 0.1),
            "lm_head": ((d, v), "normal", 1.0 / math.sqrt(d))}
    mat = lambda a, b: ((a, b), "normal", 1.0 / math.sqrt(a))
    for i, (conv, routed) in enumerate(layer_kinds(sizes)):
        p = f"layers.{i}."
        for n in NORM_LEAVES:
            spec[p + n] = ((d,), "gain", 0.1)
        if conv:
            spec[p + "in_proj"], spec[p + "out_proj"] = mat(d, 3 * d), \
                mat(d, d)
            # z is of unit size, so taps N(0, 1/K) give a sum of unit
            # size in which every tap carries a K-th: a program that
            # drops one, or reads them in another order, loses that much
            spec[p + "conv"] = ((d, taps), "normal", 1.0 / math.sqrt(taps))
        else:
            spec[p + "q_norm"] = ((dh,), "gain", 0.1)
            spec[p + "k_norm"] = ((dh,), "gain", 0.1)
            spec[p + "wq"], spec[p + "wo"] = mat(d, h * dh), mat(h * dh, d)
            spec[p + "wk"], spec[p + "wv"] = mat(d, g * dh), mat(d, g * dh)
        if not routed:
            spec[p + "w_gate"], spec[p + "w_up"] = mat(d, f), mat(d, f)
            spec[p + "w_down"] = mat(f, d)
            continue
        spec[p + "router"] = mat(d, e)
        # a tenth of the scores' own spread (sigmoid of N(0,1): 0.21):
        # the bias changes about one choice in eight of a token's
        spec[p + "router_bias"] = ((e,), "normal", 0.1)
        spec[p + "experts_gate"] = ((e, d, fe), "normal", 1 / math.sqrt(d))
        spec[p + "experts_up"] = ((e, d, fe), "normal", 1 / math.sqrt(d))
        spec[p + "experts_down"] = ((e, fe, d), "normal",
                                    1 / math.sqrt(fe))
    return spec


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, N, D] at positions 0..T-1: lane j of the first half turns
    with lane j of the second by the angle pos · theta^(-2j/D)."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _attention(q, k, v, cast):
    """q [T,H,D], k, v [T,G,D] → [T, H·D]; causal, full."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kc, vc = cast(k), cast(v)
    j = jnp.arange(t)[None, :]

    def block(args):
        qb, i0 = args                                   # [Bq,H,D], start
        i = i0 + jnp.arange(qb.shape[0])[:, None]
        s = jnp.einsum("qhd,khd->hqk", cast(qb), kc) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where((j <= i)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), vc)

    nb = t // QUERY_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, QUERY_BLOCK, h, d),
                              jnp.arange(nb) * QUERY_BLOCK))
    return out.reshape(t, h * d)


def _short_conv(m, w, cast):
    """The gated short convolution of one sequence, m [T, d] → [T, d]."""
    mm = lambda a, b: cast(a) @ cast(b)
    t, d = m.shape
    bcu = mm(m, w["in_proj"])
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    z = cast(b * u)
    taps = w["conv"].shape[1]
    conv = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j                  # tap j weighs z_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros((back, d), z.dtype), z[:t - back]]) if back else z
        conv = conv + w["conv"][:, j] * shifted
    return mm(c * conv, w["out_proj"])


def _gated_mlp(m, w_gate, w_up, w_down, cast):
    mm = lambda a, b: cast(a) @ cast(b)
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def _routed(m, w, sizes, cast):
    k = int(sizes["num_experts_per_tok"])
    s = jax.nn.sigmoid(m @ w["router"])                      # [T, E]
    _, chosen = jax.lax.top_k(
        s + w["router_bias"] if sizes["use_expert_bias"] else s, k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    share = picked / (picked.sum(axis=1, keepdims=True) + 1e-6) \
        if sizes["norm_topk_prob"] else picked
    weight = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(
        float(sizes["routed_scaling_factor"]) * share)       # 0: not chosen
    e = s.shape[1]
    groups = e // EXPERT_GROUP

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


def _block(x, w, sizes, conv, routed, cast):
    """One sequence [T, d] through one layer."""
    t, d = x.shape
    eps = float(sizes["norm_eps"])
    mm = lambda a, b: cast(a) @ cast(b)
    a = _rms(x, w["operator_norm"], eps)
    if conv:
        x = x + _short_conv(a, w, cast)
    else:
        h, g = int(sizes["num_attention_heads"]), \
            int(sizes["num_key_value_heads"])
        dh = head_dim(sizes)
        theta = float(sizes["rope_parameters"]["rope_theta"])
        q = _rope(_rms(mm(a, w["wq"]).reshape(t, h, dh), w["q_norm"], eps),
                  theta)
        k = _rope(_rms(mm(a, w["wk"]).reshape(t, g, dh), w["k_norm"], eps),
                  theta)
        v = mm(a, w["wv"]).reshape(t, g, dh)
        x = x + mm(_attention(q, k, v, cast), w["wo"])
    m = _rms(x, w["ffn_norm"], eps)
    return x + (_routed(m, w, sizes, cast) if routed else _gated_mlp(
        m, w["w_gate"], w["w_up"], w["w_down"], cast))


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [B, T] (padded at the end; T a multiple of 128),
    ``positions`` [B, N] → float32 logits [B, N, V] of the token that
    follows each position."""
    b, t = tokens.shape
    assert t % QUERY_BLOCK == 0, t
    assert not sizes["conv_bias"] \
        and sizes["rope_parameters"]["rope_type"] == "default"
    xs = [jnp.asarray(weights["tok_embed"][tokens[r]]) for r in range(b)]
    blocks = {}                   # one program a kind of layer
    for i, (conv, routed) in enumerate(layer_kinds(sizes)):
        leaves = NORM_LEAVES + (CONV_LEAVES if conv else ATTN_LEAVES) \
            + (ROUTED_LEAVES if routed else DENSE_LEAVES)
        w = {k: jnp.asarray(weights[f"layers.{i}.{k}"]) for k in leaves}
        if (conv, routed) not in blocks:
            blocks[conv, routed] = jax.jit(
                lambda x, w, c=conv, r=routed:
                _block(x, w, sizes, c, r, cast))
        xs = [blocks[conv, routed](x, w) for x in xs]
        del w
    eps = float(sizes["norm_eps"])
    head = jax.jit(lambda hid, g, m: cast(_rms(hid, g, eps)) @ cast(m))
    picked = jnp.stack([x[jnp.asarray(positions[r])]
                        for r, x in enumerate(xs)])
    return np.asarray(head(picked, jnp.asarray(weights["final_norm"]),
                           jnp.asarray(weights["lm_head"])))
