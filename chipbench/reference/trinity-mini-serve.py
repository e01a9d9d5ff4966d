"""Plain reference of Trinity-Mini's decoder (``afmoe``) at its published
widths, as ``configs/trinity-mini-serve.json`` cuts it in depth.

d = hidden, H query heads over G K/V heads of D, window W, E experts,
k a token.  For a block with input x [T, d]:

- a = RMS(x; g1); q = a·Wq [T,H,D], k = a·Wk [T,G,D], v = a·Wv [T,G,D],
  z = a·Wg [T,H·D];
- q <- RMS_D(q; gq), k <- RMS_D(k; gk) (per head, over D); on a
  **sliding** layer q, k <- RoPE(q, k) at the token's position
  (half-split rotation), on a **full** layer no position is applied;
- query head h attends K/V head h // (H/G); scores q·kT/sqrt(D); key j
  is visible to query i iff j <= i and, on a sliding layer, i - j < W;
  o = softmax(scores)·v [T,H·D];
- x <- x + RMS((o * sigmoid(z))·Wo; g2); m = RMS(x; g3);
  x <- x + RMS(FFN(m); g4);
- dense FFN (the leading layers): (silu(m·Wgate) * (m·Wup))·Wdown;
- routed FFN: s = sigmoid(m·Wr) in float32; S = the k largest of s + b
  (the bias chooses, it does not weigh); w_e = route_scale · s_e /
  (sum_{e in S} s_e + 1e-20); y = Shared(m) + sum_{e in S} w_e ·
  Expert_e(m), each a SiLU-gated MLP.  No token is dropped;
- x0 = Embed[token]·sqrt(d); logits = RMS(x_L; gf)·Whead (untied).

One teacher-forced forward over whole sequences in float32: dense
attention a block of queries at a time, **every expert on every token**
a group of experts at a time (weighted by w, which is 0 for the experts
not chosen), no cache, no paging, no sorting, one sequence after the
other; weights come from the host one layer at a time.  The caller sets
``jax.default_matmul_precision("highest")``.  ``cast`` rounds the
operands of every matrix product that the configuration states in
bfloat16: the identity for the reference, a lower precision for the
control.  The router's product is stated in float32 and is never cast.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128            # rows of scores held at once: [H, 128, T]
EXPERT_GROUP = 8             # experts whose hidden layer is held at once

ATTN_LEAVES = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm",
               "wo", "attn_post_norm", "ffn_norm", "ffn_post_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTED_LEAVES = ("router", "router_bias", "experts_gate", "experts_up",
                 "experts_down", "shared_gate", "shared_up", "shared_down")


def layer_kinds(sizes):
    """[(sliding?, routed?)] of the layers that are run: the first
    ``num_dense_layers`` have the dense feed-forward."""
    types = sizes["layer_types"]
    assert len(types) == int(sizes["num_hidden_layers"])
    return [(t == "sliding_attention", i >= int(sizes["num_dense_layers"]))
            for i, t in enumerate(types)]


def param_spec(sizes):
    d, v = int(sizes["hidden_size"]), int(sizes["vocab_size"])
    h, g = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    dh, f = int(sizes["head_dim"]), int(sizes["intermediate_size"])
    e, fe = int(sizes["num_experts"]), int(sizes["moe_intermediate_size"])
    fs = fe * int(sizes["num_shared_experts"])
    # the embedding is drawn at 1/sqrt(d), so that the muP scale sqrt(d)
    # gives a stream of unit size, as a trained embedding's is: drawn at
    # 1 the stream would be sqrt(d) times what a layer adds to it, and
    # no fault inside a layer would reach the logits
    spec = {"tok_embed": ((v, d), "normal", 1.0 / math.sqrt(d)),
            "final_norm": ((d,), "gain", 0.1),
            "lm_head": ((d, v), "normal", 1.0 / math.sqrt(d))}
    mat = lambda a, b: ((a, b), "normal", 1.0 / math.sqrt(a))
    for i, (_, routed) in enumerate(layer_kinds(sizes)):
        p = f"layers.{i}."
        for n in ("attn_norm", "attn_post_norm", "ffn_norm",
                  "ffn_post_norm"):
            spec[p + n] = ((d,), "gain", 0.1)
        spec[p + "q_norm"] = ((dh,), "gain", 0.1)
        spec[p + "k_norm"] = ((dh,), "gain", 0.1)
        spec[p + "wq"], spec[p + "wg"] = mat(d, h * dh), mat(d, h * dh)
        spec[p + "wk"], spec[p + "wv"] = mat(d, g * dh), mat(d, g * dh)
        spec[p + "wo"] = mat(h * dh, d)
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
        spec[p + "shared_gate"], spec[p + "shared_up"] = mat(d, fs), \
            mat(d, fs)
        spec[p + "shared_down"] = mat(fs, d)
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


def _attention(q, k, v, window, cast):
    """q [T,H,D], k, v [T,G,D] → [T, H·D]; ``window`` 0 = full."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    kc, vc = cast(k), cast(v)
    j = jnp.arange(t)[None, :]

    def block(args):
        qb, i0 = args                                   # [Bq,H,D], start
        i = i0 + jnp.arange(qb.shape[0])[:, None]
        seen = j <= i
        if window:
            seen = seen & (i - j < window)
        s = jnp.einsum("qhd,khd->hqk", cast(qb), kc) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", cast(p), vc)

    nb = t // QUERY_BLOCK
    out = jax.lax.map(block, (q.reshape(nb, QUERY_BLOCK, h, d),
                              jnp.arange(nb) * QUERY_BLOCK))
    return out.reshape(t, h * d)


def _gated_mlp(m, w_gate, w_up, w_down, cast):
    mm = lambda a, b: cast(a) @ cast(b)
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def _routed(m, w, sizes, cast):
    k = int(sizes["num_experts_per_tok"])
    s = jax.nn.sigmoid(m @ w["router"])                      # [T, E]
    _, chosen = jax.lax.top_k(s + w["router_bias"], k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    share = picked / (picked.sum(axis=1, keepdims=True) + 1e-20) \
        if sizes["route_norm"] else picked
    weight = jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(
        float(sizes["route_scale"]) * share)                 # 0: not chosen
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
    return y + _gated_mlp(m, w["shared_gate"], w["shared_up"],
                          w["shared_down"], cast)


def _block(x, w, sizes, sliding, routed, cast):
    """One sequence [T, d] through one layer."""
    t, d = x.shape
    h, g = int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"])
    dh, eps = int(sizes["head_dim"]), float(sizes["rms_norm_eps"])
    mm = lambda a, b: cast(a) @ cast(b)
    a = _rms(x, w["attn_norm"], eps)
    q = _rms(mm(a, w["wq"]).reshape(t, h, dh), w["q_norm"], eps)
    k = _rms(mm(a, w["wk"]).reshape(t, g, dh), w["k_norm"], eps)
    v = mm(a, w["wv"]).reshape(t, g, dh)
    if sliding:
        theta = float(sizes["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    o = _attention(q, k, v, int(sizes["sliding_window"]) if sliding else 0,
                   cast)
    o = o * jax.nn.sigmoid(mm(a, w["wg"]))
    x = x + _rms(mm(o, w["wo"]), w["attn_post_norm"], eps)
    m = _rms(x, w["ffn_norm"], eps)
    y = _routed(m, w, sizes, cast) if routed else _gated_mlp(
        m, w["w_gate"], w["w_up"], w["w_down"], cast)
    return x + _rms(y, w["ffn_post_norm"], eps)


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [B, T] (padded at the end; T a multiple of 128),
    ``positions`` [B, N] → float32 logits [B, N, V] of the token that
    follows each position."""
    b, t = tokens.shape
    assert t % QUERY_BLOCK == 0, t
    scale = math.sqrt(int(sizes["hidden_size"])) \
        if sizes["mup_enabled"] else 1.0
    xs = [jnp.asarray(weights["tok_embed"][tokens[r]]) * scale
          for r in range(b)]
    blocks = {}                   # one program a kind of layer
    for i, (sliding, routed) in enumerate(layer_kinds(sizes)):
        leaves = ATTN_LEAVES + (ROUTED_LEAVES if routed else DENSE_LEAVES)
        w = {k: jnp.asarray(weights[f"layers.{i}.{k}"]) for k in leaves}
        if (sliding, routed) not in blocks:
            blocks[sliding, routed] = jax.jit(
                lambda x, w, s=sliding, r=routed:
                _block(x, w, sizes, s, r, cast))
        xs = [blocks[sliding, routed](x, w) for x in xs]
        del w
    eps = float(sizes["rms_norm_eps"])
    head = jax.jit(lambda hid, g, m: cast(_rms(hid, g, eps)) @ cast(m))
    picked = jnp.stack([x[jnp.asarray(positions[r])]
                        for r, x in enumerate(xs)])
    return np.asarray(head(picked, jnp.asarray(weights["final_norm"]),
                           jnp.asarray(weights["lm_head"])))
