"""Plain reference of JoyAI-LLM-Flash's decoder (``joyai_llm_flash``:
multi-head latent attention, 256 routed experts) at its published
widths, as ``configs/joyai-llm-flash-serve.json`` cuts it in depth.

d = hidden, H heads, r_q = q_lora_rank, r = kv_lora_rank, d_n / d_r the
position-free and the rotary part of a head's query and key, d_v a
head's value, E experts, k a token.  For a block with input x [T, d]:

- a = RMS(x; g1).  c_q = RMS(a·W_dq; g_q) [T, r_q]; q = c_q·W_uq
  [T, H, d_n + d_r], split into q_n [T, H, d_n] and q_r [T, H, d_r];
- [c ; k_r] = a·W_dkv [T, r + d_r]; c <- RMS(c; g_kv); k_r [T, d_r] is
  ONE key part for all heads.  [k_n ; v] = c·W_ukv [T, H, d_n + d_v];
- q_r, k_r <- RoPE at the token's position, **interleaved pairs**: lane
  2j turns with lane 2j + 1 by the angle pos · theta^(-2j/d_r); no
  scaling.  q_n and k_n carry no position;
- head h: scores (q_n·k_nT + q_r·k_rT) / sqrt(d_n + d_r); key j is
  visible to query i iff j <= i; softmax in float32; o_h = P·v [T, d_v];
  x <- x + concat_h(o_h)·W_o.  No bias anywhere;
- m = RMS(x; g2); x <- x + FFN(m): two norms a block, no post-norms, no
  gate, no head norms;
- dense FFN (the leading layers): (silu(m·Wgate) * (m·Wup))·Wdown;
- routed FFN: s = sigmoid(m·Wr) in float32; S = the k largest of s + b
  (the bias chooses, it does not weigh; one group: no group limit);
  w_e = routed_scaling_factor · s_e / (sum_{e in S} s_e + 1e-20);
  y = Shared(m) + sum_{e in S} w_e · Expert_e(m), each a SiLU-gated MLP.
  No token is dropped;
- x0 = Embed[token] (no scale); logits = RMS(x_L; gf)·Whead (untied).

This is the **expanded** form only: every head's keys and values are
made from the latent and attended densely; nothing is cached, nothing
absorbed.  One teacher-forced forward over whole sequences in float32:
dense attention a block of queries at a time, **every expert on every
token** (weighted by w, which is 0 for the experts not chosen), no
paging, no sorting, one sequence after the other.  Weights come from
the host one layer at a time, a routed layer's experts ``DEVICE_GROUP``
at a time (one routed layer is 4.8 GB in float32).  The caller sets
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
DEVICE_GROUP = 32            # experts whose weights are on the device
#: spread of the two latent norms' gains, 1 + N(0, 0.3²): with matrices
#: drawn N(0, 1/fan_in) a latent is of unit size before its norm, so
#: the gain is all that a program which drops the norm would lose
LATENT_GAIN = 0.3

ATTN_LEAVES = ("attn_norm", "q_a_proj", "q_a_norm", "q_b_proj",
               "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj", "ffn_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTER_LEAVES = ("router", "router_bias", "shared_gate", "shared_up",
                 "shared_down")
EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")


def routed_layers(sizes):
    """[routed?] of the layers that are run: the first
    ``first_k_dense_replace`` have the dense feed-forward."""
    assert int(sizes["moe_layer_freq"]) == 1
    return [i >= int(sizes["first_k_dense_replace"])
            for i in range(int(sizes["num_hidden_layers"]))]


def param_spec(sizes):
    d, v = int(sizes["hidden_size"]), int(sizes["vocab_size"])
    h = int(sizes["num_attention_heads"])
    rq, r = int(sizes["q_lora_rank"]), int(sizes["kv_lora_rank"])
    dn, dr = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    dv, f = int(sizes["v_head_dim"]), int(sizes["intermediate_size"])
    e, fe = int(sizes["n_routed_experts"]), int(sizes["moe_intermediate_size"])
    fs = fe * int(sizes["n_shared_experts"])
    # no embedding scale: rows of unit size make the unit stream a
    # trained embedding gives
    spec = {"tok_embed": ((v, d), "normal", 1.0),
            "final_norm": ((d,), "gain", 0.1),
            "lm_head": ((d, v), "normal", 1.0 / math.sqrt(d))}
    mat = lambda a, b: ((a, b), "normal", 1.0 / math.sqrt(a))
    for i, routed in enumerate(routed_layers(sizes)):
        p = f"layers.{i}."
        spec[p + "attn_norm"] = ((d,), "gain", 0.1)
        spec[p + "ffn_norm"] = ((d,), "gain", 0.1)
        spec[p + "q_a_proj"] = mat(d, rq)
        spec[p + "q_a_norm"] = ((rq,), "gain", LATENT_GAIN)
        spec[p + "q_b_proj"] = mat(rq, h * (dn + dr))
        spec[p + "kv_a_proj"] = mat(d, r + dr)
        spec[p + "kv_a_norm"] = ((r,), "gain", LATENT_GAIN)
        spec[p + "kv_b_proj"] = mat(r, h * (dn + dv))
        spec[p + "o_proj"] = mat(h * dv, d)
        if not routed:
            spec[p + "w_gate"], spec[p + "w_up"] = mat(d, f), mat(d, f)
            spec[p + "w_down"] = mat(f, d)
            continue
        spec[p + "router"] = mat(d, e)
        # a tenth of the scores' own spread (sigmoid of N(0,1): 0.21):
        # the bias changes a visible share of a token's choices
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


def _rope(x, theta, interleave):
    """x [T, N, D] at positions 0..T-1, pair j turning by the angle
    pos · theta^(-2j/D): lanes (2j, 2j + 1) where the configuration
    states ``rope_interleave``, lanes (j, j + D/2) otherwise."""
    t, n, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        pairs = x.reshape(t, n, half, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(t, n, d)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, cast):
    """q, k [T,H,Dqk], v [T,H,Dv] → [T, H·Dv], causal."""
    t, h, d = q.shape
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
    return out.reshape(t, -1)


def _gated_mlp(m, w_gate, w_up, w_down, cast):
    mm = lambda a, b: cast(a) @ cast(b)
    return mm(jax.nn.silu(mm(m, w_gate)) * mm(m, w_up), w_down)


def _attend(x, w, sizes, cast):
    """One sequence [T, d] through a layer's attention: → the stream
    with the attention's output added."""
    t = x.shape[0]
    h, r = int(sizes["num_attention_heads"]), int(sizes["kv_lora_rank"])
    dn = int(sizes["qk_nope_head_dim"])
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    assert sizes["rope_scaling"] is None and not sizes["attention_bias"]
    mm = lambda a, b: cast(a) @ cast(b)
    a = _rms(x, w["attn_norm"], eps)
    cq = _rms(mm(a, w["q_a_proj"]), w["q_a_norm"], eps)
    q = mm(cq, w["q_b_proj"]).reshape(t, h, -1)
    ckr = mm(a, w["kv_a_proj"])
    c = _rms(ckr[:, :r], w["kv_a_norm"], eps)
    kv = mm(c, w["kv_b_proj"]).reshape(t, h, -1)
    turn = lambda z: _rope(z, theta, bool(sizes["rope_interleave"]))
    q = jnp.concatenate([q[..., :dn], turn(q[..., dn:])], axis=-1)
    k_r = jnp.broadcast_to(turn(ckr[:, None, r:]),
                           (t, h, ckr.shape[1] - r))
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    o = _attention(q, k, kv[..., dn:], cast)
    return x + mm(o, w["o_proj"])


def _route_weights(m, w, sizes):
    """[T, E]: each token's weight on every expert, 0 where it is not
    among the token's ``num_experts_per_tok``."""
    assert sizes["scoring_func"] == "sigmoid" \
        and int(sizes["n_group"]) == 1 and int(sizes["topk_group"]) == 1
    s = jax.nn.sigmoid(m @ w["router"])                      # [T, E]
    _, chosen = jax.lax.top_k(s + w["router_bias"],
                              int(sizes["num_experts_per_tok"]))
    picked = jnp.take_along_axis(s, chosen, axis=1)
    share = picked / (picked.sum(axis=1, keepdims=True) + 1e-20) \
        if sizes["norm_topk_prob"] else picked
    return jnp.zeros_like(s).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(
        float(sizes["routed_scaling_factor"]) * share)


def _experts(y, m, wg, wu, wd, weight, cast):
    """y + the weighted outputs of the experts whose weights are given
    ([G,d,f] [G,d,f] [G,f,d]; ``weight`` [G, T])."""
    groups = wg.shape[0] // EXPERT_GROUP

    def group(y, args):
        g, u, dn, wt = args
        hid = jax.nn.silu(jnp.einsum("td,edf->etf", cast(m), cast(g))) \
            * jnp.einsum("td,edf->etf", cast(m), cast(u))
        out = jnp.einsum("etf,efd->etd", cast(hid), cast(dn))
        return y + jnp.einsum("etd,et->td", out, wt), None

    split = lambda a: a.reshape(groups, EXPERT_GROUP, *a.shape[1:])
    y, _ = jax.lax.scan(group, y, (split(wg), split(wu), split(wd),
                                   split(weight)))
    return y


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [B, T] (padded at the end; T a multiple of 128),
    ``positions`` [B, N] → float32 logits [B, N, V] of the token that
    follows each position."""
    b, t = tokens.shape
    assert t % QUERY_BLOCK == 0, t
    eps = float(sizes["rms_norm_eps"])
    xs = [jnp.asarray(weights["tok_embed"][tokens[r]]) for r in range(b)]
    attend = jax.jit(lambda x, w: _attend(x, w, sizes, cast))
    dense = jax.jit(lambda x, w: x + _gated_mlp(
        _rms(x, w["ffn_norm"], eps), w["w_gate"], w["w_up"], w["w_down"],
        cast))

    def before(x, w):
        m = _rms(x, w["ffn_norm"], eps)
        return m, _route_weights(m, w, sizes).T, _gated_mlp(
            m, w["shared_gate"], w["shared_up"], w["shared_down"], cast)
    before = jax.jit(before)
    experts = jax.jit(lambda y, m, wg, wu, wd, wt: _experts(
        y, m, wg, wu, wd, wt, cast))
    e = int(sizes["n_routed_experts"])
    step = min(DEVICE_GROUP, e)
    for i, routed in enumerate(routed_layers(sizes)):
        leaf = lambda k: weights[f"layers.{i}.{k}"]
        w = {k: jnp.asarray(leaf(k)) for k in ATTN_LEAVES + (
            ROUTER_LEAVES if routed else DENSE_LEAVES)}
        xs = [attend(x, w) for x in xs]
        if not routed:
            xs = [dense(x, w) for x in xs]
            continue
        ms, wts, ys = zip(*(before(x, w) for x in xs))
        ys = list(ys)                       # the shared expert's output
        for e0 in range(0, e, step):
            wg, wu, wd = (jnp.asarray(leaf(k)[e0:e0 + step])
                          for k in EXPERT_LEAVES)
            ys = [experts(y, m, wg, wu, wd, wt[e0:e0 + step])
                  for y, m, wt in zip(ys, ms, wts)]
            del wg, wu, wd
        xs = [x + y for x, y in zip(xs, ys)]
        del w
    head = jax.jit(lambda hid, g, m: cast(_rms(hid, g, eps)) @ cast(m))
    picked = jnp.stack([x[jnp.asarray(positions[r])]
                        for r, x in enumerate(xs)])
    return np.asarray(head(picked, jnp.asarray(weights["final_norm"]),
                           jnp.asarray(weights["lm_head"])))
