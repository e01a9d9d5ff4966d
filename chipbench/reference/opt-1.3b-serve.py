"""Plain reference of the served decoder at OPT-1.3B's published sizes:
learned token and position embeddings, 24 pre-norm blocks (RMS norm,
32-head causal attention scaled by 1/sqrt(64), tanh-approximated GELU
feed-forward, no biases), final RMS norm, untied output head.  RMS norm
and GELU are the served decoder's block, not OPT's LayerNorm and ReLU
(listed under ``assumed`` in the configuration).

One teacher-forced forward over whole sequences in float32, dense
attention, no cache, no paging, no batching tricks; weights come from
the host one layer at a time so that it fits beside nothing else.  The
caller sets ``jax.default_matmul_precision("highest")``.  ``cast``
rounds the operands of every matrix product: the identity for the
reference, a lower precision for the control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
                "w_in", "w_out")


def param_spec(sizes):
    d, f = int(sizes["hidden_size"]), int(sizes["ffn_dim"])
    v, ctx = int(sizes["vocab_size"]), int(sizes["max_position_embeddings"])
    spec = {"tok_embed": ((v, d), "normal", 1.0),
            "pos_embed": ((ctx, d), "normal", 0.02),
            "final_norm": ((d,), "gain", 0.1),
            "lm_head": ((d, v), "normal", 1.0 / math.sqrt(d))}
    for i in range(int(sizes["num_hidden_layers"])):
        p = f"layers.{i}."
        spec[p + "attn_norm"] = ((d,), "gain", 0.1)
        spec[p + "ffn_norm"] = ((d,), "gain", 0.1)
        for w in ("wq", "wk", "wv", "wo"):
            spec[p + w] = ((d, d), "normal", 1.0 / math.sqrt(d))
        spec[p + "w_in"] = ((d, f), "normal", 1.0 / math.sqrt(d))
        spec[p + "w_out"] = ((f, d), "normal", 1.0 / math.sqrt(f))
    return spec


def _rms(x, g):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + NORM_EPS) * g


def _block(x, w, heads, cast):
    b, t, d = x.shape
    dh = d // heads
    mm = lambda a, m: cast(a) @ cast(m)
    xn = _rms(x, w["attn_norm"])
    q = mm(xn, w["wq"]).reshape(b, t, heads, dh)
    k = mm(xn, w["wk"]).reshape(b, t, heads, dh)
    v = mm(xn, w["wv"]).reshape(b, t, heads, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", cast(q), cast(k)) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", cast(p), cast(v)).reshape(b, t, d)
    x = x + mm(a, w["wo"])
    h = jax.nn.gelu(mm(_rms(x, w["ffn_norm"]), w["w_in"]), approximate=True)
    return x + mm(h, w["w_out"])


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [B, T] (padded at the end), ``positions`` [B, N] →
    float32 logits [B, N, V] of the token that follows each position."""
    heads = int(sizes["num_attention_heads"])
    b, t = tokens.shape
    x = jnp.asarray(weights["tok_embed"][tokens]
                    + weights["pos_embed"][:t][None])
    block = jax.jit(lambda x, w: _block(x, w, heads, cast))
    for i in range(int(sizes["num_hidden_layers"])):
        w = {k: jnp.asarray(weights[f"layers.{i}.{k}"])
             for k in LAYER_LEAVES}
        x = block(x, w)
    head = jax.jit(lambda h, g, m: cast(_rms(h, g)) @ cast(m))
    picked = jnp.take_along_axis(
        x, jnp.asarray(positions)[:, :, None], axis=1)
    return np.asarray(head(picked, jnp.asarray(weights["final_norm"]),
                           jnp.asarray(weights["lm_head"])))
