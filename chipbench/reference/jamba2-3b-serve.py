"""Plain reference of AI21-Jamba2-3B's decoder (``jamba``) at its
published widths and depth, as ``configs/jamba2-3b-serve.json`` runs it.

d = hidden, di = ``mamba_expand`` · d, N = ``mamba_d_state``, R =
``mamba_dt_rank``, K = ``mamba_d_conv``, H query heads over G K/V heads
of D = d / H.  Write rms(x; g) = x / sqrt(mean(x²) + eps) ⊙ g, eps =
``rms_norm_eps``.  Layer i attends where i % ``attn_layer_period`` ==
``attn_layer_offset`` and is a Mamba layer otherwise.  For a block with
input x [T, d]:

- h = x + mixer(rms(x; g_in)); x' = h + mlp(rms(h; g_ff)): two norms a
  block, nothing else normed but δ, B and C;
- **Mamba** mixer (Mamba-1): [u | z] = m·W_in (u the first di columns,
  no bias); u' = silu(Σ_{j<K} w[:, j] ⊙ u_{t-(K-1)+j} + b_conv), a
  causal depthwise sum with u_s = 0 for s < 0; [δ | B | C] = u'·W_x
  (R, N, N columns); δ <- rms(δ; g_dt), B <- rms(B; g_B), C <-
  rms(C; g_C); Δ = softplus(δ·W_dt + b_dt) [T, di]; A = −exp(A_log)
  [di, N]; in float32, h_t = exp(Δ_t ⊙ A) ⊙ h_{t−1} + (Δ_t ⊙ u'_t) ⊗
  B_t from h_{−1} = 0, y_t = h_t·C_t + D ⊙ u'_t; out (y ⊙ silu(z))·W_out;
- **attention** mixer: q = m·Wq [T,H,D], k = m·Wk, v = m·Wv [T,G,D];
  **no rotary and no position embedding**; query head h attends K/V
  head h // (H/G); scores q·kT/sqrt(D), causal, full; softmax(scores)·
  v·Wo.  No bias;
- mlp (every layer: ``num_experts`` 1): (silu(m·Wgate) * (m·Wup))·Wdown;
- x0 = E[token] (no scale); logits = rms(x_L; g_f)·Eᵀ (the head is tied).

One teacher-forced forward over whole sequences in float32: the
convolution over the whole sequence at once, the scan one position
after the other (``lax.scan``: no kernel, no chunk), dense attention a
block of queries at a time, no cache, no paging, one sequence after the
other; weights come from the host one layer at a time.  The caller sets
``jax.default_matmul_precision("highest")``.  ``cast`` rounds what the
configuration states in bfloat16: the operands of every matrix product
and u, which the served model keeps as its convolution's window; the
identity for the reference, a lower precision for the control.  The
convolution's sum, the scan and its state are stated in float32 and are
never cast.

**Weights** (``param_spec``; ``chipbench/weights.py`` draws them): as
Mamba inits them, through :func:`layer_weights` where a draw is not the
parameter itself: ``A_log`` = log(1..N) a channel (drawn as zeros and
added), ``b_dt`` = softplus⁻¹(Δ₀) with Δ₀ = 0.001 · 100^Φ(ε) log-uniform
in [0.001, 0.1] from ε ~ N(0, 1), ``D`` = 1.  The system under test
takes the same :func:`layer_weights`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128            # rows of scores held at once: [H, 128, T]

NORM_LEAVES = ("input_norm", "pre_ff_norm")
MLP_LEAVES = ("w_gate", "w_up", "w_down")
ATTN_LEAVES = ("wq", "wk", "wv", "wo")
MAMBA_LEAVES = ("in_proj", "conv", "conv_bias", "x_proj", "dt_norm",
                "b_norm", "c_norm", "dt_proj", "dt_bias", "A_log", "D",
                "out_proj")
#: Δ₀ of the dt bias, log-uniform between these (Mamba's dt_min, dt_max)
DT_RANGE = (1e-3, 1e-1)


def attends(sizes, i: int) -> bool:
    return i % int(sizes["attn_layer_period"]) \
        == int(sizes["attn_layer_offset"])


def dims(sizes):
    """(d, di, N, R, K, H, G, D)."""
    d, h = int(sizes["hidden_size"]), int(sizes["num_attention_heads"])
    return (d, int(sizes["mamba_expand"]) * d, int(sizes["mamba_d_state"]),
            int(sizes["mamba_dt_rank"]), int(sizes["mamba_d_conv"]), h,
            int(sizes["num_key_value_heads"]), d // h)


def param_spec(sizes):
    d, di, n, r, k, h, g, dh = dims(sizes)
    v, f = int(sizes["vocab_size"]), int(sizes["intermediate_size"])
    assert int(sizes["num_experts"]) == 1 and sizes["tie_word_embeddings"]
    # tied: the head is the embedding, so it is drawn N(0, 1/d) and a
    # logit is of unit size, as with a head of its own; the stream then
    # starts small and the first layer's norm brings it to unit size
    spec = {"tok_embed": ((v, d), "normal", 1.0 / math.sqrt(d)),
            "final_norm": ((d,), "gain", 0.1)}
    mat = lambda a, b: ((a, b), "normal", 1.0 / math.sqrt(a))
    for i in range(int(sizes["num_hidden_layers"])):
        p = f"layers.{i}."
        for name in NORM_LEAVES:
            spec[p + name] = ((d,), "gain", 0.1)
        spec[p + "w_gate"], spec[p + "w_up"] = mat(d, f), mat(d, f)
        spec[p + "w_down"] = mat(f, d)
        if attends(sizes, i):
            spec[p + "wq"], spec[p + "wo"] = mat(d, h * dh), mat(h * dh, d)
            spec[p + "wk"], spec[p + "wv"] = mat(d, g * dh), mat(d, g * dh)
            continue
        spec[p + "in_proj"], spec[p + "out_proj"] = mat(d, 2 * di), \
            mat(di, d)
        # u is of unit size: taps N(0, 1/K) give a sum of unit size in
        # which every tap carries a K-th of it
        spec[p + "conv"] = ((di, k), "normal", 1.0 / math.sqrt(k))
        spec[p + "conv_bias"] = ((di,), "normal", 0.1)
        spec[p + "x_proj"], spec[p + "dt_proj"] = mat(di, r + 2 * n), \
            mat(r, di)
        for name, width in (("dt_norm", r), ("b_norm", n), ("c_norm", n)):
            spec[p + name] = ((width,), "gain", 0.1)
        spec[p + "dt_bias"] = ((di,), "normal", 1.0)        # ε: see above
        spec[p + "A_log"] = ((di, n), "zeros", 0.0)         # + log(1..N)
        spec[p + "D"] = ((di,), "gain", 0.0)                # 1
    return spec


def _dt_bias(eps):
    """softplus⁻¹(Δ₀), Δ₀ = lo · (hi/lo)^Φ(ε): log-uniform in the range."""
    lo, hi = DT_RANGE
    phi = 0.5 * (1.0 + np.vectorize(math.erf)(eps / math.sqrt(2.0)))
    dt = lo * (hi / lo) ** phi
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def layer_weights(weights, sizes, i: int):
    """Layer ``i``'s parameters as the equations use them (host arrays,
    float32): the drawn leaves, with ``A_log`` and ``dt_bias`` made
    from their draws."""
    leaves = NORM_LEAVES + MLP_LEAVES + (
        ATTN_LEAVES if attends(sizes, i) else MAMBA_LEAVES)
    w = {k: weights[f"layers.{i}.{k}"] for k in leaves}
    if not attends(sizes, i):
        n = w["A_log"].shape[1]
        w["A_log"] = w["A_log"] + np.log(
            np.arange(1, n + 1, dtype=np.float32))[None, :]
        w["dt_bias"] = _dt_bias(w["dt_bias"])
    return w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


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


def _mamba(m, w, eps, cast):
    """The Mamba mixer of one sequence, m [T, d] → [T, d]."""
    mm = lambda a, b: cast(a) @ cast(b)
    t, _ = m.shape
    di, k = w["conv"].shape
    n = w["A_log"].shape[1]
    r = w["dt_proj"].shape[0]
    uz = mm(m, w["in_proj"])
    u, z = cast(uz[:, :di]), uz[:, di:]
    conv = w["conv_bias"] + jnp.zeros_like(u)
    for j in range(k):
        back = k - 1 - j                     # tap j weighs u_{t - back}
        shifted = jnp.concatenate(
            [jnp.zeros((back, di), u.dtype), u[:t - back]]) if back else u
        conv = conv + w["conv"][:, j] * shifted
    uc = jax.nn.silu(conv)
    dbc = mm(uc, w["x_proj"])
    delta = _rms(dbc[:, :r], w["dt_norm"], eps)
    b = _rms(dbc[:, r:r + n], w["b_norm"], eps)
    c = _rms(dbc[:, r + n:], w["c_norm"], eps)
    dt = jax.nn.softplus(mm(delta, w["dt_proj"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])                                  # [di, N]

    def step(h, xs):
        dt_t, u_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
        return h, jnp.sum(h * c_t, axis=1)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (dt, uc, b, c))
    y = y + w["D"] * uc
    return mm(y * jax.nn.silu(z), w["out_proj"])


def _block(x, w, sizes, attn, cast):
    """One sequence [T, d] through one layer."""
    t, _ = x.shape
    eps = float(sizes["rms_norm_eps"])
    mm = lambda a, b: cast(a) @ cast(b)
    a = _rms(x, w["input_norm"], eps)
    if attn:
        _, _, _, _, _, h, g, dh = dims(sizes)
        q = mm(a, w["wq"]).reshape(t, h, dh)
        k = mm(a, w["wk"]).reshape(t, g, dh)
        v = mm(a, w["wv"]).reshape(t, g, dh)
        x = x + mm(_attention(q, k, v, cast), w["wo"])
    else:
        x = x + _mamba(a, w, eps, cast)
    m = _rms(x, w["pre_ff_norm"], eps)
    return x + mm(jax.nn.silu(mm(m, w["w_gate"])) * mm(m, w["w_up"]),
                  w["w_down"])


def logits_at(weights, sizes, tokens: np.ndarray, positions: np.ndarray,
              cast=lambda a: a):
    """``tokens`` [B, T] (padded at the end; T a multiple of 128),
    ``positions`` [B, N] → float32 logits [B, N, V] of the token that
    follows each position."""
    b, t = tokens.shape
    assert t % QUERY_BLOCK == 0, t
    embed = jnp.asarray(weights["tok_embed"])
    xs = [embed[jnp.asarray(tokens[r])] for r in range(b)]
    blocks = {}                   # one program a kind of layer
    for i in range(int(sizes["num_hidden_layers"])):
        attn = attends(sizes, i)
        w = {k: jnp.asarray(v)
             for k, v in layer_weights(weights, sizes, i).items()}
        if attn not in blocks:
            blocks[attn] = jax.jit(
                lambda x, w, at=attn: _block(x, w, sizes, at, cast))
        xs = [blocks[attn](x, w) for x in xs]
        del w
    eps = float(sizes["rms_norm_eps"])
    head = jax.jit(lambda hid, g, e: cast(_rms(hid, g, eps)) @ cast(e).T)
    picked = jnp.stack([x[jnp.asarray(positions[r])]
                        for r, x in enumerate(xs)])
    return np.asarray(head(picked, jnp.asarray(weights["final_norm"]),
                           embed))
