"""Decoder-only transformer for the serving stack — the model half of
the continuous-batching server (``serving/server.py``).

The two entry points mirror the two serving kernels from PR 14/15:

- :meth:`DecoderModel.prefill` runs a batch of mixed-length prompts in
  ONE ``flash_attention_packed`` launch per layer ([B, T] rows flattened
  to one packed [1, B*T] row with ``segments_from_lengths``), writes
  every prompt token's K/V into the rows' KV pages via
  ``paged_kv_write``, and returns each row's first generated token;
- :meth:`DecoderModel.decode` advances a fixed-width decode batch one
  token with ``paged_decode_attention`` over the shared page pool —
  inactive (padded) slots carry a scratch page table, zero write count,
  and length 1, so the kernel touches no memory the slot does not own.

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

import functools
import json
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..layers.beam_search import eos_frozen_logits
from ..observe.trace import span as _span
from ..ops.pallas_attention import (flash_attention_packed, paged_kv_write,
                                    paged_decode_attention,
                                    segments_from_lengths)
from ..utils import enforce
from . import export as _export
from . import loader as _loader


class DecoderConfig(NamedTuple):
    """Shape of the served decoder (all sizes static — one compiled
    prefill per (B, T) bucket, one compiled decode step per batch
    width)."""
    vocab: int
    dim: int
    heads: int
    layers: int
    ffn: int
    max_context: int = 256
    eos_id: int = 1


def init_decoder_params(cfg: DecoderConfig, seed: int = 0
                        ) -> Dict[str, np.ndarray]:
    """Random fp32 decoder weights (scaled normal init); names are the
    artifact contract: ``embed``, ``pos_embed``, per layer
    ``l{i}.{ln1,ln2,wq,wk,wv,wo,w1,w2}``, ``ln_f``, ``lm_head``."""
    enforce(cfg.dim % cfg.heads == 0,
            f"dim {cfg.dim} not divisible by heads {cfg.heads}")
    rng = np.random.default_rng(seed)

    def mat(n_in, n_out):
        return (rng.standard_normal((n_in, n_out)) /
                np.sqrt(n_in)).astype(np.float32)

    p: Dict[str, np.ndarray] = {
        "embed": mat(cfg.vocab, cfg.dim) * np.float32(np.sqrt(cfg.vocab)),
        "pos_embed": (0.02 * rng.standard_normal(
            (cfg.max_context, cfg.dim))).astype(np.float32),
        "ln_f": np.ones(cfg.dim, np.float32),
        "lm_head": mat(cfg.dim, cfg.vocab),
    }
    for i in range(cfg.layers):
        p[f"l{i}.ln1"] = np.ones(cfg.dim, np.float32)
        p[f"l{i}.ln2"] = np.ones(cfg.dim, np.float32)
        for w, (a, b) in {"wq": (cfg.dim, cfg.dim), "wk": (cfg.dim, cfg.dim),
                          "wv": (cfg.dim, cfg.dim), "wo": (cfg.dim, cfg.dim),
                          "w1": (cfg.dim, cfg.ffn),
                          "w2": (cfg.ffn, cfg.dim)}.items():
            p[f"l{i}.{w}"] = mat(a, b)
    return p


def _rms(x, g, eps=1e-6):
    return (x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)) * g


def _ffn(x, p, i):
    h = jax.nn.gelu(_rms(x, p[f"l{i}.ln2"]) @ p[f"l{i}.w1"])
    return x + h @ p[f"l{i}.w2"]


def _qkv(xn, p, i, heads):
    b, t, d = xn.shape
    dh = d // heads

    def proj(w):
        return (xn @ p[f"l{i}.{w}"]).reshape(b, t, heads, dh)
    return proj("wq"), proj("wk"), proj("wv")


def _prefill_impl(params, k_pool, v_pool, tokens, lengths, page_indices,
                  cfg: DecoderConfig):
    """[B, T] padded prompts → ([B] first generated tokens, [B, V]
    logits, updated pools).  Packed causal attention: the batch is ONE
    [1, B*T] row; segment ids keep rows from attending across each
    other and mask padding outright."""
    b, t = tokens.shape
    pos = jnp.arange(t, dtype=jnp.int32)[None, :]
    x = params["embed"][tokens] + params["pos_embed"][
        jnp.clip(pos, 0, cfg.max_context - 1)]
    segments = segments_from_lengths(lengths, b, t)
    zero = jnp.zeros((b,), jnp.int32)
    for i in range(cfg.layers):
        q, k, v = _qkv(_rms(x, params[f"l{i}.ln1"]), params, i, cfg.heads)
        # the decode contract: K/V must be in the pages before any
        # later step queries them — write the whole prompt now
        kp, vp = paged_kv_write(k_pool[i], v_pool[i], k, v,
                                page_indices, zero, lengths)
        k_pool = k_pool.at[i].set(kp)
        v_pool = v_pool.at[i].set(vp)
        dh = cfg.dim // cfg.heads
        attn = flash_attention_packed(
            q.reshape(1, b * t, cfg.heads, dh),
            k.reshape(1, b * t, cfg.heads, dh),
            v.reshape(1, b * t, cfg.heads, dh),
            segments, causal=True, slot=t)
        x = x + attn.reshape(b, t, cfg.dim) @ params[f"l{i}.wo"]
        x = _ffn(x, params, i)
    last = jnp.take_along_axis(
        x, jnp.clip(lengths - 1, 0, t - 1)[:, None, None], axis=1)[:, 0]
    logits = _rms(last, params["ln_f"]) @ params["lm_head"]
    active = lengths > 0
    nxt = jnp.argmax(eos_frozen_logits(logits, active, cfg.eos_id), -1)
    return nxt.astype(jnp.int32), logits, k_pool, v_pool


def _decode_impl(params, k_pool, v_pool, tokens, page_indices, lengths,
                 active, cfg: DecoderConfig):
    """One decode step for a fixed-width batch.  ``lengths`` INCLUDE the
    token being fed (its position is ``lengths - 1``); ``active`` masks
    padded slots — their K/V write count is zero and their kernel
    length clamps to 1 over the scratch page, so padding can neither
    write nor read real pool state."""
    b = tokens.shape[0]
    pos = jnp.clip(lengths - 1, 0, cfg.max_context - 1)
    x = (params["embed"][tokens] + params["pos_embed"][pos])[:, None, :]
    counts = active.astype(jnp.int32)
    klen = jnp.where(active, lengths, 1).astype(jnp.int32)
    for i in range(cfg.layers):
        q, k, v = _qkv(_rms(x, params[f"l{i}.ln1"]), params, i, cfg.heads)
        kp, vp = paged_kv_write(k_pool[i], v_pool[i], k, v,
                                page_indices, lengths - 1, counts)
        k_pool = k_pool.at[i].set(kp)
        v_pool = v_pool.at[i].set(vp)
        # kp/vp are the layer's pool as stored, [P, page, H·D]: the
        # kernel DMAs the rows' live pages out of it, nothing else
        attn = paged_decode_attention(q, kp, vp, page_indices, klen)
        x = x + attn.reshape(b, 1, cfg.dim) @ params[f"l{i}.wo"]
        x = _ffn(x, params, i)
    logits = _rms(x[:, 0], params["ln_f"]) @ params["lm_head"]
    nxt = jnp.argmax(eos_frozen_logits(logits, active, cfg.eos_id), -1)
    return nxt.astype(jnp.int32), logits, k_pool, v_pool


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
    # (B, T)/(B,) shape bucket.  No buffer donation: CPU (the test
    # platform) does not alias donations and warns per compile —
    # on TPU the pools would be donate_argnums=(1, 2)
    prefill = jax.jit(
        lambda p, kp, vp, tk, ln, pi: _prefill_impl(
            p, kp, vp, tk, ln, pi, cfg))
    decode = jax.jit(
        lambda p, kp, vp, tk, pi, ln, ac: _decode_impl(
            p, kp, vp, tk, pi, ln, ac, cfg))
    return prefill, decode


class DecoderModel:
    """A loaded decoder + its jitted prefill/decode steps.

    Pools are owned by the caller (the server) and threaded through
    every call — the model never holds KV state, so one model instance
    serves any number of pools/replicas reentrantly."""

    def __init__(self, params: Dict[str, Any], cfg: DecoderConfig):
        enforce(cfg.dim % cfg.heads == 0,
                f"dim {cfg.dim} not divisible by heads {cfg.heads}")
        self.cfg = cfg
        # fp32 on-device once; dequantized int8 artifacts land here too
        self.params = {k: jax.device_put(np.asarray(v))
                       for k, v in params.items()}
        self._prefill, self._decode = _jitted_steps(cfg)

    # ----------------------------------------------------------- pools
    def new_pools(self, n_pages: int, page_size: int
                  ) -> Tuple[jax.Array, jax.Array]:
        """Zeroed per-layer K/V pools, ``[L, P, page, H·Dh]``: one
        lane-dense row a token, the layout the decode kernel fetches
        pages in and ``paged_kv_write`` scatters rows into.  (Stored
        ``[…, H, Dh]`` with Dh < 128 the TPU lays the page axis along
        the lanes, and every use of a layer's pool is a relayout copy
        of it: PERF.md §6, PR 26.)"""
        shape = (self.cfg.layers, n_pages, page_size, self.cfg.dim)
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)

    # ----------------------------------------------------------- steps
    def prefill(self, k_pool, v_pool, tokens, lengths, page_indices):
        """Prompts in, first generated token out (plus updated pools).
        ``tokens`` [B, T] int32 padded, ``lengths`` [B], ``page_indices``
        [B, max_pages] physical page tables covering each prompt PLUS
        the tokens to be generated."""
        shape = np.shape(tokens)
        enforce(len(shape) == 2 and shape[1] <= self.cfg.max_context,
                f"prompt batch {shape} exceeds max_context "
                f"{self.cfg.max_context}")
        with _span("prefill_dispatch"):       # host→device + launch
            nxt, logits, k_pool, v_pool = self._prefill(
                self.params, k_pool, v_pool,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(page_indices, jnp.int32))
        with _span("prefill_fetch"):          # blocks on the device
            nxt, logits = np.asarray(nxt), np.asarray(logits)
        return nxt, logits, k_pool, v_pool

    def decode(self, k_pool, v_pool, tokens, page_indices, lengths, active):
        """One continuous-batching decode step over the page pool."""
        with _span("decode_dispatch"):        # host→device + launch
            nxt, logits, k_pool, v_pool = self._decode(
                self.params, k_pool, v_pool,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(page_indices, jnp.int32),
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(active, bool))
        with _span("decode_fetch"):           # blocks on the device
            nxt, logits = np.asarray(nxt), np.asarray(logits)
        return nxt, logits, k_pool, v_pool

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
        cfg = DecoderConfig(**manifest["decoder"])
        wsec = manifest["weights"]
        weights = _loader.load_weight_entries(dirname, wsec)
        params = {e["name"]: w
                  for e, w in zip(wsec["entries"], weights)}
        model = cls(params, cfg)
        # testing/bench knob (export_decoder extra_meta): a seeded-slow
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
