"""Export an inference function to a standalone StableHLO artifact.

Artifact layout (versioned, like ``trainer/checkpoint.py``'s manifest):

    <dir>/manifest.json   {"format": "paddle-tpu-serving", "version": 1,
                           "feeds": [{name, shape, dtype}...],
                           "fetches": [name...],
                           "module": "model.stablehlo",
                           "batch_polymorphic": bool}
    <dir>/model.stablehlo  jax.export serialized bytes (weights baked in)

Weights are baked into the module as constants — the artifact is the
whole deployable model, the same way ``paddle_merge_model`` fuses config
+ parameters into one self-contained file for the C inference API
(``paddle/trainer/MergeModel.cpp``, ``paddle/capi/gradient_machine.h:36``).

**Version 2 — int8 weights-only post-training quantization**
(``quantize="int8"``): instead of baking fp32 constants, every ≥2-D
float parameter is stored as int8 with per-output-channel symmetric
scales (last axis; ``scale_c = max|w[..., c]| / 127``, no zero point) in
``weights.npz``, and the module takes the weights as runtime ARGUMENTS.
The loader dequantizes to ``dequant_dtype`` (bf16 by default — the TPU
serving compute dtype) once at load and prepends them on every call;
1-D tensors (biases, BN stats) ship raw fp32.  The manifest gains:

    "version": 2,
    "weights": {"file": "weights.npz",
                "scheme": "int8-weights-per-channel",
                "dequant_dtype": "bfloat16",
                "entries": [{name, shape, dtype, quantized, axis}...]}

Every manifest additionally carries a ``files`` section (per-file
SHA-256 + byte size, written LAST like the checkpoint manifest) and an
``exported_at_unix`` stamp — ``loader.verify_artifact`` re-hashes the
payload against it, which is what makes a truncated or bit-flipped
artifact detectable before it ever reaches a live server
(``serving/rollout.py``).  Manifests without a ``files`` section
(pre-rollout artifacts) still load; they just cannot be
digest-verified.

Version-1 artifacts keep loading unchanged (``serving/loader.py``
supports both).  The measurement template is the Gemma-on-TPU study
(PAPERS.md, arxiv 2605.25645): ~4× smaller weight payload.

Reference parity: replaces ``paddle_gradient_machine_create_for_inference
_with_parameters`` + ``_forward``; multi-threaded serving needs no
``_create_shared_param`` equivalent — the loaded module is a pure
function, reentrant by construction.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.export
import numpy as np

from ..core.dtypes import dtype_name, np_dtype
from ..utils import enforce, get_logger

log = get_logger("serving")

FORMAT_NAME = "paddle-tpu-serving"
FORMAT_VERSION = 1
QUANT_FORMAT_VERSION = 2
MODULE_FILE = "model.stablehlo"
WEIGHTS_FILE = "weights.npz"
QUANT_SCHEME = "int8-weights-per-channel"


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def artifact_file_digests(dirname: str, fnames: Sequence[str]
                          ) -> Dict[str, Dict[str, Any]]:
    """The manifest ``files`` section: per-file SHA-256 + size, same
    shape as ``trainer/checkpoint.py``'s checkpoint manifest so the
    rollout pipeline verifies artifacts and checkpoints identically.
    The manifest itself is excluded (it is written last and carries
    the digests)."""
    return {fn: {"sha256": _sha256_file(os.path.join(dirname, fn)),
                 "bytes": os.path.getsize(os.path.join(dirname, fn))}
            for fn in fnames}


def stamp_manifest(manifest: Dict[str, Any], dirname: str,
                   fnames: Sequence[str]) -> Dict[str, Any]:
    """Add the integrity + provenance fields every serving manifest
    carries: per-file digests and the export wall-clock time.  Must be
    called after every payload file is on disk, right before the
    manifest write (the manifest is the commit record)."""
    manifest["files"] = artifact_file_digests(dirname, fnames)
    manifest["exported_at_unix"] = time.time()
    return manifest


def _feed_spec(name: str, arr: np.ndarray, poly_batch: bool) -> Dict[str, Any]:
    return {"name": name,
            "shape": [None if (poly_batch and i == 0) else int(d)
                      for i, d in enumerate(np.shape(arr))],
            # dtype_name handles bfloat16 feeds (str() of the ml_dtypes
            # extension type round-trips through core.dtypes.np_dtype)
            "dtype": dtype_name(np.asarray(arr).dtype)}


# ------------------------------------------------------------ int8 PTQ
def quantize_int8(arr: np.ndarray, axis: int = -1
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric per-channel int8 quantization along ``axis`` (the
    output-channel axis: HWIO convs and [in, out] fc weights both keep
    it last).  Returns ``(q int8, scale f32[channels])`` with
    ``q = clip(round(w / scale), -127, 127)`` — max dequant error is
    ``scale/2`` per channel."""
    a = np.asarray(arr, np.float32)
    ax = axis % a.ndim
    red = tuple(i for i in range(a.ndim) if i != ax)
    amax = np.max(np.abs(a), axis=red) if red else np.abs(a)
    scale = (np.maximum(amax, 1e-12) / 127.0).astype(np.float32)
    shape = [1] * a.ndim
    shape[ax] = -1
    q = np.clip(np.round(a / scale.reshape(shape)), -127, 127) \
        .astype(np.int8)
    return q, scale


def dequantize_int8(q: np.ndarray, scale: np.ndarray, axis: int = -1,
                    dtype="float32") -> np.ndarray:
    """Inverse of :func:`quantize_int8` (the loader's load-time path)."""
    shape = [1] * q.ndim
    shape[axis % q.ndim] = -1
    return (q.astype(np.float32) * scale.reshape(shape)) \
        .astype(np_dtype(dtype))


def _quantizable(arr: np.ndarray) -> bool:
    """Weights-only: ≥2-D float tensors (matmul/conv weights).  1-D
    tensors — biases, BN scale/offset/running stats — ship raw fp32;
    they are tiny and precision-critical."""
    return arr.ndim >= 2 and np.issubdtype(arr.dtype, np.floating)


def quantize_weight_store(params: Dict[str, Any], dequant_dtype: str
                          ) -> Tuple[Dict[str, np.ndarray],
                                     List[Dict[str, Any]]]:
    """Build the version-2 ``weights.npz`` store + manifest entries for
    a parameter dict: quantizable tensors as ``q::name`` / ``s::name``
    (int8 + per-channel scales, dequantized to ``dequant_dtype`` at
    load), the rest raw as ``w::name``.  Entry order follows sorted
    names — the load order contract of ``loader.load_weight_entries``.
    Shared by the network int8 export and the decoder-artifact export
    (``serving/model.py``)."""
    deq_dt = np_dtype(dequant_dtype)
    store: Dict[str, np.ndarray] = {}
    entries: List[Dict[str, Any]] = []
    for name in sorted(params):
        arr = np.asarray(params[name])
        if _quantizable(arr):
            q, scale = quantize_int8(arr, axis=-1)
            store["q::" + name] = q
            store["s::" + name] = scale
            entries.append({"name": name, "shape": list(arr.shape),
                            "dtype": dtype_name(deq_dt),
                            "quantized": True, "axis": -1})
        else:
            raw = arr.astype(np.float32) \
                if np.issubdtype(arr.dtype, np.floating) else arr
            store["w::" + name] = raw
            entries.append({"name": name, "shape": list(arr.shape),
                            "dtype": dtype_name(raw.dtype),
                            "quantized": False, "axis": None})
    return store, entries


def _feed_arg_specs(examples: Dict[str, np.ndarray],
                    feed_names: Sequence[str], poly: bool):
    if not poly:
        return [jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in (examples[k] for k in feed_names)]
    scope = jax.export.SymbolicScope()
    b = jax.export.symbolic_shape("b", scope=scope)[0]
    out = []
    for k in feed_names:
        a = examples[k]
        shape = ((b,) + a.shape[1:]) if a.ndim >= 1 else a.shape
        out.append(jax.ShapeDtypeStruct(shape, a.dtype))
    return out


def _serialize_export(flat_fn, specs, examples, batch_polymorphic: bool):
    """jax.export with the batch-polymorphic-then-fixed fallback; one
    artifact serves every runtime (multi-platform cpu+tpu lowering)."""
    platforms = ("cpu", "tpu")

    def do_export(poly: bool):
        return jax.export.export(jax.jit(flat_fn),
                                 platforms=platforms)(*specs(poly))

    exported = None
    poly = batch_polymorphic
    if poly:
        try:
            exported = do_export(True)
        except Exception as e:  # shapes data-dependent on batch size
            log.warning(
                "batch-polymorphic export failed (%s: %s); falling back "
                "to fixed batch %s", type(e).__name__, e,
                {k: np.shape(v) for k, v in examples.items()})
            poly = False
    if exported is None:
        exported = do_export(False)
    return exported, poly


def export_inference_fn(fn, example_feed: Dict[str, Any], dirname: str,
                        fetch_names: Sequence[str],
                        batch_polymorphic: bool = True) -> str:
    """Export ``fn(feed_dict) -> dict[name, array]`` to ``dirname``.

    ``fn`` must be traceable (weights closed over; they are baked into
    the module).  With ``batch_polymorphic`` the leading axis of every
    feed is exported symbolically so one artifact serves any batch size.
    """
    feed_names = sorted(example_feed)
    examples = {k: np.asarray(example_feed[k]) for k in feed_names}

    def flat_fn(*args):
        out = fn(dict(zip(feed_names, args)))
        return [out[n] for n in fetch_names]

    def specs(poly: bool):
        return _feed_arg_specs(examples, feed_names, poly)

    exported, poly = _serialize_export(flat_fn, specs, examples,
                                       batch_polymorphic)

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, MODULE_FILE), "wb") as f:
        f.write(exported.serialize())
    manifest = {
        "format": FORMAT_NAME,
        "version": FORMAT_VERSION,
        "feeds": [_feed_spec(k, examples[k], poly) for k in feed_names],
        "fetches": list(fetch_names),
        "module": MODULE_FILE,
        "batch_polymorphic": poly,
    }
    stamp_manifest(manifest, dirname, [MODULE_FILE])
    with open(os.path.join(dirname, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    return dirname


def _resolve_output_names(network, output_names):
    if output_names is None:
        output_names = []
        for n in network.output_names:
            lyr = network.layers.get(n)
            if lyr is not None and getattr(lyr, "is_cost", False) \
                    and lyr.conf.inputs:
                output_names.append(lyr.conf.inputs[0].input_layer_name)
            else:
                output_names.append(n)
    enforce(output_names, "export_network: no output names")
    return list(output_names)


def _flatten_example_feed(example_feed: Dict[str, Any]):
    """SequenceBatch feeds → two plain-array feeds (``<name>`` +
    ``<name>_len``); returns (flat examples, seq feed names)."""
    from ..core.sequence import SequenceBatch

    seq_feeds = {k for k, v in example_feed.items()
                 if isinstance(v, SequenceBatch)}
    for k in seq_feeds:
        enforce(k + "_len" not in example_feed,
                f"export_network: feed {k + '_len'!r} collides with the "
                f"flattened lengths of sequence feed {k!r}")
    flat_examples: Dict[str, Any] = {}
    for k, v in example_feed.items():
        if k in seq_feeds:
            flat_examples[k] = np.asarray(v.data)
            flat_examples[k + "_len"] = np.asarray(v.length)
        else:
            flat_examples[k] = v
    return flat_examples, seq_feeds


def export_network(network, params: Dict[str, jax.Array],
                   example_feed: Dict[str, Any], dirname: str,
                   output_names: Optional[Sequence[str]] = None,
                   buffers: Optional[Dict[str, jax.Array]] = None,
                   batch_polymorphic: bool = True,
                   quantize: Optional[str] = None,
                   dequant_dtype: str = "bfloat16") -> str:
    """Export a layer-engine :class:`NeuralNetwork` for inference.

    ``output_names`` defaults to the network's declared outputs (cost
    layers replaced by their prediction input, as ``v2.infer`` does).

    :class:`SequenceBatch` feeds are flattened into TWO artifact feeds —
    ``<name>`` (padded data) and ``<name>_len`` (int32 lengths) — so the
    standalone loader's plain-array contract covers sequence models.

    ``quantize="int8"`` writes a **version-2 weights-only quantized**
    artifact (see the module docstring): per-channel symmetric int8
    weights in ``weights.npz``, dequantized to ``dequant_dtype`` at
    load and fed to the module as runtime arguments.  Default (None)
    keeps the version-1 weights-baked artifact byte-for-byte.
    """
    from ..core.sequence import SequenceBatch, value_of

    output_names = _resolve_output_names(network, output_names)
    bufs = buffers if buffers is not None else network.init_buffers()
    flat_examples, seq_feeds = _flatten_example_feed(example_feed)

    def fwd(weights, feed):
        rebuilt = {k: SequenceBatch(feed[k], feed[k + "_len"])
                   if k in seq_feeds else feed[k] for k in example_feed}
        values, _ = network.forward(weights, rebuilt, bufs,
                                    is_training=False, only=output_names)
        return {n: value_of(values[n]) for n in output_names}

    if quantize is None:
        return export_inference_fn(
            lambda feed: fwd(params, feed), flat_examples, dirname,
            output_names, batch_polymorphic=batch_polymorphic)
    enforce(quantize == "int8",
            f"export_network: unknown quantize scheme {quantize!r} "
            "(supported: 'int8')")
    return _export_network_int8(
        fwd, params, flat_examples, dirname, output_names,
        batch_polymorphic=batch_polymorphic, dequant_dtype=dequant_dtype)


def _export_network_int8(fwd, params, flat_examples, dirname,
                         output_names, batch_polymorphic: bool,
                         dequant_dtype: str) -> str:
    """The version-2 quantized export: weights become module ARGUMENTS
    (quantized entries at ``dequant_dtype``, raw 1-D tensors at their
    own dtype), stored int8+scales / raw in ``weights.npz``."""
    wnames = sorted(params)
    feed_names = sorted(flat_examples)
    examples = {k: np.asarray(flat_examples[k]) for k in feed_names}
    deq_dt = np_dtype(dequant_dtype)

    store, entries = quantize_weight_store(params, dequant_dtype)
    warg_specs = [jax.ShapeDtypeStruct(tuple(e["shape"]), np_dtype(e["dtype"]))
                  for e in entries]

    nw = len(wnames)

    def flat_fn(*args):
        weights = dict(zip(wnames, args[:nw]))
        out = fwd(weights, dict(zip(feed_names, args[nw:])))
        return [out[n] for n in output_names]

    def specs(poly: bool):
        return warg_specs + _feed_arg_specs(examples, feed_names, poly)

    exported, poly = _serialize_export(flat_fn, specs, examples,
                                       batch_polymorphic)

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, MODULE_FILE), "wb") as f:
        f.write(exported.serialize())
    np.savez(os.path.join(dirname, WEIGHTS_FILE), **store)
    manifest = {
        "format": FORMAT_NAME,
        "version": QUANT_FORMAT_VERSION,
        "feeds": [_feed_spec(k, examples[k], poly) for k in feed_names],
        "fetches": list(output_names),
        "module": MODULE_FILE,
        "batch_polymorphic": poly,
        "weights": {
            "file": WEIGHTS_FILE,
            "scheme": QUANT_SCHEME,
            "dequant_dtype": dtype_name(deq_dt),
            "entries": entries,
        },
    }
    stamp_manifest(manifest, dirname, [MODULE_FILE, WEIGHTS_FILE])
    with open(os.path.join(dirname, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=2)
    quant_bytes = sum(v.nbytes for k, v in store.items()
                      if k.startswith(("q::", "s::")))
    raw_bytes = sum(
        int(np.prod(e["shape"])) * 4 for e in entries if e["quantized"])
    log.info("int8 export: %d/%d tensors quantized, weight payload "
             "%.2f MB (fp32 would be %.2f MB)",
             sum(e["quantized"] for e in entries), len(entries),
             quant_bytes / 1e6, raw_bytes / 1e6)
    return dirname
