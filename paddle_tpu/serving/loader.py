"""Standalone serving loader.

Deliberately imports ONLY ``jax``, ``numpy`` and the stdlib — never the
layer engine, DSL, or trainer.  This is the deployment boundary the
reference draws with ``paddle/capi`` (a C library embedding none of the
trainer): a serving process ships the artifact directory plus this one
file's worth of code.

    from paddle_tpu.serving.loader import ServedModel
    model = ServedModel.load("exported_mnist/")
    probs = model(img=batch)["prediction"]

Version 2 artifacts (int8 weights-only quantization, see
``serving/export.py``) carry their weights in ``weights.npz`` instead of
baked constants: quantized entries are dequantized ONCE at load —
``w = q.astype(f32) * scale`` per output channel, cast to the manifest's
``dequant_dtype`` (bf16 by default) — and prepended to every module
call.  Version-1 artifacts load exactly as before.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
from typing import Any, Dict, List

import time

import jax
import jax.export
import jax.numpy as jnp
import numpy as np


def _np_dtype(name: str) -> np.dtype:
    """Dtype by name, bfloat16 included — plain ``np.dtype("bfloat16")``
    raises (the type lives in ml_dtypes, re-exported by jax.numpy);
    local on purpose so the standalone-copy deployment keeps working."""
    try:
        return np.dtype(name)
    except TypeError:
        return np.dtype(getattr(jnp, name))


def _dequantize(q: np.ndarray, scale: np.ndarray, axis: int,
                dtype: np.dtype) -> np.ndarray:
    shape = [1] * q.ndim
    shape[axis % q.ndim] = -1
    return (q.astype(np.float32) * scale.reshape(shape)).astype(dtype)

# telemetry is OPTIONAL here: paddle_tpu.observe is stdlib-only, but a
# serving process that ships just this file (the capi-style deployment
# story) runs fine without it
try:
    from ..observe import counter as _counter, gauge as _gauge
    from ..observe import histogram as _histogram
    from ..observe import fleet as _fleet, trace as _trace
except ImportError:  # standalone copy: no package context
    _counter = _gauge = _histogram = _trace = _fleet = None


class TornArtifact(ValueError):
    """An artifact whose payload does not match its manifest digests —
    truncated, bit-flipped, or mid-write.  The rollout pipeline treats
    this as "skip and keep serving the old model", never as fatal."""


def verify_artifact(dirname: str, manifest: Dict[str, Any] = None) -> bool:
    """Re-hash every payload file against the manifest ``files`` section.

    Returns True when the digests all match, False when the manifest
    predates digest stamping (nothing to verify against — pre-rollout
    artifacts still load, they just cannot be proven whole).  Raises
    :class:`TornArtifact` on a missing, short, long, or corrupt file.
    """
    if manifest is None:
        manifest = read_manifest(dirname)
    files = manifest.get("files")
    if not files:
        return False
    for fn, meta in sorted(files.items()):
        path = os.path.join(dirname, fn)
        if not os.path.exists(path):
            raise TornArtifact(f"{dirname}: missing payload file {fn!r}")
        size = os.path.getsize(path)
        if size != meta["bytes"]:
            raise TornArtifact(
                f"{dirname}: {fn} is {size} bytes, manifest says "
                f"{meta['bytes']} (truncated or partially written)")
        h = hashlib.sha256()
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
        if h.hexdigest() != meta["sha256"]:
            raise TornArtifact(f"{dirname}: {fn} sha256 mismatch "
                               f"(expected {meta['sha256'][:12]}…, got "
                               f"{h.hexdigest()[:12]}…)")
    return True


def artifact_digest(manifest: Dict[str, Any]) -> str:
    """Content-stable version id of an artifact: sha256 over the sorted
    per-file digests.  Two exports of identical payload bytes get the
    same id; any payload change changes it.  This is the
    ``model_version`` the server, fleet topology, and rollout
    coordinator all speak."""
    files = manifest.get("files")
    if not files:
        return "unversioned"
    h = hashlib.sha256()
    for fn in sorted(files):
        h.update(fn.encode())
        h.update(files[fn]["sha256"].encode())
    return h.hexdigest()


def read_manifest(dirname: str, max_version: int = 2) -> Dict[str, Any]:
    """Read and validate an artifact manifest (format + version gate);
    shared by :class:`ServedModel` and the decoder-artifact loader in
    ``serving/model.py``."""
    with open(os.path.join(dirname, "manifest.json")) as f:
        manifest = json.load(f)
    if manifest.get("format") != "paddle-tpu-serving":
        raise ValueError(f"{dirname}: not a paddle-tpu-serving artifact")
    if manifest.get("version", 0) > max_version:
        raise ValueError(
            f"{dirname}: artifact version {manifest['version']} is newer "
            f"than this loader (supports <= {max_version})")
    return manifest


def load_weight_entries(dirname: str,
                        wsec: Dict[str, Any]) -> List[np.ndarray]:
    """Materialize a manifest ``weights`` section: dequantize int8
    entries ONCE (per-output-channel ``q.astype(f32) * scale``), pass
    raw entries through, in manifest order."""
    weights: List[np.ndarray] = []
    npz = np.load(os.path.join(dirname, wsec["file"]))
    for e in wsec["entries"]:
        dt = _np_dtype(e["dtype"])
        if e["quantized"]:
            ax = e.get("axis")
            w = _dequantize(npz["q::" + e["name"]],
                            npz["s::" + e["name"]],
                            -1 if ax is None else ax, dt)
        else:
            w = np.asarray(npz["w::" + e["name"]], dtype=dt)
        weights.append(w)
    return weights


class ServedModel:
    """A loaded StableHLO inference artifact (pure function; reentrant —
    the multi-thread story ``_create_shared_param`` exists for in the
    reference C API comes for free)."""

    def __init__(self, manifest: Dict[str, Any], exported,
                 weights: List[np.ndarray] = ()):
        self.manifest = manifest
        self._exported = exported
        # v2: dequantized weights in call order, committed to device
        # ONCE here — passing host numpy instead would re-pay the full
        # weight H2D transfer on every inference call
        self._weights = [jax.device_put(w) for w in weights]
        self.feed_names = [f["name"] for f in manifest["feeds"]]
        self.fetch_names = list(manifest["fetches"])

    @classmethod
    def load(cls, dirname: str, verify: bool = True) -> "ServedModel":
        if _fleet is not None:
            # a process loading a serving artifact pushes (when
            # --fleet_addr is set) as role=serving; a dict write, free
            _fleet.set_identity(role="serving")
        manifest = read_manifest(dirname)
        if verify:
            # raises TornArtifact on digest mismatch; manifests without
            # a files section (pre-rollout exports) load unverified
            verify_artifact(dirname, manifest)
        if manifest.get("kind") == "decoder":
            raise ValueError(
                f"{dirname}: decoder artifact — load it with "
                "paddle_tpu.serving.DecoderModel.from_artifact, not "
                "ServedModel (no StableHLO module to call)")
        with open(os.path.join(dirname, manifest["module"]), "rb") as f:
            exported = jax.export.deserialize(f.read())
        weights: List[np.ndarray] = []
        wsec = manifest.get("weights")
        if wsec:   # v2 quantized artifact: dequantize once, at load
            weights = load_weight_entries(dirname, wsec)
        return cls(manifest, exported, weights)

    def __call__(self, n_requests: int = 1, **feeds) -> Dict[str, np.ndarray]:
        """Run one inference call carrying ``n_requests`` logical
        requests (a continuous-batching decode step batches N of them
        into one launch).  Telemetry is per REQUEST, not per launch:
        ``serve_requests`` ticks by N and ``serve_infer_seconds`` gets N
        observations, so fleet dashboards and reservoir quantiles stay
        comparable between batched and sequential serving."""
        if n_requests < 1:
            raise ValueError(f"n_requests must be >= 1, got {n_requests}")
        args = []
        for spec in self.manifest["feeds"]:
            name = spec["name"]
            if name not in feeds:
                raise KeyError(f"missing feed {name!r} "
                               f"(expected {self.feed_names})")
            a = np.asarray(feeds[name], dtype=_np_dtype(spec["dtype"]))
            want = spec["shape"]
            got = list(a.shape)
            if len(want) != len(got) or any(
                    w is not None and w != g for w, g in zip(want, got)):
                raise ValueError(
                    f"feed {name!r}: shape {got} incompatible with {want}")
            args.append(a)
        t0 = time.perf_counter()
        # per-request span: a serving process with tracing on gets one
        # trace per inference call (root span unless the caller opened
        # a request-level span around us)
        infer_span = _trace.span("serve_infer") if _trace is not None \
            else contextlib.nullcontext()
        with infer_span:
            outs = self._exported.call(*self._weights, *args)
            result = {n: np.asarray(v)
                      for n, v in zip(self.fetch_names, outs)}
        # np.asarray above synchronized the device, so this is true
        # end-to-end inference latency
        if _histogram is not None:
            # amortized per-request latency, observed once PER REQUEST:
            # quantiles over requests, not over launches of varying width
            per_req = (time.perf_counter() - t0) / n_requests
            h = _histogram("serve_infer_seconds",
                           "per-request ServedModel inference latency")
            for _ in range(n_requests):
                h.observe(per_req)
            _counter("serve_requests",
                     "requests served").inc(n_requests)
            _gauge("serve_batch_size",
                   "requests in the most recent inference launch").set(
                n_requests)
        return result
