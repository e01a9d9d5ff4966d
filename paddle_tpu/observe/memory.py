"""Device (HBM) memory accounting: gauges + category attribution.

The second leg of the performance observatory: until now the framework
had NO device-memory telemetry at all — an OOM was the first and only
signal.  This module samples

- ``hbm_in_use_bytes`` / ``hbm_peak_bytes`` — from the backend's
  ``device.memory_stats()`` when it reports (TPU/GPU runtimes), else
  from the sum of live committed arrays (``jax.live_arrays()``; the
  CPU backend reports no allocator stats, so the peak is tracked as a
  running max across samples — honest about being sample-resolution);
- ``hbm_category_bytes{category=...}`` — attribution of the in-use
  bytes to the trainer's known pytrees by **buffer identity**: params,
  opt_state, buffers (batch-norm stats), loss_scale, data (the feed),
  and ``other`` for everything unclaimed (mostly activations held by
  in-flight dispatch and donated-buffer slack).  Category figures are
  **per-chip** (:func:`per_chip_bytes`): a leaf sharded n ways over
  the mesh counts one shard, so the FSDP params/opt_state win is read
  directly off the gauge; replicated/single-chip leaves read as their
  full ``nbytes``, unchanged.

Sampling discipline (the 26 µs/step no-sink contract): nothing here
runs per step.  The trainer samples at pass boundaries — and only when
someone is listening (a metrics sink is attached or the ``/metrics``
endpoint is live).  jax is imported lazily so importing
:mod:`paddle_tpu.observe` stays backend-free.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .metrics import gauge

#: Running peak for backends without allocator stats (CPU): max of the
#: in-use figure across samples taken this process.
_live_peak = 0


def device_stats(device=None) -> Optional[Dict[str, Any]]:
    """The backend allocator's ``memory_stats()`` for ``device`` (the
    first device by default); None when the backend doesn't report
    (CPU) or no backend is initialized."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        return device.memory_stats()
    except Exception:  # noqa: BLE001 — telemetry never kills the host
        return None


def tree_bytes(tree) -> int:
    """Total committed bytes of a pytree's array leaves (0 for None)."""
    if tree is None:
        return 0
    import jax

    return sum(int(getattr(leaf, "nbytes", 0) or 0)
               for leaf in jax.tree_util.tree_leaves(tree))


def per_chip_bytes(leaf) -> int:
    """Bytes of ``leaf`` resident on ONE chip.

    A sharded ``jax.Array``'s ``nbytes`` is the GLOBAL logical size —
    useless for judging per-chip HBM headroom, which is what caps
    model size.  This reads the sharding's per-device shard shape
    instead: a replicated leaf costs its full size per chip, an
    FSDP-sharded one costs ``nbytes / n_shards``.  Host numpy leaves
    and scalars fall back to ``nbytes``."""
    nb = int(getattr(leaf, "nbytes", 0) or 0)
    sh = getattr(leaf, "sharding", None)
    if not nb or sh is None:
        return nb
    try:
        shard_shape = sh.shard_shape(leaf.shape)
        n = 1
        for d in shard_shape:
            n *= int(d)
        return n * int(leaf.dtype.itemsize)
    except Exception:  # noqa: BLE001 — telemetry never kills the host
        return nb


def _category_trees(trainer, feed=None) -> Dict[str, Any]:
    cats: Dict[str, Any] = {}
    if trainer is not None:
        cats["params"] = getattr(trainer, "params", None)
        cats["opt_state"] = getattr(trainer, "opt_state", None)
        cats["buffers"] = getattr(trainer, "buffers", None)
        ls = getattr(trainer, "_ls_state", None)
        if ls is not None:
            cats["loss_scale"] = ls
    if feed is not None:
        cats["data"] = feed
    return cats


def account(trainer=None, feed=None,
            device=None) -> Dict[str, Any]:
    """One memory accounting snapshot.

    Returns ``{"in_use_bytes", "peak_bytes", "source", "categories":
    {name: bytes}, "attributed_frac"}``.  Categories are attributed by
    buffer identity against the live-array set, so a leaf that is BOTH
    in ``trainer.params`` and alive is counted once under ``params``
    and never under ``other``.

    Category bytes are **per-chip** (:func:`per_chip_bytes`): under
    FSDP a parameter sharded 8 ways contributes 1/8 of its global
    size, which is exactly the HBM-headroom question the gauges
    answer; on a single chip or for replicated leaves the figure
    equals ``nbytes``, so the legacy reading is unchanged.
    """
    global _live_peak
    import jax

    cats = _category_trees(trainer, feed)
    cat_ids: Dict[int, str] = {}
    cat_bytes: Dict[str, int] = {}
    for name, tree in cats.items():
        n = 0
        if tree is not None:
            for leaf in jax.tree_util.tree_leaves(tree):
                nb = per_chip_bytes(leaf)
                if nb and id(leaf) not in cat_ids:
                    cat_ids[id(leaf)] = name
                    n += nb
        cat_bytes[name] = n

    stats = device_stats(device)
    if stats and stats.get("bytes_in_use") is not None:
        in_use = int(stats["bytes_in_use"])
        peak = int(stats.get("peak_bytes_in_use", in_use))
        source = "device"
        other = max(in_use - sum(cat_bytes.values()), 0)
    else:
        live = 0
        other = 0
        for arr in jax.live_arrays():
            nb = int(getattr(arr, "nbytes", 0) or 0)
            live += nb
            if id(arr) not in cat_ids:
                other += nb
        in_use = live
        _live_peak = max(_live_peak, live)
        peak = _live_peak
        source = "live_arrays"
    cat_bytes["other"] = other
    attributed = sum(v for k, v in cat_bytes.items() if k != "other")
    return {
        "in_use_bytes": in_use,
        "peak_bytes": peak,
        "source": source,
        "categories": cat_bytes,
        "attributed_frac": round(attributed / in_use, 4) if in_use
        else 0.0,
    }


def shard_categories(trainer=None, feed=None) -> Dict[str, Dict[str, int]]:
    """Per-SHARD category attribution: category → {device id (str) →
    bytes resident on that device}.

    The per-device refinement of :func:`account`'s per-chip figures —
    on a row-sharded 10⁷-row embedding table each ``data``-axis shard
    carries ``V/n`` rows, and this is where an imbalance (a replicated
    stray slot, an indivisible-dim degrade) becomes visible per chip.
    Replicated leaves contribute their full size to EVERY device they
    live on, sharded leaves one shard each.  One series per (category,
    device) — a label-explosion family by design; consoles summarize
    it top-k (``fleet --watch``) rather than one line per series."""
    out: Dict[str, Dict[str, int]] = {}
    for name, tree in _category_trees(trainer, feed).items():
        if tree is None:
            continue
        per_dev: Dict[str, int] = {}
        import jax
        for leaf in jax.tree_util.tree_leaves(tree):
            sh = getattr(leaf, "sharding", None)
            if sh is None or not getattr(leaf, "nbytes", 0):
                continue
            nb = per_chip_bytes(leaf)
            try:
                devices = sorted(sh.device_set, key=lambda d: d.id)
            except Exception:  # noqa: BLE001 — telemetry never kills
                continue
            for d in devices:
                key = str(d.id)
                per_dev[key] = per_dev.get(key, 0) + nb
        if per_dev:
            out[name] = per_dev
    return out


def sample(trainer=None, feed=None, device=None) -> Dict[str, Any]:
    """Take one accounting snapshot AND publish it as gauges — the
    ``/metrics`` surface (``hbm_in_use_bytes``, ``hbm_peak_bytes``,
    ``hbm_category_bytes{category=...}``, and the per-device
    ``hbm_shard_bytes{category,shard}`` family).  Returns the
    snapshot (with the per-shard breakdown under ``"shards"``)."""
    snap = account(trainer, feed, device)
    shards = shard_categories(trainer, feed)
    snap["shards"] = shards
    if shards:
        sg = gauge("hbm_shard_bytes",
                   "bytes of each accounting category resident on each "
                   "device (sharded leaves count one shard per device, "
                   "replicated leaves their full size on every device) "
                   "— a per-(category,shard) label-explosion family; "
                   "consoles render it as a top-k summary")
        for cname, per_dev in shards.items():
            for dev_id, nbytes in per_dev.items():
                sg.set(nbytes, category=cname, shard=dev_id)
    gauge("hbm_in_use_bytes",
          "device memory currently in use (allocator stats when the "
          "backend reports them, else total live committed arrays)"
          ).set(snap["in_use_bytes"])
    gauge("hbm_peak_bytes",
          "peak device memory (allocator peak_bytes_in_use; running "
          "max of samples on stat-less backends)").set(snap["peak_bytes"])
    cat = gauge("hbm_category_bytes",
                "PER-CHIP bytes attributed to the trainer's known "
                "pytrees by buffer identity (sharded leaves count "
                "their one-device shard — the FSDP win reads "
                "directly); 'other' = unclaimed (activations in "
                "flight, allocator slack)")
    for name, nbytes in snap["categories"].items():
        cat.set(nbytes, category=name)
    return snap


def reset_peak() -> None:
    """Drop the running live-array peak (tests)."""
    global _live_peak
    _live_peak = 0
