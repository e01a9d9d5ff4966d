"""Device and mesh management.

TPU-native replacement for the reference's device layer
(``paddle/platform/place.h:24-71`` CPUPlace/GPUPlace,
``paddle/platform/device_context.h:38-72``, ``paddle/cuda`` device mgmt):
on TPU the unit of execution is not "a device" but a **mesh** of devices that
one jit-compiled program spans.  ``get_mesh()`` builds the process-global
``jax.sharding.Mesh`` from ``FLAGS.mesh_shape`` (or all local devices on a
``data`` axis), and the named-sharding helpers below are what layers and the
trainer use instead of per-device placement.

Axis conventions (used across paddle_tpu.parallel):
  ``data``  — batch (data parallel / DP)
  ``model`` — weight sharding (tensor parallel / sparse table sharding)
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils import FLAGS, PaddleTpuError, get_logger

log = get_logger("device")

DATA_AXIS = "data"
MODEL_AXIS = "model"

_mesh: Optional[Mesh] = None


def parse_mesh_shape(spec: str) -> Dict[str, int]:
    """Parse ``'data=4,model=2'`` into an ordered axis→size dict."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise PaddleTpuError(f"bad mesh_shape component {part!r}")
        k, v = part.split("=", 1)
        out[k.strip()] = int(v)
    return out


def build_mesh(axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if not axes:
        axes = {DATA_AXIS: len(devices)}
    n = int(np.prod(list(axes.values())))
    if n > len(devices):
        raise PaddleTpuError(
            f"mesh {axes} needs {n} devices, have {len(devices)}"
        )
    dev_array = np.array(devices[:n]).reshape(tuple(axes.values()))
    return Mesh(dev_array, tuple(axes.keys()))


def get_mesh(refresh: bool = False) -> Mesh:
    global _mesh
    if _mesh is None or refresh:
        axes = parse_mesh_shape(FLAGS.mesh_shape) if FLAGS.mesh_shape else None
        _mesh = build_mesh(axes)
        log.info("mesh: %s over %d %s device(s)",
                 dict(zip(_mesh.axis_names, _mesh.devices.shape)),
                 _mesh.devices.size, _mesh.devices.flat[0].platform)
    return _mesh


def set_mesh(mesh: Mesh) -> None:
    global _mesh
    _mesh = mesh


def data_sharding(mesh: Optional[Mesh] = None, rank: int = 2) -> NamedSharding:
    """Batch-dim sharded over ``data``, rest replicated."""
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P(DATA_AXIS, *(None,) * (rank - 1)))


def replicated(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    return NamedSharding(mesh, P())


def num_data_shards(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or get_mesh()
    return mesh.shape.get(DATA_AXIS, 1)


def is_tpu() -> bool:
    """THE platform predicate: true only when JAX's default device is a
    TPU.  Everything that behaves differently off the chip (Pallas
    interpret mode, the embedding kernel's ``no_tpu`` veto) asks here."""
    return jax.devices()[0].platform == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` argument of every ``pallas_call`` in
    ``paddle_tpu/ops``: Mosaic on a TPU, the Pallas interpreter
    everywhere else (the CPU test mesh)."""
    return not is_tpu()


# ---------------------------------------------- kernels under a mesh
# Mosaic refuses to be partitioned by GSPMD ("Mosaic kernels cannot be
# automatically partitioned.  Please wrap the call in a shard_map." —
# four-chip run, PR 21), so a jitted program that spans more than one
# device must hand each Pallas kernel its own shard explicitly.  The
# program's owner (the Trainer) names its mesh for the duration of the
# trace; the batch-local kernel dispatch sites run through batch_local.
_KERNEL_MESH = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh: Optional[Mesh]):
    """Trace-time scope naming the mesh the enclosing jitted program is
    partitioned over.  Entered INSIDE the traced function, so every
    retrace (``.lower()``, a scan over the raw step) sees it too."""
    prev = getattr(_KERNEL_MESH, "mesh", None)
    _KERNEL_MESH.mesh = mesh
    try:
        yield
    finally:
        _KERNEL_MESH.mesh = prev


def _spanned_mesh() -> Optional[Mesh]:
    """The :func:`kernel_mesh` in scope if it spans several devices."""
    mesh = getattr(_KERNEL_MESH, "mesh", None)
    return mesh if mesh is not None and mesh.devices.size > 1 else None


def kernel_devices() -> int:
    """Devices of the :func:`kernel_mesh` in scope (1 with none)."""
    mesh = _spanned_mesh()
    return 1 if mesh is None else int(mesh.devices.size)


def local_rows(rows: int) -> int:
    """Rows of a ``rows``-row batch that one device's kernel sees under
    the :func:`kernel_mesh` in scope — what :func:`batch_local` hands
    ``fn`` — for gates that must judge the per-device shape.  A batch
    the data axis does not divide stays whole on every device, as
    ``Trainer._shard_feed`` leaves its feed."""
    mesh = _spanned_mesh()
    n = 1 if mesh is None else mesh.shape.get(DATA_AXIS, 1)
    return rows // n if rows % n == 0 else rows


def batch_local(fn: Callable, args: Sequence, batch_in: Sequence[bool],
                batch_out):
    """``fn(*args)`` with each Pallas kernel inside seeing only its own
    rows: under a :func:`kernel_mesh` of more than one device the call
    runs in ``jax.shard_map`` — arguments flagged in ``batch_in`` (and
    outputs in ``batch_out``, a pytree prefix of bools) split on their
    leading dim over ``data`` (:func:`local_rows` each), everything else
    replicated (a sharded weight is gathered at the boundary; its
    cotangent is summed back by the transpose).  With no mesh in scope,
    or one device, it is a plain call."""
    mesh = _spanned_mesh()
    if mesh is None:
        return fn(*args)
    n = mesh.shape.get(DATA_AXIS, 1)
    divides = all(a.shape[0] % n == 0 for a, is_b in zip(args, batch_in)
                  if is_b and a is not None)
    split = P(DATA_AXIS) if n > 1 and divides else P()
    spec = lambda is_b: split if is_b else P()
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(b) for b in batch_in),
        out_specs=jax.tree_util.tree_map(spec, batch_out),
        # the kernels' outputs carry no varying-axes type; the specs
        # above are the whole contract
        check_vma=False)(*args)


def describe_devices() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them —
    stamped on every line that carries a timing, so a CPU number can
    never pass for a device number."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


#: In-checkout compile cache, used only when the environment names none.
#: A fixed path: the directory is part of the cache key's lookup, so a
#: temp name, pid or timestamp here would never hit.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its path.
    ``JAX_COMPILATION_CACHE_DIR`` wins untouched (JAX reads it itself);
    otherwise ``<checkout>/.jax_cache``.  Called by every entry point
    that compiles (CLI, server start-up, chip_smoke)."""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    if jax.config.jax_compilation_cache_dir != DEFAULT_COMPILE_CACHE:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
