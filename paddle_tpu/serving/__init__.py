"""Deployment/serving — the TPU-native answer to ``paddle/capi``.

The reference ships a pure-C inference API
(``paddle/capi/gradient_machine.h:36-88``: create-for-inference, forward,
shared-parameter clones for multi-threaded serving) so trained models run
in processes that embed none of the training framework.  On TPU the
equivalent artifact is a **StableHLO module** (`jax.export`): the whole
inference function — topology and weights — compiled to a stable,
versioned IR that any PJRT runtime can execute with zero framework code.

- :mod:`paddle_tpu.serving.export` — build the artifact from a trained
  network / v2 inferer / framework program.
- :mod:`paddle_tpu.serving.loader` — standalone loader (imports only
  jax + numpy + json; never the layer engine).

The request-serving half (ISSUE 16) turns the PR-14/15 kernels into
sustained req/s:

- :mod:`paddle_tpu.serving.pagepool` — shared KV page-pool allocator
  issuing per-request page tables, recycling freed pages, with atomic
  checksummed snapshots (crash safety).
- :mod:`paddle_tpu.serving.model` — decoder transformer whose prefill
  is one ``flash_attention_packed`` launch and whose decode step is
  ``paged_decode_attention`` over the pool; int8 decoder artifacts.
- :mod:`paddle_tpu.serving.server` — the continuous-batching
  :class:`InferenceServer` (admission queue, fixed-width decode batch,
  sequential kill switch, HTTP front, per-request telemetry).
- :mod:`paddle_tpu.serving.rollout` — the zero-downtime train→serve
  pipeline (ISSUE 19): checkpoint watcher, atomic hot-swap with
  rollback, fleet-supervised rolling rollout.
"""

from .export import export_inference_fn, export_network  # noqa: F401
from .loader import (ServedModel, TornArtifact,  # noqa: F401
                     artifact_digest, verify_artifact)
from .pagepool import (PagePool, PagePoolExhausted,  # noqa: F401
                       TornSnapshot)

# The decoder/server half pulls in the attention kernels
# (paddle_tpu.ops) — resolved lazily (PEP 562) so a process that only
# LOADS artifacts keeps the loader contract: importing
# paddle_tpu.serving.loader must never drag in the layer engine
# (pinned by tests/test_serving.py's fresh-process check).
_LAZY = {
    "DecoderConfig": "model", "DecoderModel": "model", "KVPool": "model",
    "export_decoder": "model", "init_decoder_params": "model",
    "InferenceServer": "server", "Request": "server",
    "SwapTicket": "server",
    "CheckpointWatcher": "rollout", "RollingCoordinator": "rollout",
    "swap_from_artifact": "rollout", "export_checkpoint": "rollout",
    "latest_valid_artifact": "rollout", "sweep_export_dir": "rollout",
}


def __getattr__(name):
    if name in _LAZY:
        import importlib

        mod = importlib.import_module(f".{_LAZY[name]}", __name__)
        return getattr(mod, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
