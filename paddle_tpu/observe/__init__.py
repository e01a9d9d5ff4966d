"""paddle_tpu.observe — the unified telemetry layer.

Typed metrics (Counter / Gauge / Histogram) in a process-wide registry,
exported together with the ``StatSet`` wall-timer table through one
reporter: a JSONL sink (``--metrics_jsonl PATH``, one self-describing
line per flush interval) and an on-demand Prometheus text dump.

Instrumented surfaces (all against :data:`REGISTRY`):

- trainer: step latency split host-feed vs device-blocked, samples/sec,
  jit recompiles (``paddle_tpu/trainer/trainer.py``);
- data path: input wait (reader or prefetch queue) + feed-convert time
  → input-bound ratio; async-pipeline queue depth, prefetch hit/stall
  census, worker convert time, cloud read-ahead depth/chunks
  (``paddle_tpu/data/pipeline.py``, ``distributed/master.py``);
- dispatch tiers: RNN fused_blocked/fused/scan with fallback reasons,
  conv+BN fused/chain/unfused (``ops/recurrent_ops.py``,
  ``ops/nn_ops.py``), build-time fused-pair census
  (``layers/network.py``);
- fault tolerance: master reconnect/backoff/replay, checkpoint
  save/verify latency + quarantines, elastic skipped-save/election
  releases (``distributed/``, ``trainer/checkpoint.py``);
- serving: request count + inference latency (``serving/loader.py``);
- training health: per-layer grad/param norms, update ratios,
  non-finite localization and detector alerts, drained from the
  on-device accumulators every ``--health_interval`` steps
  (``observe/health.py``, ``trainer/trainer.py``);
- the fleet plane: cross-process push aggregation — every process
  with ``--fleet_addr`` ships its snapshot + recent spans + health
  digest to an aggregator any process hosts with ``--fleet_port``
  (cluster health rollup, merged Prometheus, ONE merged Perfetto
  timeline; ``observe/fleet.py``), with a chaining SIGTERM hook so
  the final interval survives an orchestrator kill
  (``observe/shutdown.py``).

Overhead contract: with no sink attached every instrument is a dict
lookup + lock + add; anything more expensive (step fencing) is gated on
:func:`active`.
"""

from .metrics import (  # noqa: F401
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    format_labels,
    gauge,
    histogram,
)
from .report import (  # noqa: F401
    MetricsReporter,
    active,
    attach,
    prometheus_dump,
)
from .report import start_from_flags as _start_reporter_from_flags
from .report import stop_global as _stop_reporter_global
from . import dump, fleet, http, memory, shutdown, trace  # noqa: F401
# costmodel and health are NOT imported eagerly: their entry points
# touch jax (lazily), and keeping them explicit `from
# paddle_tpu.observe import costmodel` / `... import health` imports
# preserves this package's import-time zero-dep rule — AND lets the
# HTTP endpoint / healthz probe resolve them through sys.modules so a
# process that never trained pays nothing for either surface.


def start_from_flags():
    """One call a long-running entry point makes (``Trainer.train``,
    the CLI): start every flag-configured observability
    surface — the ``--metrics_jsonl`` reporter (with the
    ``--fleet_addr`` push client folded in), ``--trace_jsonl`` span
    sink, the ``--metrics_port`` HTTP endpoint, the ``--fleet_port``
    aggregator, the ``--debug_dump_signal`` SIGUSR2 handler, and the
    graceful-shutdown SIGTERM flush hook (installed only once some
    surface above actually got configured).  Each piece is
    individually idempotent and a no-op when its flag is unset, so
    with nothing configured this is a few dict lookups and no thread
    starts."""
    reporter = _start_reporter_from_flags()
    trace.start_from_flags()
    http.start_from_flags()
    fleet.start_from_flags()
    dump.install_from_flags()
    shutdown.install_from_flags()
    return reporter


def stop_global():
    """Stop every process-wide observability surface (reporter + HTTP
    endpoint + fleet aggregator + trace sink + SIGTERM hook) — the
    mirror of :func:`start_from_flags`."""
    _stop_reporter_global()
    http.stop_global()
    fleet.stop_global()
    trace.disable()
    shutdown.uninstall()


__all__ = [
    "DEFAULT_BUCKETS", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "REGISTRY", "counter", "gauge", "histogram",
    "format_labels", "MetricsReporter", "active", "attach",
    "prometheus_dump", "start_from_flags", "stop_global",
    "trace", "http", "dump", "memory", "fleet", "shutdown",
]
