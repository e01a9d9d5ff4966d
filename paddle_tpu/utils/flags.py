"""Central runtime-flag registry.

Equivalent of the reference's gflags hub (``paddle/utils/Flags.cpp:18-84``):
one process-wide table of named knobs, settable from the CLI
(``--name=value``), the environment (``PADDLE_TPU_<NAME>``), or code.  The
reference defines 109 flags; we keep the ones that still mean something on
TPU (device selection is a mesh, not ``--gpu_id``) and add TPU-specific ones.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional


@dataclass
class _FlagSpec:
    name: str
    default: Any
    help: str
    parser: Callable[[str], Any]


def _parse_bool(s: str) -> bool:
    return s.lower() in ("1", "true", "yes", "on")


class FlagRegistry:
    def __init__(self) -> None:
        self._specs: Dict[str, _FlagSpec] = {}
        self._values: Dict[str, Any] = {}

    def define(self, name: str, default: Any, help: str = "") -> None:
        if name in self._specs:
            # a silent re-registration wins the table and erases the
            # first definition's default/help — always a collision bug
            # (two modules claiming one knob), never intentional
            raise ValueError(
                f"flag {name!r} is already registered "
                f"(default={self._specs[name].default!r}); duplicate "
                "registration would silently replace it")
        if isinstance(default, bool):
            parser: Callable[[str], Any] = _parse_bool
        elif isinstance(default, int):
            parser = int
        elif isinstance(default, float):
            parser = float
        else:
            parser = str
        self._specs[name] = _FlagSpec(name, default, help, parser)
        env = os.environ.get("PADDLE_TPU_" + name.upper())
        self._values[name] = parser(env) if env is not None else default

    def __getattr__(self, name: str) -> Any:
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}") from None

    def set(self, name: str, value: Any) -> None:
        if name not in self._specs:
            raise KeyError(f"unknown flag {name!r}")
        self._values[name] = value

    def get(self, name: str) -> Any:
        return self._values[name]

    def parse_argv(self, argv: List[str]) -> List[str]:
        """Consume ``--name=value`` / ``--name value`` args; return the rest."""
        rest: List[str] = []
        i = 0
        while i < len(argv):
            arg = argv[i]
            if arg.startswith("--"):
                body = arg[2:]
                if "=" in body:
                    name, val = body.split("=", 1)
                else:
                    name = body
                    if (
                        name in self._specs
                        and not isinstance(self._specs[name].default, bool)
                        and i + 1 < len(argv)
                    ):
                        i += 1
                        val = argv[i]
                    else:
                        val = "true"
                name = name.replace("-", "_")
                if name in self._specs:
                    self._values[name] = self._specs[name].parser(val)
                else:
                    rest.append(arg)
            else:
                rest.append(arg)
            i += 1
        return rest

    def snapshot(self) -> Dict[str, Any]:
        return dict(self._values)


FLAGS = FlagRegistry()

# Core knobs (reference: paddle/utils/Flags.cpp).
FLAGS.define("trainer_count", 1, "data-parallel replicas (mesh 'data' axis size)")
FLAGS.define("trainer_id", 0, "index of this host in a multi-host job")
FLAGS.define("log_period", 100, "log every N batches")
FLAGS.define("test_period", 0, "test every N batches (0: per pass)")
FLAGS.define("show_parameter_stats_period", 0, "dump param stats every N batches")
FLAGS.define("checkgrad_eps", 1e-2, "finite-difference step for --job=checkgrad")
FLAGS.define("seed", 1, "global RNG seed (0: nondeterministic)")
FLAGS.define("saving_period", 1, "checkpoint every N passes")
FLAGS.define("load_missing_parameter_strategy", "fail", "fail|rand|zero")
FLAGS.define("init_model_path", "", "checkpoint dir to warm-start from")
FLAGS.define("start_pass", 0, "first pass number (resume)")
FLAGS.define("save_dir", "./output", "checkpoint output dir")
FLAGS.define("config_args", "", "comma-sep k=v pairs visible to configs")
FLAGS.define("precision", "fp32",
             "end-to-end training precision policy: fp32 | bf16.  "
             "bf16 = mixed precision — fp32 master weights cast to "
             "bfloat16 compute at the train-step boundary, fp32 "
             "optimizer state and gradient accumulation, dynamic loss "
             "scaling with skipped-step semantics on non-finite grads "
             "(trainer/trainer.py + optimizer/loss_scale.py), and the "
             "op-level compute policy (core/dtypes.py) forced to bf16 "
             "regardless of --use_bf16.  fp32 (the default) leaves the "
             "legacy --use_bf16/--bf16_activations resolution untouched "
             "byte-for-byte")
FLAGS.define("loss_scale_init", 32768.0,
             "initial dynamic loss scale under --precision=bf16 "
             "(2^15; grows 2x every --loss_scale_growth_interval "
             "overflow-free steps, halves — floor 1.0 — and skips the "
             "step on inf/nan gradients)")
FLAGS.define("loss_scale_growth_interval", 2000,
             "overflow-free steps between dynamic loss-scale doublings")
FLAGS.define("use_bf16", True, "run matmul/conv compute in bfloat16 on TPU")
FLAGS.define("bf16_activations", False,
             "store layer activations in bfloat16 (halves activation HBM "
             "traffic; params/losses stay fp32)")
FLAGS.define("conv_bn_fuse", True,
             "fuse linear-conv→batch_norm pairs through the Pallas "
             "backward-data kernel (ops/pallas_conv.py); off = the "
             "plain composition, for A/B traffic measurement")
FLAGS.define("conv_bn_fuse_fwd", True,
             "fuse batch_norm(+relu)→conv pairs on the FORWARD side: "
             "the BN's per-channel affine + ReLU stream through the "
             "consuming conv's input pipeline (Pallas 3x3 kernel / 1x1 "
             "GEMM prologue, ops/pallas_conv.py + ops/nn_ops.py) "
             "instead of materializing the normalized activation in "
             "HBM; off = the exact round-6 lowering, for A/B traffic "
             "measurement")
FLAGS.define("flash_kernel", True,
             "run attention through the Pallas flash kernel "
             "(ops/pallas_attention.py); off = the exact dense XLA "
             "attention composition, for A/B traffic measurement")
FLAGS.define("flash_block_sparse", True,
             "block-sparse flash attention: compact the KV grid per "
             "q-block so blocks fully above the causal diagonal or past "
             "a row's scalar-prefetched length are neither DMA'd nor "
             "visited (fwd + both backward kernels); off = the legacy "
             "full (B*H, q_blocks, k_blocks) grid that fetched every "
             "block and only skipped the compute, for one-flag revert / "
             "A/B traffic measurement")
FLAGS.define("attention_packing", True,
             "sequence packing for attention layers with packed=True: "
             "mixed-length rows share one [total_tokens] segment-id "
             "layout where padding and cross-sequence blocks do zero "
             "work; off = the layer ignores the packed attr and runs "
             "the exact padded per-row lowering")
FLAGS.define("fused_rnn_hblock", True,
             "enable the hidden-blocked fused RNN tier (ops/"
             "pallas_lstm.py, ops/pallas_gru.py): 512 < H shapes run "
             "the whole-sequence Pallas kernels with w_hh streamed as "
             "[H, gates*128] column blocks instead of falling back to "
             "lax.scan; off = the round-7 H<=512 gate, for one-flag "
             "revert / A/B measurement")
FLAGS.define("master_retry_max", 5,
             "reconnect attempts per master RPC: on connection loss the "
             "TCP MasterClient re-dials with exponential backoff + jitter "
             "and replays the request up to this many times; 0 restores "
             "the legacy fail-fast behavior (first drop raises "
             "PaddleTpuError)")
FLAGS.define("ckpt_keep", 5,
             "checkpoint retention: keep the newest N pass-* dirs after "
             "each save and delete older ones; 0 disables the sweep "
             "(keep everything, the legacy behavior)")
FLAGS.define("ckpt_verify", True,
             "verify per-file SHA-256 digests from the checkpoint "
             "manifest on load, and make resume scan backward past "
             "corrupt checkpoints (quarantined as .corrupt-*); off = "
             "the legacy blind latest-checkpoint load")
FLAGS.define("log_level", "",
             "framework log level: debug|info|warning|error|fatal "
             "(empty = PADDLE_TPU_LOG_LEVEL env var, else INFO); "
             "applied by the entry points after flag parsing via "
             "utils.logger.set_log_level")
FLAGS.define("metrics_jsonl", "",
             "telemetry JSONL sink path: when set, a background "
             "reporter appends one self-describing snapshot line "
             "(typed metrics + StatSet timer table) every "
             "--metrics_interval_s seconds (paddle_tpu/observe/); "
             "empty = no sink, instrumentation stays near-zero cost "
             "and the trainer skips its step-fencing time split")
FLAGS.define("metrics_interval_s", 10.0,
             "flush interval for the --metrics_jsonl reporter")
FLAGS.define("trace_jsonl", "",
             "span-trace sink path (paddle_tpu/observe/trace.py): when "
             "set, every span (trainer step phases, pipeline workers, "
             "checkpoint ops, master RPCs incl. the server-side echo, "
             "serving requests) streams to this file as Chrome "
             "trace-event JSON — load it directly in Perfetto / "
             "chrome://tracing; empty = no stream, span() is a shared "
             "no-op and the hot path pays <50 us/step")
FLAGS.define("trace_ring_size", 65536,
             "flight-recorder capacity: the last N spans of a live run "
             "kept in a bounded in-memory ring, served by the "
             "--metrics_port /trace endpoint and the SIGUSR2 debug "
             "dump")
FLAGS.define("metrics_port", 0,
             "live observability endpoint (paddle_tpu/observe/http.py):"
             " serve GET /metrics (Prometheus text), /healthz "
             "(liveness JSON) and /trace (flight-recorder dump as "
             "Chrome trace-event JSON) on this loopback port; 0 (the "
             "default) starts no server thread")
FLAGS.define("debug_dump_signal", False,
             "install a SIGUSR2 handler that dumps Prometheus text + "
             "the flight-recorder trace of the LIVE run to timestamped "
             "files under --debug_dump_dir (kill -USR2 <pid>) — "
             "post-mortem for wedged runs without a debugger")
FLAGS.define("debug_dump_dir", "/tmp",
             "output directory for --debug_dump_signal dumps")
FLAGS.define("metrics_bind", "",
             "bind address for the --metrics_port observability "
             "endpoint (empty = 127.0.0.1).  Non-loopback is an "
             "EXPLICIT opt-in for same-host-only/container scraping "
             "and logs a loud structured warning: the endpoint is "
             "diagnostics, NOT an external API — no auth, no TLS, "
             "never expose it past a trusted network boundary")
FLAGS.define("fleet_addr", "",
             "fleet aggregator address (host:port, observe/fleet.py): "
             "when set, this process pushes one self-describing "
             "telemetry frame — metrics snapshot, recent "
             "flight-recorder spans, health digest — every "
             "--metrics_interval_s seconds from the reporter thread.  "
             "A dead/version-skewed aggregator degrades the push sink "
             "(warn-once, exponential backoff + jitter) and never "
             "touches the training loop; empty (default) = no push "
             "client, no reporter thread, zero new work")
FLAGS.define("fleet_port", 0,
             "host the fleet aggregator in THIS process on this port "
             "(observe/fleet.py): serves GET /fleet/metrics (merged "
             "Prometheus with role/pid/node labels), /fleet/healthz "
             "(cluster rollup with staleness detection), /fleet/trace "
             "(all processes' spans merged into one Chrome trace-event "
             "timeline) and /fleet/topology, plus POST /fleet/push "
             "frame intake; 0 (default) hosts nothing")
FLAGS.define("fleet_bind", "",
             "bind address for the --fleet_port aggregator (empty = "
             "127.0.0.1).  Non-loopback is an explicit opt-in and "
             "warns loudly — same not-an-external-API rule as "
             "--metrics_bind")
FLAGS.define("fleet_id", "",
             "logical fleet identity of this process (e.g. trainer-0):"
             " the key the aggregator's staleness tracking uses, so a "
             "restarted process with the same id supersedes its dead "
             "entry and the /fleet/healthz rollup recovers.  Empty = "
             "derived role@node:pid (a restart then registers as a "
             "NEW process and the old entry stays missing)")
FLAGS.define("fleet_role", "trainer",
             "fleet role this process registers as (trainer | "
             "master-client | serving by convention); the elastic "
             "trainer and the serving loader override this "
             "programmatically")
FLAGS.define("fleet_stale_factor", 3.0,
             "staleness multiplier for the /fleet/healthz rollup: a "
             "process that has not pushed for this many multiples of "
             "its own advertised interval is reported 'missing' "
             "(a restarted process pushing under the same --fleet_id "
             "flips it back to ok)")
FLAGS.define("fleet_ring_size", 4096,
             "per-process span retention in the hosted aggregator: "
             "the newest N spans of each registered process kept for "
             "the merged /fleet/trace timeline")
FLAGS.define("fleet_push_timeout_s", 2.0,
             "socket timeout for one fleet push POST; a slow or dead "
             "aggregator costs the reporter thread at most this long "
             "before the degrade/backoff path takes over")
FLAGS.define("sigterm_flush", True,
             "install a chaining SIGTERM hook when any telemetry "
             "surface is configured (observe/shutdown.py): the final "
             "metrics interval is flushed, a last going-down fleet "
             "frame is pushed, and the --trace_jsonl array is "
             "finalized before the previous handler (or the default "
             "die-by-signal disposition) runs; off = the legacy "
             "atexit-only flush, which a SIGTERM-then-SIGKILL "
             "orchestrator window can lose")
FLAGS.define("health_interval", 0,
             "training-health telemetry (observe/health.py): every N "
             "steps drain the on-device per-layer accumulators — "
             "gradient/parameter norms, update ratios ||dw||/||w||, "
             "non-finite localization — into observe gauges, /metrics "
             "and the host-side detectors (loss spike/plateau, "
             "dead/exploding layers).  The aux path is fused into the "
             "jitted train step and keyed to the same layer names as "
             "the roofline attribution; the drain's small D2H fetch is "
             "the only fence, amortized over N steps.  0 (default) = "
             "off: the step is built without any aux outputs, "
             "byte-for-byte the legacy program")
FLAGS.define("health_window", 32,
             "rolling window (in drains) for the loss median/MAD "
             "robust statistics behind the spike/plateau detectors")
FLAGS.define("health_spike_mad", 8.0,
             "loss-spike threshold: alert when loss exceeds the "
             "rolling median by this many robust sigmas (1.4826*MAD)")
FLAGS.define("health_plateau_rtol", 1e-4,
             "loss-plateau threshold: alert when the loss window's "
             "full range stays within this relative tolerance of the "
             "median for a whole window")
FLAGS.define("health_dead_ratio", 1e-10,
             "dead-layer threshold: alert when a layer's update ratio "
             "||dw||/||w|| stays at or below this for "
             "--health_patience consecutive drains")
FLAGS.define("health_explode_ratio", 0.5,
             "exploding-layer threshold: alert when a layer's update "
             "ratio exceeds this for --health_patience consecutive "
             "drains")
FLAGS.define("health_patience", 2,
             "consecutive drains a dead/exploding condition must "
             "persist before its alert fires")
FLAGS.define("roofline_dump", "",
             "write the attributed per-region roofline/cost report of "
             "the compiled train step (observe/costmodel.py: FLOPs / "
             "HBM bytes / compute-vs-memory verdict per network layer, "
             "keyed through the layer named_scopes) to this JSON path "
             "at the end of the first training pass; empty = off")
FLAGS.define("roofline_peak_flops", 0.0,
             "override the detected peak FLOP/s for roofline/MFU "
             "verdicts (0 = auto-detect from the device kind)")
FLAGS.define("roofline_peak_gbps", 0.0,
             "override the detected HBM bandwidth (GB/s) for roofline "
             "verdicts (0 = auto-detect from the device kind)")
FLAGS.define("serve_port", 0,
             "serving HTTP endpoint (serving/server.py): POST "
             "/v1/generate with {'prompt': [token ids], "
             "'max_new_tokens': n} blocks until generation completes "
             "and returns the tokens; GET /healthz reports queue depth "
             "and page-pool occupancy.  0 picks a free port when the "
             "server is started with serve_http=True; the loopback/"
             "trusted-bind rules of --metrics_bind apply via "
             "--serve_bind")
FLAGS.define("serve_bind", "",
             "bind host for the serving endpoint; empty = loopback "
             "only (same trust contract as --metrics_bind: 0.0.0.0 "
             "requires PADDLE_TPU_TRUST_NETWORK=1)")
FLAGS.define("serve_max_batch", 8,
             "continuous-batching decode width (serving/server.py): "
             "at most this many requests share one "
             "paged_decode_attention launch; new admissions join "
             "between decode steps up to this cap")
FLAGS.define("serve_continuous", True,
             "continuous batching in the inference server: requests "
             "join the in-flight decode batch between steps and "
             "prefill is packed across admissions "
             "(flash_attention_packed).  false = the kill switch — "
             "sequential single-request serving (admit one, prefill "
             "alone, decode to completion, then the next), "
             "byte-for-byte the same generated tokens")
FLAGS.define("kv_pool_pages", 128,
             "physical pages in the shared serving KV pool "
             "(serving/pagepool.py); each request holds "
             "ceil(context/--kv_page_size) pages via its page table "
             "and returns them on completion for recycling")
FLAGS.define("kv_page_size", 16,
             "tokens per KV page (the paged_decode_attention page "
             "axis; the kernel takes up to 512 tokens' worth of pages a "
             "loop step, so a small page costs DMAs, not steps); pool "
             "capacity in tokens is kv_pool_pages x kv_page_size")
FLAGS.define("serve_slo_ms", 0.0,
             "optional p99 TTFT SLO in milliseconds: when > 0 the "
             "server's /healthz reports "
             "ttft_p99_ms and slo_met from the serve_ttft_seconds "
             "WINDOWED reservoir p99 (last ~60s), so a recovered "
             "server stops advertising a stale lifetime p99; 0 "
             "(default) leaves /healthz byte-identical")
FLAGS.define("slo", "",
             "declarative serving SLOs evaluated continuously on the "
             "reporter thread (observe/slo.py): objectives joined "
             "with ',' or ';' in metric:statOPthreshold:window "
             "grammar, e.g. 'serve_ttft_seconds:p99<0.5:60s' (stat "
             "pNN windowed quantile or rate events/s, OP < or >, "
             "window Ns/Nm).  Each yields ok/breach plus fast+slow "
             "multi-window burn rates on slo_status/slo_burn_rate "
             "gauges, /slo, /healthz, and the fleet plane.  Empty "
             "(default) = no engine, every surface byte-identical")
FLAGS.define("rollout", True,
             "the zero-downtime train->serve pipeline "
             "(serving/rollout.py): checkpoint watcher + atomic "
             "hot-swap of exported artifacts into the live "
             "InferenceServer between decode steps, with automatic "
             "rollback on a failed verify/load/probe.  false is the "
             "kill switch: request_swap refuses, POST /v1/swap is an "
             "unknown path, and /healthz carries exactly the PR-15 "
             "body — the server is byte-identical to pre-rollout "
             "behavior")
FLAGS.define("rollout_poll_s", 5.0,
             "checkpoint-watcher poll interval (serving/rollout.py): "
             "how often the watcher rescans --save_dir for a new "
             "digest-verified retained checkpoint to export")
FLAGS.define("rollout_inflight", "drain",
             "what happens to in-flight sequences at the hot-swap "
             "pointer flip: 'drain' finishes them on the OLD model "
             "before flipping (admissions pause, zero recompute); "
             "'reprefill' flips immediately and restarts their "
             "generation from the prompt on the NEW model (tokens "
             "generated so far are discarded — a response always "
             "comes from exactly one model under BOTH policies)")
FLAGS.define("rollout_quantize", "int8",
             "serving-artifact quantization the watcher's export uses "
             "(int8 per-channel weights-only, or 'none' for raw fp32 "
             "— same schemes as export_decoder)")
FLAGS.define("rollout_export_dir", "",
             "directory the checkpoint watcher writes serving "
             "artifacts into (model-<digest> dirs, atomic tmp+rename; "
             "empty = <save_dir>/export)")
FLAGS.define("rollout_canary", False,
             "canary bake policy for rollouts (serving/rollout.py): "
             "the RollingCoordinator swaps ONE replica first and "
             "bakes it for --rollout_bake_s, comparing the canary's "
             "windowed p99 TTFT and error rate against the pooled "
             "baseline replicas via the fleet aggregator; on breach "
             "the canary is auto-rolled-back and the rollout HALTS "
             "(reason on /healthz, rollout_canary_total{result}), "
             "otherwise the remaining replicas swap.  Single-server "
             "swaps get the same bake-then-commit window.  false "
             "(default) = PR-18 behavior, byte-identical")
FLAGS.define("rollout_bake_s", 0.0,
             "canary bake duration in seconds (--rollout_canary): "
             "how long a freshly swapped canary serves traffic "
             "before its windowed p99 TTFT / error rate is compared "
             "against the baseline pool and the rollout commits or "
             "rolls back; 0 with --rollout_canary still does the "
             "one-replica-first walk but skips the bake wait")
FLAGS.define("rollout_canary_factor", 2.0,
             "canary breach threshold (--rollout_canary): the bake "
             "fails when canary windowed p99 TTFT > factor x pooled "
             "baseline p99, or canary error rate > factor x baseline "
             "error rate (any canary errors breach when the baseline "
             "pool is error-free)")
FLAGS.define("ckpt_export_lease_s", 600.0,
             "stale-mtime expiry for .exporting-<pid> checkpoint pin "
             "markers (trainer/checkpoint.py): the retention sweep "
             "honors a fresher marker (never reaps a checkpoint "
             "mid-export) and ignores older ones — a SIGKILLed "
             "exporter cannot pin a checkpoint forever")
FLAGS.define("sparse_grads", True,
             "sparse gradient exchange for ParamAttr(sparse_update="
             "True) embedding tables (parallel/sparse.py): the jitted "
             "train step carries each table's gradient as a fixed-"
             "capacity (rows, values) pair — batch ids deduped once, "
             "row cotangents segment-summed by autodiff — and applies "
             "it as a shard-local scatter-add through "
             "Optimizer.apply_rows, so the dense [V, D] gradient is "
             "never materialized or all-reduced.  false is the kill "
             "switch: the legacy dense gradient + lazy row masking, "
             "byte-for-byte")
FLAGS.define("sparse_grad_rows", 0,
             "fixed row capacity K of the sparse gradient exchange "
             "per table (the SelectedRows prefetch-buffer budget): "
             "rows/values ship as [K]/[K, D] whatever the batch "
             "touches.  0 (default) = auto — the batch's total id "
             "count, which can never overflow.  A manual K below the "
             "unique-id count of a batch drops the LARGEST ids from "
             "the update (jnp.unique keeps the smallest K) — size it "
             ">= the worst-case unique ids per batch")
FLAGS.define("embedding_kernel", True,
             "gather embedding rows through the Pallas scalar-prefetch "
             "kernel (ops/pallas_embedding.py): the deduped row-index "
             "table rides the grid spec's scalar prefetch so only "
             "touched rows are DMA'd HBM->VMEM; false = the plain XLA "
             "take gather, byte-for-byte, for one-flag revert / A/B "
             "traffic measurement")
FLAGS.define("embedding_kernel_interpret", False,
             "run the Pallas embedding gather in interpret mode on "
             "non-TPU backends (numerics-contract tests at tiny "
             "shapes).  Off (default), CPU/GPU dispatch falls back to "
             "the XLA gather with reason no_tpu — interpret mode "
             "emulates the grid one step at a time and costs seconds "
             "per call at production row counts")
FLAGS.define("mesh_shape", "", "mesh as 'data=8' or 'data=4,model=2' (auto if empty)")
FLAGS.define("fsdp", False,
             "shard parameters AND optimizer slots over the 'data' "
             "mesh axis (FSDP): per-chip params/opt_state HBM drops "
             "by the data-axis extent while XLA turns the gradient "
             "all-reduce into an all-gather/reduce-scatter pair; "
             "placement comes from the trainer's fsdp_rules table "
             "(parallel/rule_tables.py for zoo models) else the "
             "largest-divisible-dim heuristic.  --fsdp=false is the "
             "kill switch: the replicated path, byte-for-byte")
FLAGS.define("fsdp_min_size", 1024,
             "parameters below this many elements stay replicated "
             "under the FSDP auto heuristic (norm gains, biases): "
             "sharding KiB-scale tensors fragments collectives for "
             "no memory win; rule-table entries are exempt — a "
             "committed table says exactly what it means")
FLAGS.define("prefetch_depth", 2,
             "async input pipeline depth (data/pipeline.py): max "
             "batches in flight between the reader and the train step "
             "— reader IO, DataFeeder.convert, and the host->device "
             "transfer run on worker threads and overlap the running "
             "step; 0 restores the fully synchronous loop "
             "(read -> convert -> step, byte-for-byte)")
FLAGS.define("reader_workers", 2,
             "reader/convert worker threads per async input pipeline "
             "(clamped to prefetch_depth; reading from the source is "
             "serialized, convert+transfer parallelize)")
FLAGS.define("parallel_nn", False, "per-layer device placement (sharding annotations)")
FLAGS.define("port", 7164, "data-task coordinator service port")
