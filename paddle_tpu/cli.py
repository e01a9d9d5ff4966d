"""``paddle`` CLI — the ``paddle train`` driver
(``paddle/trainer/TrainerMain.cpp:32`` + ``paddle/scripts/submit_local.sh.in``).

Jobs: train / test / time / checkgrad (``--job=``, ``Trainer.cpp:299``,
``TrainerBenchmark.cpp``), plus ``version``.  Config files use the v1
protocol (see :mod:`paddle_tpu.config.config_parser`).

Usage:
    python -m paddle_tpu train --config=conf.py --job=time \
        --config_args batch_size=64 --num_passes=2 --save_dir=./out
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

from .utils import FLAGS, get_logger

log = get_logger("cli")


def _build_reader(ds, opt, test: bool = False):
    """Data source spec → batched reader (PyDataProvider2 protocol)."""
    from .data.reader import batch as batch_reader

    file_list: List[str] = []
    lst = ds.test_list if test and ds.test_list else ds.train_list
    if lst and os.path.exists(lst):
        with open(lst) as f:
            file_list = [ln.strip() for ln in f if ln.strip()]
    mod = importlib.import_module(ds.module)
    provider = getattr(mod, ds.obj)
    reader = provider.reader(*file_list, **ds.args)
    return batch_reader(reader, opt.batch_size), provider


def _feeder_for(provider, model):
    from .data.feeder import DataFeeder

    # init_hook providers fill settings.input_types when reader() is built
    types = provider.input_types or \
        getattr(provider.settings, "input_types", None)
    if isinstance(types, dict):
        pairs = list(types.items())
    else:
        data_layers = [l for l in model.layers if l.type == "data"]
        pairs = [(dl.name, t) for dl, t in zip(data_layers, types)]
    return DataFeeder(pairs)


def cmd_train(args) -> int:
    from .config.config_parser import parse_config
    from .distributed.launch import cluster_env, initialize_cluster
    from .layers.network import NeuralNetwork
    from .parallel.local_sgd import make_trainer

    if cluster_env() or args.distributed:
        # PADDLE_COORDINATOR set, or --distributed for TPU-pod
        # auto-detection
        env = cluster_env()
        if env and env["coordinator_address"] and not (
                env["num_processes"] and env["process_id"] is not None):
            log.error("PADDLE_COORDINATOR requires PADDLE_NUM_NODES "
                      "and PADDLE_NODE_ID")
            return 2
        initialize_cluster()

    model, opt, ds = parse_config(args.config, args.config_args)
    log.info("config parsed: %d layers, batch_size=%d, method=%s",
             len(model.layers), opt.batch_size, opt.learning_method)
    # provider modules live next to the config file
    cfg_dir = os.path.dirname(os.path.abspath(args.config))
    if cfg_dir not in sys.path:
        sys.path.insert(0, cfg_dir)
    net = NeuralNetwork(model)
    # honors OptimizationConfig.local_sgd_steps (async/local-SGD mode)
    trainer = make_trainer(net, opt)
    # restore parameters BEFORE any job runs (test must see them)
    if args.init_model_path:
        trainer.load(args.init_model_path)
    if args.save_dir:
        FLAGS.set("save_dir", args.save_dir)
        trainer.resume(args.save_dir)
    reader, provider = _build_reader(ds, opt, test=(args.job == "test"))
    feeder = _feeder_for(provider, model)

    if args.job == "time":
        from .core.device import describe_devices

        metrics = trainer.time_job(reader, feeder,
                                   batches=args.test_period or 20)
        # the line names the device it timed: a CPU run can never be
        # read as a chip number
        print(json.dumps({"job": "time", **describe_devices(),
                          **{k: round(v, 3) for k, v in metrics.items()}}))
        return 0
    if args.job == "checkgrad":
        batch = next(iter(reader()))
        diffs = trainer.check_gradients(feeder.convert(batch))
        bad = {k: v for k, v in diffs.items() if v > 1e-2}
        print(json.dumps({"job": "checkgrad", "checked": len(diffs),
                          "failed": len(bad)}))
        return 1 if bad else 0
    if args.job == "test":
        metrics = trainer.test(reader, feeder)
        print(json.dumps({"job": "test", **metrics}))
        return 0

    trainer.train(reader, num_passes=args.num_passes, feeder=feeder)
    if args.save_dir:
        trainer.save(args.save_dir, args.num_passes - 1)
    return 0


def cmd_merge_model(args) -> int:
    """``paddle_merge_model`` (``paddle/trainer/MergeModel.cpp``): config
    + trained parameters → ONE self-contained model file."""
    from .config.config_parser import parse_config
    from .trainer import interop

    model, _opt, _ds = parse_config(args.config_file, args.config_args)
    model = interop.with_full_param_specs(model)
    params = interop.checkpoint_to_params(args.model_dir)
    if not params:  # reference raw-buffer pass-%05d layout
        params = interop.load_reference_model_dir(args.model_dir, model)
    missing = [p.name for p in model.parameters if p.name not in params]
    if missing:
        log.error("model_dir %s lacks parameters: %s", args.model_dir,
                  missing)
        return 1
    interop.merge_model(model, params, args.model_file)
    print(json.dumps({"job": "merge_model", "out": args.model_file,
                      "parameters": len(model.parameters)}))
    return 0


def cmd_dump_config(args) -> int:
    """``dump_config``/``show_pb`` equivalent
    (``python/paddle/utils/dump_config.py``): print the parsed model
    config (``--whole`` adds optimization + data config)."""
    from .config.config_parser import parse_config

    model, opt, ds = parse_config(args.config, args.config_args)
    if args.whole:
        import dataclasses
        payload = {"model": json.loads(model.to_json()),
                   "opt": dataclasses.asdict(opt),
                   "data": dataclasses.asdict(ds) if ds else None}
        print(json.dumps(payload, indent=1))
    else:
        print(model.to_json())
    return 0


def cmd_diagram(args) -> int:
    """``make_model_diagram.py`` equivalent: config → graphviz DOT."""
    from .config.config_parser import parse_config
    from .utils.model_diagram import model_to_dot

    model, _, _ = parse_config(args.config, args.config_args)
    print(model_to_dot(model))
    return 0


def cmd_master(args) -> int:
    """Standalone data-task master (the reference's standalone
    coordinator binaries: ``paddle pserver`` / ``go/cmd/master``) — serve
    the C++ task-lease service over TCP for remote trainers."""
    import signal

    from .data import recordio as rio
    from .distributed import Master

    m = Master(timeout_s=args.task_timeout, failure_max=args.failure_max,
               snapshot_path=args.snapshot or "")
    if args.dataset:
        payloads = rio.chunk_payloads(args.dataset) if args.chunked \
            else rio.expand_paths(args.dataset)
        m.set_dataset(payloads)
        print(f"dataset: {len(payloads)} task(s)")
    port = m.serve(args.port, bind_any=not args.local_only)
    print(f"master serving on :{port}", flush=True)
    stop = []
    signal.signal(signal.SIGTERM, lambda *_: stop.append(1))
    signal.signal(signal.SIGINT, lambda *_: stop.append(1))
    import time

    last_snap = time.time()
    while not stop:
        time.sleep(0.5)
        if args.snapshot and time.time() - last_snap >= args.snapshot_period:
            m.snapshot()
            last_snap = time.time()
    if args.snapshot:
        m.snapshot()
    return 0


def cmd_version(_args) -> int:
    import jax

    from . import __version__
    from .core.device import describe_devices
    print(f"paddle_tpu {__version__} (jax {jax.__version__}, "
          f"devices {describe_devices()})")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(prog="paddle",
                                     description=__doc__.splitlines()[0])
    sub = parser.add_subparsers(dest="command", required=True)

    tp = sub.add_parser("train", help="train/test/time/checkgrad a config")
    tp.add_argument("--config", required=True)
    tp.add_argument("--job", default="train",
                    choices=["train", "test", "time", "checkgrad"])
    tp.add_argument("--config_args", default="")
    tp.add_argument("--num_passes", type=int, default=1)
    tp.add_argument("--save_dir", default="")
    tp.add_argument("--init_model_path", default="")
    tp.add_argument("--test_period", type=int, default=0)
    tp.add_argument("--distributed", action="store_true",
                    help="join/auto-detect a multi-host cluster "
                         "(jax.distributed)")
    tp.add_argument("--mesh_shape", default="",
                    help="e.g. data=4,model=2 (replaces --trainer_count)")
    tp.add_argument("--precision", default=None,
                    choices=["fp32", "bf16"],
                    help="training precision policy: bf16 = fp32 "
                         "master weights + bf16 compute + dynamic "
                         "loss scaling (default fp32)")
    tp.add_argument("--use_bf16", type=int, default=None)
    tp.add_argument("--bf16_activations", type=int, default=None)
    tp.add_argument("--log_level", default="",
                    help="framework log level "
                         "(debug|info|warning|error|fatal)")
    tp.add_argument("--metrics_jsonl", default="",
                    help="telemetry sink: append one metrics+timers "
                         "snapshot line here every "
                         "--metrics_interval_s seconds")
    tp.add_argument("--metrics_interval_s", type=float, default=None)
    tp.add_argument("--trace_jsonl", default="",
                    help="span-trace sink: stream every span (step "
                         "phases, pipeline workers, master RPCs, "
                         "checkpoints) here as Chrome trace-event "
                         "JSON, loadable in Perfetto")
    tp.add_argument("--metrics_port", type=int, default=None,
                    help="serve /metrics + /healthz + /trace on this "
                         "loopback port during the run (0 = off)")
    tp.add_argument("--metrics_bind", default=None,
                    help="bind address for --metrics_port (default "
                         "loopback; non-loopback is an explicit, "
                         "loudly-warned opt-in — the endpoint is "
                         "diagnostics, not an external API)")
    tp.add_argument("--fleet_addr", default=None,
                    help="push one telemetry frame (metrics + recent "
                         "spans + health digest) per interval to the "
                         "fleet aggregator at host:port "
                         "(observe/fleet.py); a dead aggregator "
                         "degrades the push sink, never the run")
    tp.add_argument("--fleet_port", type=int, default=None,
                    help="host the fleet aggregator in this process: "
                         "/fleet/metrics /fleet/healthz /fleet/trace "
                         "/fleet/topology + POST /fleet/push "
                         "(0 = off)")
    tp.add_argument("--fleet_id", default=None,
                    help="logical fleet identity (e.g. trainer-0): "
                         "stable across restarts so the cluster "
                         "rollup recovers when this process comes "
                         "back")
    tp.add_argument("--debug_dump_signal", action="store_true",
                    help="SIGUSR2 dumps metrics + flight-recorder "
                         "trace of the live run to --debug_dump_dir")
    tp.add_argument("--health_interval", type=int, default=None,
                    help="training-health telemetry: drain per-layer "
                         "grad/param/update-ratio accumulators and run "
                         "the divergence/non-finite detectors every N "
                         "steps (served on /metrics, /health and "
                         "/healthz; 0 = off, the byte-for-byte legacy "
                         "step)")
    tp.set_defaults(fn=cmd_train)

    mp = sub.add_parser(
        "merge_model",
        help="fuse config + trained parameters into one model file")
    mp.add_argument("--model_dir", required=True,
                    help="pass-%%05d checkpoint dir (ours or reference "
                         "raw-buffer layout)")
    mp.add_argument("--config_file", required=True)
    mp.add_argument("--model_file", required=True,
                    help="output merged model path")
    mp.add_argument("--config_args", default="")
    mp.set_defaults(fn=cmd_merge_model)

    dp = sub.add_parser("dump_config",
                        help="parse a config file and print the model IR")
    dp.add_argument("config")
    dp.add_argument("config_args", nargs="?", default="")
    dp.add_argument("--whole", action="store_true",
                    help="include optimization + data config")
    dp.set_defaults(fn=cmd_dump_config)

    gp = sub.add_parser("diagram",
                        help="emit a graphviz DOT diagram of a config")
    gp.add_argument("config")
    gp.add_argument("config_args", nargs="?", default="")
    gp.set_defaults(fn=cmd_diagram)

    sp = sub.add_parser(
        "master",
        help="serve the standalone data-task master (pserver-era "
             "coordinator)")
    sp.add_argument("--port", type=int, default=0,
                    help="TCP port (0 = ephemeral, printed on start)")
    sp.add_argument("--dataset", nargs="*", default=[],
                    help="task payloads: file paths / globs")
    sp.add_argument("--chunked", action="store_true",
                    help="expand recordio files into per-chunk tasks")
    sp.add_argument("--task_timeout", type=float, default=60.0)
    sp.add_argument("--failure_max", type=int, default=3)
    sp.add_argument("--snapshot", default="",
                    help="snapshot/recover state file (written every "
                         "--snapshot_period seconds and on shutdown)")
    sp.add_argument("--snapshot_period", type=float, default=30.0)
    sp.add_argument("--local_only", action="store_true",
                    help="bind loopback instead of all interfaces")
    sp.add_argument("--fleet_port", type=int, default=None,
                    help="also host the fleet telemetry aggregator on "
                         "this port (observe/fleet.py) — the natural "
                         "home: trainers already know the master's "
                         "address (0 = off)")
    sp.add_argument("--fleet_bind", default=None,
                    help="aggregator bind address (default loopback; "
                         "non-loopback warns — not an external API)")
    sp.set_defaults(fn=cmd_master)

    vp = sub.add_parser("version", help="print build info")
    vp.set_defaults(fn=cmd_version)

    args = parser.parse_args(argv)
    if getattr(args, "mesh_shape", ""):
        FLAGS.set("mesh_shape", args.mesh_shape)
    if getattr(args, "precision", None) is not None:
        FLAGS.set("precision", args.precision)
    if getattr(args, "use_bf16", None) is not None:
        FLAGS.set("use_bf16", bool(args.use_bf16))
    if getattr(args, "bf16_activations", None) is not None:
        FLAGS.set("bf16_activations", bool(args.bf16_activations))
    if getattr(args, "log_level", "") or FLAGS.get("log_level"):
        from .utils import set_log_level
        if getattr(args, "log_level", ""):
            FLAGS.set("log_level", args.log_level)
        set_log_level(FLAGS.get("log_level"))
    if getattr(args, "metrics_jsonl", ""):
        FLAGS.set("metrics_jsonl", args.metrics_jsonl)
    if getattr(args, "metrics_interval_s", None) is not None:
        FLAGS.set("metrics_interval_s", args.metrics_interval_s)
    if getattr(args, "trace_jsonl", ""):
        FLAGS.set("trace_jsonl", args.trace_jsonl)
    if getattr(args, "metrics_port", None) is not None:
        FLAGS.set("metrics_port", args.metrics_port)
    if getattr(args, "metrics_bind", None) is not None:
        FLAGS.set("metrics_bind", args.metrics_bind)
    if getattr(args, "fleet_addr", None) is not None:
        FLAGS.set("fleet_addr", args.fleet_addr)
    if getattr(args, "fleet_port", None) is not None:
        FLAGS.set("fleet_port", args.fleet_port)
    if getattr(args, "fleet_id", None) is not None:
        FLAGS.set("fleet_id", args.fleet_id)
    if getattr(args, "fleet_bind", None) is not None:
        FLAGS.set("fleet_bind", args.fleet_bind)
    if getattr(args, "debug_dump_signal", False):
        FLAGS.set("debug_dump_signal", True)
    if getattr(args, "health_interval", None) is not None:
        FLAGS.set("health_interval", args.health_interval)
    # umbrella: --metrics_jsonl reporter, --trace_jsonl span sink,
    # --metrics_port endpoint, --debug_dump_signal handler — each a
    # no-op when its flag is unset (no thread starts)
    from . import observe
    observe.start_from_flags()
    if args.command == "train":
        from .core.device import ensure_compile_cache
        ensure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
