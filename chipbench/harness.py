"""The parts of a run that no cell owns: finding a cell's files by name,
the device gate, the compile listener, the dispatch counters, the
metric readers' driver and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
a later PR adds files under ``configs/``, ``traffic/``, ``metrics/``,
``readers/``, ``flops/``, ``reference/``, ``systems/`` and ``limits/``
plus entries in ``BENCHMARK.json`` and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: host tracing + lowering (paid by every process) and backend compile
#: (what the persistent cache saves) — the listener arithmetic of
#: ``chip_smoke.py::Meter``, copied
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
DISPATCH_COUNTERS = ("rnn_dispatch_total", "attention_dispatch_total",
                     "conv_dispatch_total", "embedding_dispatch_total")


class BenchError(RuntimeError):
    """The run cannot give a result; the message says why."""


# ------------------------------------------------------------ files by name
def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def manifest(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def named_file(kind: str, name: str, ext: str, here: str = HERE) -> str:
    """``chipbench/<kind>/<name><ext>`` — the one naming rule."""
    path = os.path.join(here, kind, name + ext)
    if not os.path.isfile(path):
        raise BenchError(f"no {kind}/{name}{ext} under {here}")
    return path


def load_module(kind: str, name: str, here: str = HERE):
    """Import ``chipbench/<kind>/<name>.py`` by path: a config may be
    called ``opt-1.3b-serve``, which no import statement can spell."""
    path = named_file(kind, name, ".py", here)
    mod_name = "chipbench_%s_%s" % (
        kind, "".join(c if c.isalnum() else "_" for c in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic
    mix and its limits, all read from their own files."""

    def __init__(self, man: Dict[str, Any], workload: str,
                 root: str = ROOT):
        rows = [w for w in man["workloads"] if w["name"] == workload]
        if not rows:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
        self.row = rows[0]
        self.name = workload
        self.chips = int(self.row["chips"])
        cfg_rows = [c for c in man["configs"]
                    if c["name"] == self.row["config"]]
        if not cfg_rows:
            raise BenchError(f"no config {self.row['config']!r}")
        self.config_name = cfg_rows[0]["name"]
        self.config = load_json(os.path.join(root, cfg_rows[0]["file"]))
        here = os.path.join(root, man["paths"][0])
        self.traffic_name = self.row["traffic"]
        self.traffic = load_json(
            named_file("traffic", self.traffic_name, ".json", here))
        self.limits = load_json(
            named_file("limits", workload, ".json", here))

    def metrics(self, man: Dict[str, Any], group: str) -> List[Dict]:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in man[group]
                if "workloads" not in m or self.name in m["workloads"]]


# --------------------------------------------------------------- the device
def device_gate(chips: int, here: str = HERE) -> Dict[str, Any]:
    """The device as JAX reports it and its row of ``peaks.json``.
    Raises unless it is a TPU with a row and enough chips.  Never sets
    ``jax_platforms``."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    peaks = load_json(os.path.join(here, "peaks.json"))
    if dev["platform"] != "tpu":
        raise BenchError(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["kind"] not in peaks:
        raise BenchError(f"device_kind {dev['kind']!r} has no row in "
                         "chipbench/peaks.json")
    if dev["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    return {"device": dev, "peaks": peaks[dev["kind"]]}


def rehearsal_gate(here: str = HERE) -> Dict[str, Any]:
    """What :func:`device_gate` gives, for a rehearsal: whatever JAX
    finds, and the table's first row so that the readers have peaks to
    divide by (nothing a rehearsal reads is printed as a result)."""
    import jax

    devs = jax.devices()
    return {"device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "peaks": next(iter(load_json(
                os.path.join(here, "peaks.json")).values()))}


def keep_compiled_programs() -> None:
    """The program places JAX's persistent cache
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``);
    every program is kept, also the sub-second ones, so that the second
    run of a cell compiles nothing."""
    import jax
    from paddle_tpu.core.device import ensure_compile_cache

    ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def context(cell: "Cell", gate: Dict[str, Any], seed: int, seconds: float,
            *, trace: bool = False, rehearsal: bool = False,
            t_process: Optional[float] = None, meter=None,
            chips: Optional[int] = None) -> Dict[str, Any]:
    """What a driver's ``run`` is handed."""
    return {"cell": cell, "seed": int(seed), "seconds": float(seconds),
            "trace": bool(trace), "rehearsal": bool(rehearsal),
            "t_process": time.perf_counter() if t_process is None
            else t_process,
            "meter": meter or CompileMeter(),
            "device": gate["device"], "peaks": gate["peaks"],
            "chips": cell.chips if chips is None else chips,
            "root": ROOT, "here": HERE}


def memory_peak_bytes(n_devices: int) -> int:
    """Peak bytes on the fullest of the devices used: the allocator's
    ``peak_bytes_in_use`` (arrays) plus its ``peak_bytes_reserved``, the
    scratch of the compiled programs, which this runtime keeps out of
    the first (a ResNet-50 step at batch 128 shows 0.47 GB in use and
    5.39 GB reserved; my chip run, PR 24)."""
    import jax

    peak = 0
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


# ------------------------------------------------------- compile and counters
class CompileMeter:
    """``jax.monitoring`` compile durations with the host clock of each,
    so that set-up's and the window's can be told apart."""

    def __init__(self):
        import jax.monitoring

        self.events: List = []          # (t, event, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), event, float(secs)))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(s for t, _, s in self.events if t0 <= t < t1)

    def compiles_between(self, t0: float, t1: float) -> int:
        """Programs that reached the backend (or its cache) in [t0, t1)."""
        return sum(1 for t, e, _ in self.events
                   if t0 <= t < t1 and e == COMPILE_EVENTS[2])


def dispatch_rows() -> List[Dict[str, Any]]:
    """The program's trace-time ``*_dispatch_total{path,reason}`` rows."""
    from paddle_tpu import observe

    rows = []
    for name in DISPATCH_COUNTERS:
        m = observe.REGISTRY.find(name)
        for s in (m.samples() if m is not None else ()):
            rows.append({"counter": name, **s["labels"],
                         "count": s["value"]})
    return rows


# ------------------------------------------------------------------ metrics
def read_metrics(rows: List[Dict], run: Dict[str, Any], cell: Cell,
                 here: str = HERE) -> Dict[str, Dict[str, Any]]:
    """Each metric of ``rows`` through the reader its own file names.
    A reader that finds nothing returns None; a metric that the manifest
    promises for this cell and that read nothing fails the run by name
    (never a 0 in its place)."""
    out, missing = {}, []
    for row in rows:
        spec = load_json(named_file("metrics", row["name"], ".json", here))
        reader = load_module("readers", spec["reader"], here)
        value = reader.read(run, **spec.get("args", {}))
        if value is None:
            missing.append(row["name"])
            continue
        out[row["name"]] = {"value": float(value), "unit": row["unit"]}
    if missing:
        raise BenchError("nothing to read for " + ", ".join(missing)
                         + f" in cell {cell.name}")
    return out


# ------------------------------------------------------------- result line
def hold(cell: Cell, numbers: Dict[str, float]) -> Dict[str, Dict]:
    """Each number the cell compares, beside its limit (a number that
    is missing is no number, and fails)."""
    return {k: {"value": float(numbers.get(k, float("nan"))),
                "limit": float(v)} for k, v in cell.limits.items()}


def planted(run: Dict[str, Any]) -> Dict[str, Dict]:
    """The control and the faults a driver can plant under its own
    comparison, each held to the cell's limits as a run is: what the
    readings record on the chip and the tests assert."""
    out = {}
    for name, numbers_of in run.get("planted", {}).items():
        numbers = numbers_of()
        checks = hold(run["cell"], numbers)
        out[name] = {"numbers": numbers, "compared": checks,
                     "correct": decide(checks)}
    return out


def decide(checks: Dict[str, Dict[str, float]]) -> bool:
    """``correct``: every number compared lies within its limit (and is
    a number)."""
    ok = bool(checks)
    for c in checks.values():
        v = c["value"]
        ok = ok and v == v and v <= c["limit"]
    return ok


def print_result(run: Dict[str, Any], metrics: Dict, device: Dict,
                 breakdown: Optional[Dict] = None) -> None:
    checks = run["checks"]
    line: Dict[str, Any] = {
        "correct": decide(checks), "attempted": int(run["attempted"]),
        "failed": int(run["failed"]), "metrics": metrics,
        "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["observed"] = {k: v for k, v in run.get("numbers", {}).items()
                        if k not in checks}     # read, not compared
    line["compared"] = checks               # last, as the contract asks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"chipbench compared {name} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
