"""Perf-regression gate over the bench trajectory.

Bench lines used to be write-only history: nothing compared a new run
against an old one, so a silent 2x regression would merge clean.  This
module turns a bench run into a guarded baseline:

- :func:`series_from_line` flattens one bench JSON line into named
  scalar **series** — the headline ``median`` (the attempts/spread
  band machinery from round 6 rides along as the tolerance input) plus
  the nested per-workload timings of the composite lanes
  (pipeline sync/prefetch ms, precision fp32/bf16 ms);
- :func:`make_baseline` renders a run into a committed baseline file:
  per series the value, the observed relative spread, a **direction**
  (``lower`` / ``higher`` is better, or ``abs`` for bounded ratios)
  and an explicit tolerance — self-describing, so the gate needs no
  out-of-band config and a human can read why a row trips;
- :func:`compare` judges a new run against the baseline band and
  :func:`render_table` prints the human diff.  ``bench.py --baseline
  FILE --check`` drives it (exit nonzero on regression,
  ``bench_regressions_total`` counter per tripped series);
  ``--write-baseline`` produces the artifact.

Stdlib-only (no jax): the gate must run in CI against replayed
artifacts (``bench.py --from_jsonl``) without a backend.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Sequence

SCHEMA = 1

#: Relative-tolerance floor for timing/throughput series.  CPU CI boxes
#: are noisy run to run (shared cores, thermal state — the round-4
#: ResNet bimodality was a 10% band on a DEDICATED chip), so the floor
#: is generous; a real regression (2x = +100%) clears it with margin.
REL_TOL_FLOOR = 0.5
#: Spread multiplier: a workload that already wobbles k% between
#: attempts gets a proportionally wider band.
SPREAD_FACTOR = 4.0
#: Absolute tolerance for bounded-ratio series (input_bound_ratio).
ABS_TOL = 0.05


def _direction(metric: str, unit: str = "") -> str:
    """``lower`` | ``higher`` | ``abs`` for a series name."""
    name = metric.lower()
    if "ratio" in name or "bound" in name:
        return "abs"
    for needle in ("ms_per_batch", "ms_per_call", "_ms", "seconds",
                   "overhead", "latency", "degradation"):
        if needle in name:
            return "lower"
    for needle in ("per_sec", "speedup", "samples", "tokens", "mfu",
                   "throughput"):
        if needle in name:
            return "higher"
    # unknown metrics: assume the headline follows its unit text
    u = unit.lower()
    if "ms/" in u or "seconds" in u or "us" in u:
        return "lower"
    return "higher"


def series_from_line(line: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """One bench JSON line → ``{series_key: {"value", "spread",
    "direction", "unit"}}``.  Error lines produce no series (the gate
    reports them separately)."""
    out: Dict[str, Dict[str, Any]] = {}
    metric = line.get("metric")
    if not metric or "error" in line:
        return out
    spread = float(line.get("spread", 0.0) or 0.0)
    value = line.get("median", line.get("value"))
    if value is not None:
        out[metric] = {
            "value": float(value), "spread": spread,
            "direction": _direction(metric, str(line.get("unit", ""))),
            "unit": line.get("unit", ""),
        }
    # composite lanes: nested per-workload timings are where a "2x on
    # one workload" regression actually lives (the headline of the
    # pipeline lane is a bounded ratio that would never see it).
    # Modes: pipeline sync/prefetch, precision fp32/bf16, attention
    # dense/legacy/block-skip + padded/packed + paged decode, serving
    # continuous/sequential, multichip fsdp/replicated, embedding
    # sparse (lookup kernel + sparse-exchange training, dense A/B),
    # rollout steady/swap (req/s + p99 with a hot-swap in the window).
    for row in line.get("rows", ()):
        tag = row.get("workload", "?")
        for mode in ("sync", "prefetch", "fp32", "bf16", "dense",
                     "legacy", "block_skip", "padded", "packed",
                     "decode", "continuous", "sequential",
                     "fsdp", "replicated", "sparse",
                     "steady", "swap"):
            sub = row.get(mode) or {}
            for key, unit, direction, suffix in (
                    ("ms_per_batch", "ms/batch", "lower", "_ms"),
                    ("ms_per_call", "ms/call", "lower", "_ms"),
                    # serving lane: sustained throughput gates
                    # higher-better, the p99 tail lower-better
                    ("req_per_sec", "req/s", "higher", "_req_per_sec"),
                    ("p99_ms", "ms", "lower", "_p99_ms"),
                    # multichip lane: scaling throughput gates
                    # higher-better; per-chip hbm fields are
                    # informational (not series keys)
                    ("samples_per_sec", "samples/s", "higher",
                     "_samples_per_sec"),
                    # sparse embedding lane: lookup throughput gates
                    # higher-better; exchanged_grad_bytes and call_ms
                    # are informational (not series keys)
                    ("lookups_per_sec", "lookups/s", "higher",
                     "_lookups_per_sec")):
                v = sub.get(key)
                if v is not None:
                    out[f"{metric}.{tag}.{mode}{suffix}"] = {
                        "value": float(v), "spread": spread,
                        "direction": direction, "unit": unit}
                    if suffix == "_ms":
                        break  # one _ms series per mode: a dict with
                        # both keys must not overwrite ms/batch
    return out


def _tolerance(direction: str, spread: float) -> float:
    if direction == "abs":
        return ABS_TOL
    return max(REL_TOL_FLOOR, SPREAD_FACTOR * spread)


def make_baseline(lines: Sequence[Dict[str, Any]],
                  meta: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """Render a bench run (its emitted JSON lines) into the committed
    baseline document.  The raw lines ride along under ``"lines"`` so
    the artifact can be replayed through the gate without re-running
    the workloads (``bench.py --from_jsonl``)."""
    series: Dict[str, Any] = {}
    for line in lines:
        for key, s in series_from_line(line).items():
            series[key] = {
                "value": s["value"],
                "spread": s["spread"],
                "direction": s["direction"],
                "tolerance": round(_tolerance(s["direction"],
                                              s["spread"]), 4),
                "unit": s["unit"],
            }
    return {
        "schema": SCHEMA,
        "created_unix": round(time.time(), 1),
        "meta": meta or {},
        "series": series,
        "lines": [dict(line) for line in lines],
    }


class GateResult:
    """Verdict of one comparison: per-series rows + the failing set."""

    def __init__(self) -> None:
        self.rows: List[Dict[str, Any]] = []
        self.regressions: List[Dict[str, Any]] = []
        self.errors: List[str] = []
        self.skipped: List[str] = []     # baseline series absent here

    @property
    def ok(self) -> bool:
        return not self.regressions and not self.errors


def compare(lines: Sequence[Dict[str, Any]],
            baseline: Dict[str, Any]) -> GateResult:
    """Judge a bench run against a baseline document.

    A series regresses when it is worse than the baseline value by more
    than the baseline's recorded tolerance (relative for timings and
    throughputs — direction-aware — absolute for bounded ratios).  A
    row that errored regresses unconditionally: a workload that stopped
    producing numbers is the worst kind of perf regression.  Baseline
    series with no counterpart in this run are *skipped* (a ``--only``
    subset run judges only what it ran).
    """
    res = GateResult()
    current: Dict[str, Dict[str, Any]] = {}
    for line in lines:
        if line.get("error"):
            res.errors.append(f"{line.get('metric', '?')}: "
                              f"{line['error']}")
            continue
        current.update(series_from_line(line))

    base_series = baseline.get("series", {})
    for key, base in sorted(base_series.items()):
        cur = current.get(key)
        if cur is None:
            res.skipped.append(key)
            continue
        direction = base.get("direction", "lower")
        tol = float(base.get("tolerance",
                             _tolerance(direction,
                                        float(base.get("spread", 0.0)))))
        bval, cval = float(base["value"]), float(cur["value"])
        if direction == "abs":
            delta = cval - bval
            worse_by = delta
            regressed = delta > tol
            ratio = None
        elif direction == "lower" and bval <= 0:
            # difference-style series (traced-minus-untraced overhead)
            # can record ~0/negative baselines where a ratio is
            # undefined or sign-flipped; judge the delta against the
            # larger magnitude so a real blow-up still trips
            scale = max(abs(bval), abs(cval), 1e-9)
            worse_by = (cval - bval) / scale
            regressed = worse_by > tol
            ratio = None
        else:
            ratio = (cval / bval) if direction == "lower" \
                else (bval / cval) if cval else float("inf")
            worse_by = ratio - 1.0
            regressed = worse_by > tol
        row = {"series": key, "baseline": bval, "current": cval,
               "direction": direction, "tolerance": tol,
               "worse_by": round(worse_by, 4),
               "ratio": round(ratio, 4) if ratio is not None else None,
               "regressed": regressed}
        res.rows.append(row)
        if regressed:
            res.regressions.append(row)
    # new series this run that the baseline has never seen: informative
    for key in sorted(set(current) - set(base_series)):
        res.rows.append({"series": key, "baseline": None,
                         "current": current[key]["value"],
                         "direction": current[key]["direction"],
                         "tolerance": None, "worse_by": None,
                         "ratio": None, "regressed": False})
    return res


def render_table(res: GateResult, baseline_path: str = "") -> str:
    """The human diff table ``--check`` prints (to stderr — stdout
    stays the machine-parsed JSONL stream)."""
    lines = [f"perf gate vs {baseline_path or 'baseline'}:"]
    lines.append(f"{'series':<58} {'base':>12} {'current':>12} "
                 f"{'worse-by':>9} {'tol':>6}  verdict")
    for r in res.rows:
        base = "—" if r["baseline"] is None else f"{r['baseline']:.4g}"
        wb = "—" if r["worse_by"] is None else f"{r['worse_by']:+.1%}" \
            if r["direction"] != "abs" else f"{r['worse_by']:+.4f}"
        tol = "—" if r["tolerance"] is None else (
            f"{r['tolerance']:.0%}" if r["direction"] != "abs"
            else f"{r['tolerance']:.3f}")
        verdict = "REGRESSED" if r["regressed"] else (
            "new" if r["baseline"] is None else "ok")
        lines.append(f"{r['series']:<58} {base:>12} "
                     f"{r['current']:>12.4g} {wb:>9} {tol:>6}  "
                     f"{verdict}")
    for key in res.skipped:
        lines.append(f"{key:<58} {'(not run this invocation)':>45}")
    for err in res.errors:
        lines.append(f"ERROR row: {err}")
    n = len(res.regressions)
    lines.append(
        f"perf gate: {'PASS' if res.ok else 'FAIL'} — "
        f"{n} regression(s), {len(res.errors)} error row(s), "
        f"{len(res.rows)} series judged, {len(res.skipped)} skipped")
    return "\n".join(lines)


def load_baseline(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if doc.get("schema") != SCHEMA:
        raise ValueError(
            f"baseline {path!r}: schema {doc.get('schema')!r} != "
            f"{SCHEMA} (regenerate with bench.py --write-baseline)")
    return doc


def write_baseline(path: str, lines: Sequence[Dict[str, Any]],
                   meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    doc = make_baseline(lines, meta)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return doc
