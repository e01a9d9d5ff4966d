"""ptpu-lint (`paddle_tpu/analysis/`) — the analyzer, analyzed.

Three layers, mirroring ISSUE 9's acceptance criteria:

1. **Fixtures** (`tests/fixtures/lint/`): per rule family a violation
   file (every class the rule catches, exact rule code + line pinned),
   a suppressed file (the same hazards under justified
   ``# ptpu: lint-ok[RULE]`` pragmas) and a clean file (the near-miss
   shapes that must NOT be flagged — the false-positive contract).
2. **Engine semantics**: pragma placement rules, multi-code pragmas,
   baselines, text/JSON rendering, CLI exit codes.
3. **The repo gate**: ``paddle_tpu/`` itself lints to zero
   non-suppressed findings (tier-1 — every new hazard fails CI here),
   and the analysis package stays stdlib-only (no jax import).

Plus the runtime half of PT-LOCK (`analysis/lockorder.py`): hierarchy
edges recorded per blocking acquire, cycle/self-deadlock violations,
and the ``PADDLE_TPU_LOCK_ORDER_CHECK`` env switch the chaos/pipeline
suites run under.
"""

import ast
import json
import os
import subprocess
import sys
import threading

import pytest

from paddle_tpu.analysis import engine, lockorder
from paddle_tpu.analysis.__main__ import main as lint_main
from paddle_tpu.analysis.rules import ALL_RULES, lock_order

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures", "lint")
PKG_DIR = os.path.join(os.path.dirname(HERE), "paddle_tpu")


def _fx(name):
    return os.path.join(FIXTURES, name)


def _run_one(name, rules=None):
    return engine.run([_fx(name)], rules=rules)


def _lines(result, rule):
    return sorted(f.line for f in result.findings if f.rule == rule)


# ===================================================== fixture contracts
def test_trace_fixture_catches_every_impurity_class():
    res = _run_one("trace_violation.py", rules=["PT-TRACE"])
    assert all(f.rule == "PT-TRACE" for f in res.findings)
    # host sync in a callee reached FROM the jit root, clock, subscript
    # store, discarded .update(), np.asarray, float(), print — one each
    assert _lines(res, "PT-TRACE") == [10, 14, 15, 16, 17, 18, 19]
    by_line = {f.line: f.message for f in res.findings}
    assert "block_until_ready" in by_line[10] and "_helper" in by_line[10]
    assert "wall clock" in by_line[14]
    assert "buffers" in by_line[15] and "buffers" in by_line[16]
    assert "np.asarray" in by_line[17]
    assert "float()" in by_line[18]
    assert "print()" in by_line[19]


def test_trace_fixture_suppressed_and_clean():
    sup = _run_one("trace_suppressed.py", rules=["PT-TRACE"])
    assert not sup.findings and len(sup.suppressed) == 2
    assert _run_one("trace_clean.py", rules=["PT-TRACE"]).findings == []


def test_recompile_fixture_catches_every_hazard_class():
    res = _run_one("recompile_violation.py", rules=["PT-RECOMPILE"])
    assert _lines(res, "PT-RECOMPILE") == [10, 10, 16, 20, 24]
    msgs = " | ".join(f.message for f in res.findings)
    assert "inside a loop" in msgs
    assert "closes over loop variable(s) ['x']" in msgs
    assert "builds and discards" in msgs
    assert "f-string used as a cache key" in msgs


def test_recompile_fixture_suppressed_and_clean():
    sup = _run_one("recompile_suppressed.py", rules=["PT-RECOMPILE"])
    assert not sup.findings and len(sup.suppressed) == 3
    assert _run_one("recompile_clean.py",
                    rules=["PT-RECOMPILE"]).findings == []


def test_resource_fixture_catches_every_hygiene_class():
    res = _run_one("resource_violation.py", rules=["PT-RESOURCE"])
    assert _lines(res, "PT-RESOURCE") == [8, 12, 16, 25, 29, 34, 35, 44]
    by_line = {f.line: f.message for f in res.findings}
    assert "manual __enter__" in by_line[8]
    assert "manual __exit__" in by_line[12]
    assert "outside `with`/try-finally" in by_line[16]
    assert "broad silent" in by_line[25]
    assert "bare `except:`" in by_line[29]
    assert "'worker-1' lacks the 'ptpu-' prefix" in by_line[34]
    assert "without a name=" in by_line[35]
    # the fleet-aggregator serve-thread shape (round 17): an unprefixed
    # HTTP serve-loop thread escapes the conftest leak guard
    assert "'fleet-http' lacks the 'ptpu-' prefix" in by_line[44]


def test_resource_fixture_suppressed_and_clean():
    sup = _run_one("resource_suppressed.py", rules=["PT-RESOURCE"])
    assert not sup.findings and len(sup.suppressed) == 3
    assert _run_one("resource_clean.py",
                    rules=["PT-RESOURCE"]).findings == []


def test_dtype_fixture_catches_every_bypass_op():
    res = _run_one("dtype_violation.py", rules=["PT-DTYPE"])
    assert _lines(res, "PT-DTYPE") == [9, 13, 17, 21, 26]
    ops = {f.message.split()[1] for f in res.findings}
    assert ops == {"jnp.einsum", "jnp.dot", "jnp.matmul",
                   "lax.conv_general_dilated", "lax.dot_general"}


def test_dtype_fixture_suppressed_and_clean():
    sup = _run_one("dtype_suppressed.py", rules=["PT-DTYPE"])
    assert not sup.findings and len(sup.suppressed) == 1
    assert _run_one("dtype_clean.py", rules=["PT-DTYPE"]).findings == []


def test_dtype_rule_exempts_ops_and_core():
    """The policy's own home (ops/, core/) may call jnp.dot freely."""
    res = engine.run([os.path.join(PKG_DIR, "ops", "math_ops.py")],
                     rules=["PT-DTYPE"])
    assert res.findings == []


def test_lock_fixture_catches_cycle_and_self_deadlock():
    res = _run_one("lock_violation.py", rules=["PT-LOCK"])
    assert len(res.findings) == 2
    cycle = next(f for f in res.findings if "cycle" in f.message)
    selfd = next(f for f in res.findings if "self-deadlock" in f.message)
    assert "lock_violation.lock_a" in cycle.message
    assert "lock_violation.lock_b" in cycle.message
    assert cycle.line == 11                 # first witness edge a -> b
    assert "lock_violation.lock_c" in selfd.message
    assert "`inner`" in selfd.message and selfd.line == 23


def test_lock_fixture_suppressed_and_clean():
    sup = _run_one("lock_suppressed.py", rules=["PT-LOCK"])
    assert not sup.findings and len(sup.suppressed) == 2
    assert _run_one("lock_clean.py", rules=["PT-LOCK"]).findings == []


def test_metric_fixture_catches_every_dynamic_name_class():
    res = _run_one("metric_violation.py", rules=["PT-METRIC"])
    assert all(f.rule == "PT-METRIC" for f in res.findings)
    # f-string counter, concatenated histogram, variable through the
    # imported shim, %-format on REGISTRY, f-string span, call-result
    # record_span, concatenated health-alert family, concatenated
    # fleet-push family — one per line-pinned site
    assert _lines(res, "PT-METRIC") == [9, 13, 17, 21, 25, 30, 34, 38]
    by_line = {f.line: f.message for f in res.findings}
    assert "an f-string" in by_line[9]
    assert "concatenation" in by_line[13]
    assert "the variable 'name'" in by_line[17]
    assert by_line[25].startswith("span name")
    assert "a call result" in by_line[30]
    assert "concatenation" in by_line[34]
    assert "concatenation" in by_line[38]     # fleet push site (r17)
    assert "labels" in by_line[9] and "span attrs" in by_line[25]


def test_metric_fixture_suppressed_and_clean():
    sup = _run_one("metric_suppressed.py", rules=["PT-METRIC"])
    assert not sup.findings and len(sup.suppressed) == 2
    assert _run_one("metric_clean.py", rules=["PT-METRIC"]).findings == []


def test_shape_fixture_catches_every_mismatch_class():
    res = _run_one("shape_violation.py", rules=["PT-SHAPE"])
    assert all(f.rule == "PT-SHAPE" for f in res.findings)
    # wrong conv num_channels, class-count mismatch, float label,
    # embedding over dense, addto width disagreement, embedding table
    # smaller than its declared id space — one each
    assert _lines(res, "PT-SHAPE") == [11, 20, 27, 32, 38, 43]
    by_line = {f.line: f.message for f in res.findings}
    assert "wrong num_channels" in by_line[11]
    assert "10 class probabilities" in by_line[20] \
        and "2 classes" in by_line[20]
    assert "integer class-id label" in by_line[27]
    assert "embedding lookup over a non-integer input" in by_line[32]
    assert "addto inputs disagree" in by_line[38]
    assert "1000 rows" in by_line[43] and "5000-value range" in by_line[43]
    # full layer-path provenance rides along on graph findings
    assert "[layer path:" in by_line[20]


def test_shape_fixture_suppressed_and_clean():
    sup = _run_one("shape_suppressed.py", rules=["PT-SHAPE"])
    assert not sup.findings and len(sup.suppressed) == 2
    assert _run_one("shape_clean.py", rules=["PT-SHAPE"]).findings == []


def test_shard_fixture_catches_every_table_breakage():
    res = _run_one("shard_violation.py", rules=["PT-SHARD"])
    assert _lines(res, "PT-SHARD") == [9, 11, 12, 19, 25]
    by_line = {f.line: f.message for f in res.findings}
    assert "does not compile" in by_line[9]
    assert "silently shadowed" in by_line[11]
    assert "not a mesh-axis NAME" in by_line[12]
    assert "dead" in by_line[19]
    assert "does not compile" in by_line[25]


def test_shard_fixture_suppressed_and_clean():
    sup = _run_one("shard_suppressed.py", rules=["PT-SHARD"])
    assert not sup.findings and len(sup.suppressed) == 1
    assert _run_one("shard_clean.py", rules=["PT-SHARD"]).findings == []


def test_race_fixture_catches_every_sharing_class():
    res = _run_one("race_violation.py", rules=["PT-RACE"])
    # unguarded counter write, unguarded module-global mutation,
    # one-side-only lock — anchored at the racy write
    assert _lines(res, "PT-RACE") == [26, 27, 34]
    by_line = {f.line: f.message for f in res.findings}
    assert "Collector.total" in by_line[26]
    assert "no common named_lock guard" in by_line[26]
    assert "_seen" in by_line[27] and "module global" in by_line[27]
    assert "Collector.latest" in by_line[34]
    # the pooled comprehension entrypoint is named as a witness
    assert "ptpu-fix-p" in by_line[26]


def test_race_fixture_suppressed_and_clean():
    sup = _run_one("race_suppressed.py", rules=["PT-RACE"])
    assert not sup.findings and len(sup.suppressed) == 1
    assert _run_one("race_clean.py", rules=["PT-RACE"]).findings == []


def test_race_entrypoint_discovery_on_fixture():
    from paddle_tpu.analysis import racecheck

    project, _ = engine.build_project([_fx("race_violation.py")])
    entries = {e.label(): e.pooled
               for e in racecheck.find_entrypoints(project)}
    assert any("_worker [ptpu-fix-w]" in k for k in entries)
    # the comprehension-constructed pool is marked concurrent-with-self
    assert any("ptpu-fix-p" in k and entries[k] for k in entries)


def test_lock_graph_builds_named_edges():
    project, _ = engine.build_project([_fx("lock_clean.py")])
    graph, findings = lock_order.build_lock_graph(project)
    assert findings == []
    assert ("fixture.front", "fixture.back") in graph.edges
    assert graph.topo_order().index("fixture.front") \
        < graph.topo_order().index("fixture.back")


def test_lock_edges_from_with_context_expressions(tmp_path):
    """A call in a `with` ITEM's context expression runs while the
    earlier-listed locks are held — `with a, open_b():` must contribute
    the a->b edge (regression: walk() only descended into bodies)."""
    src = (
        "import threading\n"
        "lock_a = threading.Lock()\n"
        "lock_b = threading.Lock()\n"
        "def open_b():\n"
        "    with lock_b:\n"
        "        return 1\n"
        "def fwd():\n"
        "    with lock_a, open_b():\n"
        "        return 2\n"
        "def rev():\n"
        "    with lock_b:\n"
        "        with lock_a:\n"
        "            return 3\n")
    p = tmp_path / "ctxexpr.py"
    p.write_text(src)
    res = engine.run([str(p)], rules=["PT-LOCK"])
    assert len(res.findings) == 1 and "cycle" in res.findings[0].message


def test_package_init_relative_imports_resolve(tmp_path):
    """`from .sub import f` inside a package __init__ must resolve to
    pkg.sub (regression: the package was treated as a plain module and
    one level was stripped too many, killing re-export reachability)."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "sub.py").write_text(
        "def leaf(x):\n"
        "    return x.block_until_ready()\n")
    (pkg / "__init__.py").write_text(
        "from .sub import leaf\n")
    (tmp_path / "user.py").write_text(
        "import jax\n"
        "from pkg import leaf\n"
        "def step(p):\n"
        "    return leaf(p)\n"
        "g = jax.jit(step)\n")
    res = engine.run([str(tmp_path)], rules=["PT-TRACE"])
    assert len(res.findings) == 1
    assert "block_until_ready" in res.findings[0].message
    assert res.findings[0].path.endswith("sub.py")


def test_dtype_catches_jax_dot_numpy_spelling(tmp_path):
    """`import jax; jax.numpy.matmul(...)` is the same bypass as
    `jnp.matmul` (regression: only the aliased spelling was matched)."""
    p = tmp_path / "m.py"
    p.write_text(
        "import jax\n"
        "def f(a, b):\n"
        "    return jax.numpy.matmul(a, b)\n"
        "def g(a, b):\n"
        "    return jax.lax.dot_general(a, b, ((1,), (0,)))\n")
    res = engine.run([str(p)], rules=["PT-DTYPE"])
    assert _lines(res, "PT-DTYPE") == [3, 5]


def test_dtype_exemption_keys_on_module_not_path(tmp_path):
    """A checkout living under a directory named core/ or ops/ must not
    vacuously exempt the whole tree (regression: the exemption matched
    the absolute filesystem path)."""
    d = tmp_path / "core"
    d.mkdir()
    (d / "m.py").write_text(
        "import jax.numpy as jnp\n"
        "def f(a, b):\n"
        "    return jnp.dot(a, b)\n")
    res = engine.run([str(d)], rules=["PT-DTYPE"])
    assert _lines(res, "PT-DTYPE") == [3]


def test_fingerprints_distinguish_same_basename(tmp_path):
    """Identical findings in same-named files in different directories
    must not share a fingerprint — one baselined __init__.py would
    otherwise grandfather violations in every other __init__.py."""
    src = "def f():\n    try:\n        pass\n    except:\n        pass\n"
    for d in ("a", "b"):
        (tmp_path / d).mkdir()
        (tmp_path / d / "__init__.py").write_text(src)
    res = engine.run([str(tmp_path)], rules=["PT-RESOURCE"])
    assert len(res.findings) == 2
    fps = {f.fingerprint for f in res.findings}
    assert len(fps) == 2


# ===================================================== engine semantics
def test_pragma_trailing_governs_own_line_only(tmp_path):
    src = (
        "import time\n"
        "import jax\n"
        "def f(p):\n"
        "    a = time.time()   # ptpu: lint-ok[PT-TRACE]\n"
        "    b = time.time()\n"          # NOT covered by the line above
        "    return a + b + p\n"
        "g = jax.jit(f)\n")
    p = tmp_path / "m.py"
    p.write_text(src)
    res = engine.run([str(p)], rules=["PT-TRACE"])
    assert _lines(res, "PT-TRACE") == [5]
    assert len(res.suppressed) == 1


def test_pragma_multi_code_and_all(tmp_path):
    src = (
        "import time\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def f(p):\n"
        "    # ptpu: lint-ok[PT-TRACE, PT-DTYPE]\n"
        "    return jnp.dot(p, p) * time.time()\n"
        "def h(p):\n"
        "    # ptpu: lint-ok[ALL]\n"
        "    return jnp.dot(p, p) * time.time()\n"
        "g = jax.jit(f)\n"
        "k = jax.jit(h)\n")
    p = tmp_path / "m.py"
    p.write_text(src)
    res = engine.run([str(p)])
    assert res.findings == []
    assert len(res.suppressed) == 4     # 2 rules x 2 functions


def test_baseline_grandfathers_by_fingerprint(tmp_path):
    base = tmp_path / "baseline.json"
    res1 = _run_one("dtype_violation.py")
    engine.write_baseline(str(base), res1)
    loaded = engine.load_baseline(str(base))
    assert len(loaded) == len({f.fingerprint for f in res1.findings})
    res2 = engine.run([_fx("dtype_violation.py")],
                      baseline=loaded)
    assert res2.findings == [] and len(res2.baselined) == 5
    assert res2.exit_code == 0


def test_json_report_schema():
    res = _run_one("dtype_violation.py", rules=["PT-DTYPE"])
    data = json.loads(res.to_json())
    assert data["files"] == 1 and len(data["findings"]) == 5
    row = data["findings"][0]
    assert set(row) == {"rule", "path", "line", "col", "message",
                       "fingerprint"}
    assert row["rule"] == "PT-DTYPE"


def test_rule_registry_is_complete():
    assert set(ALL_RULES) == set(engine.RULE_CODES)
    with pytest.raises(ValueError, match="unknown rule"):
        engine.run([FIXTURES], rules=["PT-BOGUS"])


# ================================================================== CLI
def test_cli_exit_codes_and_text(capsys):
    assert lint_main([_fx("dtype_clean.py")]) == 0
    assert lint_main([_fx("dtype_violation.py")]) == 1
    out = capsys.readouterr().out
    assert "PT-DTYPE" in out and "dtype_violation.py:9:" in out
    assert lint_main(["/no/such/path"]) == 2
    assert lint_main([FIXTURES, "--rules", "PT-BOGUS"]) == 2


def test_cli_json_and_rule_selection(capsys):
    rc = lint_main([_fx("resource_violation.py"), "--format", "json",
                    "--rules", "PT-RESOURCE"])
    assert rc == 1
    data = json.loads(capsys.readouterr().out)
    assert {f["rule"] for f in data["findings"]} == {"PT-RESOURCE"}
    assert len(data["findings"]) == 8


def test_cli_baseline_roundtrip(tmp_path, capsys):
    base = str(tmp_path / "b.json")
    assert lint_main([_fx("lock_violation.py"),
                      "--write-baseline", base]) == 0
    assert lint_main([_fx("lock_violation.py"),
                      "--baseline", base]) == 0
    out = capsys.readouterr().out
    assert "2 baselined" in out
    assert lint_main([_fx("lock_violation.py"),
                      "--baseline", "/no/such/base.json"]) == 2


def test_cli_lock_graph_dump(capsys):
    assert lint_main([_fx("lock_clean.py"), "--lock-graph"]) == 0
    out = capsys.readouterr().out
    assert "fixture.front -> fixture.back" in out
    assert "acyclic" in out


def test_cli_list_rules(capsys):
    """--list-rules prints every rule id with its one-line doc."""
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for code in engine.RULE_CODES:
        assert code in out
    assert "shape/dtype" in out and "named_lock guard" in out


def test_cli_unknown_rule_names_the_valid_set(capsys):
    """A typo'd --rules errors (exit 2) and prints the valid choices
    instead of silently matching nothing."""
    assert lint_main([FIXTURES, "--rules", "PT-SHAPES"]) == 2
    err = capsys.readouterr().err
    assert "PT-SHAPES" in err and "PT-SHAPE" in err \
        and "PT-RACE" in err


def test_cli_exit_codes_for_verify_rules(capsys):
    """The 0/1/2 contract covers the three ptpu-verify rules."""
    assert lint_main([_fx("shape_clean.py"),
                      "--rules", "PT-SHAPE"]) == 0
    assert lint_main([_fx("shape_violation.py"),
                      "--rules", "PT-SHAPE"]) == 1
    assert lint_main([_fx("shard_violation.py"),
                      "--rules", "PT-SHARD"]) == 1
    assert lint_main([_fx("race_violation.py"),
                      "--rules", "PT-RACE"]) == 1
    out = capsys.readouterr().out
    assert "PT-SHAPE" in out and "PT-SHARD" in out \
        and "PT-RACE" in out
    assert lint_main([_fx("race_clean.py"), "--rules",
                      "PT-SHAPE,PT-SHARD,PT-RACE"]) == 0


# ======================================================== the repo gate
def test_repo_lints_clean():
    """THE tier-1 gate: zero non-suppressed findings over paddle_tpu/.
    A finding here means a new hazard (fix it) or a deliberate site
    (pragma it with a justification) — never ignore it.  The default
    rule set MUST include the ptpu-verify rules (PT-SHAPE / PT-SHARD /
    PT-RACE), so this one test extends the zero-findings contract to
    them as the rule count grows."""
    assert {"PT-SHAPE", "PT-SHARD", "PT-RACE"} <= set(engine.RULE_CODES)
    assert set(ALL_RULES) == set(engine.RULE_CODES)
    res = engine.run([PKG_DIR])
    assert res.files > 100      # the walker actually saw the package
    rendered = "\n".join(f.render() for f in res.findings)
    assert not res.findings, f"ptpu-lint findings:\n{rendered}"


def test_repo_race_entrypoints_cover_the_thread_fleet():
    """PT-RACE's sweep is only as good as its entrypoint discovery:
    the known framework threads (pipeline workers, reader pool, trace
    writer, metrics reporter, SIGTERM flusher, debug dump, master
    read-ahead, the two HTTP handler families) must all resolve."""
    from paddle_tpu.analysis import racecheck

    project, _ = engine.build_project([PKG_DIR])
    labels = {e.label() for e in racecheck.find_entrypoints(project)}
    text = " | ".join(sorted(labels))
    for needle in ("AsyncPipeline._worker", "ptpu-trace-writer",
                   "ptpu-metrics-reporter", "ptpu-sigterm-flush",
                   "ptpu-debug-dump", "fetcher", "http:_Handler",
                   "http:_FleetHandler"):
        assert needle in text, f"missing entrypoint {needle}: {text}"
    assert len(labels) >= 10


def test_parse_cache_single_parse_property():
    """The engine speedup satellite's pin: one ast.parse per file
    CONTENT — a second sweep over the same tree re-parses nothing
    (rules already share one Project per run; the content-hash cache
    shares it across runs too)."""
    from paddle_tpu.analysis import callgraph

    callgraph.clear_parse_cache()
    engine._PRAGMA_CACHE.clear()
    res1 = engine.run([FIXTURES])
    parses_after_first = callgraph.parse_stats["parses"]
    assert parses_after_first >= res1.files
    res2 = engine.run([FIXTURES])
    assert res2.files == res1.files
    assert callgraph.parse_stats["parses"] == parses_after_first, \
        "second sweep re-parsed files the cache should have served"
    assert callgraph.parse_stats["cache_hits"] >= res1.files
    # pragma tables are cached by the same content hash
    assert len(engine._PRAGMA_CACHE) > 0


def test_repo_lock_graph_is_current():
    """The derived hierarchy: pipeline source lock
    nests the queue condition, reporter flush nests warn-once — and the
    whole graph stays acyclic."""
    project, _ = engine.build_project([PKG_DIR])
    graph, findings = lock_order.build_lock_graph(project)
    assert findings == []
    assert ("pipeline.source", "pipeline.queue") in graph.edges
    assert ("observe.reporter", "logger.warn_once") in graph.edges


def test_analysis_package_is_stdlib_only():
    """The analyzer itself must never import jax (or any framework
    module outside analysis/): the tier-1 gate has to stay fast and the
    lockorder shim is pulled by serving/loader-adjacent modules that
    promise to run without jax."""
    adir = os.path.join(PKG_DIR, "analysis")
    for dirpath, _, files in os.walk(adir):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path, "r", encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [al.name for al in node.names]
                elif isinstance(node, ast.ImportFrom) and node.level == 0:
                    mods = [node.module or ""]
                for m in mods:
                    root = m.split(".")[0]
                    assert root != "jax", f"{path} imports jax"
                    assert root != "paddle_tpu" or ".analysis" in m, \
                        f"{path} imports framework module {m}"


# ==================================================== runtime lock order
@pytest.fixture
def lock_checker():
    lockorder.reset()
    lockorder.enable(raise_on_violation=False)
    yield lockorder
    lockorder.disable()
    lockorder.reset()


def test_lockorder_records_edges_and_stays_quiet(lock_checker):
    a, b = lockorder.named_lock("t.a"), lockorder.named_lock("t.b")
    with a:
        with b:
            pass
    assert lock_checker.edges() == {"t.a": {"t.b"}}
    assert lock_checker.violations() == []
    lock_checker.check_acyclic()        # no raise


def test_lockorder_flags_opposite_order_cycle(lock_checker):
    a, b = lockorder.named_lock("t.a"), lockorder.named_lock("t.b")
    with a:
        with b:
            pass
    with b:
        with a:                         # reverse order: the hazard
            pass
    v = lock_checker.violations()
    assert len(v) == 1 and "cycle" in v[0]
    assert "t.a" in v[0] and "t.b" in v[0]
    with pytest.raises(lockorder.LockOrderError):
        lock_checker.check_acyclic()


def test_lockorder_raise_mode_reports_before_blocking(lock_checker):
    """Re-acquiring a held non-reentrant lock would block forever; the
    checker raises from _before_acquire instead of demonstrating it."""
    lockorder.enable(raise_on_violation=True)
    c = lockorder.named_lock("t.c")
    with c:
        with pytest.raises(lockorder.LockOrderError,
                           match="self-deadlock"):
            c.acquire()
    # the lock survived: still usable after the refused acquire
    with c:
        pass


def test_lockorder_peers_and_rlock_are_exempt(lock_checker):
    p1, p2 = lockorder.named_lock("t.peer"), lockorder.named_lock("t.peer")
    with p1:
        with p2:                        # distinct instances, one name
            pass
    r = lockorder.named_lock("t.r", reentrant=True)
    with r:
        with r:                         # RLock re-entry is legal
            pass
    assert lock_checker.violations() == []


def test_lockorder_condition_waits_track(lock_checker):
    cond = lockorder.named_condition("t.cond")
    hits = []

    def waiter():
        with cond:
            while not hits:
                cond.wait(1.0)

    t = threading.Thread(target=waiter, name="ptpu-test-cond")
    t.start()
    with cond:
        hits.append(1)
        cond.notify()
    t.join(2.0)
    assert not t.is_alive()
    assert lock_checker.violations() == []


def test_lockorder_cross_thread_orders_compose(lock_checker):
    """Thread 1 witnesses a->b, thread 2 witnesses b->a: the cycle is
    caught even though neither thread ever deadlocks alone."""
    a, b = lockorder.named_lock("t.x"), lockorder.named_lock("t.y")

    def t1():
        with a:
            with b:
                pass

    th = threading.Thread(target=t1, name="ptpu-test-order")
    th.start()
    th.join(2.0)
    with b:
        with a:
            pass
    v = lock_checker.violations()
    assert len(v) == 1 and "cycle" in v[0]


def test_lockorder_disabled_is_transparent():
    lockorder.reset()
    assert not lockorder.enabled()
    a = lockorder.named_lock("t.off")
    with a:
        pass
    assert lockorder.edges() == {}
    assert a.locked() is False


def test_lockorder_env_var_enables(tmp_path):
    """PADDLE_TPU_LOCK_ORDER_CHECK=1 — the switch the chaos/pipeline
    suites run under — enables the checker at import."""
    code = ("from paddle_tpu.analysis import lockorder; "
            "import sys; sys.exit(0 if lockorder.enabled() else 3)")
    env = dict(os.environ, PADDLE_TPU_LOCK_ORDER_CHECK="1",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=os.path.dirname(PKG_DIR), timeout=120)
    assert proc.returncode == 0


# ======================================================== flags registry
def test_duplicate_flag_registration_raises():
    from paddle_tpu.utils.flags import FlagRegistry
    reg = FlagRegistry()
    reg.define("knob", 7, "first owner")
    with pytest.raises(ValueError, match="already registered"):
        reg.define("knob", 9, "second claimant")
    assert reg.knob == 7                # the first definition survives
    reg.set("knob", 11)
    assert reg.knob == 11
