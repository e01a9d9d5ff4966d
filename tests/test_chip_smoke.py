"""Nothing hides the device: ``chip_smoke.py`` off the chip, the compile
cache's placement, the peaks table and the one ``interpret=`` predicate.
(What the smoke proves ON the chip is the chip run's to say — see
CHANGES.md PR 21 / PERF.md.)"""

import ast
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
PASS_LINE = '{"ok": true'


def _run_smoke(args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)


def test_default_run_off_chip_fails_before_any_work(tmp_path):
    r = _run_smoke([], tmp_path)
    assert r.returncode not in (0, 3), r.stdout[-2000:]
    assert PASS_LINE not in r.stdout
    assert "no TPU" in r.stderr
    # the device line and nothing else: no phase ran, nothing was written
    assert '"phase"' not in r.stdout
    assert not (tmp_path / "out").exists()


def test_rehearsal_runs_every_phase_and_cannot_pass(tmp_path):
    r = _run_smoke(["--rehearsal"], tmp_path)
    assert r.returncode == 3, (r.stdout[-3000:], r.stderr[-3000:])
    assert "REHEARSAL" in r.stdout and PASS_LINE not in r.stdout
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert set(phases) == {"lstm_cli", "transformer", "server", "kernels"}
    assert all(p["ok"] for p in phases.values()), phases
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] is True
    # the cache went where the environment said, not into the checkout
    placed = next(ln for ln in lines if "compile_cache" in ln)
    assert placed["placed_by"] == "JAX_COMPILATION_CACHE_DIR"
    assert placed["compile_cache"] == str(tmp_path / "cache")
    # everything it wrote is under --out
    assert (tmp_path / "out" / "chip_smoke.json").exists()
    # the fused kernels were the path taken, in the interpreter here
    assert phases["lstm_cli"]["dispatch"] == [
        {"counter": "rnn_dispatch_total", "kind": "lstm", "path": "fused",
         "reason": "", "count": 2}]
    assert all(k.endswith("[interpret]")
               for k in phases["transformer"]["pallas_calls"])


def test_only_runs_the_named_phase_the_decode_walks_table(tmp_path):
    """``--only decode_walk``: the paged decode kernel by shape, fill
    and pages a loop step, each beside the reference; at toy sizes here,
    where the times say nothing."""
    r = _run_smoke(["--rehearsal", "--only", "decode_walk"], tmp_path)
    assert r.returncode == 3, (r.stdout[-3000:], r.stderr[-3000:])
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    (phase,) = [ln for ln in lines if "phase" in ln]
    assert phase["phase"] == "decode_walk" and phase["ok"], phase
    rows = phase["decode_walk"]
    assert [(r["shape"], r["fill"]) for r in rows] == [
        ("trinity_window", "cell"), ("trinity_window", "one_page_a_row")]
    for row in rows:
        # the rule's chunk (the table's 8 slots bound it) and the one asked
        assert row["rule_chunk"] == 8
        assert set(row["us_a_call"]) == set(row["rel_err"]) == {"1", "8"}
        assert max(row["rel_err"].values()) < 3e-2
    assert rows[0]["live_pages"] > rows[1]["live_pages"] == 4
    r = _run_smoke(["--rehearsal", "--only", "no_such_phase"], tmp_path)
    assert r.returncode == 2 and "phases are" in r.stderr


def test_compile_cache_is_placed_from_outside(monkeypatch):
    from paddle_tpu.core import device

    def no_update(*a, **kw):
        raise AssertionError(f"jax.config.update called: {a}")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert device.ensure_compile_cache() == "/some/where"

    monkeypatch.undo()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = device.ensure_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_no_entry_point_sets_the_cache_dir_itself():
    """Only the helper may name a cache directory."""
    hits = []
    for root in ("paddle_tpu", "chip_smoke.py", "__graft_entry__.py"):
        path = os.path.join(REPO, root)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith(".py")]
        for f in files:
            src = open(f).read()
            if "jax_compilation_cache_dir" in src \
                    and not f.endswith(os.path.join("core", "device.py")):
                hits.append(os.path.relpath(f, REPO))
    assert not hits, hits


def test_detect_peaks_has_no_default_row():
    from paddle_tpu.observe import costmodel
    from paddle_tpu.utils import PaddleTpuError

    class Unknown:
        device_kind = "TPU v99 imaginary"

    with pytest.raises(PaddleTpuError, match="tpu v99 imaginary"):
        costmodel.detect_peaks(Unknown())

    class V5e:
        device_kind = "TPU v5 lite"     # what the chip reports

    assert costmodel.detect_peaks(V5e())["flops"] == 197e12

    class Broken:
        @property
        def device_kind(self):
            raise RuntimeError("backend fell over")

    with pytest.raises(RuntimeError):   # not swallowed into CPU peaks
        costmodel.detect_peaks(Broken())


PALLAS_MODULES = ("pallas_attention", "pallas_conv", "pallas_embedding",
                  "pallas_gru", "pallas_lstm")


@pytest.mark.parametrize("mod", PALLAS_MODULES)
def test_one_predicate_decides_interpret(mod):
    """Every ``pallas_call`` passes ``interpret=pallas_interpret()``, the
    one predicate in ``core/device.py`` ("tpu" only)."""
    path = os.path.join(REPO, "paddle_tpu", "ops", mod + ".py")
    tree = ast.parse(open(path).read())
    imported = any(
        isinstance(n, ast.ImportFrom) and n.module == "core.device"
        and n.level == 2
        and any(a.name == "pallas_interpret" for a in n.names)
        for n in ast.walk(tree))
    assert imported, f"{mod} does not import core.device.pallas_interpret"
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "pallas_call"]
    assert calls
    for c in calls:
        kw = {k.arg: k.value for k in c.keywords}
        # kwargs splatted from a `common = dict(interpret=...)` are
        # checked at the dict below
        if "interpret" in kw:
            v = kw["interpret"]
            assert isinstance(v, ast.Call) and \
                getattr(v.func, "id", "") == "pallas_interpret", \
                f"{mod}:{c.lineno} interpret= is not pallas_interpret()"
        else:
            assert any(k.arg is None for k in c.keywords), \
                f"{mod}:{c.lineno} pallas_call without interpret="
    for n in ast.walk(tree):
        if isinstance(n, ast.keyword) and n.arg == "interpret":
            assert isinstance(n.value, ast.Call) and \
                getattr(n.value.func, "id", "") == "pallas_interpret"
    assert "default_backend" not in open(path).read()


def test_platform_predicate_is_tpu_only(monkeypatch):
    from paddle_tpu.core import device

    class Dev:
        def __init__(self, platform):
            self.platform = platform

    for platform, want in (("tpu", True), ("cpu", False), ("gpu", False),
                           ("tpu-like", False)):
        monkeypatch.setattr(jax, "devices", lambda p=platform: [Dev(p)])
        assert device.is_tpu() is want
        assert device.pallas_interpret() is (not want)
