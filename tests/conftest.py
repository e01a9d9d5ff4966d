"""Test harness configuration.

Mirrors the reference's CPU-stub trick (``paddle/cuda/include/stub/`` lets the
whole engine test without CUDA): we force the JAX CPU backend with 8 virtual
devices so every multi-chip sharding test runs on any machine, no TPU needed.
Must run before jax initializes a backend, hence the env mutation at import
time of this conftest.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"
# fp32 on CPU — bf16 matmuls are TPU-only territory; tests check numerics.
os.environ.setdefault("PADDLE_TPU_USE_BF16", "0")
# hermetic CI: dataset loaders must not attempt network downloads
os.environ.setdefault("PADDLE_TPU_NO_DOWNLOAD", "1")

import jax

# The environment above only binds if jax is imported AFTER it; a pytest
# plugin may have imported jax first, so pin the config directly too.
# (XLA_FLAGS stays for the child processes tests spawn.)
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import time

import numpy as np
import pytest

# Quick-lane wall-time budget: the advertised fast path (`pytest` =
# `-m "not slow"`) measured 278 s in round 6; the guard keeps it from
# silently creeping past the point where it stops being quick.  Default
# is a LOUD warning (machines vary and a hard fail would flake CI on
# slow boxes); set PADDLE_TPU_FAST_LANE_STRICT=1 to turn the breach
# into a failing exit status.
FAST_LANE_BUDGET_S = 420
_SESSION_T0 = None


def pytest_sessionstart(session):
    global _SESSION_T0
    _SESSION_T0 = time.perf_counter()


def _fast_lane_elapsed(config):
    """Elapsed seconds when this run IS the fast lane, else None."""
    if _SESSION_T0 is None or config.option.markexpr != "not slow":
        return None
    return time.perf_counter() - _SESSION_T0


def _call_reports(tr):
    return [r for key in ("passed", "failed")
            for r in tr.stats.get(key, ())
            if getattr(r, "when", None) == "call"]


def _write_timing_artifact(tr, config):
    """Ship the per-test timing table through the observe JSONL sink
    (one self-describing line appended per session) so CI keeps a
    machine-readable artifact of where the quick lane's budget goes —
    the same schema the trainer's --metrics_jsonl lines use."""
    path = os.environ.get("PADDLE_TPU_TEST_TIMINGS_JSONL",
                          "/tmp/paddle_tpu_test_timings.jsonl")
    reports = _call_reports(tr)
    if not path or not reports:
        return
    try:
        from paddle_tpu.observe import MetricsRegistry, MetricsReporter

        reg = MetricsRegistry()
        hist = reg.histogram(
            "test_duration_seconds",
            "distribution of per-test call durations this session")
        per = reg.gauge("test_duration",
                        "per-test call duration, labeled by node id")
        for r in reports:
            hist.observe(r.duration)
            per.set(round(r.duration, 4), test=r.nodeid,
                    outcome=r.outcome)
        lane = reg.gauge("fast_lane", "quick-lane budget state")
        elapsed = _fast_lane_elapsed(config)
        if elapsed is not None:
            lane.set(round(elapsed, 1), field="elapsed_s")
            lane.set(FAST_LANE_BUDGET_S, field="budget_s")
        MetricsReporter(path, registry=reg, stat=None).flush()
    except Exception as e:   # noqa: BLE001 — never fail the run on it
        tr.line(f"(timing artifact not written: {e})")


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    tr = terminalreporter
    _write_timing_artifact(tr, config)
    elapsed = _fast_lane_elapsed(config)
    if elapsed is None or elapsed <= FAST_LANE_BUDGET_S:
        return
    tr.section("FAST-LANE BUDGET EXCEEDED", sep="=", red=True, bold=True)
    tr.line(f"the default quick lane (-m 'not slow') took {elapsed:.0f} s "
            f"> {FAST_LANE_BUDGET_S} s budget (round-6 reference: 278 s).")
    # name the offenders: the three slowest call phases, so the breach
    # points at the tests to mark slow instead of just announcing itself
    for r in sorted(_call_reports(tr), key=lambda r: r.duration,
                    reverse=True)[:3]:
        tr.line(f"  slowest: {r.duration:7.1f} s  {r.nodeid}")
    tr.line("Move heavyweight tests to @pytest.mark.slow or speed them "
            "up; set PADDLE_TPU_FAST_LANE_STRICT=1 to make this fail.")


def pytest_sessionfinish(session, exitstatus):
    elapsed = _fast_lane_elapsed(session.config)
    if (elapsed is not None and elapsed > FAST_LANE_BUDGET_S
            and os.environ.get("PADDLE_TPU_FAST_LANE_STRICT") == "1"
            and session.exitstatus == 0):
        session.exitstatus = 1


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run the full lane: re-include tests marked slow "
             "(pytest.ini's addopts deselects them by default)")


def pytest_configure(config):
    # drop the default fast-lane filter from pytest.ini when the user
    # asked for the full lane (--runslow) OR named specific tests by
    # node id — running `pytest tests/x.py::test_y` must execute the
    # test, not silently deselect it.  An explicit -m on the command
    # line still wins (it differs from the pytest.ini default).
    explicit_ids = any("::" in a for a in config.args)
    if (config.getoption("--runslow") or explicit_ids) \
            and config.option.markexpr == "not slow":
        config.option.markexpr = ""


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def lock_order_check():
    """Runtime half of PT-LOCK (analysis/lockorder.py), opt-in per
    suite: every blocking acquire of a `named_lock` records hierarchy
    edges while the test runs, and teardown asserts no ordering cycle
    was witnessed — the programmatic twin of
    PADDLE_TPU_LOCK_ORDER_CHECK=1.  The chaos and pipeline suites pull
    this through a module-local autouse shim."""
    from paddle_tpu.analysis import lockorder
    lockorder.reset()
    lockorder.enable(raise_on_violation=False)
    try:
        yield lockorder
        lockorder.check_acyclic()
    finally:
        lockorder.disable()
        lockorder.reset()


@pytest.fixture(autouse=True)
def _reset_global_state(_io_thread_leak_guard):
    # depends on the thread-leak guard so THIS teardown (which stops the
    # global trace writer / HTTP server threads) runs before the guard
    # judges what's still alive
    yield
    from paddle_tpu import observe
    from paddle_tpu.core import device
    from paddle_tpu.observe import REGISTRY
    from paddle_tpu.utils.logger import reset_warn_once
    from paddle_tpu.utils.stat import global_stat

    global_stat.reset()
    REGISTRY.reset()
    reset_warn_once()
    # the process-global mesh: a test that sets one (set_mesh, the
    # multichip dry run) must not hand it to the next test of its
    # worker — under a 4-device mesh left behind, every later Trainer
    # built without a mesh of its own splits its batch four ways
    device.set_mesh(None)
    # tracing + the HTTP endpoint + the fleet plane are process-wide: a
    # test that enabled them must not leak its recorder/server/pusher/
    # reporter (threads) or SIGTERM disposition into the next
    observe.stop_global()        # reporter + http + fleet agg + trace
    observe.fleet.reset_identity()
    # the training-health observatory keeps a process-wide latest
    # report for /health — resolved through sys.modules so tests that
    # never import it pay nothing
    import sys as _sys
    hmod = _sys.modules.get("paddle_tpu.observe.health")
    if hmod is not None:
        hmod.reset()
    # same discipline for the SLO engine (observe/slo.py)
    smod = _sys.modules.get("paddle_tpu.observe.slo")
    if smod is not None:
        smod.reset()


# Thread-leak guard: every framework-owned service thread is named so
# it can be audited — pipeline/reader workers ("ptpu-io-*"), the trace
# JSONL writer ("ptpu-trace-writer", observe/trace.py) and the
# observability HTTP server ("ptpu-metrics-http", observe/http.py).
# After each test none may still be alive — a stray worker means a
# teardown path regressed (the round-11 buffered/xmap bug class, or a
# trace/endpoint left enabled).  Default is a LOUD warning (a slow box
# can race a join); set PADDLE_TPU_THREAD_GUARD_STRICT=1 to fail the
# test instead — the same escalation contract as the fast-lane guard.
_THREAD_GUARD_GRACE_S = 2.0


@pytest.fixture(autouse=True)
def _io_thread_leak_guard(request):
    import threading
    import warnings

    from paddle_tpu.data.pipeline import IO_THREAD_PREFIX
    from paddle_tpu.observe.fleet import AGGREGATOR_THREAD_NAME
    from paddle_tpu.observe.http import SERVER_THREAD_NAME
    from paddle_tpu.observe.trace import WRITER_THREAD_NAME

    # "ptpu-serve-" covers the inference server's decode + HTTP threads
    # (serving/server.py), "ptpu-rollout-" the checkpoint watcher
    # (serving/rollout.py) — without importing the serving stack here
    prefixes = (IO_THREAD_PREFIX, WRITER_THREAD_NAME, SERVER_THREAD_NAME,
                AGGREGATOR_THREAD_NAME, "ptpu-serve-", "ptpu-rollout-")

    def stray():
        return [t for t in threading.enumerate()
                if t.is_alive() and t.name.startswith(prefixes)]

    yield
    deadline = time.perf_counter() + _THREAD_GUARD_GRACE_S
    leaked = stray()
    while leaked and time.perf_counter() < deadline:
        time.sleep(0.02)     # drain in-flight joins before judging
        leaked = stray()
    if not leaked:
        return
    msg = (f"STRAY IO THREADS after {request.node.nodeid}: "
           f"{sorted(t.name for t in leaked)} — a pipeline/reader "
           "worker outlived its generator (leaked producer or missing "
           "close()); set PADDLE_TPU_THREAD_GUARD_STRICT=1 to fail on "
           "this")
    if os.environ.get("PADDLE_TPU_THREAD_GUARD_STRICT") == "1":
        pytest.fail(msg)
    warnings.warn(msg)


# Dtype-drift guard: under the --precision=bf16 policy, master
# parameters and optimizer state must STAY fp32 — an accidental in-place
# downcast (assigning a compute-cast tree back onto the trainer) is the
# classic mixed-precision bug and silently destroys convergence.  After
# each test, every live Trainer's params + opt-state leaves are checked
# for half-precision dtypes; violations LOUD-WARN by default (the same
# escalation contract as the thread-leak guard above), and
# PADDLE_TPU_DTYPE_GUARD_STRICT=1 turns them into failures.
@pytest.fixture(autouse=True)
def _master_dtype_drift_guard(request):
    import sys
    import warnings

    yield
    trainer_mod = sys.modules.get("paddle_tpu.trainer.trainer")
    if trainer_mod is None:          # test never touched the trainer
        return
    import jax
    import jax.numpy as jnp

    half = (jnp.bfloat16, np.float16)
    bad = []
    for tr in list(trainer_mod._LIVE_TRAINERS):
        for tag, tree in (("params", getattr(tr, "params", None)),
                          ("opt_state", getattr(tr, "opt_state", None))):
            if tree is None:
                continue
            for path, leaf in \
                    jax.tree_util.tree_flatten_with_path(tree)[0]:
                if getattr(leaf, "dtype", None) in half:
                    bad.append(f"{tag}{jax.tree_util.keystr(path)}"
                               f"={leaf.dtype}")
    if not bad:
        return
    msg = (f"MASTER DTYPE DRIFT after {request.node.nodeid}: "
           f"{sorted(set(bad))[:8]} — a master parameter or "
           "optimizer-state leaf ended up half-precision (in-place "
           "downcast through the bf16 compute path); set "
           "PADDLE_TPU_DTYPE_GUARD_STRICT=1 to fail on this")
    if os.environ.get("PADDLE_TPU_DTYPE_GUARD_STRICT") == "1":
        pytest.fail(msg)
    warnings.warn(msg)
