"""The load generators and the entry point: the same multiset under two
seeds, weights from the seed, and a rehearsal that prints no result."""

import collections
import itertools
import json
import os
import subprocess
import sys

import numpy as np

from chipbench import harness as H
from chipbench import weights as W

ROOT = H.ROOT


def test_closed_loop_offers_the_same_multiset_under_two_seeds():
    drv = H.load_module("drivers", "serve_closed")
    mix = H.load_json(H.named_file("traffic", "closed_c12", ".json"))
    all_lens = [n for b in mix["blocks"] for n in b["prompt_lens"]]
    all_budgets = [n for b in mix["blocks"] for n in b["output_budgets"]]
    n = len(all_lens)
    seen = []
    for seed in (3, 2 ** 31 + 11):
        plan = list(itertools.islice(drv.request_plan(mix, 50272, seed),
                                     2 * n))
        for cycle in (plan[:n], plan[n:]):
            lens = collections.Counter(len(p) for p, _ in cycle)
            budgets = collections.Counter(b for _, b in cycle)
            assert lens == collections.Counter(all_lens)
            assert budgets == collections.Counter(all_budgets)
            # block by block the work is the same: 5 requests, 480
            # output tokens, about the same prompt tokens
            for k in range(0, n, 5):
                assert sum(b for _, b in cycle[k:k + 5]) == 480
                assert 1024 <= sum(len(p) for p, _ in cycle[k:k + 5]) <= 1152
        assert all(min(p) >= drv.FIRST_PROMPT_ID and max(p) < 50272
                   for p, _ in plan)
        seen.append([(len(p), b) for p, b in plan])
    assert seen[0] != seen[1]                       # another order
    shares = collections.Counter(all_lens)
    assert [shares[k] / n for k in (128, 256, 384, 512)] == \
        [0.5, 0.3, 0.15, 0.05]
    assert sum(all_budgets) / n == 96


def test_the_windows_edges_lie_just_after_a_launch_has_emitted():
    """Both edges of the serving window are taken when a launch's tokens
    are all out, so the window holds whole launches."""
    import threading
    import time
    import types

    drv = H.load_module("drivers", "serve_closed")
    server = types.SimpleNamespace(
        generated_tokens=0, pool=types.SimpleNamespace(used_pages=lambda: 0))
    stop = threading.Event()

    def launches():                  # 12 tokens, one by one, every 60 ms
        while not stop.is_set():
            time.sleep(0.06)
            for _ in range(12):
                server.generated_tokens += 1
                time.sleep(0.0005)

    mix = {"admit_cap": 1, "stagger_s": 0.0, "clients": 0}
    loop = drv.ClosedLoop(server, iter(()), mix, time.perf_counter())
    worker = threading.Thread(target=launches, daemon=True)
    worker.start()
    try:
        t0 = loop.run_to_edge(0.002)
        n0 = server.generated_tokens
        loop.run_for(0.1, 0.002)
        t1 = loop.run_to_edge(0.002)
        n1 = server.generated_tokens
    finally:
        stop.set()
        worker.join()
    assert n0 % 12 == 0 and n1 % 12 == 0 and n1 > n0
    assert 0.05 < loop.longest_gap < 0.2 and t1 - t0 >= 0.1
    # a server that emits nothing is an error, not an endless wait
    stuck = drv.ClosedLoop(server, iter(()), mix, time.perf_counter())
    try:
        stuck.run_to_edge(0.002, patience_s=0.05)
    except H.BenchError as e:
        assert "emitted nothing" in str(e)
    else:
        raise AssertionError("a silent server gave an edge")


def test_train_batches_come_from_the_seed_and_rows_differ():
    drv = H.load_module("drivers", "train")
    spec = {"x": {"shape": [5], "dtype": "float32", "draw": "normal"},
            "y": {"shape": [], "dtype": "int32", "draw": "randint",
                  "high": 10}}
    a = drv.make_batches(spec, 4, 3, seed=2 ** 31 + 5)
    b = drv.make_batches(spec, 4, 3, seed=2 ** 31 + 5)
    c = drv.make_batches(spec, 4, 3, seed=6)
    assert all(np.array_equal(p["x"], q["x"]) for p, q in zip(a, b))
    assert not np.array_equal(a[0]["x"], c[0]["x"])
    rows = np.concatenate([p["x"] for p in a])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_weights_are_a_function_of_the_seed():
    spec = {"w": ((8, 4), "normal", 0.5), "g": ((4,), "gain", 0.1),
            "b": ((4,), "zeros", 0.0)}
    a, b, c = W.make(spec, 2 ** 31 + 9), W.make(spec, 2 ** 31 + 9), \
        W.make(spec, 1)
    assert all(np.array_equal(a[k], b[k]) for k in spec)
    assert not np.array_equal(a["w"], c["w"])
    assert abs(a["g"].mean() - 1.0) < 0.3 and not a["b"].any()


def _run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "chipbench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def test_without_a_tpu_nothing_runs_and_no_metrics_print():
    p = _run("--workload", "serve_closed_c12", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert '"metrics"' not in p.stdout and "no TPU" in p.stderr


def test_rehearsal_never_prints_a_metrics_line():
    p = _run("--workload", "serve_closed_c12", "--seed", "7",
             "--seconds", "1", "--rehearsal")
    assert p.returncode == 3, p.stderr[-2000:]
    assert "REHEARSAL" in p.stdout
    for line in p.stdout.splitlines():
        assert '"metrics"' not in line and '"correct"' not in line


def test_unknown_device_kind_has_no_row(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v0 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    try:
        H.device_gate(1)
    except H.BenchError as e:
        assert "peaks.json" in str(e)
    else:
        raise AssertionError("an unknown device_kind passed the gate")


def test_alone_in_a_directory_it_gives_no_result(tmp_path):
    import shutil
    shutil.copytree(H.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run("--workload", "serve_closed_c12", "--seed", "1",
             "--seconds", "1", "--trace", "0", cwd=str(tmp_path))
    assert p.returncode != 0 and '"metrics"' not in p.stdout
