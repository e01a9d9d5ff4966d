"""Continuous-batching inference server + page-pool allocator (ISSUE 16).

Three layers under test:

- :class:`paddle_tpu.serving.pagepool.PagePool` — churn, the
  uniform-page fragmentation bound, table correctness after heavy
  reuse, atomic snapshots refusing torn state;
- :class:`paddle_tpu.serving.server.InferenceServer` — end-to-end
  generation, the ``--serve_continuous`` kill switch (byte-for-byte
  token equality against sequential single-request serving, BOTH flag
  directions), admission backpressure, per-request telemetry, the HTTP
  front;
- the chaos contract — a SIGKILLed serving process
  (:class:`paddle_tpu.testing.fault.ServeServerProcess`) restarted
  from the same snapshot path never serves a torn page table.
"""

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.serving.pagepool import (PagePool, PagePoolExhausted,
                                         SCRATCH_PAGE, TornSnapshot)
from paddle_tpu.utils import FLAGS
from paddle_tpu.utils.error import PaddleTpuError


# ------------------------------------------------------------ page pool
def test_pool_alloc_release_roundtrip():
    pool = PagePool(n_pages=17, page_size=8)
    assert pool.capacity == 16
    a = pool.alloc("a", 20)          # ceil(20/8) = 3 pages
    b = pool.alloc("b", 8)           # 1 page
    assert len(a) == 3 and len(b) == 1
    assert SCRATCH_PAGE not in a + b
    assert not set(a) & set(b)
    assert pool.used_pages() == 4
    assert pool.free_pages() == 12
    assert pool.table_of("a") == a and pool.length_of("a") == 20
    pool.verify()
    assert pool.release("a") == 3
    assert pool.release("a") == 0    # idempotent (crash-recovery path)
    assert pool.free_pages() == 15
    pool.verify()


def test_pool_churn_fragmentation_bound():
    """The no-starvation bound: with uniform pages an allocation
    succeeds exactly when enough free pages exist, no matter how
    churned the free list is."""
    pool = PagePool(n_pages=33, page_size=4)
    rng = np.random.RandomState(7)
    live = {}
    for i in range(600):
        if live and rng.rand() < 0.45:
            owner = rng.choice(sorted(live))
            pool.release(owner)
            del live[owner]
        else:
            tokens = int(rng.randint(1, 40))
            need = pool.pages_needed(tokens)
            owner = f"r{i}"
            if need <= pool.free_pages():
                live[owner] = pool.alloc(owner, tokens)
            else:       # the ONLY legal failure: not enough free pages
                with pytest.raises(PagePoolExhausted):
                    pool.alloc(owner, tokens)
        if i % 97 == 0:
            pool.verify()
    pool.verify()
    # every live table still disjoint and scratch-free after the churn
    seen = set()
    for owner, pages in live.items():
        assert pool.table_of(owner) == pages
        assert SCRATCH_PAGE not in pages
        assert not seen & set(pages)
        seen |= set(pages)


def test_pool_table_correctness_after_heavy_reuse():
    """LIFO recycling reissues the hottest pages — after many full
    alloc/release generations the same physical ids have served many
    owners, and each generation's tables must still verify."""
    pool = PagePool(n_pages=9, page_size=2)
    first_gen = [tuple(pool.alloc(f"g0.{j}", 4)) for j in range(4)]
    issued = set().union(*map(set, first_gen))
    for j in range(4):
        pool.release(f"g0.{j}")
    for gen in range(1, 50):
        tables = [pool.alloc(f"g{gen}.{j}", 4) for j in range(4)]
        assert pool.free_pages() == 0
        # uniform pool: every generation reuses exactly the same ids
        assert set().union(*map(set, tables)) == issued
        pool.verify()
        for j in range(4):
            pool.release(f"g{gen}.{j}")
    assert pool.free_pages() == pool.capacity


def test_pool_exhaustion_takes_nothing():
    pool = PagePool(n_pages=5, page_size=8)
    pool.alloc("a", 24)              # 3 of 4 pages
    free_before = pool.free_pages()
    with pytest.raises(PagePoolExhausted):
        pool.alloc("b", 17)          # needs 3, only 1 free
    assert pool.free_pages() == free_before     # failed alloc is atomic
    assert pool.owners() == ["a"]
    pool.verify()


def test_pool_extend():
    pool = PagePool(n_pages=9, page_size=4)
    t = pool.alloc("a", 3)           # 1 page covers tokens 0..3
    assert pool.extend("a", 4) == t  # same page still suffices
    t2 = pool.extend("a", 5)         # crosses the boundary: +1 page
    assert t2[:1] == t and len(t2) == 2
    assert pool.length_of("a") == 5
    with pytest.raises(PaddleTpuError):
        pool.extend("a", 2)          # shrink is a programming error
    pool.alloc("b", 24)              # drain the pool (6 pages free)
    with pytest.raises(PagePoolExhausted):
        pool.extend("a", 100)
    pool.verify()


def test_pool_snapshot_roundtrip(tmp_path):
    pool = PagePool(n_pages=17, page_size=8)
    pool.alloc("a", 20)
    pool.alloc("b", 5)
    pool.release("a")
    path = str(tmp_path / "pool.json")
    pool.snapshot(path)
    back = PagePool.restore(path)
    back.verify()
    assert back.owners() == ["b"]
    assert back.table_of("b") == pool.table_of("b")
    assert back.length_of("b") == 5
    assert back.free_pages() == pool.free_pages()
    # no stray tmp files from the atomic-write discipline
    assert [f for f in os.listdir(tmp_path)
            if f.startswith(".pagepool-")] == []


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_pool_snapshot_torn_is_refused(tmp_path, mode):
    from paddle_tpu.testing.fault import corrupt_checkpoint

    pool = PagePool(n_pages=17, page_size=8)
    pool.alloc("a", 40)
    pool.snapshot(str(tmp_path / "pool.json"))
    corrupt_checkpoint(str(tmp_path), "pool.json", mode=mode)
    with pytest.raises(TornSnapshot):
        PagePool.restore(str(tmp_path / "pool.json"))


def test_pool_snapshot_invariant_violations_refused(tmp_path):
    """A snapshot that parses and checksums but encodes an impossible
    pool (doubly-owned page) must still be refused — the checksum
    guards the wire, verify() guards the semantics."""
    pool = PagePool(n_pages=9, page_size=4)
    pool.alloc("a", 4)
    path = str(tmp_path / "pool.json")
    pool.snapshot(path)
    doc = json.load(open(path))
    doc.pop("checksum")
    doc["tables"]["b"] = list(doc["tables"]["a"])    # alias a's pages
    doc["lengths"]["b"] = doc["lengths"]["a"]
    doc["checksum"] = PagePool._checksum(doc)        # re-sign it
    json.dump(doc, open(path, "w"))
    with pytest.raises(TornSnapshot):
        PagePool.restore(path)


# ------------------------------------------------------------- server
@pytest.fixture(scope="module")
def tiny_model():
    from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                          init_decoder_params)

    cfg = DecoderConfig(vocab=64, dim=32, heads=2, layers=1, ffn=64,
                        max_context=64, eos_id=1)
    return DecoderModel(init_decoder_params(cfg, seed=0), cfg)


def _prompts(n, vocab=64, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, vocab, rng.randint(2, 9)).tolist()
            for _ in range(n)]


def _serve_all(model, prompts, max_new=6, **kw):
    from paddle_tpu.serving.server import InferenceServer

    kw.setdefault("n_pages", 33)
    kw.setdefault("page_size", 8)
    with InferenceServer(model, max_batch=4, **kw) as srv:
        reqs = [srv.submit(p, max_new) for p in prompts]
        return [srv.result(r, timeout=120.0) for r in reqs]


def test_server_generates(tiny_model):
    outs = _serve_all(tiny_model, _prompts(5), continuous=True)
    assert len(outs) == 5
    for toks in outs:
        assert 1 <= len(toks) <= 6
        assert all(0 <= t < tiny_model.cfg.vocab for t in toks)
        # eos may end a request early, but only as the last token
        assert tiny_model.cfg.eos_id not in toks[:-1]


def test_continuous_equals_sequential_arg_driven(tiny_model):
    """The kill-switch contract: batched continuous decode and
    sequential single-request serving produce byte-identical tokens."""
    prompts = _prompts(6)
    cont = _serve_all(tiny_model, prompts, continuous=True)
    seq = _serve_all(tiny_model, prompts, continuous=False)
    assert cont == seq


def test_kill_switch_flag_driven(tiny_model):
    """Same pin, driven through --serve_continuous in BOTH directions
    (the ctor default reads the flag)."""
    from paddle_tpu.serving.server import InferenceServer

    prompts = _prompts(4, seed=11)
    saved = FLAGS.get("serve_continuous")
    outs = {}
    try:
        for flag in (False, True):
            FLAGS.set("serve_continuous", flag)
            with InferenceServer(tiny_model, max_batch=4, n_pages=33,
                                 page_size=8) as srv:
                assert srv.continuous is flag
                reqs = [srv.submit(p, 5) for p in prompts]
                outs[flag] = [srv.result(r, timeout=120.0) for r in reqs]
    finally:
        FLAGS.set("serve_continuous", saved)
    assert outs[False] == outs[True]


def _serial_greedy(model, prompt, max_new, page=8):
    """The plain loop the server is held to: one request alone, prefill
    then one width-1 decode step a token through ``model.prefill`` /
    ``model.decode``, the host reading every id before the next step."""
    slots = -(-model.cfg.max_context // page)
    k, v = model.new_pools(1 + slots, page)
    table = np.arange(1, 1 + slots, dtype=np.int32)[None, :]
    tokens = np.zeros((1, -(-len(prompt) // 16) * 16), np.int32)
    tokens[0, :len(prompt)] = prompt
    nxt, _, k, v = model.prefill(k, v, tokens,
                                 np.array([len(prompt)], np.int32), table)
    out, length = [int(nxt[0])], len(prompt)
    while out[-1] != model.cfg.eos_id and len(out) < max_new:
        length += 1
        nxt, _, k, v, _ = model.decode(
            k, v, np.array([out[-1]], np.int32), table,
            np.array([length], np.int32), np.array([True]))
        out.append(int(nxt[0]))
    return out


@pytest.mark.parametrize("continuous", [True, False])
def test_served_tokens_are_the_serial_greedy_loops(tiny_model, continuous):
    """The loop runs a launch ahead of the host and feeds rows from the
    device; request for request it serves what a serial loop serves,
    under admissions that arrive while others decode."""
    from paddle_tpu.serving.server import InferenceServer

    prompts = _prompts(9, seed=31)
    budgets = [1, 2, 9, 5, 12, 3, 7, 12, 4]
    want = [_serial_greedy(tiny_model, p, n)
            for p, n in zip(prompts, budgets)]
    with InferenceServer(tiny_model, max_batch=3, n_pages=33, page_size=8,
                         continuous=continuous) as srv:
        reqs = []
        for p, n in zip(prompts, budgets):
            reqs.append(srv.submit(p, n))
            # the next one arrives once this one is decoding (or done)
            assert _wait(lambda: reqs[-1].tokens or reqs[-1].done.is_set())
        got = [srv.result(r, timeout=120.0) for r in reqs]
        assert got == want
        assert srv.generated_tokens == sum(map(len, want))
        assert srv.pool.used_pages() == 0 and not srv._inflight


def _wait(pred, timeout_s=60.0):
    import time

    end = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > end:
            return False
        time.sleep(0.001)
    return True


def test_a_row_launched_past_its_eos_is_dropped(tiny_model):
    """EOS mid-stream: the host learns of it one launch late, so the row
    rides in the next launch dead.  Its extra token is neither emitted
    nor counted, ``serve_rows_discarded_total`` counts the row, and a
    request admitted into the recycled pages generates what it
    generates alone."""
    from paddle_tpu import observe
    from paddle_tpu.serving.model import DecoderModel
    from paddle_tpu.serving.server import InferenceServer

    prompts = _prompts(4, seed=41)
    free = _serial_greedy(tiny_model, prompts[0], 12)
    assert len(free) == 12                  # no EOS of its own
    stop_at = next(i for i in range(3, 9) if free[i] not in free[:i])
    # the same weights, with the id of that step as the end of sequence
    model = DecoderModel(
        {k: np.asarray(v) for k, v in tiny_model.params.items()},
        tiny_model.cfg._replace(eos_id=free[stop_at]))
    want = [_serial_greedy(model, p, 12) for p in prompts]
    assert want[0] == free[:stop_at + 1]
    discarded = observe.counter("serve_rows_discarded_total", "")
    before = discarded.value()
    # 4 pages of 8: two requests of prompt <= 8 plus 12 hold all of
    # them, so the third waits for the pages the first gives back
    with InferenceServer(model, max_batch=4, n_pages=5,
                         page_size=8) as srv:
        reqs = [srv.submit(p, 12) for p in prompts]
        got = [srv.result(r, timeout=120.0) for r in reqs]
        assert got == want
        assert srv.generated_tokens == sum(map(len, got))
        assert srv.pool.used_pages() == 0
    dead = sum(len(t) < 12 for t in want)
    assert dead >= 1 and discarded.value() - before == dead


def _faulty_launches(model, fail_at=None, on_launch=None):
    """``model.launch_decode`` wrapped on the instance: the ``fail_at``-th
    call raises, and ``on_launch(n)`` runs after each launch is queued."""
    real, calls = model.launch_decode, []

    def launch_decode(*args, **kw):
        calls.append(1)
        if len(calls) == fail_at:
            raise RuntimeError("planted launch failure")
        out = real(*args, **kw)
        if on_launch is not None:
            on_launch(len(calls))
        return out
    model.launch_decode = launch_decode
    return calls


def test_a_failing_launch_with_one_in_flight_fails_its_requests(tiny_model):
    """The third decode launch raises while the second is queued on the
    device: that one is collected, every active request fails, no page
    stays held, and the loop serves the next request."""
    from paddle_tpu.serving.server import InferenceServer

    try:
        _faulty_launches(tiny_model, fail_at=3)
        with InferenceServer(tiny_model, max_batch=4, n_pages=33,
                             page_size=8) as srv:
            reqs = [srv.submit(p, 10) for p in _prompts(3, seed=43)]
            assert all(r.done.wait(120.0) for r in reqs)
            assert {r.state for r in reqs} <= {"done", "failed"}
            failed = [r for r in reqs if r.state == "failed"]
            assert failed and all("planted launch failure" in r.error
                                  for r in failed)
            # its prefill and both decode launches before the failing
            # one were collected, the second of them by the failure path
            assert max(len(r.tokens) for r in failed) == 3
            assert srv.pool.used_pages() == 0 and not srv._inflight
            srv.pool.verify()
            again = srv.generate(_prompts(1, seed=44)[0], 6, timeout=120.0)
            assert again == _serial_greedy(tiny_model,
                                           _prompts(1, seed=44)[0], 6)
    finally:
        del tiny_model.launch_decode


def test_a_stalled_turn_is_counted_under_its_phase(tiny_model, monkeypatch):
    """A decode fetch that holds the loop's thread for longer than
    ``STALL_S`` (asleep: no CPU) ticks ``serve_stall_total{phase=fetch}``
    and leaves a line that says so.  Tracing is off: no span is there
    to say it."""
    import logging

    from paddle_tpu import observe
    from paddle_tpu.serving import server as srv_mod
    from paddle_tpu.serving.model import DecoderModel

    model = DecoderModel(
        {k: np.asarray(v) for k, v in tiny_model.params.items()},
        tiny_model.cfg)
    _serve_all(model, _prompts(2, seed=5), max_new=4)    # compiled
    real, calls = model.collect_decode, []

    def collect_decode(handle):
        calls.append(1)
        if len(calls) == 3:
            time.sleep(2 * srv_mod.STALL_S)
        return real(handle)
    monkeypatch.setattr(model, "collect_decode", collect_decode)
    stalls = observe.counter("serve_stall_total", "")
    before = stalls.value(phase="fetch")
    records, handler = [], logging.Handler()
    handler.emit = records.append
    srv_mod.log.addHandler(handler)
    try:
        got = _serve_all(model, _prompts(2, seed=5), max_new=8)
    finally:
        srv_mod.log.removeHandler(handler)
    assert len(calls) >= 4 and all(len(t) == 8 for t in got)
    # (a loaded machine may stall a turn of its own: held to nothing)
    assert stalls.value(phase="fetch") - before >= 1
    planted = [r.getMessage() for r in records
               if "serve stall" in r.getMessage()
               and " in fetch " in r.getMessage()]
    assert planted and "launch period of a decode took 0." in planted[0]
    # asleep, not running: the thread's CPU seconds stay far below
    ran = float(planted[0].split("ran for ")[1].split(" s")[0])
    assert ran < srv_mod.STALL_S


def test_stop_with_a_launch_in_flight_leaves_nothing(tiny_model):
    """``stop()`` while the loop has a launch queued ahead: the thread
    ends, nothing stays in flight, no page is held, every request is
    done or failed."""
    from paddle_tpu.serving.server import (DECODE_THREAD_NAME,
                                           InferenceServer)

    queued = threading.Event()
    try:
        _faulty_launches(
            tiny_model, on_launch=lambda n: n >= 3 and queued.set())
        srv = InferenceServer(tiny_model, max_batch=4, n_pages=33,
                              page_size=8).start()
        reqs = [srv.submit(p, 40) for p in _prompts(6, seed=45)]
        assert queued.wait(60.0)
        srv.stop()
    finally:
        del tiny_model.launch_decode
    assert DECODE_THREAD_NAME not in [t.name for t in threading.enumerate()]
    assert not srv._inflight and srv.pool.used_pages() == 0
    srv.pool.verify()
    assert all(r.done.is_set() and r.state in ("done", "failed")
               for r in reqs)
    assert any(r.state == "failed" and r.error == "server stopped"
               for r in reqs)


def test_submit_validation(tiny_model):
    from paddle_tpu.serving.server import InferenceServer

    with InferenceServer(tiny_model, max_batch=2, n_pages=17,
                         page_size=8) as srv:
        with pytest.raises(PaddleTpuError):
            srv.submit([], 4)
        with pytest.raises(PaddleTpuError):
            srv.submit([2, 3], 0)
        with pytest.raises(PaddleTpuError):
            srv.submit([2] * 60, 10)     # 70 > max_context 64


def test_admission_backpressure_drains(tiny_model):
    """A pool that fits ~one request at a time must still serve the
    whole queue: exhaustion is admission backpressure, not failure."""
    # capacity 4 pages of 8 tokens; each request reserves
    # ceil((prompt + max_new) / 8) pages up front
    outs = _serve_all(tiny_model, _prompts(6, seed=5), max_new=6,
                      continuous=True, n_pages=5, page_size=8)
    assert len(outs) == 6 and all(len(t) >= 1 for t in outs)


def test_server_telemetry(tiny_model):
    from paddle_tpu import observe

    prompts = _prompts(3, seed=13)
    _serve_all(tiny_model, prompts, continuous=True)
    assert observe.counter("serve_requests", "").value() >= 3
    assert observe.counter("serve_tokens_generated", "").value() >= 3
    h = observe.histogram("serve_ttft_seconds", "")
    assert h.retained_samples() >= 3
    assert observe.histogram("serve_request_seconds",
                             "").retained_samples() >= 3


def _traced_serve(model, prompts, max_new=6):
    """Serve ``prompts`` with the ring on.  → (server, requests, the
    ring's events, the ``lengths`` and ``active`` of every decode
    launch as the model got them, in launch order)."""
    from paddle_tpu.observe import trace
    from paddle_tpu.serving.server import InferenceServer

    fed = []
    real = model.launch_decode

    def launch_decode(k, v, tokens, tables, lengths, active, *feed):
        fed.append((np.array(lengths), np.array(active)))
        return real(k, v, tokens, tables, lengths, active, *feed)

    trace.enable(fences=False)
    model.launch_decode = launch_decode
    try:
        with InferenceServer(model, max_batch=4, n_pages=33,
                             page_size=8) as srv:
            reqs = [srv.submit(p, max_new) for p in prompts]
            for r in reqs:
                srv.result(r, timeout=120.0)
        events = trace.events()
    finally:
        del model.launch_decode
        trace.disable()
    return srv, reqs, events, fed


def _named(events, name):
    return [e for e in events if e["name"] == name]


def test_spans_belong_to_a_request(tiny_model):
    """One ``serve_queue_wait`` and one ``serve_request`` per request,
    under the request's trace id, with its own clocks."""
    _, reqs, events, _ = _traced_serve(tiny_model, _prompts(6, seed=21))
    waits = {e["args"]["request"]: e
             for e in _named(events, "serve_queue_wait")}
    whole = {e["args"]["request"]: e
             for e in _named(events, "serve_request")}
    assert len(_named(events, "serve_queue_wait")) == len(reqs)
    assert len(_named(events, "serve_request")) == len(reqs)
    assert len({r.trace_id for r in reqs}) == len(reqs)
    for r in reqs:
        w, d = waits[r.id], whole[r.id]
        assert w["args"]["trace_id"] == d["args"]["trace_id"] == r.trace_id
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done
        assert w["ts"] == d["ts"]                       # both from submit
        assert w["dur"] == pytest.approx(
            (r.t_admit - r.t_submit) * 1e6, abs=1.0)
        assert d["dur"] == pytest.approx(r.latency_s * 1e6, abs=1.0)
        assert d["args"]["prompt"] == len(r.prompt)
        assert d["args"]["tokens"] == len(r.tokens)
        assert d["args"]["ttft_ms"] == pytest.approx(r.ttft_s * 1e3,
                                                     abs=1e-3)


def test_a_request_joins_its_submitters_trace(tiny_model):
    from paddle_tpu.observe import trace
    from paddle_tpu.serving.server import Request

    trace.enable(fences=False)
    with trace.span("client_call") as sp:
        inside = Request([2, 3], 2)
    assert inside.trace_id == sp.context.trace_id
    assert Request([2, 3], 2).trace_id != inside.trace_id


def _launch_spans(events):
    """The launch spans in launch order (they tile the loop thread's
    time, so by start), and every span's children by name."""
    children = {}
    for e in events:
        children.setdefault(e["args"].get("parent_id"), []).append(e)
    launches = sorted(_named(events, "serve_decode_step")
                      + _named(events, "serve_prefill"),
                      key=lambda e: e["ts"])
    return launches, children


def _kind(e):
    return "prefill" if e["name"] == "serve_prefill" else "decode"


def test_a_step_is_split_into_host_phases(tiny_model):
    """The launch-ordered layout: a ``serve_loop_iter`` holds one launch
    span; under launch k's span lie the fetch and the emit of launch k
    and the build and dispatch of launch k+1 (queued ``behind`` it), or
    launch k's own when the device was ``idle``; and the spans' counts
    add up to the tokens the server generated."""
    srv, reqs, events, _ = _traced_serve(tiny_model, _prompts(6, seed=22))
    launches, children = _launch_spans(events)
    iters = {e["args"]["span_id"] for e in _named(events,
                                                  "serve_loop_iter")}
    steps, prefills = (_named(events, "serve_decode_step"),
                       _named(events, "serve_prefill"))
    assert steps and prefills
    assert len({e["args"]["parent_id"] for e in launches}) == len(launches)
    for k, e in enumerate(launches):
        assert e["args"]["parent_id"] in iters
        under = sorted(children[e["args"]["span_id"]],
                       key=lambda c: c["ts"])
        names = [c["name"] for c in under]
        own = ["serve_step_build", _kind(e) + "_dispatch"] \
            if e["args"]["queued"] == "idle" else []
        nxt = launches[k + 1] if k + 1 < len(launches) else None
        ahead = ["serve_step_build", _kind(nxt) + "_dispatch"] \
            if nxt is not None and nxt["args"]["queued"] == "behind" else []
        assert [n for n in names if n != "serve_admit"] == \
            own + ahead + [_kind(e) + "_fetch", "serve_step_emit"]
        # the admission check of the next launch's planning (the
        # launch's own, from idle, ran before the span opened)
        assert names.count("serve_admit") <= 1
    assert launches[0]["args"]["queued"] == "idle"
    assert any(e["args"]["queued"] == "behind" for e in steps)
    for e in prefills:
        assert e["args"]["t_pad"] % 16 == 0
    assert sum(e["args"]["batch"] for e in steps) \
        + sum(e["args"]["n"] for e in prefills) == srv.generated_tokens
    assert srv.generated_tokens == sum(len(r.tokens) for r in reqs)
    assert sum(e["args"]["prompt_tokens"] for e in prefills) \
        == sum(len(r.prompt) for r in reqs)
    admitted = [i for e in prefills for i in e["args"]["requests"].split(",")]
    assert sorted(admitted) == sorted(r.id for r in reqs)


def test_a_launch_span_runs_from_collect_to_collect(tiny_model):
    """Spans follow launches: the span that bears a launch's attributes
    closes after that launch's fetch returned (and its emit), and opens
    no earlier than the fetch of the launch before it returned, so the
    spans tile the loop thread's time in launch order; and the counter
    says of each launch what its span says."""
    from paddle_tpu import observe

    counted = observe.counter("serve_launch_total", "")
    before = {(k, q): counted.value(kind=k, queued=q)
              for k in ("decode", "prefill") for q in ("behind", "idle")}
    _, _, events, _ = _traced_serve(tiny_model, _prompts(6, seed=25),
                                    max_new=8)
    launches, children = _launch_spans(events)
    end = lambda e: e["ts"] + e["dur"]
    fetched = []
    for e in launches:
        fetch, = [c for c in children[e["args"]["span_id"]]
                  if c["name"] == _kind(e) + "_fetch"]
        emit, = [c for c in children[e["args"]["span_id"]]
                 if c["name"] == "serve_step_emit"]
        assert e["ts"] <= fetch["ts"] and end(fetch) <= emit["ts"]
        assert end(emit) <= end(e)
        if fetched:
            assert e["ts"] >= fetched[-1]
        fetched.append(end(fetch))
    for (k, q), was in before.items():
        assert counted.value(kind=k, queued=q) - was == sum(
            _kind(e) == k and e["args"]["queued"] == q for e in launches)


def test_decode_span_states_the_kv_it_attends_over(tiny_model):
    """``live_tokens`` is the sum of the lengths the launch was fed for
    its active rows, ``live_pages`` the pages those lengths occupy;
    ``batch`` the rows whose token was emitted (a row launched past its
    EOS is ``discarded``)."""
    srv, _, events, fed = _traced_serve(tiny_model, _prompts(5, seed=23),
                                        max_new=8)
    steps = sorted(_named(events, "serve_decode_step"),
                   key=lambda e: e["ts"])
    assert len(steps) == len(fed)
    for e, (lengths, active) in zip(steps, fed):
        assert e["args"]["batch"] + e["args"].get("discarded", 0) \
            == int(active.sum())
        assert e["args"]["live_tokens"] == int(lengths[active].sum())
        assert e["args"]["live_pages"] == sum(
            srv.pool.pages_needed(int(n)) for n in lengths[active])


def test_spans_state_what_the_conv_layers_hold(tiny_model):
    """``conv_tokens`` of a prefill (prompt tokens x conv layers) and
    ``state_rows`` of a decode step (launched rows x conv layers): zero
    for a plan in which every layer attends, and the state's gauge reads
    0 bytes a sequence beside the pools' bytes a token."""
    from paddle_tpu import observe
    from paddle_tpu.observe import trace
    from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                          init_decoder_params)

    _, _, events, _ = _traced_serve(tiny_model, _prompts(3, seed=29))
    assert all(e["args"]["conv_tokens"] == 0
               for e in _named(events, "serve_prefill"))
    assert all(e["args"]["state_rows"] == 0
               for e in _named(events, "serve_decode_step"))
    gauge = lambda name: [s["value"] for s in
                          observe.REGISTRY.find(name).samples()]
    assert gauge("serve_state_bytes_per_sequence") == [0]
    assert gauge("serve_cache_bytes_per_token") == [2 * 1 * 32 * 4]
    cfg = DecoderConfig(vocab=64, dim=32, heads=2, layers=3, ffn=64,
                        max_context=64, pos_embed=False,
                        plan=("conv/gelu", "full+rope/gelu", "conv/gelu"))
    prompts = _prompts(3, seed=29)
    model = DecoderModel(init_decoder_params(cfg, seed=0), cfg)
    trace.enable(fences=False)
    try:
        _serve_all(model, prompts)
        events = trace.events()
    finally:
        trace.disable()
    fills = _named(events, "serve_prefill")
    assert sum(e["args"]["conv_tokens"] for e in fills) \
        == 2 * sum(map(len, prompts))
    steps = [e["args"] for e in _named(events, "serve_decode_step")]
    assert steps and all(
        a["state_rows"] == 2 * (a["batch"] + a.get("discarded", 0))
        for a in steps)
    assert gauge("serve_state_bytes_per_sequence") == [2 * 2 * 32 * 4]
    assert gauge("serve_cache_bytes_per_token") == [2 * 1 * 32 * 4]


def test_snapshot_has_its_span(tiny_model, tmp_path):
    from paddle_tpu.observe import trace
    from paddle_tpu.serving.server import InferenceServer

    trace.enable(fences=False)
    with InferenceServer(tiny_model, max_batch=2, n_pages=17, page_size=8,
                         snapshot_path=str(tmp_path / "pool.snap")) as srv:
        srv.generate([2, 3, 4], 3, timeout=120.0)
    events = trace.events()
    iters = {e["args"]["span_id"] for e in _named(events,
                                                  "serve_loop_iter")}
    snaps = _named(events, "serve_snapshot")
    assert snaps and all(e["args"]["parent_id"] in iters for e in snaps)


def test_tracing_off_records_nothing_and_counts_every_token(tiny_model):
    from paddle_tpu import observe
    from paddle_tpu.observe import trace
    from paddle_tpu.serving.server import InferenceServer

    assert not trace.enabled()
    assert trace.span("serve_decode_step", batch=1) is trace._NULL_SPAN
    with InferenceServer(tiny_model, max_batch=4, n_pages=33,
                         page_size=8) as srv:
        reqs = [srv.submit(p, 6) for p in _prompts(5, seed=24)]
        outs = [srv.result(r, timeout=120.0) for r in reqs]
    assert trace.events() == [] and not trace.enabled()
    assert all(r.t_admit is not None and r.trace_id for r in reqs)
    made = sum(len(t) for t in outs)
    assert srv.generated_tokens == made
    assert observe.counter("serve_tokens_generated", "").value() == made
    assert observe.gauge("serve_batch_size", "").value() >= 1


def test_server_thread_names(tiny_model):
    from paddle_tpu.serving.server import (DECODE_THREAD_NAME,
                                           InferenceServer)

    assert DECODE_THREAD_NAME.startswith("ptpu-serve-")
    with InferenceServer(tiny_model, max_batch=2, n_pages=17,
                         page_size=8) as srv:
        srv.generate([2, 3, 4], 3, timeout=120.0)
        names = [t.name for t in threading.enumerate()]
        assert DECODE_THREAD_NAME in names
    # __exit__ joined the loop; the leak guard in conftest watches the
    # prefix too, but assert locally for a direct failure message
    assert DECODE_THREAD_NAME not in [t.name for t in
                                      threading.enumerate()]


def test_http_front(tiny_model):
    from paddle_tpu.serving.server import InferenceServer

    with InferenceServer(tiny_model, max_batch=2, n_pages=17,
                         page_size=8) as srv:
        port = srv.start_http(0)
        body = json.dumps({"prompt": [2, 3, 4],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = json.loads(resp.read())
        assert 1 <= len(out["tokens"]) <= 4
        assert out["ttft_ms"] > 0 and out["latency_ms"] >= out["ttft_ms"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as resp:
            health = json.loads(resp.read())
        assert health["status"] == "ok"
        assert health["max_batch"] == 2


def test_decoder_artifact_roundtrip(tiny_model, tmp_path):
    """export_decoder → from_artifact: unquantized round-trip serves
    byte-identical tokens; the int8 PTQ artifact loads through the
    shared loader path and serves (its logits are approximations, so
    tokens are checked for validity, not equality)."""
    from paddle_tpu.serving.loader import ServedModel
    from paddle_tpu.serving.model import DecoderModel, export_decoder

    prompts = _prompts(3, seed=17)
    want = _serve_all(tiny_model, prompts)

    raw_dir = str(tmp_path / "raw")
    export_decoder({k: np.asarray(v) for k, v in
                    tiny_model.params.items()}, tiny_model.cfg, raw_dir,
                   quantize=None)
    assert _serve_all(DecoderModel.from_artifact(raw_dir),
                      prompts) == want

    q_dir = str(tmp_path / "int8")
    export_decoder({k: np.asarray(v) for k, v in
                    tiny_model.params.items()}, tiny_model.cfg, q_dir,
                   quantize="int8", dequant_dtype="float32")
    manifest = json.load(open(os.path.join(q_dir, "manifest.json")))
    assert manifest["kind"] == "decoder"
    assert any(e["quantized"] for e in manifest["weights"]["entries"])
    outs = _serve_all(DecoderModel.from_artifact(q_dir), prompts)
    assert all(all(0 <= t < tiny_model.cfg.vocab for t in toks)
               for toks in outs)
    # a decoder artifact must be refused by the module loader (and
    # point the caller at the right one)
    with pytest.raises(ValueError, match="decoder artifact"):
        ServedModel.load(q_dir)


def test_loader_batch_aware_call(tmp_path):
    """ServedModel.__call__(n_requests=N) books telemetry per REQUEST:
    serve_requests ticks by N and serve_infer_seconds receives N
    observations for the single launch."""
    import jax.numpy as jnp

    from paddle_tpu import observe
    from paddle_tpu.serving import ServedModel, export_inference_fn

    w = np.linspace(-1, 1, 12).reshape(4, 3).astype(np.float32)

    def fn(feed):
        return {"y": feed["x"] @ jnp.asarray(w)}

    d = str(tmp_path / "artifact")
    x = np.ones((2, 4), np.float32)
    export_inference_fn(fn, {"x": x}, d, fetch_names=["y"])
    m = ServedModel.load(d)

    c = observe.counter("serve_requests", "")
    h = observe.histogram("serve_infer_seconds", "")
    base_c, base_h = c.value(), h.retained_samples()
    out = m(n_requests=5, x=x)
    np.testing.assert_allclose(out["y"], x @ w, rtol=1e-6)
    assert c.value() == base_c + 5
    assert h.retained_samples() == base_h + 5
    assert observe.gauge("serve_batch_size", "").value() == 5
    with pytest.raises(ValueError):
        m(n_requests=0, x=x)


# -------------------------------------------------------------- chaos
def test_make_pool_recovery_paths(tmp_path):
    """The restart decision table: valid snapshot → restore + release
    orphans; torn snapshot → fresh pool; missing → fresh pool.  All
    three outcomes verify clean — a torn table is never served."""
    from paddle_tpu.serving.server import InferenceServer
    from paddle_tpu.testing.fault import corrupt_checkpoint

    path = str(tmp_path / "pool.json")
    pool = PagePool(n_pages=17, page_size=8)
    pool.alloc("dead-req", 24)       # orphan: its KV died with the proc
    pool.snapshot(path)

    recovered = InferenceServer._make_pool(17, 8, path)
    recovered.verify()
    assert recovered.owners() == []  # orphans released
    assert recovered.free_pages() == recovered.capacity

    corrupt_checkpoint(str(tmp_path), "pool.json", mode="bitflip")
    fresh = InferenceServer._make_pool(17, 8, path)
    fresh.verify()
    assert fresh.free_pages() == fresh.capacity

    missing = InferenceServer._make_pool(17, 8,
                                         str(tmp_path / "nope.json"))
    missing.verify()


@pytest.mark.chaos
@pytest.mark.slow
def test_sigkilled_server_restart_never_serves_torn_table(tmp_path):
    """The ISSUE 16 chaos case: SIGKILL a serving process mid-churn,
    restart a server on the same snapshot path — the recovered pool
    verifies, holds no orphaned tables, and serves new requests."""
    from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                          init_decoder_params)
    from paddle_tpu.serving.server import InferenceServer
    from paddle_tpu.testing.fault import ServeServerProcess

    path = str(tmp_path / "pool.json")
    child = ServeServerProcess(path, max_batch=4, n_pages=32,
                               page_size=8)
    with child:
        child.wait_served(4)         # snapshot went through real churn
        child.kill()                 # preemption: no flush hook runs
    assert os.path.exists(path)      # churn persisted at least once

    cfg = DecoderConfig(vocab=64, dim=32, heads=2, layers=1, ffn=64,
                        max_context=64, eos_id=1)
    model = DecoderModel(init_decoder_params(cfg, seed=0), cfg)
    with InferenceServer(model, max_batch=child.max_batch,
                         n_pages=child.n_pages,
                         page_size=child.page_size,
                         snapshot_path=path) as srv:
        srv.pool.verify()
        assert srv.pool.owners() == []
        toks = srv.generate([2, 3, 4, 5], 5, timeout=120.0)
        assert 1 <= len(toks) <= 5
