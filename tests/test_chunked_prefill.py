"""The server's chunked prefill (``serving/server.py``, ``serving/
model.py``): where every layer of a plan can continue a prefill from
what a sequence holds (``model.continues``), a prompt longer than a
chunk is prefilled a chunk a launch, and every decoding row rides in
each chunk launch instead of waiting for a decode launch of its own.
At toy sizes, a chunk of 8: the rows a launch carries, the tokens, the
plans that keep today's launches, and the one program a chunk takes."""

import numpy as np
import pytest

from paddle_tpu.observe import trace as ptrace
from paddle_tpu.serving import model as M
from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                      init_decoder_params)
from paddle_tpu.serving.server import InferenceServer

#: mamba layers beside a full attention layer, as Jamba's plan has them
SMALL = DecoderConfig(
    vocab=64, dim=32, heads=4, kv_heads=1, layers=4, ffn=48, max_context=64,
    pos_embed=False, conv_taps=4, ssm_inner=64, ssm_state=16, dt_rank=4,
    tied_head=True,
    plan=("mamba/swiglu", "full/swiglu", "mamba/swiglu", "mamba/gelu"))
LENGTHS = (5, 27, 19, 3, 33, 12, 21, 8)
BUDGETS = (14, 9, 12, 16, 7, 11, 13, 10)


def _model(chunk, cfg=SMALL):
    model = DecoderModel(init_decoder_params(cfg, seed=4), cfg)
    model.chunk = chunk
    return model


def _prompts(seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 64, n).tolist() for n in LENGTHS]


def _serve(model, prompts, budgets=BUDGETS, width=4):
    """The requests through a server; → (their tokens, the launches in
    the order they were queued: (kind, ids of the rows, (id of the
    request whose prompt it continues, start, positions) or None))."""
    log = []
    with InferenceServer(model, max_batch=width, n_pages=120,
                         page_size=4) as srv:
        launch = srv._launch

        def spy(item):
            log.append((item.kind, [r.id for r in item.rows],
                        None if item.chunk is None else
                        (item.chunk[0].id, *item.chunk[1:])))
            launch(item)

        srv._launch = spy
        reqs = [srv.submit(p, n) for p, n in zip(prompts, budgets)]
        out = [srv.result(r, timeout=300.0) for r in reqs]
        ids = [r.id for r in reqs]
    return out, log, ids


@pytest.fixture(scope="module")
def served():
    prompts = _prompts()
    whole = _serve(_model(0), prompts)
    ptrace.enable(fences=False)
    try:
        chunked = _serve(_model(8), prompts)
        spans = ptrace.events()
    finally:
        ptrace.disable()
    return prompts, whole, chunked, spans


def test_the_chunked_server_serves_the_unchunked_servers_tokens(served):
    prompts, (whole, log0, _), (chunked, log, _), _ = served
    assert chunked == whole
    assert all(kind in ("prefill", "decode") for kind, *_ in log0)
    # every prompt past a chunk went in chunks of 8, in order, and those
    # of a chunk or less whole
    kinds = {kind for kind, *_ in log}
    assert kinds == {"prefill", "decode", "chunk"}
    ids = served[2][2]
    for rid, prompt in zip(ids, prompts):
        parts = [c[1:] for kind, _, c in log if kind == "chunk"
                 and c[0] == rid]
        whole_launches = [1 for kind, rows, _ in log
                          if kind == "prefill" and rid in rows]
        if len(prompt) > 8:
            assert parts == [(s, min(8, len(prompt) - s))
                             for s in range(0, len(prompt), 8)]
            assert not whole_launches
        else:
            assert not parts and whole_launches == [1]


def test_every_chunk_launch_carries_every_decoding_row(served):
    """From the launch after the one that yields a request's first token
    is queued behind it, the request rides in every chunk and decode
    launch until it ends (a prompt of a chunk or less is still prefilled
    whole, and the rows wait out that launch, as before): chunk launches
    carry it as decode launches do, and no decode launch runs while a
    prompt is in chunks."""
    prompts, _, (tokens, log, ids), _ = served
    steps = [i for i, (kind, *_) in enumerate(log) if kind != "prefill"]
    for rid, prompt, out in zip(ids, prompts, tokens):
        if len(prompt) > 8:
            first = max(i for i, (kind, _, c) in enumerate(log)
                        if kind == "chunk" and c[0] == rid)
        else:
            first = next(i for i, (kind, rows, _) in enumerate(log)
                         if kind == "prefill" and rid in rows)
        rides = [k for k, i in enumerate(steps) if rid in log[i][1]]
        assert len(rides) >= len(out) - 1
        assert rides == list(range(rides[0], rides[0] + len(rides)))
        assert first < steps[rides[0]] <= min(
            i for i in steps if i >= first + 2)
    chunking = [i for i, (kind, *_) in enumerate(log) if kind == "chunk"]
    for rid in ids:
        mine = [i for i in chunking if log[i][2][0] == rid]
        if mine:
            assert all(log[i][0] != "decode"
                       for i in range(mine[0], mine[-1]))
    assert sum(len(log[i][1]) for i in chunking) > len(chunking)


def test_a_chunk_launch_is_a_prefill_span_and_its_rows_a_decode_step(
        served):
    """A chunk launch states its chunk on ``serve_prefill`` (its real
    positions, those times the mamba layers, the pairs its queries see,
    ``riding``, ``chunk_start``) and, where rows ride, their step on a
    ``serve_decode_step`` inside it."""
    spans = served[3]
    by_id = {s["args"]["span_id"]: s for s in spans
             if "span_id" in s.get("args", {})}
    chunks = [s["args"] for s in spans if s["name"] == "serve_prefill"
              and "chunk_start" in s["args"]]
    assert chunks
    for a in chunks:
        n, start = a["prompt_tokens"], a["chunk_start"]
        assert a["scan_tokens"] == 3 * n and a["t_pad"] == 8
        assert a["attn_pairs"] == n * start + n * (n + 1) // 2
    riding = [s for s in spans if s["name"] == "serve_decode_step"
              and by_id.get(s["args"].get("parent_id"), {}).get("name")
              == "serve_prefill"]
    assert len(riding) == sum(a["riding"] > 0 for a in chunks)
    for s in riding:
        parent = by_id[s["args"]["parent_id"]]["args"]
        assert s["args"]["batch"] + s["args"].get("discarded", 0) \
            == parent["riding"]
        assert s["args"]["state_rows"] == 3 * parent["riding"]


CANNOT = {
    "window": dict(plan=("full/swiglu", "window/swiglu"), window=8),
    "latent": dict(plan=("latent+rope/swiglu",) * 2, q_rank=8, kv_rank=8,
                   nope_dim=8, rope_dim=8, v_dim=8),
    "conv": dict(plan=("conv/swiglu", "full/swiglu")),
    "block": dict(plan=("full/swiglu",) * 2, block_length=4,
                  denoise_steps=2, mask_id=3),
    "routed": dict(plan=("mamba/swiglu", "full/routed"), experts=4, top_k=2,
                   expert_ffn=16),
}


@pytest.mark.parametrize("kind", sorted(CANNOT))
def test_a_plan_that_cannot_continue_takes_whole_prompts(kind):
    """A window, a latent row, a conv layer, blocks or routed experts:
    the plan is not taught to continue a prefill, its model's chunk is 0
    and the server plans every prompt as one launch, as before."""
    cfg = SMALL._replace(layers=2, **CANNOT[kind])
    assert not M.continues(cfg)
    model = DecoderModel(init_decoder_params(cfg, seed=1), cfg)
    assert model.chunk == 0
    if kind in ("window", "conv"):
        prompts = _prompts()[:4]
        _, log, _ = _serve(model, prompts, BUDGETS[:4])
        assert {kind for kind, *_ in log} == {"prefill", "decode"}


def test_a_plan_that_continues_chunks_only_past_a_chunk():
    """Full and mamba layers with dense feed-forwards continue (the
    default plan too); prompts of a chunk or less take today's
    launches."""
    assert M.continues(SMALL) and DecoderModel(
        init_decoder_params(SMALL, seed=4), SMALL).chunk == M.CHUNK == 512
    dense = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=48)
    assert M.continues(dense)
    prompts = _prompts()
    short = [p[:8] for p in prompts]
    _, log, _ = _serve(_model(8), short)
    assert {kind for kind, *_ in log} == {"prefill", "decode"}


def test_prefill_of_any_chunk_multiple_is_one_program():
    """``DecoderModel.prefill`` at four widths past a chunk runs chunk
    launches of one program, at the server's width, the one the
    server's chunk launches then find compiled (the benchmark's warm-up
    calls it so: a K and a V pool, the states supplied)."""
    cfg = SMALL._replace(vocab=65)            # programs of its own
    model = _model(8, cfg)
    chunk, prefill = M._jitted_chunk(cfg), M._jitted_steps(cfg)[0]
    with InferenceServer(model, max_batch=4, n_pages=120,
                         page_size=4) as srv:
        k, v = srv._k_pool, srv._v_pool
        for t in (16, 24, 32, 56):
            nxt, logits, *_ = model.prefill(
                k, v, np.full((1, t), 3, np.int32), np.array([t], np.int32),
                np.zeros((1, srv.max_pages), np.int32))
            assert nxt.shape == (1,) and logits.shape == (1, 65)
        assert chunk._cache_size() == 1 and prefill._cache_size() == 0
        reqs = [srv.submit(p, 6) for p in _prompts()[:5]]
        for r in reqs:
            srv.result(r, timeout=300.0)
    assert chunk._cache_size() == 1
