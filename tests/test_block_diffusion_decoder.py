"""Generation by diffusion over blocks through the served decoder
(``serving/model.py``'s block step, the server's block launches) against
the plain reference of the configuration that brought it
(``chipbench/reference/sdar-30b-a3b-serve.py``, which imports nothing of
the program) on seeded weights at the configuration's rehearsal sizes:
hidden 64, 4 heads over 2 K/V heads of 16, 16 experts with 8 a token by
softmax score, blocks of 4, 2 denoising steps and a commit a block.

Tolerance: in float32 storage the program and the reference compute the
same sums in another order (a kernel's online softmax, sorted expert
rows); 1e-4 of logits of size 1-4 is some ten times what is seen and far
under what a planted fault moves (``chipbench/tests/test_sdar_correct.py``)."""

import json
import os

import jax
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.ops import pallas_moe as moe
from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                      reveal_schedule)
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.utils import PaddleTpuError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "sdar-30b-a3b-serve"
TOLERANCE = 1e-4
B = 4


@pytest.fixture(scope="module")
def bench():
    """(sizes, reference, system, seeded weights) of the rehearsal."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness as H, weights as W

    with open(os.path.join(ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        sizes = json.load(f)["rehearsal"]["sizes"]
    ref = H.load_module("reference", CONFIG)
    system = H.load_module("systems", CONFIG)
    return sizes, ref, system, W.make(ref.param_spec(sizes), 20390001)


def _model(bench, **change):
    sizes, _, system, weights = bench
    cfg = system.decoder_config(sizes)._replace(storage="float32", **change)
    return DecoderModel(system.program_weights(sizes, weights), cfg)


def _reference_steps(bench, steps):
    """``steps``: [(committed ids, block start, the block as fed)] → the
    reference's logits of each [B, V]."""
    sizes, ref, _, weights = bench
    with jax.default_matmul_precision("highest"):
        got = ref.run(weights, sizes, [
            {"seq": seq, "starts": [at], "blocks": [block]}
            for seq, at, block in steps])
    return [np.asarray(s)[0] for _, s in got]


def _confidence(logits):
    """The log of each position's top probability."""
    return -np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1))


def _revealed(block, logits, n):
    """What a step that reveals ``n`` does to ``block`` by these logits:
    the ``n`` most confident masked positions take their argmax."""
    conf = _confidence(logits)
    order = np.argsort(-np.where(block < 0, conf, -np.inf), kind="stable")
    out = block.copy()
    out[order[:n]] = logits.argmax(-1)[order[:n]]
    return out


def test_reveal_schedule():
    assert [reveal_schedule(m, 2) for m in (4, 3, 2, 1)] \
        == [(2, 2), (2, 1), (1, 1), (1,)]
    assert reveal_schedule(4, 3) == (2, 1, 1)


def test_prefill_then_block_steps_through_pages_are_the_reference(bench):
    """Prompts of remainders 0-3 mod 4 are prefilled through their whole
    blocks (block-causal), then four block launches at width 6 (two rows
    idle) through page tables neither contiguous nor in order: the
    first steps, fed by the host; the second steps and a commit in one
    launch, queued behind the first before it is collected and fed from
    it on the device; commits beside a new block's first step; the next
    blocks.  Every row's logits are the reference's for the block as it
    stood, over the committed sequence before it, and what each row
    reveals is what those logits reveal."""
    sizes = bench[0]
    model = _model(bench)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, 255, n).tolist() for n in (8, 13, 18, 23)]
    width, slots, page = 6, 16, 4
    k, v = model.new_pools(1 + 4 * slots, page)
    tables = np.zeros((width, slots), np.int32)
    tables[:4] = 1 + rng.permutation(4 * slots).reshape(4, slots)
    whole = [len(p) // B * B for p in prompts]
    tokens = np.zeros((4, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :whole[i]] = p[:whole[i]]
    model.prefill(k, v, tokens, np.array(whole, np.int32), tables[:4])
    seqs = [p[:n] for p, n in zip(prompts, whole)]
    blocks = [np.array(p[n:] + [-1] * (B - len(p) + n), np.int32)
              for p, n in zip(prompts, whole)]
    starts = list(whole)
    # (reveal a row, src a row): -1 src is the host's block
    launches = [([2, 2, 1, 1], [-1] * 4),        # remainders 0, 1: 4, 3 masked
                ([2, 1, 1, 0], [0, 1, 2, 3]),    # row 3: masked 1, commits
                ([0, 0, 0, 2], [0, 1, 2, -1]),   # row 3: its next block
                ([2, 2, 2, 2], [-1, -1, -1, 3])]
    prev = None
    for n, (reveal, src) in enumerate(launches):
        for i in range(4):
            if n and reveal[i] and src[i] < 0:     # a new block starts
                seqs[i] = seqs[i] + blocks[i].tolist()
                starts[i] += B
                blocks[i] = np.full(B, -1, np.int32)
        fed = np.full((width, B), -1, np.int32)
        fed[:4] = [blocks[i] if src[i] < 0 else [-7] * B for i in range(4)]
        at = np.zeros(width, np.int32)
        at[:4] = starts
        active = np.arange(width) < 4
        launch = model.launch_block_step(
            k, v, fed, tables, at, active,
            np.array(reveal + [0, 0], np.int32), np.zeros(width, bool),
            prev, np.array(src + [-1, -1], np.int32))
        if prev is not None:
            model.collect_block_step(prev)
        want = _reference_steps(bench, [
            (seqs[i], starts[i], blocks[i]) for i in range(4)])
        after, conf, logits, counts = model.collect_block_step(launch)
        logits = np.asarray(logits)
        for i in range(4):
            assert np.abs(logits[i] - want[i]).max() < TOLERANCE, (n, i)
            assert np.abs(conf[i] - _confidence(want[i])).max() < TOLERANCE
            blocks[i] = _revealed(blocks[i], want[i], reveal[i])
            np.testing.assert_array_equal(after[i], blocks[i])
        assert (after[4:] == -1).all()            # idle rows: as fed
        # 16 tokens a layer (4 live rows of 4) over 16 experts, 8 each
        layers = model.cfg.layers
        assert 8 * layers <= counts["experts_hit"] <= 16 * layers
        prev = launch
    # the last launch revealed every row's block whole but row 3's
    assert [int((b < 0).sum()) for b in blocks] == [2, 2, 2, 0]
    assert model.attn_pairs([8, 13]) \
        == model.cfg.layers * (16 * 3 + 16 * 6 + 1 * 13)
    assert sizes["block_length"] == B


def _pools_equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x.array), np.asarray(y.array),
                                   rtol=0, atol=1e-5)


def test_a_fused_launch_is_a_commit_then_a_first_step(bench):
    """Four rows at width 6 through two sets of pools, prefilled alike.
    Rows 0-2 take their first block's two steps, row 3 one launch late;
    row 2's block is its last and takes no commit.  Then, split: a
    launch of commits (reveal 0) for rows 0 and 1 beside row 3's second
    step, and a launch of their next blocks' first steps; fused: one
    launch in which rows 0 and 1 commit and step, beside row 3's second
    step, row 0's finished block fed from the launch it is queued
    behind and row 1's from the host.  Both then take the second steps,
    fed on the device.  The ids, the confidences and the pages agree."""
    model = _model(bench)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 255, n).tolist() for n in (8, 12, 16, 8)]
    width, slots, page = 6, 12, 4
    tables = np.zeros((width, slots), np.int32)
    tables[:4] = 1 + rng.permutation(4 * slots).reshape(4, slots)
    starts = np.zeros(width, np.int32)
    starts[:4] = [len(p) for p in prompts]
    tokens = np.zeros((4, 16), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    sets = [model.new_pools(1 + 4 * slots, page) for _ in range(2)]
    for pools in sets:
        model.prefill(*pools, tokens, starts[:4], tables[:4])
    masked = np.full((width, B), -1, np.int32)
    rows = lambda *r: np.isin(np.arange(width), r)

    def step(pools, live, reveal, src=(), prev=None, fed=masked, at=starts,
             commit=rows()):
        pad = lambda a, v: np.array(list(a) + [v] * (width - len(a)),
                                    np.int32)
        return model.launch_block_step(
            *pools, fed, tables, at, live, pad(reveal, 0), commit, prev,
            pad(src, -1))

    seconds = []
    for pools in sets:
        first = step(pools, rows(0, 1, 2), [2, 2, 2])
        seconds.append(step(pools, rows(0, 1, 2, 3), [2, 2, 2, 2],
                            [0, 1, 2, -1], first))
        model.collect_block_step(first)
        done = model.collect_block_step(seconds[-1])[0]
    assert (done[:3] >= 0).all() and (done[3] >= 0).sum() == 2
    fed = masked.copy()
    fed[1] = done[1]                    # row 1's finished block: the host's
    # split
    commits = step(sets[0], rows(0, 1, 3), [0, 0, 0, 2], [0, -1, -1, 3],
                   seconds[0], fed)
    split = step(sets[0], rows(0, 1), [2, 2], at=starts + B)
    after_c, conf_c = model.collect_block_step(commits)[:2]
    after_s, conf_s = model.collect_block_step(split)[:2]
    again_a = step(sets[0], rows(0, 1), [2, 2], [0, 1], split,
                   at=starts + B)
    # fused
    fused = step(sets[1], rows(0, 1, 3), [2, 2, 0, 2], [0, -1, -1, 3],
                 seconds[1], fed, commit=rows(0, 1))
    after_f, conf_f = model.collect_block_step(fused)[:2]
    again_b = step(sets[1], rows(0, 1), [2, 2], [0, 1], fused,
                   at=starts + B)
    np.testing.assert_array_equal(after_c[:2], done[:2])   # nothing revealed
    np.testing.assert_array_equal(after_f[:2], after_s[:2])
    np.testing.assert_allclose(conf_f[:2], conf_s[:2], rtol=0, atol=1e-5)
    np.testing.assert_array_equal(after_f[3], after_c[3])
    np.testing.assert_allclose(conf_f[3], conf_c[3], rtol=0, atol=1e-5)
    assert ((after_f[:2] >= 0).sum(axis=1) == 2).all()
    assert (after_f[3] >= 0).all()
    (after_a, conf_a), (after_b, conf_b) = (
        model.collect_block_step(h)[:2] for h in (again_a, again_b))
    np.testing.assert_array_equal(after_b[:2], after_a[:2])
    np.testing.assert_allclose(conf_b[:2], conf_a[:2], rtol=0, atol=1e-5)
    assert (after_b[:2] >= 0).all()
    _pools_equal(*sets)


def _served(server, prompts, budgets):
    rs = [server.submit(p, n) for p, n in zip(prompts, budgets)]
    return [(server.result(r, timeout=600), r) for r in rs]


def test_a_server_emits_what_the_reference_reveals(bench):
    """Requests of remainders 0-3 and budgets that end mid-block through
    the server (width 4, so rows join and leave a launch behind another)
    : every block each request logged, replayed step by step through the
    reference, revealed the positions the reference is most confident of
    with the ids it puts first, in the configured schedule, and every
    block but the last was committed; the tokens are the blocks' in
    order, the budget's worth.  With an id of the served tokens made the
    EOS, the same request ends at it, inside a finished block."""
    import sys
    sys.path.insert(0, ROOT)
    from chipbench import harness as H

    driver = H.load_module("drivers", "serve_closed_bd")
    sizes = bench[0]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 255, n).tolist() for n in (8, 13, 18, 23, 9)]
    budgets = [6, 9, 5, 13, 2]
    flat = observe.REGISTRY.flat(kinds=("counter",))
    before = {kind: flat.get(
        f'serve_block_passes_total{{kind="{kind}"}}', 0.0)
        for kind in ("denoise", "commit")}
    with InferenceServer(_model(bench, eos_id=0), max_batch=4, n_pages=64,
                         page_size=4) as server:
        served = _served(server, prompts, budgets)
        assert server._positions(23, 13) == 36
    steps = 0
    for (tokens, r), prompt, budget in zip(served, prompts, budgets):
        assert len(tokens) == budget
        plan = driver.replay_plan({"prompt": prompt, "blocks": r.blocks},
                                  sizes)
        assert plan["off_schedule"] == 0
        ids = [t for blk in r.blocks for t in blk["states"][-1]]
        assert tokens == ids[len(prompt) % B:][:budget]
        want = _reference_steps(bench, [
            (plan["seq"][:at], at, state)
            for at, state in zip(plan["starts"], plan["blocks"])])
        for s, logits in enumerate(want):
            n = int(plan["revealed"][s].sum())
            np.testing.assert_array_equal(
                _revealed(plan["blocks"][s], logits, n),
                np.where(plan["blocks"][s] < 0,
                         np.where(plan["revealed"][s], plan["served"][s], -1),
                         plan["blocks"][s]))
        steps += len(want)
    flat = observe.REGISTRY.flat(kinds=("counter",))
    commits = sum(len(r.blocks) - 1 for _, r in served)
    assert flat['serve_block_passes_total{kind="denoise"}'] \
        - before["denoise"] == steps
    assert flat['serve_block_passes_total{kind="commit"}'] \
        - before["commit"] == commits
    # an EOS inside a finished block: the request ends at it
    tokens, r = served[3]
    eos = tokens[5]
    cut = tokens.index(eos) + 1
    with InferenceServer(_model(bench, eos_id=eos), max_batch=4, n_pages=64,
                         page_size=4) as server:
        (again, r2), = _served(server, prompts[3:4], budgets[3:4])
    assert again == tokens[:cut] and cut < len(tokens)
    seen = lambda rec: [b["states"] for b in rec.blocks]
    assert seen(r2) == seen(r)[:len(r2.blocks)]


def test_a_commit_rides_in_the_next_blocks_first_step(bench, monkeypatch):
    """Through the server: every row a block launch computes reveals at
    least one position, so no launch holds a commit alone; every block
    but a request's last is committed, each commit in a row of its own
    launch's denoising, and a block of two steps takes two rows: the
    rows computed per token emitted (``row_passes_per_token``) come to
    0.5 over whole blocks."""
    launched = []
    real = InferenceServer._launch_blocks

    def launch(self, lch):
        launched.append([(p[1], p[4]) for p in lch.passes])
        return real(self, lch)

    monkeypatch.setattr(InferenceServer, "_launch_blocks", launch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 255, n).tolist() for n in (8, 12, 16, 4, 20)]
    budgets = [32, 24, 40, 16, 28]
    flat = lambda: observe.REGISTRY.flat(kinds=("counter",))
    key = 'serve_block_passes_total{kind="%s"}'
    before = {k: flat().get(key % k, 0.0) for k in ("denoise", "commit")}
    with InferenceServer(_model(bench, eos_id=1), max_batch=4, n_pages=96,
                         page_size=4) as server:
        served = _served(server, prompts, budgets)
        generated = server.generated_tokens
    assert [len(t) for t, _ in served] == budgets
    assert all(r.blocks[-1]["committed"] is False
               and all(b["committed"] for b in r.blocks[:-1])
               for _, r in served)
    passes = [p for lch in launched for p in lch]
    assert min(reveal for reveal, _ in passes) >= 1
    commits = sum(commit for _, commit in passes)
    assert commits == sum(len(r.blocks) - 1 for _, r in served)
    # commits beside rows that commit nothing
    assert any(any(c for _, c in lch) and not all(c for _, c in lch)
               for lch in launched)
    after = {k: flat()[key % k] - before[k] for k in before}
    assert after == {"denoise": len(passes), "commit": commits}
    assert generated == sum(budgets)
    assert len(passes) / generated <= 0.52
    assert len(passes) == 2 * sum(len(r.blocks) for _, r in served)
    # an EOS in the third block of a request of nine: the row that
    # carries its commit and the fourth block's first step was queued
    # before the host saw it, and is discarded
    tokens, r = served[2]
    eos = tokens[9]
    cut = tokens.index(eos) + 1
    dropped = flat().get("serve_rows_discarded_total", 0.0)
    launched.clear()
    with InferenceServer(_model(bench, eos_id=eos), max_batch=4,
                         n_pages=96, page_size=4) as server:
        (again, r2), = _served(server, prompts[2:3], budgets[2:3])
    assert again == tokens[:cut] and cut <= 12
    assert [b["states"] for b in r2.blocks] \
        == [b["states"] for b in r.blocks[:len(r2.blocks)]]
    assert not r2.blocks[-1]["committed"]
    assert flat()["serve_rows_discarded_total"] - dropped == 1
    assert launched[-1] == [(2, True)]


def test_softmax_routing_is_a_plain_top_k(bench):
    """``route(score="softmax")``: the top 8 of a softmax over all 16
    experts, their shares renormalised to 1; the reference's routing
    weights are the same numbers; a selection bias of zeros chooses
    nothing."""
    sizes, ref, _, _ = bench
    rng = np.random.default_rng(1)
    x = rng.standard_normal((12, 64)).astype(np.float32)
    w = (rng.standard_normal((64, 16)) / 8).astype(np.float32)
    experts, weights = moe.route(x, w, np.zeros(16, np.float32), 8, 1.0,
                                 score="softmax")
    z = x.astype(np.float64) @ w
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, axis=-1, kind="stable")[:, :8]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(top, -1))
    picked = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(np.asarray(weights),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(ref.route_weights(x, w, 8))
    np.testing.assert_allclose(
        np.take_along_axis(dense, np.asarray(experts), -1),
        np.asarray(weights), rtol=1e-5)
    # sigmoid, the default, chooses otherwise
    _, sig = moe.route(x, w, np.zeros(16, np.float32), 8, 1.0)
    assert np.abs(np.asarray(sig) - np.asarray(weights)).max() > 1e-3
    with pytest.raises(PaddleTpuError, match="router score"):
        moe.route(x, w, np.zeros(16, np.float32), 8, 1.0, score="relu")


@pytest.mark.parametrize("change,why", [
    (dict(block_length=3), "power of two"),
    (dict(denoise_steps=0), "denoise_steps"),
    (dict(mask_id=256), "mask_id"),
    (dict(plan=("window+rope/routed",) * 6, window=8), "full attention"),
    (dict(route_score="relu"), "route_score")])
def test_a_block_model_the_decoder_cannot_run_is_refused(bench, change,
                                                         why):
    sizes, _, system, _ = bench
    cfg = system.decoder_config(sizes)._replace(**change)
    with pytest.raises(PaddleTpuError, match=why):
        DecoderModel({}, cfg)


def test_a_token_model_is_untouched_by_the_block_fields():
    """The fields' defaults are generation one token a row a step and
    sigmoid routing: a config that names none of them is the one it
    was."""
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=1, ffn=64)
    assert (cfg.block_length, cfg.denoise_steps, cfg.route_score) \
        == (0, 0, "sigmoid")
