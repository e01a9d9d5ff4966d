"""The decoder with ``conv`` layers in its plan (``serving/model.py``):
gated short-convolution layers that keep a fixed-size state a sequence
beside the K/V pages of the attention layers, against the plain
reference of the configuration that brought them
(``chipbench/reference/lfm2-24b-a2b-serve.py``, which imports nothing of
the program and keeps no state) on seeded weights at the configuration's
rehearsal sizes: hidden 64, 4 heads over 2 K/V heads of 16, 3 taps, page
4, top-4 of 16 experts, the configuration's own nine-layer plan; and
through the server, where a sequence's state has to follow its request
while rows move between launches."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe import trace as ptrace
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import pallas_moe as pm
from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                      export_decoder, init_decoder_params,
                                      layer_plan)
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.utils import PaddleTpuError

from test_routed_decoder import _rehearsal

CONFIG = "lfm2-24b-a2b-serve"


@pytest.fixture(scope="module")
def bench():
    return _rehearsal(CONFIG)


def _model(bench, storage="float32", **replace):
    sizes, _, system, weights = bench
    cfg = system.decoder_config(sizes)._replace(storage=storage, **replace)
    return DecoderModel({system.leaf_name(k): v
                         for k, v in weights.items()}, cfg)


@pytest.fixture(scope="module")
def decoder(bench):
    return _model(bench)


def _reference_logits(bench, seqs):
    sizes, ref, _, weights = bench
    tokens = np.zeros((len(seqs), 128), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    at = np.array([[len(s) - 1] for s in seqs])
    with jax.default_matmul_precision("highest"):
        return ref.logits_at(weights, sizes, tokens, at)[:, 0]


def _serve(model, prompts, steps, page=4, width=5, between=None,
           slotted=False):
    """Prefill the prompts as one batch, then ``steps`` decode steps at
    a fixed width with an idle slot, through page tables that are
    neither contiguous nor in order (so a sequence's state lies at no
    place its row index would give).  ``between(pools)`` runs after the
    prefill.  ``slotted``: the states in ``width + 1`` slots of their
    own, the rows' in no order, the idle rows' the last, handed to every
    step; else one a page, at each row's first page.  → [(sequences so
    far, the program's logits for each)]."""
    b = len(prompts)
    slots = model.cfg.max_context // page
    pools = model.new_pools(1 + b * slots, page,
                            width + 1 if slotted else None)
    given = {}
    if slotted:
        given["slots"] = np.array((3, 0, 1)[:b], np.int32)
    tables = 1 + np.random.default_rng(5).permutation(b * slots) \
        .reshape(b, slots).astype(np.int32)         # page 0: scratch
    tokens = np.zeros((b, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    nxt, logits, *_ = model.prefill(
        *pools, tokens, np.array([len(p) for p in prompts], np.int32),
        tables, **given)
    if between is not None:
        between(pools)
    seqs = [list(p) for p in prompts]
    out = [([list(s) for s in seqs], np.asarray(logits))]
    if slotted:
        given["slots"] = np.concatenate(
            [given["slots"], np.full((width - b,), width, np.int32)])
    for _ in range(steps):
        for i in range(b):
            seqs[i].append(int(nxt[i]))
        fed = np.zeros((width,), np.int32)
        lengths = np.ones((width,), np.int32)
        active = np.zeros((width,), bool)
        tab = np.zeros((width, slots), np.int32)
        fed[:b], active[:b], tab[:b] = nxt[:b], True, tables
        lengths[:b] = [len(s) for s in seqs]
        nxt, logits, *_, counts = model.decode(*pools, fed, tab, lengths,
                                               active, **given)
        assert 8 * 4 <= counts["experts_hit"] <= 8 * min(16, 4 * b)
        out.append(([list(s) for s in seqs], np.asarray(logits)[:b]))
    return out


# Tolerances, and why.  In float32 storage the program and the reference
# compute the same function in another order: the program keeps the
# newest two z of a sequence and forms a decode step's sum from them
# where the reference shifts the whole sequence; the packed kernel's
# online softmax; rows sorted by expert.  Logits are of size 1-4 and a
# row reads 1e-6 to 3e-6: 1e-4 is thirty times the worst seen and a
# ten-thousandth of what a planted fault moves (below), and every row of
# every step is held to it.  In bfloat16 storage every matrix product
# rounds its operands to 8 bits of mantissa, the K/V rows and the conv
# state are kept so, and a router score that rounds across a near-tie of
# the top 4 of 16 picks another expert; so bfloat16 holds the median row
# to 0.15 and, run through the float32 comparison, fails it: the strict
# comparison tells the stated precision from a lower one.
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.15}
PROMPTS = (21, 5, 30)


def _gaps(bench, model, steps=5, lengths=PROMPTS, **kw):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 256, n).tolist() for n in lengths]
    return np.array([np.abs(_reference_logits(bench, seqs) - logits).max(-1)
                     for seqs, logits in _serve(model, prompts, steps, **kw)])


@pytest.mark.parametrize("slotted", [False, True])
def test_prefill_then_decode_through_the_state_is_the_reference(
        bench, decoder, slotted):
    """Prefill (step 0: every position's sum in one pass, the state
    written) and five decode steps (the state read, rolled and written
    back in place; K/V through page tables on two of nine layers)
    against the reference's one full forward of the same sequence: with
    the state at each row's first page, as the server keeps a conv
    plan's, and in slots of its own, as it keeps a mamba plan's."""
    gaps = _gaps(bench, decoder, slotted=slotted)
    assert gaps.shape == (6, 3)
    assert gaps.max() < TOLERANCE["float32"], gaps


@pytest.mark.parametrize("n", [1, 2, 3])
def test_decode_from_a_prompt_shorter_than_the_taps(bench, decoder, n):
    """A prompt of one token leaves two rows of state of which one is
    zeros (z_{-1}), a prompt of two fills both: the sums of the first
    decode steps reach back to the sequence's own start and no further."""
    held = {}
    gaps = _gaps(bench, decoder, steps=3, lengths=(n, 17),
                 between=lambda pools: held.update(
                     state=np.asarray(pools[-1].array)))
    assert gaps.max() < TOLERANCE["float32"], gaps
    state = held["state"]                            # [7, places, 2, 64]
    assert state.shape[0] == 7 and state.shape[2:] == (2, 64)
    written = np.flatnonzero(np.abs(state).sum(axis=(0, 2, 3)))
    assert len(written) == 2                         # one place a row
    short = min(written, key=lambda p: np.count_nonzero(state[:, p, 0]))
    assert bool(np.abs(state[:, short, 0]).sum()) == (n >= 2)
    assert np.abs(state[:, short, 1]).min(axis=-1).max() > 0


def test_bfloat16_storage_is_near_and_fails_the_float32_comparison(bench):
    model = _model(bench, "bfloat16")
    assert model.new_pools(9, 4)[-1].dtype == jnp.bfloat16
    gaps = _gaps(bench, model)
    assert np.median(gaps) < TOLERANCE["bfloat16"], gaps
    assert gaps.max() > 100 * TOLERANCE["float32"], gaps


def _taps_reversed(model):
    for name in [n for n in model.params if n.endswith(".conv")]:
        model.params[name] = model.params[name][:, ::-1]


def _thirds_swapped(model):
    """``in_proj``'s thirds read as C, B, u."""
    for name in [n for n in model.params if n.endswith(".in_proj")]:
        b, c, u = jnp.split(model.params[name], 3, axis=1)
        model.params[name] = jnp.concatenate([c, b, u], axis=1)


def _state_forgotten(pools):
    pools[-1].array = jnp.zeros_like(pools[-1].array)


@pytest.mark.parametrize("fault", ["taps_reversed", "thirds_swapped",
                                   "state_from_zeros"])
def test_a_planted_fault_is_seen(bench, fault):
    """Each reads thousands of times the float32 tolerance: the taps in
    the other order (drawn N(0, 1/3), each carries a third of the sum),
    the gate taken for an input of the convolution, and decode steps
    that start from a zeroed state after the prefill (then the step
    after the prompt misses two of its three terms)."""
    model, between = _model(bench), None
    if fault == "taps_reversed":
        _taps_reversed(model)
    elif fault == "thirds_swapped":
        _thirds_swapped(model)
    else:
        between = _state_forgotten
    gaps = _gaps(bench, model, steps=2, between=between)
    assert gaps[1].min() > 0.1, gaps
    if between is not None:
        assert gaps[0].max() < TOLERANCE["float32"]     # prefill is sound


def test_a_decode_step_is_the_prefill_at_the_same_position(decoder):
    """Position n of a sequence computed twice over one set of weights:
    as the last token of a prefill of n + 1 tokens, and as a decode step
    behind a prefill of n; the state each leaves is the same too."""
    page, slots = 4, decoder.cfg.max_context // 4
    seq = np.random.default_rng(8).integers(2, 256, 27).tolist()
    table = np.arange(1, 1 + slots, dtype=np.int32)[None, :]
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :27] = seq
    pools = decoder.new_pools(1 + slots, page)
    _, whole, *_ = decoder.prefill(*pools, tokens, np.array([27]), table)
    state_whole = np.asarray(pools[-1].array)
    pools = decoder.new_pools(1 + slots, page)
    decoder.prefill(*pools, tokens, np.array([26]), table)
    _, step, *_ = decoder.decode(*pools, np.array([seq[26]], np.int32),
                                 table, np.array([27]), np.array([True]))
    assert np.abs(np.asarray(whole) - np.asarray(step)).max() < 2e-5
    assert np.abs(state_whole - np.asarray(pools[-1].array)).max() < 2e-5
    assert np.abs(state_whole[:, 1]).min() > 0       # at its first page


def test_the_pools_hold_the_layers_that_attend_and_the_state_the_others(
        decoder):
    """Two of nine layers have K and V: the pools are two layers deep,
    the counts that size a step's reads count those two, and the seven
    conv layers' state is one more cache, a place a page.  A caller that
    names K and V alone (the benchmark's warm-up) is given a state for
    the call; the steps donate what they are handed."""
    assert decoder.n_pools == 3 and decoder.n_kv_pools == 2
    assert decoder.conv_layers == 7
    k, v, state = decoder.new_pools(9, 4)
    assert k.shape == v.shape == (2, 9, 4, 2 * 16)
    assert state.shape == (7, 9, 2, 64)
    assert decoder.cache_bytes_per_token() == 2 * 2 * 32 * 4
    assert decoder.state_bytes_per_sequence() == 7 * 2 * 64 * 4
    assert decoder.attended_tokens([5, 7]) == 2 * 12
    assert decoder.attn_pairs([5]) == 2 * 15
    assert decoder.pages_behind_window([40], 4) == 0
    table = np.arange(1, 9, dtype=np.int32)[None, :]
    held = [p.array for p in (k, v, state)]
    out = decoder.prefill(k, v, state, np.full((1, 16), 3, np.int32),
                          np.array([7], np.int32), table)
    assert len(out) == 5 and out[2:] == (k, v, state)
    assert all(a.is_deleted() for a in held)
    assert not np.asarray(k.array)[:, 3:].any()      # 7 tokens: 2 pages
    assert not np.asarray(state.array)[:, 2:].any()  # one place: page 1
    assert not np.asarray(state.array)[:, 0].any()
    out = decoder.decode(k, v, state, np.array([5], np.int32), table,
                         np.array([8], np.int32), np.array([True]))
    assert len(out) == 6 and out[2:5] == (k, v, state)
    # K and V alone: the same programs, a state of the call's own
    before = np.asarray(state.array)
    out = decoder.prefill(k, v, np.full((1, 16), 3, np.int32),
                          np.array([7], np.int32), table)
    assert len(out) == 5 and out[2:4] == (k, v)
    assert out[4].shape == state.shape and out[4] is not state
    out = decoder.decode(k, v, np.array([5], np.int32), table,
                         np.array([8], np.int32), np.array([False]))
    assert len(out) == 6 and out[4].shape == state.shape
    assert np.array_equal(np.asarray(state.array), before)
    with pytest.raises(PaddleTpuError, match="pools handed"):
        decoder.prefill(k, np.full((1, 16), 3, np.int32),
                        np.array([7], np.int32), table)


def test_a_packed_prefill_of_two_prompts_is_the_two_alone(decoder):
    """Two prompts in one launch: no sum reaches across the boundary
    into the row before, and each sequence's state lands in its own
    place."""
    page, slots = 4, decoder.cfg.max_context // 4
    rng = np.random.default_rng(12)
    prompts = [rng.integers(2, 256, n).tolist() for n in (13, 2)]
    tables = np.arange(1, 1 + 2 * slots, dtype=np.int32).reshape(2, slots)
    tokens = np.zeros((2, 16), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    pools = decoder.new_pools(1 + 2 * slots, page)
    nxt, logits, *_ = decoder.prefill(*pools, tokens, np.array([13, 2]),
                                      tables)
    both = np.asarray(pools[-1].array)
    for i, p in enumerate(prompts):
        alone = decoder.new_pools(1 + 2 * slots, page)
        n1, l1, *_ = decoder.prefill(*alone, tokens[i:i + 1],
                                     np.array([len(p)]), tables[i:i + 1])
        # (a product of 32 rows and one of 16 round differently; a sum
        # that reached across the boundary would read about 1)
        assert int(n1[0]) == int(nxt[i])
        assert np.abs(np.asarray(l1)[0] - np.asarray(logits)[i]).max() < 2e-5
        place = tables[i, 0]
        assert np.abs(np.asarray(alone[-1].array)[:, place]
                      - both[:, place]).max() < 2e-5
        assert np.abs(both[:, place, 1]).min() > 0


@pytest.mark.parametrize("plan,taps,why", [
    (("conv+rope/gelu", "full/gelu"), 3, "conv alone"),
    (("conv+gate/gelu", "full/gelu"), 3, "conv alone"),
    (("conv/gelu", "latent+rope/gelu"), 3, "latent in every layer"),
    (("conv/gelu", "full/gelu"), 1, "conv_taps >= 2")])
def test_a_conv_plan_the_decoder_cannot_run_is_refused(plan, taps, why):
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                        plan=plan, conv_taps=taps, q_rank=8, kv_rank=8,
                        nope_dim=8, rope_dim=8, v_dim=8)
    with pytest.raises(PaddleTpuError, match=why):
        layer_plan(cfg)


# ------------------------------------------------------ through the server
SMALL = DecoderConfig(
    vocab=64, dim=64, heads=4, kv_heads=2, layers=4, ffn=96, max_context=64,
    pos_embed=False, experts=8, top_k=2, expert_ffn=32, norm_eps=1e-5,
    plan=("conv/swiglu", "full+rope+qknorm/routed", "conv/routed",
          "conv/gelu"))


@pytest.fixture(scope="module")
def small():
    return DecoderModel(init_decoder_params(SMALL, seed=4), SMALL)


def _alone(model, prompt, max_new, page=4):
    """One request alone through ``model.prefill`` / ``model.decode`` at
    width 1, the host reading every id before the next step."""
    slots = -(-model.cfg.max_context // page)
    pools = model.new_pools(1 + slots, page)
    table = np.arange(1, 1 + slots, dtype=np.int32)[None, :]
    tokens = np.zeros((1, -(-len(prompt) // 16) * 16), np.int32)
    tokens[0, :len(prompt)] = prompt
    nxt, *_ = model.prefill(*pools, tokens,
                            np.array([len(prompt)], np.int32), table)
    out, length = [int(nxt[0])], len(prompt)
    while out[-1] != model.cfg.eos_id and len(out) < max_new:
        length += 1
        nxt, *_ = model.decode(
            *pools, np.array([out[-1]], np.int32), table,
            np.array([length], np.int32), np.array([True]))
        out.append(int(nxt[0]))
    return out


def _wait(pred, timeout_s=120.0):
    end = time.monotonic() + timeout_s
    while not pred():
        if time.monotonic() > end:
            return False
        time.sleep(0.001)
    return True


def _prompts(lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, 64, n).tolist() for n in lengths]


def test_rows_that_move_keep_their_state(small):
    """Three requests of different lengths at width 3; the first ends,
    the other two move up a row, and a fourth is admitted into the freed
    pages, its state into a place the first one's lay in.  Each serves
    what it serves alone, byte for byte: a state follows its request,
    not its row."""
    prompts = _prompts((9, 14, 5, 7), seed=22)
    budgets = [3, 14, 12, 9]
    want = [_alone(small, p, n) for p, n in zip(prompts, budgets)]
    assert [len(w) for w in want] == budgets     # no EOS: the first ends
    # 16 pages of 4: the three hold 3 + 7 + 5 of them and all three
    # rows, so the fourth waits for the first's
    with InferenceServer(small, max_batch=3, n_pages=17,
                         page_size=4) as srv:
        assert len(srv._pools) == 3
        assert srv._k_pool is srv._pools[0] and srv._v_pool is srv._pools[1]
        reqs = [srv.submit(p, n) for p, n in zip(prompts[:3], budgets)]
        assert _wait(lambda: all(r.tokens for r in reqs))
        first = set(srv.pool.table_of(reqs[0].id)) if not \
            reqs[0].done.is_set() else None
        reqs.append(srv.submit(prompts[3], budgets[3]))
        got = [srv.result(r, timeout=120.0) for r in reqs]
        assert got == want
        assert srv.pool.used_pages() == 0 and not srv._inflight
    late = reqs[3]
    assert late.t_admit > reqs[0].t_done         # it waited for a row
    if first is not None:
        assert int(late.table[0]) in first       # and took a freed place


def test_a_row_past_its_eos_harms_no_other(small):
    """A row that EOS ended rides one more launch dead: it rolls a state
    it still owns, in its own place, and whoever is admitted into the
    freed pages writes a whole state over it."""
    prompts = _prompts((6, 11, 4, 9), seed=20)
    free = _alone(small, prompts[0], 12)
    assert len(free) == 12
    stop_at = next(i for i in range(2, 9) if free[i] not in free[:i])
    model = DecoderModel({k: np.asarray(v) for k, v in small.params.items()},
                         SMALL._replace(eos_id=free[stop_at]))
    want = [_alone(model, p, 12) for p in prompts]
    assert want[0] == free[:stop_at + 1]
    discarded = observe.counter("serve_rows_discarded_total", "")
    before = discarded.value()
    # 12 pages of 4: three requests of at most 11 + 12 tokens hold them
    # all but one, so the fourth waits for pages an EOS gives back
    with InferenceServer(model, max_batch=4, n_pages=14,
                         page_size=4) as srv:
        reqs = [srv.submit(p, 12) for p in prompts]
        got = [srv.result(r, timeout=120.0) for r in reqs]
        assert got == want
        assert srv.generated_tokens == sum(map(len, got))
        assert srv.pool.used_pages() == 0
    dead = sum(len(t) < 12 for t in want)
    assert dead >= 1 and discarded.value() - before == dead


def test_continuous_and_sequential_serving_give_the_same_tokens(small):
    """The kill switch's promise under a plan with conv layers, and what
    the spans and gauges state of it."""
    prompts = _prompts((9, 30, 1, 17, 2, 22), seed=6)

    def serve(continuous):
        with InferenceServer(small, max_batch=4, n_pages=64, page_size=4,
                             continuous=continuous) as srv:
            reqs = [srv.submit(p, 8) for p in prompts]
            return [srv.result(r, timeout=300.0) for r in reqs]

    ptrace.enable(fences=False)
    try:
        batched = serve(True)
        spans = ptrace.events()
    finally:
        ptrace.disable()
    assert batched == serve(False)
    assert batched == [_alone(small, p, 8) for p in prompts]
    prefills = [s["args"] for s in spans if s["name"] == "serve_prefill"]
    assert prefills and all(
        a["conv_tokens"] == 3 * a["prompt_tokens"]
        and a["moe_tokens"] == 2 * a["prompt_tokens"] for a in prefills)
    # one attention layer: a prompt's triangle, once
    assert sum(a["attn_pairs"] for a in prefills) == sum(
        len(p) * (len(p) + 1) // 2 for p in prompts)
    steps = [s["args"] for s in spans if s["name"] == "serve_decode_step"]
    assert steps and all(
        a["attended_tokens"] == a["live_tokens"]
        and a["state_rows"] == 3 * (a["batch"] + a.get("discarded", 0))
        for a in steps)
    gauge = lambda name: [s["value"] for s in
                          observe.REGISTRY.find(name).samples()]
    assert gauge("serve_cache_bytes_per_token") == [2 * 1 * 32 * 4]
    assert gauge("serve_state_bytes_per_sequence") == [3 * 2 * 64 * 4]


@pytest.mark.parametrize("policy", ["drain", "reprefill"])
def test_a_hot_swap_keeps_or_rebuilds_the_state_as_it_does_the_pools(
        small, policy):
    """A swap parked while a request is mid-generation: under ``drain``
    the old model finishes it on the state it has; under ``reprefill``
    the new model's fresh caches (the state among them) are filled again
    from the prompt.  Either way the tokens are one model's."""
    prompt, max_new = _prompts((11,), seed=23)[0], 16
    new = DecoderModel(init_decoder_params(SMALL, seed=5), SMALL)
    ref_old, ref_new = (_alone(m, prompt, max_new) for m in (small, new))
    assert ref_old != ref_new
    with InferenceServer(small, max_batch=3, n_pages=33, page_size=4,
                         rollout=True) as srv:
        state = srv._pools[-1]
        r = srv.submit(prompt, max_new)
        assert _wait(lambda: len(r.tokens) >= 2)
        report = srv.request_swap(new, version="v-new",
                                  inflight=policy).wait(120.0)
        assert report["result"] == "ok"
        assert srv.result(r, timeout=120.0) == \
            (ref_old if policy == "drain" else ref_new)
        assert len(srv._pools) == 3 and srv._pools[-1] is not state
        assert srv.generate(prompt, max_new, timeout=120.0) == ref_new


def test_the_artifact_carries_the_conv_leaves(small, tmp_path):
    """export_decoder → from_artifact with ``in_proj``, ``conv`` and
    ``out_proj`` among the weights and ``conv_taps`` in the config: the
    unquantized round trip serves the same tokens."""
    prompts = _prompts((9, 3), seed=24)
    want = [_alone(small, p, 6) for p in prompts]
    raw = str(tmp_path / "raw")
    export_decoder({k: np.asarray(v) for k, v in small.params.items()},
                   SMALL, raw, quantize=None)
    loaded = DecoderModel.from_artifact(raw)
    assert loaded.cfg == SMALL and loaded.cfg.conv_taps == 3
    assert {"l0.in_proj", "l0.conv", "l0.out_proj"} <= set(loaded.params)
    assert loaded.params["l0.conv"].dtype == jnp.float32
    assert [_alone(loaded, p, 6) for p in prompts] == want
    q = str(tmp_path / "int8")
    export_decoder({k: np.asarray(v) for k, v in small.params.items()},
                   SMALL, q, quantize="int8")
    tokens = _alone(DecoderModel.from_artifact(q), prompts[0], 6)
    assert all(0 <= t < SMALL.vocab for t in tokens)


def test_a_plan_of_conv_layers_alone_has_empty_pools():
    """No layer attends: the pools are zero layers deep, a sequence's
    state still lies at its first page, and the steps run."""
    cfg = SMALL._replace(layers=2, plan=("conv/swiglu", "conv/gelu"),
                         experts=0, top_k=0, expert_ffn=0)
    model = DecoderModel(init_decoder_params(cfg, seed=1), cfg)
    k, v, state = model.new_pools(5, 4)
    assert k.shape == (0, 5, 4, 32) and state.shape == (2, 5, 2, 64)
    assert model.cache_bytes_per_token() == 0
    prompt = _prompts((6,), seed=25)[0]
    a, b = _alone(model, prompt, 5), _alone(model, prompt[:5], 1)
    assert len(a) == 5 and len(b) == 1
    with InferenceServer(model, max_batch=2, n_pages=9, page_size=8) as srv:
        assert srv.generate(prompt, 5, timeout=120.0) == a


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("n,tile", [(32, 32), (768, 768), (1024, 1024),
                                    (1280, 640), (1536, 768), (2048, 1024)])
def test_the_column_tile_divides_the_columns(n, tile):
    assert pm._column_tile(n) == tile and n % tile == 0


def test_grouped_matmul_at_an_expert_width_of_1536_writes_every_column():
    """``n`` = 1536 is no multiple of the column tile's most, 1024: the
    tile is 768, and the columns past 1024 are the groups' products too
    (on a grid of ``n // 1024`` blocks they were never written)."""
    rng = np.random.default_rng(2)
    m, k, n, e = 40, 128, 1536, 6
    sizes = np.array([7, 0, 12, 1, 0, 9], np.int32)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((e, k, n)), jnp.float32)
    got = np.asarray(pm.grouped_matmul(lhs, rhs, jnp.asarray(sizes)))
    assert got.shape == (m, n)
    group = np.repeat(np.arange(e), sizes)
    rows = len(group)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jnp.einsum("mk,mkn->mn", lhs[:rows], rhs[group]))
    assert np.abs(got[:rows] - want).max() < 1e-3
    assert np.abs(got[:rows, 1024:]).min(axis=0).max() > 0
    with pytest.raises(PaddleTpuError, match="no column tile"):
        pm.grouped_matmul(lhs, jnp.zeros((e, k, 1100), jnp.float32),
                          jnp.asarray(sizes))


def test_paged_decode_at_eight_kv_heads_of_64():
    """32 query heads over 8 K/V heads of 64 lanes (a head is half a
    lane tile), pools one lane-dense row of 512 a token, tables in no
    order, rows of 0, 1, one page, a ragged last page."""
    rng = np.random.default_rng(0)
    b, h, g, d, page, n_pages, slots = 5, 32, 8, 64, 8, 40, 6
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((n_pages, page, g * d)),
                        jnp.float32) for _ in range(2))
    lengths = np.array([0, 1, 8, 19, 45], np.int32)
    tables = rng.permutation(n_pages - 1)[:b * slots].reshape(b, slots) \
        .astype(np.int32)
    got = pa.paged_decode_attention(q, k, v, jnp.asarray(tables),
                                    jnp.asarray(lengths), name="paged_decode")
    want = pa.paged_decode_reference(q, k, v, jnp.asarray(tables),
                                     jnp.asarray(lengths))
    assert got.shape == (b, 1, h, d)
    assert not np.asarray(got[0]).any()
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_packed_attention_at_eight_kv_heads_of_64():
    """The prefill kernel at the same heads: two prompts in one packed
    row, four query heads a K/V head, against the dense forward."""
    rng = np.random.default_rng(1)
    t, h, g, d = 256, 32, 8, 64
    q = jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((1, t, g, d)), jnp.float32)
            for _ in range(2))
    seg = pa.segments_from_lengths(jnp.asarray([100, 128]), 2, 128)
    assert pa._heads_per_step(h, h // g, 512, 512, d, d, 2) == 4
    got = pa.flash_attention_packed(q, k, v, seg, True, 128, 128, 128)
    want, _ = pa._dense_forward(q, k, v, None, True, seg)
    valid = np.asarray(seg)[0] >= 0
    assert got.shape == (1, t, h, d)
    assert np.abs(np.asarray(got - want))[0][valid].max() < 1e-5
