"""The decoder with ``mamba`` layers in its plan (``serving/model.py``):
Mamba-1 selective-scan layers that keep a fixed-size state a sequence
in slots of their own beside the K/V pages of the attention layers,
against the plain reference of the configuration that brought them
(``chipbench/reference/jamba2-3b-serve.py``, which imports nothing of
the program, keeps no state and scans one position after the other) on
seeded weights at the configuration's rehearsal sizes: the published
28-layer pattern (layers 7 and 21 attend) at hidden 64, 4 heads over 1
K/V head of 16, d_inner 128, d_state 16, dt_rank 8, 4 taps, page 4, a
tied head; the scan kernel (``ops/pallas_ssm.py``) in its two paths
against a sequential scan; and through the server, where a sequence's
state has to follow its request while rows move between launches."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe import trace as ptrace
from paddle_tpu.ops import pallas_ssm as ssm
from paddle_tpu.serving import model as M
from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                      init_decoder_params, layer_plan,
                                      leaf_shapes)
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.utils import PaddleTpuError

from test_routed_decoder import _rehearsal

CONFIG = "jamba2-3b-serve"


@pytest.fixture(scope="module")
def bench():
    return _rehearsal(CONFIG)


def _model(bench, storage="float32", **replace):
    sizes, _, system, weights = bench
    cfg = system.decoder_config(sizes)._replace(storage=storage, **replace)
    return DecoderModel(system.program_weights(sizes, weights), cfg)


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


#: the rows' slots: in no order their batch rows would give
SLOTS = (3, 0, 1)


def _serve(model, prompts, steps, page=4, width=5, between=None,
           decode_slots=None):
    """Prefill the prompts as one batch into the slots :data:`SLOTS`,
    then ``steps`` decode steps at a fixed width with idle rows on the
    scratch slot, through page tables that are neither contiguous nor in
    order.  ``between(pools)`` runs after the prefill;
    ``decode_slots`` replaces the slots the decode steps are handed.
    → [(sequences so far, the program's logits for each)]."""
    b = len(prompts)
    pages = model.cfg.max_context // page
    pools = model.new_pools(1 + b * pages, page, width + 1)
    tables = 1 + np.random.default_rng(5).permutation(b * pages) \
        .reshape(b, pages).astype(np.int32)          # page 0: scratch
    tokens = np.zeros((b, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    nxt, logits, *_ = model.prefill(
        *pools, tokens, np.array([len(p) for p in prompts], np.int32),
        tables, slots=np.array(SLOTS[:b], np.int32))
    if between is not None:
        between(pools)
    seqs = [list(p) for p in prompts]
    out = [([list(s) for s in seqs], np.asarray(logits))]
    slots = np.full((width,), width, np.int32)      # idle: the scratch
    slots[:b] = SLOTS[:b] if decode_slots is None else decode_slots[:b]
    for _ in range(steps):
        for i in range(b):
            seqs[i].append(int(nxt[i]))
        fed = np.zeros((width,), np.int32)
        lengths = np.ones((width,), np.int32)
        active = np.zeros((width,), bool)
        tab = np.zeros((width, pages), np.int32)
        fed[:b], active[:b], tab[:b] = nxt[:b], True, tables
        lengths[:b] = [len(s) for s in seqs]
        nxt, logits, *_ = model.decode(*pools, fed, tab, lengths, active,
                                       slots=slots)
        out.append(([list(s) for s in seqs], np.asarray(logits)[:b]))
    return out


# Tolerances, and why.  In float32 storage the program and the reference
# compute the same function in another order: the program keeps the
# newest three u and the scan's state of a sequence and forms a decode
# step from them where the reference convolves and scans the whole
# sequence again; the packed kernel's online softmax.  Logits are of
# size 1-4 and a row reads under 1e-5: 1e-4 is ten times the worst seen
# and a thousandth of what a planted fault moves (below), and every row
# of every step is held to it.  In bfloat16 storage every matrix product
# rounds its operands to 8 bits of mantissa and the K/V rows and the
# convolution's window are kept so; the median row moves by about 0.05,
# so bfloat16 is held to 0.15 there and, run through the float32
# comparison, fails it: the strict comparison tells the stated precision
# from a lower one.
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.15}
PROMPTS = (21, 5, 30)


def _gaps(bench, model, steps=5, lengths=PROMPTS, **kw):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 256, n).tolist() for n in lengths]
    return np.array([np.abs(_reference_logits(bench, seqs) - logits).max(-1)
                     for seqs, logits in _serve(model, prompts, steps, **kw)])


def test_the_plan_is_the_published_order(bench, decoder):
    """Layers 7 and 21 attend, the other 26 are mamba layers; every
    feed-forward is the dense SwiGLU; no rotary, no position table."""
    plan = layer_plan(decoder.cfg)
    assert len(plan) == 28
    assert [i for i, (attn, _) in enumerate(plan) if "full" in attn] \
        == [7, 21]
    assert all(attn in ({"full"}, {"mamba"}) and ffn == {"swiglu"}
               for attn, ffn in plan)
    assert decoder.mamba_layers == 26 and decoder.state_layers == 26
    assert not decoder.cfg.pos_embed and decoder.cfg.tied_head


def test_prefill_then_decode_through_the_slots_is_the_reference(
        bench, decoder):
    """Prefill of three rows of mixed length (step 0: the convolution and
    the scan over each whole row, from a zero state, each row's state
    written into its slot) and five decode steps (the window and the
    state read at the slot, advanced and written back in place; K/V
    through page tables on 2 of 28 layers) against the reference's one
    full forward of the same sequence."""
    gaps = _gaps(bench, decoder)
    assert gaps.shape == (6, 3)
    assert gaps.max() < TOLERANCE["float32"], gaps


def test_bfloat16_storage_is_near_and_fails_the_float32_comparison(bench):
    model = _model(bench, "bfloat16")
    pools = model.new_pools(9, 4, 3)
    assert [p.dtype for p in pools] == [jnp.bfloat16] * 3 + [jnp.float32]
    gaps = _gaps(bench, model)
    assert np.median(gaps) < TOLERANCE["bfloat16"], gaps
    assert gaps.max() > 100 * TOLERANCE["float32"], gaps


def _state_zeroed(pools):
    pools[-1].array = jnp.zeros_like(pools[-1].array)


def _u_z_swapped(model):
    for name in [n for n in model.params if n.endswith(".in_proj")]:
        u, z = jnp.split(model.params[name], 2, axis=1)
        model.params[name] = jnp.concatenate([z, u], axis=1)


def _norms_dropped(uc, params, i, cfg):
    """``_mamba_dbc`` without δ's, B's and C's norms (nor their gains)."""
    p = lambda leaf: params[f"l{i}.{leaf}"]
    r, n = cfg.dt_rank, cfg.ssm_state
    dbc = M.weight_matmul(uc, p("x_proj"))
    return jax.nn.softplus(M.weight_matmul(dbc[..., :r], p("dt_proj"))
                           + p("dt_bias")), dbc[..., r:r + n], \
        dbc[..., r + n:]


def _carry_dropped(u, delta, a, b, c, d, h0, chunk=8):
    """The scan started again from zeros every ``chunk`` positions."""
    cut = lambda x, s: x[:, s:s + chunk]
    out = [ssm.selective_scan(cut(u, s), cut(delta, s), a, cut(b, s),
                              cut(c, s), d,
                              h0 if s == 0 else jnp.zeros_like(h0))
           for s in range(0, u.shape[1], chunk)]
    return jnp.concatenate([y for y, _ in out], axis=1), out[-1][1]


@pytest.fixture
def planted(monkeypatch):
    """Replace one function of the decoder for a test's steps."""
    def plant(name, fn):
        monkeypatch.setattr(M, name, fn)
        M._jitted_steps.cache_clear()
    yield plant
    monkeypatch.undo()
    M._jitted_steps.cache_clear()


@pytest.mark.parametrize("fault", ["state_from_zeros", "read_by_row",
                                   "u_z_swapped", "norms_dropped",
                                   "carry_dropped"])
def test_a_planted_fault_is_seen(bench, fault, planted):
    """Each reads a thousand times the float32 tolerance: decode steps
    that start from a zeroed scan state after the prefill (the prompt's
    whole context lost), a state read at the batch row instead of the
    slot the prefill wrote, the gate taken for the scan's input, δ, B
    and C not normed (their gains are drawn 1 + N(0, 0.1²), the norms
    themselves bring them to unit size), and a scan that starts again
    from zeros at each chunk of 8 positions."""
    kw = {}
    if fault == "norms_dropped":
        planted("_mamba_dbc", _norms_dropped)
    elif fault == "carry_dropped":
        planted("selective_scan", _carry_dropped)
    model = _model(bench)          # its steps traced with the plant
    if fault == "state_from_zeros":
        kw["between"] = _state_zeroed
    elif fault == "read_by_row":
        kw["decode_slots"] = np.arange(3)
    elif fault == "u_z_swapped":
        _u_z_swapped(model)
    gaps = _gaps(bench, model, steps=2, **kw)
    assert gaps[1].min() > 0.1, gaps
    if fault in ("state_from_zeros", "read_by_row"):
        assert gaps[0].max() < TOLERANCE["float32"]     # prefill is sound


def test_a_decode_step_is_the_prefill_at_the_same_position(decoder):
    """Position n of a sequence computed twice over one set of weights:
    as the last token of a prefill of n + 1 tokens, and as a decode step
    behind a prefill of n in another slot; the states each leaves are
    the same too."""
    page, pages = 4, decoder.cfg.max_context // 4
    seq = np.random.default_rng(8).integers(2, 256, 27).tolist()
    table = np.arange(1, 1 + pages, dtype=np.int32)[None, :]
    tokens = np.zeros((1, 32), np.int32)
    tokens[0, :27] = seq
    pools = decoder.new_pools(1 + pages, page, 3)
    _, whole, *_ = decoder.prefill(*pools, tokens, np.array([27]), table,
                                   slots=np.array([1]))
    win, h = (np.asarray(p.array)[:, 1] for p in pools[2:])
    pools = decoder.new_pools(1 + pages, page, 3)
    decoder.prefill(*pools, tokens, np.array([26]), table,
                    slots=np.array([0]))
    _, step, *_ = decoder.decode(*pools, np.array([seq[26]], np.int32),
                                 table, np.array([27]), np.array([True]),
                                 slots=np.array([0]))
    assert np.abs(np.asarray(whole) - np.asarray(step)).max() < 2e-5
    assert np.abs(win - np.asarray(pools[2].array)[:, 0]).max() < 2e-5
    assert np.abs(h - np.asarray(pools[3].array)[:, 0]).max() < 2e-5
    assert np.abs(h).max(axis=(1, 2)).min() > 0      # every layer's
    assert not np.asarray(pools[3].array)[:, 1:].any()   # slot 0 alone


def test_the_pools_hold_the_attending_layers_and_slots_the_state(decoder):
    """Two of 28 layers have K and V: the pools are two layers deep; the
    26 mamba layers' window and float32 state are two caches more, a
    slot a sequence.  A caller that names neither states nor slots (the
    benchmark's warm-up) is given states of as many slots for the call,
    and a row's slot is its first page: the same programs."""
    assert decoder.n_pools == 4 and decoder.n_kv_pools == 2
    k, v, win, h = decoder.new_pools(9, 4, 5)
    assert k.shape == v.shape == (2, 9, 4, 16)
    assert win.shape == (26, 5, 3, 128) and h.shape == (26, 5, 16, 128)
    assert h.dtype == jnp.float32
    assert decoder.state_bytes_per_sequence() == 26 * (3 * 128 * 4
                                                       + 16 * 128 * 4)
    assert decoder.cache_bytes_per_token() == 2 * 2 * 16 * 4
    table = np.zeros((1, 8), np.int32)
    prefill, decode = M._jitted_steps(decoder.cfg)
    out = decoder.prefill(k, v, np.full((1, 16), 3, np.int32),
                          np.array([7], np.int32), table)
    assert len(out) == 6 and out[2:4] == (k, v)
    assert out[4].shape == win.shape and out[4] is not win
    out = decoder.decode(k, v, np.array([5], np.int32), table,
                         np.array([8], np.int32), np.array([False]))
    assert out[5].shape == h.shape
    sizes = prefill._cache_size(), decode._cache_size()
    decoder.prefill(k, v, win, h, np.full((1, 16), 3, np.int32),
                    np.array([7], np.int32), table, slots=np.array([4]))
    decoder.decode(k, v, win, h, np.array([5], np.int32), table,
                   np.array([8], np.int32), np.array([True]),
                   slots=np.array([4]))
    assert (prefill._cache_size(), decode._cache_size()) == sizes
    assert np.asarray(h.array)[:, 4].any() and not \
        np.asarray(h.array)[:, :4].any()


def test_the_head_is_the_embedding(decoder):
    """A tied head has no leaf of its own: the logits are the final
    norm's output times the embedding's transpose, so moving one row of
    the embedding moves that token's logit and no other."""
    assert "lm_head" not in leaf_shapes(decoder.cfg)
    params = {k: np.asarray(v) for k, v in decoder.params.items()}
    params["embed"] = params["embed"].copy()
    params["embed"][7] *= 2.0              # a token the prompt never feeds
    moved = DecoderModel(params, decoder.cfg)
    page, pages = 4, decoder.cfg.max_context // 4
    table = np.arange(1, 1 + pages, dtype=np.int32)[None, :]
    tokens = np.full((1, 16), 3, np.int32)
    logits = [np.asarray(m.prefill(*m.new_pools(1 + pages, page, 2), tokens,
                                   np.array([9]), table,
                                   slots=np.array([0]))[1])[0]
              for m in (decoder, moved)]
    diff = np.flatnonzero(np.abs(logits[0] - logits[1]) > 1e-6)
    assert diff.tolist() == [7]
    np.testing.assert_allclose(logits[1][7], 2.0 * logits[0][7], rtol=1e-5)


@pytest.mark.parametrize("plan,why", [
    (("mamba+rope/gelu", "full/gelu"), "mamba alone"),
    (("mamba/gelu", "conv+gate/gelu"), "conv alone"),
    (("mamba/gelu", "latent+rope/gelu"), "latent in every layer")])
def test_a_mamba_plan_the_decoder_cannot_run_is_refused(plan, why):
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                        plan=plan, conv_taps=4, ssm_inner=64, ssm_state=8,
                        dt_rank=4, q_rank=8, kv_rank=8, nope_dim=8,
                        rope_dim=8, v_dim=8)
    with pytest.raises(PaddleTpuError, match=why):
        layer_plan(cfg)
    with pytest.raises(PaddleTpuError, match="ssm_inner, ssm_state"):
        layer_plan(cfg._replace(plan=("mamba/gelu", "full/gelu"),
                                dt_rank=0))


# ------------------------------------------------------------ the kernel
def _sequential(u, dt, a, b, c, d, h0):
    """The scan one position after the other, float64 on the host."""
    h = h0.astype(np.float64).copy()
    y = np.zeros(u.shape)
    for t in range(u.shape[1]):
        h = np.exp(dt[:, t, None, :] * a) * h \
            + (dt[:, t] * u[:, t])[:, None, :] * b[:, t, :, None]
        y[:, t] = (h * c[:, t, :, None]).sum(1) + d * u[:, t]
    return y, h


def _scan_inputs(rows, t, ch, n=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (rows, t, ch)))
    a = -np.exp(np.log(np.arange(1, n + 1))[:, None] + 0.1 * f(n, ch))
    return (f(rows, t, ch), dt.astype(np.float32), a.astype(np.float32),
            f(rows, t, n), f(rows, t, n), f(ch), f(rows, n, ch))


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("t", [37, 600])
def test_the_scan_is_the_sequential_scan(impl, t, monkeypatch):
    """Both paths against the recurrence one position after the other:
    two rows, a nonzero starting state, T no multiple of the kernel's
    chunk (at a chunk of 16 positions, 37 is three chunks and a part; at
    the chunk the chip takes, 600 is two and a part), channels wider
    than a block of the kernel: the state crosses chunk and block
    boundaries."""
    monkeypatch.setattr(ssm, "CHANNELS", 128)
    if t < 100:
        monkeypatch.setattr(ssm, "CHUNK", 16)
    args = _scan_inputs(2, t, 256)
    y, h = ssm.selective_scan(*args, impl=impl)
    want_y, want_h = _sequential(*args)
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4
    assert np.abs(np.asarray(h) - want_h).max() < 1e-5


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_padding_with_no_step_leaves_the_state_exact(impl, monkeypatch):
    """A row padded past its length with Δ = 0 ends holding, bit for
    bit, the state after its own last position."""
    monkeypatch.setattr(ssm, "CHUNK", 16)
    u, dt, a, b, c, d, h0 = _scan_inputs(2, 40, 128, seed=1)
    dt[1, 19:] = 0.0
    _, h = ssm.selective_scan(u, dt, a, b, c, d, h0, impl=impl)
    _, short = ssm.selective_scan(u[1:, :19], dt[1:, :19], a, b[1:, :19],
                                  c[1:, :19], d, h0[1:], impl=impl)
    np.testing.assert_array_equal(np.asarray(h)[1], np.asarray(short)[0])


def test_the_kernel_counts_its_work():
    def flops():
        work = observe.REGISTRY.find("pallas_kernel_work_total")
        return 0.0 if work is None else sum(
            s["value"] for s in work.samples()
            if s["labels"] == {"kernel": "ssm_scan", "kind": "flops"})

    args = _scan_inputs(1, 24, 128, seed=2)
    before = flops()
    jax.jit(lambda *a: ssm.selective_scan(*a, impl="pallas")).lower(*args)
    assert flops() - before == 24 * 128 * (7 * 16 + 3)


# ------------------------------------------------------ through the server
SMALL = DecoderConfig(
    vocab=64, dim=32, heads=4, kv_heads=1, layers=4, ffn=48, max_context=64,
    pos_embed=False, conv_taps=4, ssm_inner=64, ssm_state=16, dt_rank=4,
    tied_head=True,
    plan=("mamba/swiglu", "full/swiglu", "mamba/swiglu", "mamba/gelu"))


@pytest.fixture(scope="module")
def small():
    return DecoderModel(init_decoder_params(SMALL, seed=4), SMALL)


def _alone(model, prompt, max_new, page=4):
    """One request alone through ``model.prefill`` / ``model.decode`` at
    width 1 in slot 0, the host reading every id before the next step."""
    pages = -(-model.cfg.max_context // page)
    pools = model.new_pools(1 + pages, page, 2)
    table = np.arange(1, 1 + pages, dtype=np.int32)[None, :]
    tokens = np.zeros((1, -(-len(prompt) // 16) * 16), np.int32)
    tokens[0, :len(prompt)] = prompt
    slot = np.zeros((1,), np.int32)
    nxt, *_ = model.prefill(*pools, tokens,
                            np.array([len(prompt)], np.int32), table,
                            slots=slot)
    out, length = [int(nxt[0])], len(prompt)
    while out[-1] != model.cfg.eos_id and len(out) < max_new:
        length += 1
        nxt, *_ = model.decode(
            *pools, np.array([out[-1]], np.int32), table,
            np.array([length], np.int32), np.array([True]), slots=slot)
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


def test_a_state_follows_its_slot_across_a_compaction(small):
    """Three requests of different lengths at width 3 hold the three
    slots; the first ends, the other two move up a row, and a fourth is
    admitted into the first one's slot.  Each serves what it serves
    alone, byte for byte: a state follows its request, not its row, and
    the slot a request gives back is taken whole by the next."""
    prompts = _prompts((9, 14, 5, 7), seed=22)
    budgets = [3, 14, 12, 9]
    want = [_alone(small, p, n) for p, n in zip(prompts, budgets)]
    assert [len(w) for w in want] == budgets     # no EOS: the first ends
    with InferenceServer(small, max_batch=3, n_pages=40,
                         page_size=4) as srv:
        assert [p.shape[1] for p in srv._pools[2:]] == [4, 4]
        reqs = [srv.submit(p, n) for p, n in zip(prompts[:3], budgets)]
        assert _wait(lambda: all(r.tokens for r in reqs))
        held = [r.slot for r in reqs]
        assert sorted(held) == [0, 1, 2]         # slot 3: the idle rows'
        late = srv.submit(prompts[3], budgets[3])
        assert _wait(lambda: late.slot >= 0)
        assert late.slot == held[0] and reqs[0].done.is_set()
        got = [srv.result(r, timeout=120.0) for r in reqs + [late]]
        assert got == want
        assert sorted(srv._free_slots) == [0, 1, 2] and not srv._inflight
    gauge = observe.REGISTRY.find("serve_state_slots_used")
    assert [s["value"] for s in gauge.samples()] == [0]


def test_continuous_and_sequential_serving_give_the_same_tokens(small):
    """The kill switch's promise under a plan with mamba layers, and what
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
        a["scan_tokens"] == 3 * a["prompt_tokens"] and a["conv_tokens"] == 0
        for a in prefills)
    steps = [s["args"] for s in spans if s["name"] == "serve_decode_step"]
    assert steps and all(
        a["state_rows"] == 3 * (a["batch"] + a.get("discarded", 0))
        for a in steps)
    gauge = lambda name: [s["value"] for s in
                          observe.REGISTRY.find(name).samples()]
    assert gauge("serve_state_bytes_per_sequence") == [
        3 * (3 * 64 * 4 + 16 * 64 * 4)]


# ---------------------------------------------------- prefill in chunks
@pytest.fixture(scope="module")
def chunked(bench):
    """The decoder with a chunk of 8 positions (the server's is 512)."""
    model = _model(bench)
    model.chunk = 8
    return model


@pytest.mark.parametrize("length", [5, 8, 13, 19])
def test_a_prefill_in_chunks_continues_from_the_slot(bench, decoder, chunked,
                                                     length):
    """A prompt prefilled as chunk launches of 8 positions, then decoded:
    at 13 and 19 the convolution's three leading taps and a page of 4
    straddle a chunk boundary, and the scan continues from the state the
    chunk before left in the slot (5 and 8 are one chunk).  Every step
    reads the whole prefill's logits and the reference's at the float32
    tolerance, and after the last chunk the slot holds the whole
    prefill's window and scan state, the pages its K/V."""
    prompt = np.random.default_rng(length).integers(2, 256, length).tolist()
    held, logits = [], []
    for model in (decoder, chunked):
        steps = _serve(model, [prompt], 3, between=lambda pools: held.append(
            [np.asarray(p.array) for p in pools]))
        logits.append(np.stack([lg[0] for _, lg in steps]))
        seqs = [s[0] for s, _ in steps]
    want = np.stack([_reference_logits(bench, [s])[0] for s in seqs])
    assert np.abs(logits[1] - want).max() < TOLERANCE["float32"]
    assert np.abs(logits[1] - logits[0]).max() < TOLERANCE["float32"]
    whole, parts = held
    slot = SLOTS[0]
    for a, b in zip(whole[:2], parts[:2]):               # K and V pages
        assert np.abs(a - b).max() < TOLERANCE["float32"]
    for a, b in zip(whole[2:], parts[2:]):               # window, h
        assert np.abs(a[:, slot] - b[:, slot]).max() < TOLERANCE["float32"]
        assert np.abs(b[:, slot]).max() > 0
