"""The decoder with a layer plan (``serving/model.py``): routed experts,
grouped K/V heads, window and full attention mixed, against the plain
reference of the configuration that brought them
(``chipbench/reference/trinity-mini-serve.py``, which imports nothing of
the program) on seeded weights at the configuration's rehearsal sizes:
hidden 64, 4 heads over 2 K/V heads of 16, window 8, page 4, 32 experts
with 8 a token, the configuration's own five-layer plan."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe import trace as ptrace
from paddle_tpu.ops import kernels as K
from paddle_tpu.ops import pallas_moe as moe
from paddle_tpu.ops.pallas_attention import paged_kv_write
from paddle_tpu.serving import model as decoder_module
from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                      export_decoder, init_decoder_params,
                                      layer_plan, leaf_shapes)
from paddle_tpu.serving.pagepool import SCRATCH_PAGE
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.utils import PaddleTpuError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "trinity-mini-serve"


def _rehearsal(config):
    """(sizes, reference module, system module, seeded weights) of a
    configuration's rehearsal."""
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from chipbench import harness as H, weights as W

    with open(os.path.join(ROOT, "chipbench", "configs",
                           config + ".json")) as f:
        sizes = json.load(f)["rehearsal"]["sizes"]
    ref = H.load_module("reference", config)
    system = H.load_module("systems", config)
    return sizes, ref, system, W.make(ref.param_spec(sizes), 20270001)


@pytest.fixture(scope="module")
def bench():
    return _rehearsal(CONFIG)


@pytest.fixture(scope="module")
def dense_bench():
    """The same of the default plan's configuration: hidden 128, 4
    heads of 32, two layers, float32."""
    return _rehearsal("opt-1.3b-serve")


def _model(bench, storage, drop=()):
    sizes, _, system, weights = bench
    cfg = system.decoder_config(sizes)._replace(storage=storage)
    params = {system.leaf_name(k): v for k, v in weights.items()}
    for name in drop:                       # a planted fault
        params = {k: np.zeros_like(v) if k.endswith(name) else v
                  for k, v in params.items()}
    return DecoderModel(params, cfg)


def _reference_logits(bench, seqs):
    """The reference's logits for the token after each sequence."""
    sizes, ref, _, weights = bench
    tokens = np.zeros((len(seqs), 128), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    at = np.array([[len(s) - 1] for s in seqs])
    with jax.default_matmul_precision("highest"):
        return ref.logits_at(weights, sizes, tokens, at)[:, 0]


def _serve(model, prompts, steps, page=4, width=4):
    """Prefill the prompts as one batch, then ``steps`` decode steps at
    a fixed width through page tables that are neither contiguous nor
    in order.  → [(sequences so far, the program's logits for each)]."""
    b = len(prompts)
    slots = model.cfg.max_context // page
    k, v = model.new_pools(1 + b * slots, page)
    tables = 1 + np.random.default_rng(5).permutation(b * slots) \
        .reshape(b, slots).astype(np.int32)         # page 0: scratch
    t_pad = -(-max(map(len, prompts)) // 16) * 16
    tokens = np.zeros((b, t_pad), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    nxt, logits, k, v = model.prefill(
        k, v, tokens, np.array([len(p) for p in prompts], np.int32), tables)
    seqs = [list(p) for p in prompts]
    out = [([list(s) for s in seqs], logits)]
    for _ in range(steps):
        for i in range(b):
            seqs[i].append(int(nxt[i]))
        fed = np.zeros((width,), np.int32)
        lengths = np.ones((width,), np.int32)
        active = np.zeros((width,), bool)
        tab = np.zeros((width, slots), np.int32)
        fed[:b], active[:b], tab[:b] = nxt[:b], True, tables
        lengths[:b] = [len(s) for s in seqs]
        nxt, logits, k, v, counts = model.decode(
            k, v, fed, tab, lengths, active)
        out.append(([list(s) for s in seqs], logits[:b]))
        if model.routed_layers:   # 4 of 32 experts, 8 a token
            assert 4 * 8 <= counts["experts_hit"] <= 4 * min(32, 8 * b)
            assert 1 <= counts["expert_load_max"] <= b
    return out


# Tolerances, and why.  In float32 storage the program and the reference
# compute the same sums in another order (a packed kernel's online
# softmax, sorted rows): 1e-4 of logits of size 1-4 is some ten times
# what is seen and a hundredth of what the smallest planted fault moves;
# every row of every step is held to it.  In bfloat16 storage every
# matrix product rounds its operands to 8 bits of mantissa and K/V are
# kept so: at these toy widths (a norm over 64 lanes) a row reads
# 0.05-0.14 — but a router score rounded across a near-tie of the top 8
# of 32 sends the token to another expert, and that row then reads
# 0.2-1.0 (four rows in ten here), as the reference itself does when
# computed in bfloat16 (PERF.md §6, PR 27).  So only the median row is
# held, to 0.2 (seen: 0.11); float32 is the strict comparison, and the
# one the planted faults are judged by.
TOLERANCE = {"float32": 1e-4, "bfloat16": 0.2}


@pytest.mark.parametrize("storage", ["float32", "bfloat16"])
def test_prefill_then_decode_through_pages_is_the_reference(bench, storage):
    """Rows of mixed length in one batch — shorter than the window of 8,
    past it, past several pages of 4 and ending on a page's edge — are
    prefilled, then decoded 6 steps at width 4 (one slot idle); every
    step's logits are the full forward's of the plain reference."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 256, n).tolist() for n in (21, 5, 32)]
    model = _model(bench, storage)
    rows = []                    # the worst logit of each row of each step
    for seqs, logits in _serve(model, prompts, 6):
        want = _reference_logits(bench, seqs)
        rows.extend(np.abs(want - logits).max(axis=-1))
    rows, limit = np.array(rows), TOLERANCE[storage]
    if storage == "float32":
        assert rows.max() < limit, rows
    else:
        assert np.median(rows) < limit, rows
    # what the step must read: rows of 27, 11 and 38 positions under
    # four window layers of 8 and one full layer
    assert model.attended_tokens([27, 11, 38]) == 4 * 24 + 76
    assert model.pages_behind_window([27, 11, 38], 4) == 4 * (4 + 0 + 7)


@pytest.mark.parametrize("fault", ["window", "bias"])
def test_a_planted_fault_is_seen(bench, fault):
    """A program that attends without the window on sliding layers, or
    that drops the router's selection bias, is far outside the
    tolerance (so the comparison holds the program to both)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 256, n).tolist() for n in (21, 32)]
    if fault == "window":
        sizes, _, system, weights = bench
        cfg = system.decoder_config(sizes)._replace(
            storage="float32", window=10 ** 6)
        model = DecoderModel({system.leaf_name(k): v
                              for k, v in weights.items()}, cfg)
    else:
        model = _model(bench, "float32", drop=("router_bias",))
    worst = 0.0
    for seqs, logits in _serve(model, prompts, 2):
        want = _reference_logits(bench, seqs)
        worst = max(worst, float(np.abs(want - logits).max()))
    assert worst > 0.4, worst


# ------------------------------------------------------- the routed op
def _per_token(x, router_w, bias, wg, wu, wd, top_k, scale):
    """The routed layer one token at a time, float64 numpy."""
    out = np.zeros_like(x, dtype=np.float64)
    hit = np.zeros(router_w.shape[1], np.int64)
    for t, m in enumerate(x.astype(np.float64)):
        s = 1.0 / (1.0 + np.exp(-(m @ router_w)))
        chosen = np.argsort(-(s + bias), kind="stable")[:top_k]
        norm = s[chosen].sum() + 1e-20
        for e in chosen:
            hid = m @ wg[e]
            hid = hid / (1.0 + np.exp(-hid)) * (m @ wu[e])
            out[t] += scale * s[e] / norm * (hid @ wd[e])
            hit[e] += 1
    return out, hit


@pytest.mark.parametrize("tokens,experts,top_k", [
    (5, 8, 2),          # fewer rows than a tile: most of it padding
    (40, 8, 3),         # several experts share a row tile
    (300, 4, 2),        # an expert's rows span row tiles of 256
    (24, 16, 1)])       # experts with no token at all
def test_routed_experts_is_the_per_token_loop(tokens, experts, top_k):
    """Selection by s + b, weights by s alone, normalised and scaled:
    the sorted, grouped product equals a loop over tokens and their
    experts; the group sizes count each expert's tokens; a token that
    is not valid reaches no expert and gets zeros."""
    rng = np.random.default_rng(tokens)
    d, f, scale = 32, 48, 2.826
    x = rng.standard_normal((tokens, d)).astype(np.float32)
    router_w = rng.standard_normal((d, experts)).astype(np.float32) * 0.3
    # a bias of the scores' own size: it changes who is chosen
    bias = rng.standard_normal(experts).astype(np.float32) * 0.3
    wg, wu = (rng.standard_normal((experts, d, f)).astype(np.float32)
              / np.sqrt(d) for _ in range(2))
    wd = rng.standard_normal((experts, f, d)).astype(np.float32) \
        / np.sqrt(f)
    valid = np.ones(tokens, bool)
    valid[1::4] = False
    y, sizes = moe.routed_experts(
        jnp.asarray(x), jnp.asarray(router_w), jnp.asarray(bias),
        jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        top_k=top_k, route_scale=scale, valid=jnp.asarray(valid))
    want, hit = _per_token(x[valid], router_w, bias, wg, wu, wd, top_k,
                           scale)
    # float32 sums in another order against float64: 2e-5 of values of
    # size 1 (seen: 3e-6)
    np.testing.assert_allclose(np.asarray(y)[valid], want, atol=2e-5)
    assert np.abs(np.asarray(y)[~valid]).max() == 0.0
    np.testing.assert_array_equal(np.asarray(sizes), hit)
    # the bias chose: without it other experts are hit
    _, unbiased = moe.routed_experts(
        jnp.asarray(x), jnp.asarray(router_w), jnp.zeros(experts),
        jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
        top_k=top_k, route_scale=scale, valid=jnp.asarray(valid))
    assert (np.asarray(unbiased) != hit).any()


def _kernel_work():
    """``pallas_kernel_work_total``: {(kernel, kind): value}."""
    return {(s["labels"]["kernel"], s["labels"]["kind"]): s["value"]
            for s in observe.REGISTRY.find(
                "pallas_kernel_work_total").samples()}


def test_grouped_matmul_reads_only_groups_with_rows():
    """An empty group's weights are never fetched: they hold NaN and
    the result is finite.  The call is named and counted."""
    rng = np.random.default_rng(3)
    sizes = np.array([0, 7, 0, 0, 30, 0, 3, 0], np.int32)
    m, k, n = int(sizes.sum()), 32, 128
    lhs = rng.standard_normal((m, k)).astype(np.float32)
    rhs = rng.standard_normal((8, k, n)).astype(np.float32)
    rhs[sizes == 0] = np.nan
    out = np.asarray(moe.grouped_matmul(jnp.asarray(lhs), jnp.asarray(rhs),
                                        jnp.asarray(sizes)))
    group = np.repeat(np.arange(8), sizes)
    want = np.einsum("mk,mkn->mn", lhs, rhs[group])
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    rows = _kernel_work()
    assert rows[(K.MOE_GMM, "calls")] >= 1
    assert rows[(K.MOE_GMM, "flops")] >= 2.0 * m * k * n


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sizes,m,n", [
    ([70, 100, 30], 200, 128),      # row tile 0 holds groups 0 and 1,
                                    # tile 1 groups 1 and 2
    ([0, 37, 0, 90, 0], 127, 128),  # experts with no row: never read
    ([60, 90], 300, 128),           # 150 rows of padding, a whole row
                                    # tile of them, behind the last group
    ([9, 0, 30], 39, 768),          # the three expert widths: one
    ([9, 0, 30], 39, 1024),         # column block, one, and two of 768
    ([9, 0, 30], 39, 1536)],        # (every column written)
    ids=["shared-tile", "empty-expert", "padding", "n768", "n1024",
         "n1536"])
def test_grouped_glu_is_the_two_products_and_the_activation(sizes, m, n,
                                                            dtype):
    """The fused gate-up call equals ``silu(grouped_matmul(gate)) *
    grouped_matmul(up)`` cast to the storage dtype, to the dtype's last
    place (the activation is float32 on both sides; a transcendental may
    differ in its last bit before the cast)."""
    rng, k = np.random.default_rng(n + m), 64
    sizes = np.array(sizes, np.int32)
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    gate, up = (rng.standard_normal((len(sizes), k, n)).astype(np.float32)
                / np.sqrt(k) for _ in range(2))
    gate[sizes == 0] = up[sizes == 0] = np.nan      # never read
    gate, up, gs = (jnp.asarray(gate, dtype), jnp.asarray(up, dtype),
                    jnp.asarray(sizes))
    got = moe.grouped_glu(lhs, gate, up, gs, lhs.dtype)
    want = (jax.nn.silu(moe.grouped_matmul(lhs, gate, gs))
            * moe.grouped_matmul(lhs, up, gs)).astype(lhs.dtype)
    assert got.shape == (m, n) and got.dtype == lhs.dtype
    rows = int(sizes.sum())
    got, want = (np.asarray(a[:rows], np.float32) for a in (got, want))
    assert np.isfinite(want).all()
    ulp = {"float32": 2.0 ** -23, "bfloat16": 2.0 ** -7}[dtype]
    np.testing.assert_allclose(got, want, rtol=ulp, atol=1e-30)
    # no column block left as it was allocated
    assert (np.abs(got).max(axis=0) > 0).all()


def test_grouped_glu_refuses_weights_that_differ():
    lhs = jnp.zeros((16, 32))
    with pytest.raises(PaddleTpuError, match="gate .* and up .* differ"):
        moe.grouped_glu(lhs, jnp.zeros((2, 32, 128)),
                        jnp.zeros((2, 32, 256)), jnp.array([8, 8]),
                        jnp.float32)


@pytest.mark.parametrize("invalid", ["none", "some", "all"])
def test_group_sizes_count_the_choices_of_valid_tokens(invalid):
    """The choice-major flat list counts what the token-major one did:
    ``group_sizes[e]`` is the number of (valid token, choice) pairs the
    router sent to ``e``, without ``valid`` too; a token's result does
    not depend on where among its group's rows it lies (the tokens in
    another order give the same rows in that order)."""
    rng = np.random.default_rng(9)
    t, d, f, e, top_k = 48, 32, 128, 8, 3
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    router_w = jnp.asarray(rng.standard_normal((d, e)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(e) * 0.3, jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)) / np.sqrt(d),
                          jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, f, d)) / np.sqrt(f),
                     jnp.float32)
    valid = {"none": None, "all": np.zeros(t, bool),
             "some": rng.random(t) < 0.6}[invalid]
    run = lambda x, valid: moe.routed_experts(
        x, router_w, bias, wg, wu, wd, top_k=top_k, route_scale=1.5,
        valid=None if valid is None else jnp.asarray(valid))
    y, sizes = run(x, valid)
    chosen = np.asarray(moe.route(x, router_w, bias, top_k, 1.5)[0])
    keep = np.ones(t, bool) if valid is None else valid
    np.testing.assert_array_equal(
        np.asarray(sizes), np.bincount(chosen[keep].reshape(-1),
                                       minlength=e))
    assert sizes.dtype == jnp.int32 and int(sizes.sum()) == keep.sum() * top_k
    assert np.abs(np.asarray(y)[~keep]).sum() == 0.0
    perm = rng.permutation(t)
    y_perm, sizes_perm = run(x[perm], None if valid is None
                             else valid[perm])
    np.testing.assert_array_equal(np.asarray(sizes_perm), np.asarray(sizes))
    np.testing.assert_allclose(np.asarray(y_perm), np.asarray(y)[perm],
                               rtol=1e-5, atol=1e-6)


def test_a_routed_layer_is_two_named_kernel_calls():
    """One traced layer ticks ``moe_gmm`` twice: the fused gate-up call
    at ``2·m·d·(2f)`` and ``down`` at ``2·m·f·d`` over the ``m`` =
    T·top_k sorted rows, each operand and result once; both calls carry
    the name the benchmark's ``moe_time_share.serve`` and
    ``moe_decode_roofline.serve`` find their op events by."""
    t, d, f, e, top_k = 24, 32, 128, 8, 2
    m = t * top_k
    z = lambda *shape: jnp.zeros(shape, jnp.bfloat16)
    layer = lambda x: moe.routed_experts(
        x, jnp.zeros((d, e)), jnp.zeros(e), z(e, d, f), z(e, d, f),
        z(e, f, d), top_k=top_k, route_scale=1.0)
    jaxpr = jax.make_jaxpr(layer)(jnp.zeros((t, d)))       # trace only
    rows = _kernel_work()
    assert rows[(K.MOE_GMM, "calls")] == 2
    assert rows[(K.MOE_GMM, "flops")] == \
        2.0 * m * d * (2 * f) + 2.0 * m * f * d
    # rows, gate, up in and h out; h, down in and ys (float32) out
    assert rows[(K.MOE_GMM, "bytes")] == \
        2 * (m * d + 2 * e * d * f + m * f) \
        + 2 * (m * f + e * f * d) + 4 * m * d
    dispatch = {(s["labels"]["path"], s["labels"]["reason"]): s["value"]
                for s in observe.REGISTRY.find(
                    "moe_dispatch_total").samples()}
    assert dispatch == {("grouped", ""): 1}

    def kernel_names(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from kernel_names(sub)
    names = list(kernel_names(jaxpr.jaxpr))
    assert len(names) == 2
    for name in names:
        assert re.search(K.instruction_pattern(K.MOE_GMM),
                         f"%{name}.7 = bf16[48,128] custom-call(")


# ------------------------------------------------------------ the plan
def test_the_default_plan_is_the_decoder_it_was():
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64)
    assert layer_plan(cfg) == ((frozenset({"full"}),
                                frozenset({"gelu"})),) * 2
    assert sorted(leaf_shapes(cfg)) == sorted(
        ["embed", "pos_embed", "ln_f", "lm_head"]
        + [f"l{i}.{w}" for i in range(2)
           for w in ("ln1", "ln2", "wq", "wk", "wv", "wo", "w1", "w2")])
    model = DecoderModel(init_decoder_params(cfg, 0), cfg)
    k, _ = model.new_pools(8, 4)
    assert k.shape == (2, 8, 4, 32) and k.dtype == jnp.float32
    assert model.routed_layers == 0
    assert model.attended_tokens([5, 7]) == 2 * 12


@pytest.mark.parametrize("plan,why", [
    (("full/gelu",), "entries for 2 layers"),
    (("full/gelu", "slow/gelu"), "attention is full, window or latent"),
    (("full/gelu", "full+window/gelu"), "attention is full, window or latent"),
    (("full/gelu", "full/shared"), "feed-forward is gelu"),
    (("full/gelu", "window/gelu"), "needs window > 0"),
    (("full/gelu", "full/routed"), "needs experts")])
def test_a_plan_the_decoder_cannot_run_is_refused(plan, why):
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                        plan=plan)
    with pytest.raises(PaddleTpuError, match=why):
        layer_plan(cfg)


# ------------------------------------------------------------ artifacts
def test_an_artifact_round_trips_the_plan(bench, tmp_path):
    """``export_decoder`` → ``from_artifact`` gives the same config
    (the plan a tuple again) and the same logits."""
    model = _model(bench, "float32")
    host = {k: np.asarray(v, np.float32) for k, v in model.params.items()}
    art = export_decoder(host, model.cfg, str(tmp_path / "art"),
                         quantize=None)
    loaded = DecoderModel.from_artifact(art)
    assert loaded.cfg == model.cfg and isinstance(loaded.cfg.plan, tuple)
    prompts = [list(range(2, 20))]
    a = _serve(model, prompts, 1)
    b = _serve(loaded, prompts, 1)
    for (_, la), (_, lb) in zip(a, b):
        np.testing.assert_array_equal(la, lb)


def test_an_artifact_from_before_the_plan_loads_as_the_default(tmp_path):
    """A manifest whose ``decoder`` holds only the seven keys it had
    before the plan loads as the default plan."""
    cfg = DecoderConfig(vocab=64, dim=32, heads=4, layers=2, ffn=64,
                        max_context=32)
    art = export_decoder(init_decoder_params(cfg, 1), cfg,
                         str(tmp_path / "old"), quantize=None)
    path = os.path.join(art, "manifest.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["decoder"] = {k: manifest["decoder"][k] for k in (
        "vocab", "dim", "heads", "layers", "ffn", "max_context", "eos_id")}
    with open(path, "w") as f:
        json.dump(manifest, f)
    loaded = DecoderModel.from_artifact(art, verify=False)
    assert loaded.cfg == cfg and loaded.cfg.plan == ()


# ------------------------------------------------------- through the server
def test_the_server_reports_what_its_routed_steps_did(bench):
    """Through ``InferenceServer``: the tokens are the reference's
    greedy tokens within the tolerance's reach, the decode span carries
    the routing counts and the attended positions, the prefill span
    ``moe_tokens``, and the gauge counts the pages behind the window."""
    model = _model(bench, "float32")
    server = InferenceServer(model, max_batch=4, n_pages=64, page_size=4,
                             continuous=True)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 256, n).tolist() for n in (30, 9)]
    ptrace.enable(fences=False)
    server.start()
    try:
        reqs = [server.submit(p, 8) for p in prompts]
        for r in reqs:
            assert r.done.wait(120) and r.state == "done", r.error
        spans = ptrace.events()
    finally:
        server.stop()
        ptrace.disable()
    for p, r in zip(prompts, reqs):
        seq = list(p)
        for tok in r.tokens:
            want = _reference_logits(bench, [seq])[0]
            assert want.max() - want[tok] < 1e-4
            seq.append(tok)
    steps = [e["args"] for e in spans if e["name"] == "serve_decode_step"]
    assert steps and all(
        {"experts_hit", "expert_load_max", "attended_tokens",
         "live_tokens"} <= set(a) for a in steps)
    assert all(a["attended_tokens"] <= 5 * a["live_tokens"] for a in steps)
    fills = [e["args"] for e in spans if e["name"] == "serve_prefill"]
    assert sum(a["moe_tokens"] for a in fills) == 4 * (30 + 9)
    gauge = observe.REGISTRY.find("serve_kv_pages_behind_window")
    assert gauge is not None and gauge.samples()
    flat = observe.REGISTRY.flat(kinds=("counter",))
    assert flat['moe_dispatch_total{path="grouped",reason=""}'] >= 1


# ------------------------------------------------- the pools, in place
def _dense_model(dense_bench):
    sizes, _, system, weights = dense_bench
    cfg = DecoderConfig(
        vocab=sizes["vocab_size"], dim=sizes["hidden_size"],
        heads=sizes["num_attention_heads"],
        layers=sizes["num_hidden_layers"], ffn=sizes["ffn_dim"],
        max_context=sizes["max_position_embeddings"])
    return DecoderModel({system.leaf_name(k): v
                         for k, v in weights.items()}, cfg)


@pytest.fixture(params=["default", "planned"])
def decoder(request, bench, dense_bench):
    """Both served decoders: the default plan (float32 pool, a K/V head
    a query head) and a planned one (bfloat16 pool, grouped K/V heads,
    window layers, routed experts)."""
    if request.param == "default":
        return _dense_model(dense_bench)
    return _model(bench, "bfloat16")


def test_the_default_plan_through_pages_is_its_reference(dense_bench):
    """The default plan's prefill and six decode steps, through the
    stacked pools, against the plain reference of its configuration
    (the planned decoder's: test_prefill_then_decode_through_pages…)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(2, 512, n).tolist() for n in (21, 5, 32)]
    for seqs, logits in _serve(_dense_model(dense_bench), prompts, 6):
        want = _reference_logits(dense_bench, seqs)
        assert np.abs(want - logits).max() < TOLERANCE["float32"]


def _recorded(impl, cfg):
    """``impl`` (the model's own ``_prefill_impl`` / ``_decode_impl``)
    jitted as the server jits it, pools donated, giving besides its
    results every layer's new K/V rows as ``paged_kv_write`` got
    them."""
    def run(params, k_pool, v_pool, *inputs):
        rows = []

        def write(k_pool, v_pool, k_new, v_new, *rest):
            rows.append((k_new, v_new))
            return paged_kv_write(k_pool, v_pool, k_new, v_new, *rest)
        real, decoder_module.paged_kv_write = \
            decoder_module.paged_kv_write, write
        # the slots of a plan that keeps no state: read by nothing
        slots = jnp.zeros(inputs[0].shape[:1], jnp.int32)
        try:
            return impl(params, (k_pool, v_pool), *inputs, slots, cfg), rows
        finally:
            decoder_module.paged_kv_write = real
    return jax.jit(run, donate_argnums=(1, 2))


def _a_layer_at_a_time(k_pool, v_pool, rows, tables, start, counts):
    """What the steps did before the pools were updated in place:
    ``paged_kv_write`` on each layer's own pool, put back in the
    stack."""
    k_pool, v_pool = np.array(k_pool), np.array(v_pool)
    for i, (k_new, v_new) in enumerate(rows):
        k_pool[i], v_pool[i] = paged_kv_write(
            jnp.asarray(k_pool[i]), jnp.asarray(v_pool[i]), k_new, v_new,
            jnp.asarray(tables), jnp.asarray(start), jnp.asarray(counts))
    return k_pool, v_pool


def _bits(a):
    """An array's bytes, so that bfloat16 compares exactly too."""
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def test_a_step_leaves_the_pools_as_a_layer_at_a_time_would(decoder):
    """A prefill of padded prompts and four decode steps with an idle
    slot: after each, both stacked pools are bit for bit what
    ``paged_kv_write`` gives on every layer's own pool, whole pool
    compared, so no dropped row (prompt padding, ``counts == 0``, the
    last layer's too) landed in another layer or wrapped around; and
    the served path gives those pools and those tokens."""
    cfg, page, width = decoder.cfg, 4, 4
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, cfg.vocab, n).tolist() for n in (21, 5, 30)]
    b, slots = len(prompts), cfg.max_context // page
    n_pages = 1 + width * slots
    tables = np.zeros((width, slots), np.int32)
    tables[:b] = 1 + rng.permutation(b * slots).reshape(b, slots)
    lengths = np.array([len(p) for p in prompts], np.int32)
    tokens = np.zeros((b, 32), np.int32)          # 32 > every prompt
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    k, v = decoder.new_pools(n_pages, page)
    assert k.shape == (cfg.layers, n_pages, page, k.shape[-1])
    want_k, want_v = np.asarray(k.array), np.asarray(v.array)

    def check(out, rows, start, counts, tab):
        nonlocal want_k, want_v
        want_k, want_v = _a_layer_at_a_time(want_k, want_v, rows, tab,
                                            start, counts)
        for got, want in ((out[2], want_k), (out[3], want_v),
                          (k.array, want_k), (v.array, want_v)):
            np.testing.assert_array_equal(_bits(got), _bits(want))

    prefill = _recorded(decoder_module._prefill_impl, cfg)
    out, rows = prefill(decoder.params, jnp.asarray(want_k),
                        jnp.asarray(want_v), tokens, lengths, tables[:b])
    nxt, logits, k, v = decoder.prefill(k, v, tokens, lengths, tables[:b])
    np.testing.assert_array_equal(nxt, np.asarray(out[0]))
    # the ids came to the host, the logits stayed on the device
    assert isinstance(nxt, np.ndarray) and isinstance(logits, jax.Array)
    np.testing.assert_array_equal(_bits(logits), _bits(out[1]))
    check(out, rows, np.zeros((b,), np.int32), lengths, tables[:b])
    assert np.abs(np.asarray(want_k, np.float32)).sum(axis=(1, 2, 3)).all()

    decode = _recorded(decoder_module._decode_impl, cfg)
    active = np.arange(width) < b
    for _ in range(4):
        fed = np.zeros((width,), np.int32)
        fed[:b] = nxt[:b]
        lengths = lengths + 1
        klen = np.where(active, np.resize(lengths, width), 1).astype(np.int32)
        out, rows = decode(decoder.params, jnp.asarray(want_k),
                           jnp.asarray(want_v), fed, tables, klen, active)
        nxt, logits, k, v, _ = decoder.decode(k, v, fed, tables, klen,
                                              active)
        np.testing.assert_array_equal(nxt, np.asarray(out[0])[:width])
        assert isinstance(nxt, np.ndarray) and isinstance(logits, jax.Array)
        np.testing.assert_array_equal(_bits(logits), _bits(out[1]))
        check(out, rows, klen - 1, active.astype(np.int32), tables)
    # the pages no row holds, each layer's scratch page among them,
    # were never written
    free = np.setdiff1d(np.arange(n_pages), tables[:b])
    assert SCRATCH_PAGE in free
    assert not _bits(want_k[:, free]).any() and not _bits(want_v[:, free]).any()


def test_a_launch_fed_from_the_one_before_is_the_host_fed_step(decoder):
    """Five decode launches queued one behind the other, each row fed
    on the device from whatever slot it had in the launch before (the
    rows change slots every step) and no id read in between, give bit
    for bit the ids, the logits and the pools of five steps whose ids
    the host read and fed back; and both ways run one program."""
    cfg, page, width = decoder.cfg, 4, 4
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, cfg.vocab, n).tolist() for n in (17, 6, 25)]
    b, slots = len(prompts), cfg.max_context // page
    tables = 1 + rng.permutation(b * slots).reshape(b, slots) \
        .astype(np.int32)
    tokens = np.zeros((b, 32), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lengths = np.array([len(p) for p in prompts], np.int32)

    def start():
        k, v = decoder.new_pools(1 + width * slots, page)
        first, _, k, v = decoder.prefill(k, v, tokens, lengths, tables)
        return k, v, first

    def inputs(step, order):
        """Row r sits in slot order[r] of this step."""
        tab = np.zeros((width, slots), np.int32)
        klen = np.ones((width,), np.int32)
        active = np.zeros((width,), bool)
        tab[order], klen[order], active[order] = \
            tables, lengths + step + 1, True
        return tab, klen, active

    orders = [rng.permutation(width)[:b] for _ in range(5)]
    k, v, nxt = start()
    want = []
    for step, order in enumerate(orders):
        tab, klen, active = inputs(step, order)
        fed = np.zeros((width,), np.int32)
        fed[order] = nxt
        ids, logits, k, v, _ = decoder.decode(k, v, fed, tab, klen, active)
        nxt = ids[order]
        want.append((nxt, np.asarray(logits)[order]))
    want_k, want_v = np.asarray(k.array), np.asarray(v.array)

    programs = decoder._decode._cache_size()
    k, v, first = start()
    launches, prev = [], None
    for step, order in enumerate(orders):
        tab, klen, active = inputs(step, order)
        fed = np.zeros((width,), np.int32)
        src = np.full((width,), -1, np.int32)
        if prev is None:
            fed[order] = first
        else:
            src[order] = orders[step - 1]
        prev = decoder.launch_decode(k, v, fed, tab, klen, active, prev, src)
        launches.append(prev)
    assert decoder._decode._cache_size() == programs
    for launch, order, (ids, logits) in zip(launches, orders, want):
        got, got_logits, _ = decoder.collect_decode(launch)
        np.testing.assert_array_equal(got[order], ids)
        np.testing.assert_array_equal(
            _bits(np.asarray(got_logits)[order]), _bits(logits))
    np.testing.assert_array_equal(_bits(k.array), _bits(want_k))
    np.testing.assert_array_equal(_bits(v.array), _bits(want_v))


def test_a_step_donates_its_pools(decoder):
    """The array a pool held before a step is deleted after it, the
    pool holds the step's result, and the caller's two objects are the
    ones that come back."""
    k, v = decoder.new_pools(9, 4)
    held = (k.array, v.array)
    out = decoder.prefill(k, v, np.full((1, 16), 3, np.int32),
                          np.array([7], np.int32),
                          np.arange(1, 9, dtype=np.int32)[None, :])
    assert out[2] is k and out[3] is v
    assert all(a.is_deleted() for a in held)
    held = (k.array, v.array)
    assert not any(a.is_deleted() for a in held)
    out = decoder.decode(k, v, np.array([5], np.int32),
                         np.arange(1, 9, dtype=np.int32)[None, :],
                         np.array([8], np.int32), np.array([True]))
    assert out[2] is k and out[3] is v
    assert all(a.is_deleted() for a in held)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(held[0])
    assert np.abs(np.asarray(k.array, np.float32)[:, 2]).sum() > 0


def test_the_benchmarks_warm_up_leaves_the_servers_pages(decoder):
    """``chipbench/drivers/serve_closed.py::warm`` sends the server's
    own two pool objects through several prefills and a decode step and
    drops the results: it runs, compiles the programs the loop then
    uses, writes nothing but each layer's scratch page, and the server
    serves as before."""
    from chipbench.drivers.serve_closed import warm

    server = InferenceServer(decoder, max_batch=4, n_pages=64, page_size=4,
                             continuous=True)
    prompt = np.random.default_rng(4).integers(2, 256, 13).tolist()
    server.start()
    try:
        first = server.submit(prompt, 6)
        assert first.done.wait(120) and first.state == "done", first.error
        k, v = server._k_pool, server._v_pool
        before = [np.asarray(p.array) for p in (k, v)]
        warm(server, {"blocks": [{"prompt_lens": [13, 30]}],
                      "admit_cap": 2}, decoder.cfg.vocab)
        assert server._k_pool is k and server._v_pool is v
        for pool, was in zip((k, v), before):
            now = np.asarray(pool.array)
            np.testing.assert_array_equal(
                _bits(now[:, SCRATCH_PAGE + 1:]),
                _bits(was[:, SCRATCH_PAGE + 1:]))
            assert _bits(now[:, SCRATCH_PAGE]).any()
        # what the loop launches from here on, warm has compiled: a
        # prompt length that only warm has run, and decode launches fed
        # from the device where warm's were fed from the host
        programs = (decoder._prefill._cache_size(),
                    decoder._decode._cache_size())
        again = server.submit(prompt, 6)
        longer = server.submit(prompt + prompt + [7, 8, 9], 6)
        for r in (again, longer):
            assert r.done.wait(120) and r.state == "done", r.error
        assert again.tokens == first.tokens
        assert (decoder._prefill._cache_size(),
                decoder._decode._cache_size()) == programs
    finally:
        server.stop()
