"""Device time under the program's own names (``ops/scopes.py``,
``utils/profiler.py``): the wire reader of a window's event metadata on
the repo's recorded chip trace, exclusive seconds by scope path, and the
scopes the served decoder and the layer engine enter: they name nearly
every op, and they change no compiled program."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import kernels as K
from paddle_tpu.ops import scopes as S
from paddle_tpu.serving import model as M
from paddle_tpu.utils import profiler

SMALL_TRACE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "tests", "data", "small.xplane.pb")


# ----------------------------------------------------------- the wire reader
@pytest.fixture(scope="module")
def xspace():
    with open(SMALL_TRACE, "rb") as f:
        return f.read()


def test_the_recorded_trace_gives_each_hlo_line_its_tf_op(xspace):
    table = profiler.read_ops(xspace)
    assert set(table) == {"/device:TPU:0",
                          "/device:CUSTOM:Megascale Trace"}
    ops = table["/device:TPU:0"]
    # 66 metadata entries: the async copy's start is there twice
    assert len(ops) == 65
    fusion = [info for line, info in ops.items()
              if line.startswith("%convolution_multiply_fusion = ")]
    assert fusion == [profiler.OpInfo(
        "jit(<lambda>)/dot_general", 4591950563644620049, 25165824,
        17196646400)]
    copy = next(info for line, info in ops.items()
                if line.startswith("%copy-done = "))
    assert copy.tf_op == "" and copy.bytes_accessed == 8388632


def test_the_table_joins_the_names_profile_data_gives(xspace):
    from jax.profiler import ProfileData

    ops = profiler.read_ops(xspace)["/device:TPU:0"]
    plane = next(p for p in ProfileData.from_serialized_xspace(xspace).planes
                 if p.name == "/device:TPU:0")
    names = {ev.name for line in plane.lines for ev in line.events}
    assert len(names) >= 4 and names <= set(ops)


@pytest.mark.parametrize("cut", [1, 5, 1000, 40000])
def test_a_truncated_file_raises(xspace, cut):
    with pytest.raises(ValueError, match="truncated"):
        profiler.read_ops(xspace[:-cut])


# ------------------------------------------------------------ scope_seconds
def _table(**paths):
    return {line: profiler.OpInfo(path, 1, 0, 0)
            for line, path in paths.items()}


def test_seconds_are_exclusive_under_nesting():
    """A ``while`` that holds two ops and a gap counts the gap and what
    the ops leave; the rows sum to the busy time."""
    ops = _table(loop="jit(f)/L0/ffn/while", a="jit(f)/L0/ffn/dense/dot",
                 b="jit(f)/head/dot")
    events = [(0.0, 10.0, "loop"), (1.0, 4.0, "a"), (5.0, 9.0, "a"),
              (2.0, 3.0, "b"), (12.0, 13.0, "b"), (12.0, 12.5, "nowhere")]
    rows = {line: (path, s)
            for path, line, s in profiler.scope_seconds(events, ops)}
    assert rows["loop"] == ("jit(f)/L0/ffn/while", pytest.approx(3.0))
    assert rows["a"] == ("jit(f)/L0/ffn/dense/dot", pytest.approx(6.0))
    assert rows["b"] == ("jit(f)/head/dot", pytest.approx(1.5))
    assert rows["nowhere"] == ("", pytest.approx(0.5))
    assert sum(s for _, s in rows.values()) == pytest.approx(11.0)


BACKWARD = "jit(step)/transpose(jvp(batch_norm))/res2a_bn/mul"


@pytest.mark.parametrize("path,scope,inside", [
    (BACKWARD, "batch_norm", True),
    (BACKWARD, "res2a_bn", True),
    (BACKWARD, "batch_norm/res2a_bn", True),
    (BACKWARD, "batch_norm/mul", False),
    ("jit(step)/jvp(my_batch_norm)/bn/mul", "batch_norm", False),
    ("jit(step)/jvp(batch_norm_x)/bn/mul", "batch_norm", False),
    ("jit(step)/optimizer/jvp(clip)/mul", "optimizer/clip", True),
    ("jit(f)/L12/mixer/cache_write/scatter", "L*/mixer", True),
    ("jit(f)/L12/mixer/cache_write/scatter", "mixer/cache_write", True),
    ("jit(f)/L12/mixer/cache_write/scatter", "L1/mixer", False),
    ("jit(f)/L12/mixer/cache_write/scatter", "mixer/scatter", False),
    ("jit(f)/L12/ffn/experts/moe_gmm/pallas_call", "ffn/*/moe_gmm", True),
    ("jit(f)/head", "head", True),
    ("", "head", False),
    (None, "head", False),
])
def test_a_scope_is_matched_as_whole_path_components(path, scope, inside):
    assert profiler.in_scope(path, scope) is inside


def test_a_line_two_programs_name_differently_is_under_neither():
    """``%fusion.3 = …`` of the prefill under ``head`` and of the decode
    step under ``embed``: one event name, two paths."""

    def field(number, payload):             # a length-delimited field
        n, size = len(payload), b""
        while n >= 0x80:
            size, n = size + bytes([n & 0x7F | 0x80]), n >> 7
        return bytes([number << 3 | 2]) + size + bytes([n]) + payload

    def entry(key, line, tf_op):            # one of event_metadata's map
        stat = b"\x08\x01" + field(5, tf_op)
        meta = field(2, line) + field(5, stat)
        return field(4, bytes([0x08, key]) + field(2, meta))

    plane = field(2, b"/device:TPU:0") \
        + entry(1, b"%fusion.3", b"jit(p)/head/dot:") \
        + entry(2, b"%fusion.3", b"jit(d)/embed/dot:") \
        + entry(3, b"%fusion.4", b"jit(d)/embed/dot:") \
        + field(5, b"\x08\x01" + field(2, b"\x08\x01" + field(2, b"tf_op")))
    ops = profiler.read_ops(field(1, plane))["/device:TPU:0"]
    assert ops["%fusion.3"].tf_op is None
    assert ops["%fusion.4"].tf_op == "jit(d)/embed/dot"
    rows = list(profiler.scope_seconds([(0., 1., "%fusion.3")], ops))
    assert rows == [(None, "%fusion.3", 1.0)]
    assert not profiler.in_scope(rows[0][0], "head") \
        and not profiler.in_scope(rows[0][0], "embed")


def test_a_window_keeps_its_table_until_the_next_opens(tmp_path):
    """On the CPU a window has no device plane: the table is there and
    empty, and gone while the next window is open."""
    with profiler.trace(str(tmp_path / "a")):
        jnp.ones((8, 8)).sum().block_until_ready()
    assert profiler.last_window_ops() == {}
    with profiler.trace(str(tmp_path / "b")):
        assert profiler.last_window_ops() is None
    assert profiler.last_window_ops() == {}


# --------------------------------------------- the decoder's scopes, lowered
BASE = dict(vocab=64, dim=32, heads=4, layers=2, ffn=64, max_context=32)
PLANS = {
    "dense": M.DecoderConfig(**BASE),
    "routed": M.DecoderConfig(
        **BASE, kv_heads=2, window=8, pos_embed=False, experts=4, top_k=2,
        expert_ffn=16, storage="bfloat16",
        plan=("window+rope+qknorm+gate+postnorm/swiglu",
              "full+rope/routed+shared")),
    "latent": M.DecoderConfig(
        **BASE, pos_embed=False, experts=4, top_k=2, expert_ffn=16,
        q_rank=16, kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8,
        rope_interleave=True, plan=("latent+rope/swiglu",
                                    "latent+rope/routed+shared")),
    "conv": M.DecoderConfig(
        **BASE, kv_heads=2, pos_embed=False, experts=4, top_k=2,
        expert_ffn=16, plan=("conv/swiglu", "full+rope+qknorm/routed")),
    "mamba": M.DecoderConfig(
        **BASE, kv_heads=1, pos_embed=False, conv_taps=4, ssm_inner=64,
        ssm_state=16, dt_rank=4, tied_head=True,
        plan=("mamba/swiglu", "full/swiglu")),
}
#: the scopes a plan word brings, beside those every plan has
EVERY = {"embed", "head", "cache_layout", "L1/ffn/norm"}
ATTENDS = {"L1/mixer/norm", "L1/mixer/qkv", "L1/mixer/cache_write",
           "L1/mixer/out"}
ROUTED = {"L1/ffn/route", "L1/ffn/sort", "L1/ffn/experts",
          "L1/ffn/combine"}
EXPECTED = {
    "dense": EVERY | ATTENDS | {"L0/ffn/dense", "L1/ffn/dense"},
    "routed": EVERY | ATTENDS | ROUTED | {"L0/ffn/dense", "L1/ffn/shared",
                                         "L1/mixer/attend"},
    "latent": EVERY | ATTENDS | ROUTED | {"L0/ffn/dense", "L1/ffn/shared",
                                         "L1/mixer/attend"},
    "conv": EVERY | ATTENDS | ROUTED | {"L0/mixer/conv", "L0/ffn/dense",
                                       "L1/mixer/attend"},
    "mamba": EVERY | ATTENDS | {"L0/mixer/ssm", "L0/ffn/dense",
                                "L1/ffn/dense", "L1/mixer/attend"},
}


@pytest.fixture
def mosaic(monkeypatch):
    """Lower the kernels as the chip gets them, one ``tpu_custom_call``
    each, where the CPU would unroll the Pallas interpreter into the
    step's text."""
    from paddle_tpu.ops import pallas_attention, pallas_moe, pallas_ssm

    for module in (pallas_attention, pallas_moe, pallas_ssm):
        monkeypatch.setattr(module, "pallas_interpret", lambda: False)
    monkeypatch.setattr(pallas_ssm, "is_tpu", lambda: True)
    M._jitted_steps.cache_clear()       # the steps are cached a config
    pallas_attention._decode_call.clear_cache()     # and so is this call
    yield
    # nor may a later test of this process be handed a Mosaic call
    M._jitted_steps.cache_clear()
    pallas_attention._fa_sparse_call.clear_cache()
    pallas_attention._decode_call.clear_cache()


def _lowered(cfg, step):
    """The jitted ``step`` of a toy decoder of ``cfg``, batch 2 (prompts
    of 8) over pools of 6 pages of 4, lowered for the TPU."""
    model = M.DecoderModel(M.init_decoder_params(cfg, 0), cfg)
    prefill, decode = M._jitted_steps(cfg)
    pools = [p.array for p in model.new_pools(6, 4, 3)]
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    if step == "prefill":
        traced = prefill.trace(model.params, *pools, i32(2, 8), i32(2),
                               i32(2, 2), i32(2))
    else:
        traced = decode.trace(model.params, *pools, i32(2), i32(2), i32(2),
                              i32(2, 2), i32(2), jnp.zeros((2,), bool),
                              i32(2))
    return traced.lower(lowering_platforms=("tpu",))


def _op_names(lowered, bare=False):
    """``(scope path, the op's line)`` of every op of a lowered step
    whose location names one (``bare``: or names the op alone)."""
    text = lowered.as_text(debug_info=True)
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    return [(named[m.group(1)], m.group(0)) for m in re.finditer(
        r"^.*loc\((#loc\d+)\)$", text, re.M)
        if "/" in named.get(m.group(1), "")
        or bare and m.group(1) in named]


LAYER = re.compile(r"/L\d+/(mixer|ffn)(/|$)")


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_decoders_ops_carry_a_path_of_the_table(plan, step, mosaic):
    names = [n for n, _ in _op_names(_lowered(PLANS[plan], step))]
    assert len(names) > 300
    top = (S.EMBED, S.HEAD, S.CACHE_LAYOUT)
    under = [n for n in names if LAYER.search(n)
             or any(profiler.in_scope(n, s) for s in top)]
    # the default plan's decode call stands bare, and with it what its
    # wrapper makes of the lengths and, at toy widths, the padded rows
    enough = 0.90 if (plan, step) == ("dense", "decode") else 0.95
    assert len(under) >= enough * len(names), sorted(
        set(names) - set(under))
    expected = set(EXPECTED[plan])
    if plan == "dense" and step == "prefill":
        expected.add("L1/mixer/attend")   # at decode its call stands bare
    assert not {s for s in expected
                if not any(profiler.in_scope(n, s) for n in names)}
    # and every name a layer's path uses is the table's
    words = {w for s in S.SCOPE_NAMES.values() for w in s.split("/")}
    for n in filter(LAYER.search, names):
        kind, part = n[LAYER.search(n).start() + 1:].split("/")[1:3]
        assert kind in words and (part in words or kind == "ffn"), n


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_the_default_plans_decode_call_stands_under_no_scope(plan, mosaic):
    """An unnamed ``pallas_call`` is named by the scope it is traced
    under: the default plan's must keep the ``_lambda_`` of the jits
    around it (the step's, and the call's own, which its layers
    share), by which the benchmark finds it; a planned decoder's calls
    pass ``name=`` and lie under ``attend`` and ``experts``."""
    calls = [n for n, line in _op_names(_lowered(PLANS[plan], "decode"),
                                        bare=True)
             if "@tpu_custom_call" in line]
    assert calls
    for n in calls:
        if plan == "dense":
            # in the body of the jit its layers share, under nothing
            assert n == "pallas_call", n
        else:
            assert profiler.in_scope(n, "ffn/experts/moe_gmm") \
                or profiler.in_scope(n, "mixer/attend/paged_decode") \
                or profiler.in_scope(n, "mixer/attend/latent_decode"), n


def _without_locations(lowered):
    """A lowered step's text less every location: its own and, inside
    each kernel's serialized Mosaic module, the kernel's."""
    import base64

    from jax._src.lib.mlir import ir, passmanager

    def kernel(match):
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            passmanager.PassManager.parse(
                "builtin.module(strip-debuginfo)").run(module.operation)
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'(?<=body\\22: \\22)([A-Za-z0-9+/=]+)', kernel,
                  lowered.as_text())


@pytest.mark.parametrize("step", ["prefill", "decode"])
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_a_scope_changes_no_compiled_program(plan, step, mosaic,
                                             monkeypatch):
    """The lowered step less its locations is the same text with the
    scopes entered or not."""
    with_scopes = _lowered(PLANS[plan], step)
    assert "/mixer/" in with_scopes.as_text(debug_info=True)
    M._jitted_steps.cache_clear()
    real = jax.named_scope
    kernels = set(K.KERNEL_NAMES.values())
    # the kernels' own ``name=`` scope is JAX's, on both sides
    monkeypatch.setattr(jax, "named_scope", lambda name: real(name)
                        if name in kernels else contextlib.nullcontext())
    without = _lowered(PLANS[plan], step)
    assert "/mixer/" not in without.as_text(debug_info=True)
    plain = _without_locations(without)
    assert "@tpu_custom_call" in plain and "stable_mosaic" in plain
    assert plain == _without_locations(with_scopes)


# ------------------------------------------------------- the layer engine
def test_a_layer_runs_under_its_type_and_inside_it_its_name():
    """A conv + batch-norm + fc net under the trainer: forward and
    backward ops of a layer read ``<type>/<name>`` inside the
    transformations, the update ``optimizer``."""
    import numpy as np

    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.observe import costmodel
    from paddle_tpu.trainer.trainer import Trainer

    with config_scope():
        img = dsl.data("image", dense_vector(4 * 6 * 6), height=6, width=6)
        lab = dsl.data("label", integer_value(2))
        c1 = dsl.img_conv(img, filter_size=3, num_filters=4, stride=1,
                          padding=1, num_channels=4,
                          act=dsl.LinearActivation(), name="c1")
        bn1 = dsl.batch_norm(c1, act=dsl.ReluActivation(), name="bn1")
        out = dsl.fc(bn1, size=2, act=dsl.SoftmaxActivation(), name="out")
        cfg = dsl.topology(dsl.classification_cost(out, lab, name="cost"))
    trainer = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="momentum", momentum=0.9, learning_rate=0.05))
    rng = np.random.RandomState(0)
    feed = {"image": jnp.asarray(rng.randn(4, 144).astype(np.float32)),
            "label": jnp.asarray(rng.randint(0, 2, 4), jnp.int32)}
    trainer.train_one_batch(feed)
    names = [n for n, _ in _op_names(trainer._train_step.lower(
        *costmodel._step_args(trainer, feed)))]
    # the 3x3 conv runs inside the batch norm it is fused with
    for scope in ("batch_norm/bn1", "fc/out", S.OPTIMIZER):
        assert any(profiler.in_scope(n, scope) for n in names), scope
    backward = [n for n in names if profiler.in_scope(n, "batch_norm/bn1")
                and "transpose(" in n]
    assert backward and all(profiler.in_scope(n, "batch_norm")
                            for n in backward)
    types = {layer.conf.type for layer in trainer.network.layers.values()}
    typed = [n for n in names
             if any(profiler.in_scope(n, t) for t in types | {S.OPTIMIZER})]
    assert len(typed) >= 0.9 * len(names), sorted(set(names) - set(typed))
