"""Kernels the trace can name (``ops/kernels.py``): every
``pl.pallas_call`` passes ``name=`` from the one table, and the work
counter ticks what the op is from the shapes at the call."""

import ast
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import observe
from paddle_tpu.ops import kernels as K

OPS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "paddle_tpu", "ops")
#: the one call whose name is its caller's to give: the default plan's
#: decoder gives none, so that the accepted benchmark metric
#: ``paged_decode_roofline.serve`` finds the kernel as ``%_lambda_…``,
#: the instruction name it inherits (see the call site; PERF.md §7
#: queues the rename); a decoder with a layer plan gives
#: ``K.PAGED_DECODE`` (``serving/model.py``)
CALLERS_NAME = {("pallas_attention.py", "_decode_pallas")}


def _sites():
    """(file, enclosing top-level function, the Call node, that
    function's node) of every ``pl.pallas_call`` in
    ``paddle_tpu/ops/pallas_*.py``."""
    out = []
    for path in sorted(glob.glob(os.path.join(OPS, "pallas_*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for fn in tree.body:
            if not isinstance(fn, ast.FunctionDef):
                continue
            fn.module = tree
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call":
                    out.append((os.path.basename(path), fn.name, node,
                                fn))
    return out


SITES = _sites()


def _table_constants(node, fn):
    """The table constants an expression can evaluate to: ``K.X``, a
    conditional of those, or a local name assigned one of those."""
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id == "K":
        return {node.attr}
    if isinstance(node, ast.IfExp):
        return _table_constants(node.body, fn) \
            | _table_constants(node.orelse, fn)
    if isinstance(node, ast.Name):
        params = [a.arg for a in fn.args.args]
        if node.id in params:
            return _passed_by_callers(fn, params.index(node.id), node.id)
        found = set()
        for stmt in ast.walk(fn):
            if isinstance(stmt, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == node.id
                    for t in stmt.targets):
                found |= _table_constants(stmt.value, fn)
        return found
    return {"<not from the table>"}


def _passed_by_callers(fn, index, param):
    """What every call of ``fn`` in its module passes as its parameter
    ``param`` (position ``index``): a launcher that several kernels
    share takes its name from its callers."""
    found = set()
    for caller in fn.module.body:
        if not isinstance(caller, ast.FunctionDef):
            continue
        caller.module = fn.module
        for call in ast.walk(caller):
            if isinstance(call, ast.Call) and isinstance(call.func, ast.Name) \
                    and call.func.id == fn.name:
                given = [kw.value for kw in call.keywords if kw.arg == param] \
                    or call.args[index:index + 1]
                found |= _table_constants(given[0], caller) if given \
                    else {"<not passed>"}
    return found or {"<never called>"}


def test_the_walk_finds_every_site():
    assert len(SITES) == 23
    assert len(set(K.KERNEL_NAMES.values())) == len(K.KERNEL_NAMES)
    used = set()
    for _, _, call, fn in SITES:
        for kw in call.keywords:
            if kw.arg == "name":
                used |= _table_constants(kw.value, fn)
    # a name nobody passes is a metric that can never read: only the
    # two that the serving decoder hands down are given outside ops/
    assert set(K.KERNEL_NAMES) - used == {"PAGED_DECODE", "BLOCK_DECODE"}
    with open(os.path.join(os.path.dirname(OPS), "serving",
                           "model.py")) as f:
        text = f.read()
    assert "name = K.PAGED_DECODE if cfg.plan else None" in text
    assert "name=K.BLOCK_DECODE" in text


@pytest.mark.parametrize(
    "fname,func,call,fn", SITES,
    ids=[f"{f}:{fn}:{c.lineno}" for f, fn, c, _ in SITES])
def test_every_pallas_call_is_named_from_the_table(fname, func, call, fn):
    names = [kw.value for kw in call.keywords if kw.arg == "name"]
    if (fname, func) in CALLERS_NAME:
        # name=<the function's own ``name`` parameter>, None by default
        assert [n.id for n in names] == ["name"]
        arg = [a.arg for a in fn.args.kwonlyargs].index("name")
        default = fn.args.kw_defaults[arg]
        assert default.value is None, \
            "the default plan's call must stay unnamed"
        # and so is the public wrapper's, which hands it down
        import inspect

        from paddle_tpu.ops import pallas_attention as pa
        assert inspect.signature(pa.paged_decode_attention) \
            .parameters["name"].default is None
        return
    assert len(names) == 1, f"{fname}:{call.lineno} passes no name="
    constants = _table_constants(names[0], fn)
    assert constants and constants <= set(K.KERNEL_NAMES), constants


def test_instruction_pattern_finds_a_kernel_behind_its_wrappers():
    lines = ["%conv_bn_fwd.1 = (bf16[2,8,8,64]) custom-call(",
             "%jvp_conv_bn_fwd_.12 = bf16[2,8,8,64] custom-call(",
             "  ROOT %conv_bn_fwd = bf16[2] custom-call(",
             "%conv_bn_fwd_bwd.1 = (bf16[2]) custom-call(",
             "%transpose_jvp_flash_bwd_dq__.1 = f32[8] custom-call(",
             "%jvp___exconv_1___.2 = bf16[2] custom-call(%conv_bn_fwd.1)"]
    hits = lambda name: [bool(re.search(K.instruction_pattern(name), l))
                         for l in lines]
    assert hits(K.CONV_BN_FWD) == [True, True, True, False, False, False]
    assert hits(K.CONV_BN_FWD_BWD) == [False, False, False, True, False,
                                       False]
    assert hits(K.FLASH_BWD_DQ) == [False] * 4 + [True, False]


def _work():
    rows = {}
    for s in observe.REGISTRY.find("pallas_kernel_work_total").samples():
        rows[(s["labels"]["kernel"], s["labels"]["kind"])] = s["value"]
    return rows


def test_conv_bn_pair_ticks_its_flops_per_direction():
    """A ResNet-style BN→conv→BN sandwich (the chain pair) and a plain
    conv→BN pair, traced forward and backward: each kernel's FLOPs are
    2·N·H·W·9·Cin·Cout, its bytes every operand and result once."""
    from paddle_tpu.ops import nn_ops

    n, h, w, cin, cout = 2, 8, 8, 64, 128
    conv = 2.0 * n * h * w * 9 * cin * cout
    z = jnp.ones((n, h, w, cin), jnp.bfloat16)
    wt = jnp.ones((3, 3, cin, cout), jnp.bfloat16)
    a = c = jnp.ones((cin,), jnp.float32)
    g = b = rm = jnp.ones((cout,), jnp.float32)

    def chain(z, wt):
        y, _, _ = nn_ops.conv2d_bn(z, wt, None, g, b, rm, rm,
                                   in_affine=(a, c, "relu"))
        return jnp.sum(y.astype(jnp.float32))

    def pair(z, wt):
        y, _, _ = nn_ops.conv2d_bn(z, wt, None, g, b, rm, rm)
        return jnp.sum(y.astype(jnp.float32))

    jax.eval_shape(jax.grad(chain, (0, 1)), z, wt)     # trace only
    rows = _work()
    for kernel in (K.CONV_BN_FWD, K.CONV_BN_CHAIN_BWD):
        assert rows[(kernel, "calls")] == 1
        assert rows[(kernel, "flops")] == conv
    # activations and weights travel in the policy's compute dtype
    from paddle_tpu.core.dtypes import current_policy
    size = jnp.dtype(current_policy().compute_dtype).itemsize
    act_in, act_out = n * h * w * cin * size, n * h * w * cout * size
    weights = 9 * cin * cout * size
    assert rows[(K.CONV_BN_FWD, "bytes")] == \
        act_in + 8 * cin * 4 + weights + act_out
    assert (K.CONV_BN_DX, "calls") not in rows

    jax.eval_shape(jax.grad(pair, (0, 1)), z, wt)
    rows = _work()
    assert rows[(K.CONV_BN_DX, "calls")] == 1
    assert rows[(K.CONV_BN_DX, "flops")] == conv
    # dy, z, coefficients, flipped weights in; dx and dz out
    assert rows[(K.CONV_BN_DX, "bytes")] == \
        2 * act_out + 8 * cout * 4 + weights + act_in + act_out
    assert rows[(K.CONV_BN_FWD, "calls")] == 1          # XLA's forward


def test_flash_ticks_the_unmasked_blocks():
    from paddle_tpu.ops import pallas_attention as pa

    b, t, h, d, blk = 1, 256, 2, 64, 128
    q = jnp.ones((b, t, h, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, None, True, blk, blk)
                       .astype(jnp.float32))

    jax.eval_shape(jax.grad(loss, (0, 1, 2)), q, q, q)
    rows = _work()
    # causal 2×2 blocks: 3 of 4 live; 4·d FLOPs a position pair
    want = 4.0 * d * blk * blk * 3 * b * h
    for kernel in (K.FLASH_FWD, K.FLASH_BWD_DQ, K.FLASH_BWD_DKV):
        assert rows[(kernel, "calls")] == 1
        assert rows[(kernel, "flops")] == want
