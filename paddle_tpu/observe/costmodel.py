"""Per-region roofline/MFU attribution over compiled XLA executables.

A whole-step FLOP or byte count is one number per step, with no way to
see *which* layer is the bottleneck.  This module is the attribution
half of the performance observatory:

- :func:`analyze_trainer_step` lowers the trainer's compiled train step,
  parses the **optimized HLO text** (``Compiled.as_text()``) and breaks
  FLOPs / HBM bytes down **per fused region**, keyed back to network
  layer names through the ``jax.named_scope`` each layer executes under
  (``layers/network.py`` threads the layer name into XLA's ``op_name``
  metadata; autodiff wraps it as ``jvp(name)`` / ``transpose(jvp(name))``
  so forward and backward cost of one layer land in one region);
- each region gets a **roofline verdict** — compute- vs memory-bound
  against the detected chip peaks (:func:`detect_peaks`), with
  arithmetic intensity and a peak-bound time estimate;
- :func:`mfu` is the model-level MFU the ``--roofline_dump`` report is
  stamped with: measured-step FLOPs over ``time x peak x chips``.

Counting conventions (deliberately XLA-compatible so the per-region
costs reconcile against ``Compiled.cost_analysis()``):

- every computation is counted ONCE (``total_flops`` matches XLA's
  ``flops``, which does NOT multiply a ``while`` body by its trip
  count); the *executed* cost — what the roofline and MFU use — is the
  trip-count-amortized ``flops_per_step`` (trip counts recovered from
  the loop-condition ``compare(lt, constant)`` pattern ``lax.scan``
  emits);
- transcendentals (tanh/exp/...) are tracked separately (``trans``),
  again matching XLA's split, but count as work for roofline/MFU;
- HBM bytes are charged at **kernel granularity**: instructions inside
  a fusion/called computation touch VMEM/registers, not HBM, so only
  top-level (entry / loop-body) instructions and fusion/call sites
  contribute operand+result bytes — the same model behind the round-7
  fused-kernel traffic arithmetic;
- ``custom-call`` regions (the Pallas kernels) are **opaque**: XLA
  reports zero FLOPs for them and so does this parser (bytes are still
  charged from the call-site shapes).  A step containing opaque regions
  reports them (``opaque_custom_calls``), so a reader knows its FLOP
  total leaves the kernels out.

jax is imported lazily (function scope) — the parser itself is pure
text and testable without a backend; the zero-dependency rule of
:mod:`paddle_tpu.observe` holds for module import.
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

# ------------------------------------------------------------- shapes
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _shape_stats(text: str) -> Tuple[int, int]:
    """(total bytes, total elements) over every array shape token in
    ``text`` — tuples contribute the sum of their elements."""
    bytes_, elems = 0, 0
    for dtype, dims in _SHAPE_RE.findall(text):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue                      # token/opaque types
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        bytes_ += n * size
    return bytes_, elems


def _split_top_level(s: str) -> List[str]:
    """Split an operand list on top-level commas (brackets tracked)."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


# -------------------------------------------------------------- parser
# A computation header is "<name> (params) -> result {": the name is
# followed directly by its parameter list (instructions read "<name> =
# ..."), and tuple-typed parameters nest parens — (p: (s32[], f32[8]))
# — so the params cannot be regexed away; matching up to the first "("
# and requiring the "-> ... {" tail is enough to tell headers apart.
_COMP_NAME_RE = re.compile(r"^%?([\w.\-]+)\s*\(")


def _comp_header(line: str) -> Optional[Tuple[str, bool]]:
    """(name, is_entry) when ``line`` is a computation header."""
    s = line.strip()
    if not s.endswith("{") or "->" not in s:
        return None
    is_entry = s.startswith("ENTRY ")
    if is_entry:
        s = s[len("ENTRY "):].lstrip()
    m = _COMP_NAME_RE.match(s)
    return (m.group(1), is_entry) if m else None
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_ATTR_RES = {
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "body": re.compile(r"body=%?([\w.\-]+)"),
    "condition": re.compile(r"condition=%?([\w.\-]+)"),
    "lhs_contracting": re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}"),
    "rhs_contracting": re.compile(r"rhs_contracting_dims=\{([0-9,]*)\}"),
    "lhs_batch": re.compile(r"lhs_batch_dims=\{([0-9,]*)\}"),
    "rhs_batch": re.compile(r"rhs_batch_dims=\{([0-9,]*)\}"),
    "custom_call_target": re.compile(r'custom_call_target="([^"]*)"'),
    "feature_group_count": re.compile(r"feature_group_count=(\d+)"),
    "dim_labels": re.compile(r"dim_labels=(\S+?)(?:,|\s|$)"),
    "branches": re.compile(r"branch_computations=\{([^}]*)\}"),
}

#: Opcodes that move/alias data without arithmetic (FLOPs 0 — matches
#: XLA's convention closely enough for the reconciliation tolerance).
_ZERO_FLOP = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "copy", "copy-start", "copy-done", "reshape", "transpose",
    "broadcast", "slice", "dynamic-slice", "dynamic-update-slice",
    "concatenate", "pad", "reverse", "iota", "convert", "gather",
    "after-all", "optimization-barrier", "partition-id", "replica-id",
    "rng-bit-generator", "rng", "infeed", "outfeed", "domain",
    "custom-call", "call", "fusion", "while", "conditional",
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "send", "recv", "bitcast-convert", "real", "imag", "sort",
))

#: Transcendental opcodes — XLA counts these in its separate
#: ``transcendentals`` bucket, not ``flops``.
_TRANS_OPS = frozenset((
    "tanh", "exp", "expm1", "log", "log1p", "logistic", "sqrt", "rsqrt",
    "cbrt", "sine", "cosine", "tan", "atan2", "power", "erf",
))


class _Instr:
    __slots__ = ("name", "opcode", "result", "operands", "line",
                 "op_name", "attrs")

    def __init__(self, name, opcode, result, operands, line, op_name,
                 attrs):
        self.name = name
        self.opcode = opcode
        self.result = result          # result shape text
        self.operands = operands      # operand list text (inside parens)
        self.line = line              # full line (attribute regexes)
        self.op_name = op_name
        self.attrs = attrs            # parsed attribute dict


class _Computation:
    __slots__ = ("name", "is_entry", "instrs")

    def __init__(self, name: str, is_entry: bool):
        self.name = name
        self.is_entry = is_entry
        self.instrs: List[_Instr] = []


def _operand_segment(line: str) -> str:
    """Text inside the instruction's top-level operand parens."""
    i = line.find("(")
    if i < 0:
        return ""
    depth = 0
    for j in range(i, len(line)):
        if line[j] == "(":
            depth += 1
        elif line[j] == ")":
            depth -= 1
            if depth == 0:
                return line[i + 1:j]
    return line[i + 1:]


_BARE_OPERAND_RE = re.compile(r"^\s*%?([\w.\-]+)\s*$")


def _typed_operands(segment: str, shapes: Dict[str, str]) -> str:
    """Operand list with every bare ``%name`` reference given back its
    shape.  XLA (jaxlib 0.9) prints operands by name only; the cost
    formulas below read operand shapes from this text, so each name is
    resolved against the results already defined in its computation
    (HLO text is def-before-use).  Operands that still carry an inline
    shape pass through unchanged."""
    out = []
    for tok in _split_top_level(segment):
        m = _BARE_OPERAND_RE.match(tok)
        if m and m.group(1) in shapes:
            tok = f"{shapes[m.group(1)]} %{m.group(1)}"
        out.append(tok)
    return ",".join(out)


def parse_hlo(text: str) -> Dict[str, _Computation]:
    """Optimized HLO module text → ``{computation name: _Computation}``
    (the entry computation has ``is_entry`` set)."""
    comps: Dict[str, _Computation] = {}
    cur: Optional[_Computation] = None
    shapes: Dict[str, str] = {}       # result shape by name, per comp
    for raw in text.splitlines():
        line = raw.rstrip()
        if cur is None:
            head = _comp_header(line)
            if head is not None:
                cur = _Computation(*head)
                comps[cur.name] = cur
                shapes = {}
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, result, opcode = m.group(1), m.group(2), m.group(3)
        attrs: Dict[str, Any] = {}
        for key, rx in _ATTR_RES.items():
            am = rx.search(line)
            if am:
                attrs[key] = am.group(1)
        opn = _OP_NAME_RE.search(line)
        cur.instrs.append(_Instr(
            name, opcode, result,
            _typed_operands(_operand_segment(line), shapes), line,
            opn.group(1) if opn else "", attrs))
        shapes[name] = result
    return comps


# --------------------------------------------------------- cost of one
def _dims_prod(shape_text: str, dims: Sequence[int]) -> int:
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return 1
    sizes = [int(d) for d in m.group(2).split(",") if d]
    out = 1
    for d in dims:
        if 0 <= d < len(sizes):
            out *= sizes[d]
    return out


def _parse_int_list(s: str) -> List[int]:
    return [int(x) for x in s.split(",") if x.strip()]


def _instr_flops(instr: _Instr) -> Tuple[float, float]:
    """(flops, transcendentals) of one instruction, XLA-style."""
    out_bytes, out_elems = _shape_stats(instr.result)
    op = instr.opcode
    if op == "dot":
        operands = _split_top_level(instr.operands)
        if len(operands) < 2:
            return 0.0, 0.0
        lhs, rhs = operands[0], operands[1]
        _, lhs_elems = _shape_stats(lhs)
        rcd = _parse_int_list(instr.attrs.get("rhs_contracting", ""))
        rbd = _parse_int_list(instr.attrs.get("rhs_batch", ""))
        _, rhs_elems = _shape_stats(rhs)
        shared = _dims_prod(rhs, rcd) * _dims_prod(rhs, rbd)
        return 2.0 * lhs_elems * (rhs_elems / max(shared, 1)), 0.0
    if op == "convolution":
        operands = _split_top_level(instr.operands)
        if len(operands) < 2:
            return 0.0, 0.0
        rhs = operands[1]
        _, k_elems = _shape_stats(rhs)
        # dim_labels like b01f_01io->b01f: 'o' indexes output features
        labels = instr.attrs.get("dim_labels", "")
        kernel_labels = labels.split("_")[1].split("-")[0] \
            if "_" in labels else ""
        o_dim = kernel_labels.find("o")
        m = _SHAPE_RE.search(rhs)
        o = 1
        if m and o_dim >= 0:
            sizes = [int(d) for d in m.group(2).split(",") if d]
            if o_dim < len(sizes):
                o = sizes[o_dim]
        groups = int(instr.attrs.get("feature_group_count", 1) or 1)
        taps = k_elems / max(o, 1) / max(groups, 1)
        return 2.0 * out_elems * taps, 0.0
    if op in _TRANS_OPS:
        return 0.0, float(out_elems)
    if op in _ZERO_FLOP:
        return 0.0, 0.0
    if op in ("reduce", "reduce-window", "select-and-scatter", "scatter",
              "map"):
        _, in_elems = _shape_stats(instr.operands)
        return float(max(in_elems, out_elems)), 0.0
    # default: one op per output element (add/mul/select/compare/...)
    return float(out_elems), 0.0


def _while_trip_count(instr: _Instr,
                      comps: Dict[str, _Computation]) -> int:
    """Recover a static trip count from the ``lax.scan`` loop shape:
    the condition computation's ROOT is ``compare(counter, constant)``
    direction=LT and the bound constant is defined in the condition.
    Returns 1 when the pattern doesn't match (honest under-estimate)."""
    cond = comps.get(instr.attrs.get("condition", ""))
    if cond is None:
        return 1
    root = cond.instrs[-1] if cond.instrs else None
    if root is None or root.opcode != "compare" \
            or "direction=LT" not in root.line:
        return 1
    consts = {}
    for i in cond.instrs:
        if i.opcode == "constant":
            m = re.search(r"constant\((-?\d+)\)", i.line)
            if m:
                consts[i.name] = int(m.group(1))
    for name in re.findall(r"%([\w.\-]+)", root.operands):
        if name in consts and consts[name] > 0:
            return consts[name]
    return 1


# ------------------------------------------------------------- regions
_WRAP_RE = re.compile(r"([^()]+)\((.*)\)$")


def _region_of(op_name: str, known: frozenset) -> Tuple[str, bool]:
    """(region, is_backward) for an ``op_name`` metadata path: the
    innermost path component whose unwrapped token (``transpose(jvp(x))``
    → ``x``) is a known region name; backward iff an autodiff
    ``transpose(...)`` wrapper encloses it.  JAX closes a wrapper
    behind the first name under it, and a layer's name follows its type
    (``transpose(jvp(fc))/out/dot_general``, ``layers/network.py``), so
    a wrapper encloses the components behind its own too."""
    region, bwd, transposed = "_unattributed", False, False
    for comp in op_name.split("/"):
        tokens = []
        cur = comp
        while True:
            m = _WRAP_RE.match(cur)
            if not m:
                tokens.append(cur)
                break
            tokens.append(m.group(1))
            cur = m.group(2)
        hit = None
        for t in tokens:
            if t in known:
                hit = t
        transposed = transposed or "transpose" in tokens[:-1]
        if hit is not None:
            region, bwd = hit, transposed
    return region, bwd


_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")


def _streaming_discount(instr: _Instr,
                        comps: Dict[str, _Computation]) -> float:
    """HBM bytes to discount for slice-granularity access patterns —
    the shapes every ``lax.scan`` body reads/writes its buffers
    through.  XLA aliases a ``dynamic-update-slice`` result to the
    updated operand and streams only the slice, and a
    ``dynamic-slice`` reads only the slice, so charging whole buffers
    as read+written per trip overstates a 100-trip scan's traffic
    ~buffer/slice-fold (XLA's own ``bytes accessed`` counts slices).
    Covers the bare opcodes and fusions that consume a parameter via
    ``dynamic-slice`` or root in a ``dynamic-update-slice``."""
    op = instr.opcode
    if op == "dynamic-update-slice":
        res_bytes, _ = _shape_stats(instr.result)
        ops = _split_top_level(instr.operands)
        upd = _shape_stats(ops[1])[0] if len(ops) > 1 else 0
        return max(2.0 * (res_bytes - upd), 0.0)
    if op == "dynamic-slice":
        ops = _split_top_level(instr.operands)
        src = _shape_stats(ops[0])[0] if ops else 0
        res_bytes, _ = _shape_stats(instr.result)
        return max(float(src - res_bytes), 0.0)
    if op != "fusion":
        return 0.0
    callee = comps.get(instr.attrs.get("calls")
                       or instr.attrs.get("to_apply", ""))
    if callee is None:
        return 0.0
    discount = 0.0
    params: Dict[str, int] = {}
    for i in callee.instrs:
        if i.opcode == "parameter":
            params[i.name] = _shape_stats(i.result)[0]
    for i in callee.instrs:
        if i.opcode == "dynamic-slice":
            ops = _split_top_level(i.operands)
            m = _OPERAND_NAME_RE.search(ops[0]) if ops else None
            if m and m.group(1) in params:
                discount += max(params.pop(m.group(1))
                                - _shape_stats(i.result)[0], 0)
    if callee.instrs and callee.instrs[-1].opcode \
            == "dynamic-update-slice":
        res_bytes, _ = _shape_stats(instr.result)
        ops = _split_top_level(callee.instrs[-1].operands)
        upd = _shape_stats(ops[1])[0] if len(ops) > 1 else 0
        discount += max(2.0 * (res_bytes - upd), 0.0)
    return discount


def attribute(text: str, known: Iterable[str] = ()) -> Dict[str, Any]:
    """Parse + attribute one optimized HLO module.

    Returns ``{"regions": {name: {...}}, "total_flops",
    "total_trans", "total_bytes", "flops_per_step", "bytes_per_step",
    "opaque_calls": [target names], "while_trips": {instr: n}}`` —
    totals follow the XLA count-each-computation-once convention,
    ``*_per_step`` amortize loop bodies by their recovered trip count.
    """
    comps = parse_hlo(text)
    known = frozenset(known)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {"regions": {}, "total_flops": 0.0, "total_trans": 0.0,
                "total_bytes": 0.0, "flops_per_step": 0.0,
                "bytes_per_step": 0.0, "opaque_calls": [],
                "while_trips": {}}

    # computation roles + executed-count multipliers, propagated from
    # the entry (HLO computations cannot recurse, so this terminates):
    # kernel-level computations (entry, while body/cond, conditional
    # branches) charge HBM bytes; fusion/to_apply callees do not.
    mult: Dict[str, float] = {c: 0.0 for c in comps}
    kernel_level = {entry.name}
    mult[entry.name] = 1.0
    # region fallback per computation: a loop body's carry plumbing
    # (copies, slices, tuple shuffles) carries no layer op_name of its
    # own, but the `while` that runs it usually does — an lstm layer's
    # scan overhead should land in THAT layer's region, not in
    # _unattributed
    comp_fallback: Dict[str, str] = {entry.name: "_unattributed"}
    # second-chance fallback: XLA's loop-optimization passes (double
    # buffering, "wide" region cloning) synthesize `while` instructions
    # with NO op_name of their own, so the site tells us nothing — but
    # the body's surviving instructions still carry their scopes.  Each
    # computation votes with its resolvable op_names; a callee reached
    # through an unattributed site inherits its own majority region
    # (the paged decode kernel's per-page DMA loop is the motivating
    # case: 512 trips of pool-carry copies must land in attn_decode,
    # not smear the report with phantom _unattributed terabytes).
    dominant: Dict[str, str] = {}
    for comp in comps.values():
        votes: Dict[str, float] = {}
        for instr in comp.instrs:
            region, _ = _region_of(instr.op_name, known)
            if region != "_unattributed":
                votes[region] = votes.get(region, 0.0) + 1.0
        if votes:
            dominant[comp.name] = max(votes, key=lambda k: votes[k])
    while_trips: Dict[str, int] = {}
    stack = [entry.name]
    seen_edges = set()
    while stack:
        cname = stack.pop()
        comp = comps.get(cname)
        if comp is None:
            continue
        for instr in comp.instrs:
            callees: List[Tuple[str, float, bool]] = []
            if instr.opcode == "while":
                trips = _while_trip_count(instr, comps)
                while_trips[instr.name] = trips
                for key in ("body", "condition"):
                    tgt = instr.attrs.get(key)
                    if tgt:
                        callees.append((tgt, float(trips), True))
            elif instr.opcode == "conditional":
                for tgt in re.findall(r"%([\w.\-]+)",
                                      instr.attrs.get("branches", "")):
                    callees.append((tgt, 1.0, True))
            else:
                for key in ("calls", "to_apply"):
                    tgt = instr.attrs.get(key)
                    if tgt:
                        callees.append((tgt, 1.0, False))
            site_region, _ = _region_of(instr.op_name, known)
            if site_region == "_unattributed":
                site_region = comp_fallback.get(cname, "_unattributed")
            for tgt, factor, kernel in callees:
                if tgt not in comps:
                    continue
                if kernel:
                    kernel_level.add(tgt)
                comp_fallback.setdefault(
                    tgt, site_region if site_region != "_unattributed"
                    else dominant.get(tgt, "_unattributed"))
                edge = (cname, tgt)
                mult[tgt] = mult.get(tgt, 0.0) \
                    + mult.get(cname, 1.0) * factor
                if edge not in seen_edges:
                    seen_edges.add(edge)
                    stack.append(tgt)

    regions: Dict[str, Dict[str, float]] = {}
    totals = {"flops": 0.0, "trans": 0.0, "bytes": 0.0}
    per_step = {"flops": 0.0, "bytes": 0.0}
    opaque: List[str] = []

    def bucket(name: str) -> Dict[str, float]:
        r = regions.get(name)
        if r is None:
            r = regions[name] = {
                "flops": 0.0, "trans": 0.0, "bytes": 0.0,
                "flops_once": 0.0, "bytes_once": 0.0,
                "bwd_flops": 0.0, "instrs": 0.0, "opaque": 0.0}
        return r

    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0 and not comp.is_entry:
            continue                      # dead computation
        charge_bytes = comp.name in kernel_level
        for instr in comp.instrs:
            flops, trans = _instr_flops(instr)
            # control-flow sites (while/conditional/call) charge no
            # bytes of their own: their callees are kernel-level and
            # already charged, so the carried-tuple operands here would
            # double-count
            if charge_bytes and instr.opcode not in (
                    "parameter", "constant", "get-tuple-element",
                    "tuple", "bitcast", "while", "conditional", "call"):
                op_bytes, _ = _shape_stats(instr.operands)
                res_bytes, _ = _shape_stats(instr.result)
                ibytes = max(float(op_bytes + res_bytes)
                             - _streaming_discount(instr, comps),
                             0.0)
            else:
                ibytes = 0.0
            region, bwd = _region_of(instr.op_name, known)
            if region == "_unattributed":
                region = comp_fallback.get(comp.name, "_unattributed")
            r = bucket(region)
            r["flops_once"] += flops
            r["bytes_once"] += ibytes
            r["flops"] += flops * m
            r["trans"] += trans * m
            r["bytes"] += ibytes * m
            r["instrs"] += 1
            if bwd:
                r["bwd_flops"] += flops * m
            if instr.opcode == "custom-call":
                r["opaque"] += 1
                opaque.append(instr.attrs.get("custom_call_target", "?"))
            totals["flops"] += flops
            totals["trans"] += trans
            totals["bytes"] += ibytes
            per_step["flops"] += (flops + trans) * m
            per_step["bytes"] += ibytes * m

    return {"regions": regions,
            "total_flops": totals["flops"],
            "total_trans": totals["trans"],
            "total_bytes": totals["bytes"],
            "flops_per_step": per_step["flops"],
            "bytes_per_step": per_step["bytes"],
            "opaque_calls": opaque,
            "while_trips": while_trips}


# ------------------------------------------------------------- roofline
#: ``device_kind`` exactly as JAX reports it, lower-cased → (peak FLOP/s
#: dense bf16, HBM bandwidth B/s).  A kind with no row is an error, not
#: a default: add the row with its source, or pass both
#: ``--roofline_peak_*`` flags.
_PEAKS_BY_KIND = {
    # what libtpu 0.0.34 reports for a v5e chip (chip run, PR 21);
    # peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
    # 819 GB/s HBM)
    "tpu v5 lite": (197e12, 819e9),
    # host CPU (the test mesh): order-of-magnitude figures so that a
    # CPU run's verdicts have a ridge point between elementwise
    # (<1 flop/byte) and matmul (tens of flops/byte) intensity — never a
    # device number (ROADMAP D7)
    "cpu": (2e11, 4e10),
}


def detect_peaks(device=None) -> Dict[str, Any]:
    """{"flops": peak FLOP/s, "bw": HBM B/s, "ridge": flops/byte,
    "source", "device_kind"} for the attached accelerator.  The
    ``--roofline_peak_flops`` / ``--roofline_peak_gbps`` flags override
    the table (0 = auto).  Raises on a ``device_kind`` the table has no
    row for (unless both flags are given) and lets a failed device
    detection propagate: a silent default would put one chip's peaks
    under another chip's name."""
    import jax

    from ..utils import FLAGS, PaddleTpuError

    if device is None:
        device = jax.devices()[0]
    kind = str(device.device_kind).lower()
    flag_flops = float(FLAGS.get("roofline_peak_flops"))
    flag_bw = float(FLAGS.get("roofline_peak_gbps")) * 1e9
    row = _PEAKS_BY_KIND.get(kind)
    if row is None and not (flag_flops > 0 and flag_bw > 0):
        raise PaddleTpuError(
            f"no peak-FLOP/s / bandwidth row for device_kind {kind!r} "
            f"(known: {sorted(_PEAKS_BY_KIND)}); add it to "
            "observe/costmodel._PEAKS_BY_KIND with its source, or pass "
            "--roofline_peak_flops and --roofline_peak_gbps")
    flops, bw = row if row is not None else (flag_flops, flag_bw)
    source = kind
    if flag_flops > 0:
        flops, source = flag_flops, "flag"
    if flag_bw > 0:
        bw, source = flag_bw, "flag"
    return {"flops": flops, "bw": bw, "ridge": flops / bw,
            "source": source, "device_kind": kind}


def roofline(flops: float, bytes_: float,
             peaks: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Roofline verdict for one region: arithmetic intensity vs the
    ridge point, plus the peak-bound time estimate."""
    peaks = peaks or detect_peaks()
    intensity = flops / max(bytes_, 1.0)
    t_compute = flops / peaks["flops"]
    t_memory = bytes_ / peaks["bw"]
    return {
        "intensity": intensity,
        "bound": "compute" if intensity >= peaks["ridge"] else "memory",
        "time_est_s": max(t_compute, t_memory),
    }


def mfu(flops_per_step: float, seconds_per_step: float,
        devices: int = 1,
        peaks: Optional[Dict[str, Any]] = None) -> float:
    """Model FLOP utilization: executed FLOPs per step over
    ``time x peak x chips``."""
    peaks = peaks or detect_peaks()
    denom = max(seconds_per_step, 1e-12) * peaks["flops"] \
        * max(devices, 1)
    return flops_per_step / denom


# ----------------------------------------------------- trainer analysis
def _step_args(trainer, feed):
    """The train step's argument tuple, exactly as ``train_one_batch``
    dispatches it (loss-scale state appended under --precision=bf16,
    the health accumulator appended under --health_interval > 0)."""
    import jax
    import jax.numpy as jnp

    sfeed = trainer._shard_feed(feed)
    return (trainer.params, trainer.opt_state, trainer.buffers, sfeed,
            jax.random.PRNGKey(0), jnp.zeros((), jnp.float32)) \
        + trainer._step_extras()


def _known_regions(network) -> frozenset:
    names = set(network.layers)
    # recurrent-group step layers scope as "<layer>.<group>" (see
    # layers/recurrent_group.py — "@" doesn't survive XLA's op_name
    # sanitizer)
    for gname, grp in getattr(network, "groups", {}).items():
        names.update(f"{n}.{gname}" for n in grp.layers)
    names.add("optimizer")
    # the --health_interval aux path scopes as its own region so its
    # (small) reduction cost is attributed, not smeared over layers
    names.add("health")
    return frozenset(names)


#: Version stamped on every report this module emits.  v1 = the PR-10
#: unversioned dump; v2 adds ``schema`` + optional ``mfu_est``.  Bump on
#: any region-row field change so two dumps are comparable by machine.
SCHEMA_VERSION = 2

# most recent report produced in this process — the /roofline endpoint
# body (observe/http.py reads it lazily at scrape time)
_latest_report: Optional[Dict[str, Any]] = None


def latest_report() -> Optional[Dict[str, Any]]:
    """The most recent :func:`analyze_trainer_step` report (None before
    the first analysis)."""
    return _latest_report


def analyze_trainer_step(trainer, feed, top: int = 12,
                         peaks: Optional[Dict[str, Any]] = None
                         ) -> Optional[Dict[str, Any]]:
    """Attributed cost report of ONE compiled train step.

    Lowers the trainer's jitted step for ``feed`` (hits the jit/persistent
    compile cache — the step was already compiled by the run that wants
    the report), reconciles the parsed per-region costs against XLA's
    ``cost_analysis()`` totals, and renders the per-region roofline.
    Returns None when anything in the stack declines (missing cost
    analysis, exotic backend) — the report is an artifact field, never
    a crash.
    """
    try:
        # build+compile the step only if the trainer has never stepped:
        # at a pass boundary (--roofline_dump) the step exists, and
        # running a real batch here would advance params/opt state
        # outside the training loop — observability must not train
        if getattr(trainer, "_train_step", None) is None:
            trainer.train_one_batch(feed)
        compiled = trainer._train_step.lower(
            *_step_args(trainer, feed)).compile()
        return _report_from_compiled(
            compiled, _known_regions(trainer.network), top, peaks)
    except Exception as e:   # noqa: BLE001 — best-effort artifact field
        from ..utils.logger import get_logger, warn_once

        warn_once("costmodel_analyze_failed",
                  "train-step cost attribution unavailable (%s: %s)",
                  type(e).__name__, e, logger=get_logger("observe"))
        return None


def _report_from_compiled(compiled, known: frozenset, top: int,
                          peaks: Optional[Dict[str, Any]]
                          ) -> Dict[str, Any]:
    """Back half of :func:`analyze_trainer_step`: optimized-HLO
    attribution reconciled against ``cost_analysis()``, rendered as the
    versioned per-region roofline report."""
    global _latest_report
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    report = attribute(compiled.as_text(), known)

    peaks = peaks or detect_peaks()
    xla_flops = float(ca.get("flops", 0.0) or 0.0)
    xla_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    rows = []
    for name, r in report["regions"].items():
        work = r["flops"] + r["trans"]
        verdict = roofline(work, r["bytes"], peaks)
        rows.append({
            "region": name,
            "flops": round(work, 1),
            "bytes": round(r["bytes"], 1),
            "bwd_frac": round(r["bwd_flops"] / work, 3) if work else 0.0,
            "opaque": int(r["opaque"]),
            "intensity": round(verdict["intensity"], 4),
            "bound": verdict["bound"],
            # time_est_s keeps full precision until the shares are
            # derived — tiny/CPU regions sit at 1e-8 s, where a fixed
            # decimal rounding collapses every share to zero
            "time_est_s": verdict["time_est_s"],
        })
    rows.sort(key=lambda r: r["time_est_s"], reverse=True)
    total_time_est = sum(r["time_est_s"] for r in rows) or 1.0
    for r in rows:
        r["share"] = round(r["time_est_s"] / total_time_est, 3)
        r["time_est_s"] = float(f"{r['time_est_s']:.4g}")
    out = {
        "schema": SCHEMA_VERSION,
        "regions": rows[:top],
        "regions_elided": max(len(rows) - top, 0),
        "flops_per_step": report["flops_per_step"],
        "bytes_per_step": report["bytes_per_step"],
        "parsed_flops": report["total_flops"],
        "parsed_trans": report["total_trans"],
        "parsed_bytes": report["total_bytes"],
        "xla_flops": xla_flops,
        "xla_bytes": xla_bytes,
        "flop_agreement": round(report["total_flops"] / xla_flops, 3)
        if xla_flops else None,
        "opaque_custom_calls": sorted(set(report["opaque_calls"])),
        "while_trips": report["while_trips"],
        "peaks": {"flops": peaks["flops"], "bw": peaks["bw"],
                  "ridge": round(peaks["ridge"], 2),
                  "source": peaks["source"]},
    }
    _latest_report = out
    return out


def dump_report(report: Dict[str, Any], path: str) -> None:
    """Write a cost report as JSON (the ``--roofline_dump`` artifact)."""
    report.setdefault("schema", SCHEMA_VERSION)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
