"""NeuralNetwork: config-driven executor over the layer registry.

Equivalent of ``paddle/gserver/gradientmachines/NeuralNetwork.cpp`` — but
where the reference loops layers twice (``forward:245`` / ``backward:295``
with hand-written per-layer gradients), here :meth:`forward` is a **pure
traceable function** and the backward pass is jax autodiff over the whole
graph, so the entire fwd+bwd+update compiles into one XLA computation
(the SURVEY §7 north-star jit path).

Handles: topological execution, parameter creation/sharing
(``input_parameter_name``), static parameters, batch-norm buffers, cost
aggregation (``Argument::sum``), and recurrent-group sub-models (delegated
to :class:`paddle_tpu.layers.recurrent_group.RecurrentGroup`).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config.model_config import LayerConfig, ModelConfig, ParameterConfig
from ..core.sequence import SequenceBatch, value_of
from ..utils import ConfigError, enforce, global_stat, layer_stack
from .base import (
    LAYERS,
    ForwardContext,
    Layer,
    cast_layer_output,
    init_parameter,
)
from . import common, conv, cost, rnn, seq  # noqa: F401  (register layers)
from . import detection, image3d  # noqa: F401  (register layers)
from . import beam_search  # noqa: F401  (registers beam_gen)
from . import attention  # noqa: F401  (registers flash-attention layers)


class NeuralNetwork:
    """Builds and executes a ModelConfig as a functional graph."""

    def __init__(self, config: ModelConfig):
        self.config = config
        self.layers: Dict[str, Layer] = {}
        self.order: List[str] = []
        sub_layer_names: Set[str] = set()
        self.group_of: Dict[str, str] = {}
        for sm in config.sub_models:
            if sm.name == "root":
                continue
            for ln in sm.layer_names:
                sub_layer_names.add(ln)
                self.group_of[ln] = sm.name

        from .recurrent_group import RecurrentGroup

        self.groups: Dict[str, "RecurrentGroup"] = {}
        for sm in config.sub_models:
            if sm.name != "root" and not sm.is_generating:
                self.groups[sm.name] = RecurrentGroup(sm, config)
        self.gen_groups = {
            sm.name: sm for sm in config.sub_models
            if sm.name != "root" and sm.is_generating
        }
        self._decoders: Dict[str, Any] = {}

        for lconf in config.layers:
            if lconf.name in sub_layer_names and lconf.type != "data":
                continue  # executed inside its recurrent group
            cls = LAYERS.get(lconf.type)
            self.layers[lconf.name] = cls(lconf, config)
            self.order.append(lconf.name)

        # parameter specs (merge layer-declared with config-declared)
        declared = {p.name: p for p in config.parameters}
        self.param_specs: Dict[str, ParameterConfig] = {}
        self._collect_specs(self.layers.values(), declared)
        for g in self.groups.values():
            self._collect_specs(g.layers.values(), declared)
        for sm in self.gen_groups.values():
            from .beam_search import BeamSearchDecoder
            dec = BeamSearchDecoder(sm, config)
            self._decoders[sm.name] = dec
            self._collect_specs(dec.group.layers.values(), declared)
        self.static_params: Set[str] = {
            n for n, s in self.param_specs.items() if s.is_static}

        self.data_layers = [n for n in self.order
                            if self.layers[n].conf.type == "data"]
        self.cost_layers = [
            n for n in self.order
            if getattr(self.layers[n], "is_cost", False)]
        self.output_names = config.output_layer_names or (
            [self.order[-1]] if self.order else [])

        # classification-cost logits peephole: when a multi-class CE
        # cost reads a softmax-activated fc, route it the layer's
        # '.logits' sub-output so the fused logits-CE path can run (the
        # softmax output is then dead in training and XLA removes it)
        lmap = config.layer_map()
        self._cost_logit_alias: Dict[str, str] = {}
        for cname in self.cost_layers:
            lyr = self.layers[cname]
            if lyr.conf.type != "multi-class-cross-entropy" \
                    or not lyr.conf.inputs:
                continue
            pname = lyr.conf.inputs[0].input_layer_name
            pconf = lmap.get(pname)
            if pconf is not None \
                    and pconf.type in ("fc", "mkldnn_fc") \
                    and pconf.active_type == "softmax" \
                    and pconf.drop_rate == 0 \
                    and pconf.error_clipping_threshold == 0:
                self._cost_logit_alias[cname] = pname + ".logits"

        # conv→BN fusion peepholes: a batch-norm whose sole producer is
        # a linear 3×3 stride-1 pad-1 conv routes through the fused
        # conv+BN op (ops/nn_ops.py::conv2d_bn — the Pallas backward-
        # data kernel with the BN-backward affine folded into its input
        # pipeline), and a batch-norm whose sole consumer is a fusable
        # conv defers its normalize+act apply pass into that conv's
        # input prologue (nn_ops.affine_act_conv2d) so the normalized
        # activation never round-trips HBM.  Pattern-matched once at
        # build time on the static config — the resolution itself lives
        # in :func:`paddle_tpu.analysis.netcheck.fusion_plan` (pure
        # function of the config, shared with the static verifier so
        # the PT-SHAPE census can never drift from the gauge below);
        # the ops re-gate on shapes/dtype at trace time and fall back
        # to the exact unfused composition, so firing is always
        # semantics-preserving.  Kill switches: --conv_bn_fuse (bwd),
        # --conv_bn_fuse_fwd (fwd).
        from ..analysis import netcheck
        from ..utils import FLAGS

        self._conv_bn_fuse, self._bn_conv_fuse = netcheck.fusion_plan(
            config, root_layers=set(self.layers),
            output_names=self.output_names,
            fuse_bwd=bool(FLAGS.get("conv_bn_fuse")),
            fuse_fwd=bool(FLAGS.get("conv_bn_fuse_fwd")))

        # fused-pair census: how many conv/BN pairs THIS topology
        # resolved at build time, per direction and kernel family —
        # ResNet-50 pins 16 Pallas-3×3 + 16 GEMM-1×1 forward pairs (the
        # round-7 resolution; its bwd entries are all evicted into fwd
        # chains).  Gauges reflect the most recently built network.
        from ..observe import gauge
        fwd3 = sum(1 for cv in self._bn_conv_fuse
                   if lmap[cv].attrs.get("filter_size") == 3)
        pairs = gauge("network_conv_bn_fused_pairs",
                      "conv/BN pairs resolved by the build-time fusion "
                      "peepholes of the last-built network")
        pairs.set(len(self._conv_bn_fuse), direction="bwd", kernel="3x3")
        pairs.set(fwd3, direction="fwd", kernel="3x3")
        pairs.set(len(self._bn_conv_fuse) - fwd3,
                  direction="fwd", kernel="1x1")

        # build-time precision census: which compute/output dtypes the
        # op policy resolved to when each network was built (the
        # trainer may still override per-step via policy_scope — this
        # records the flag-resolved default).  A monotonic per-policy
        # counter, like the fused-pair census above: a process that
        # builds under two policies keeps both series honest.
        from ..core.dtypes import current_policy, dtype_name
        from ..observe import counter
        pol = current_policy()
        counter("network_builds_total",
                "networks built, labeled by the op-policy dtypes "
                "resolved at build time").inc(
            compute=dtype_name(pol.compute_dtype),
            output=dtype_name(pol.output_dtype))

    def verify(self) -> list:
        """Config-time whole-graph verification — the
        :mod:`paddle_tpu.analysis.netcheck` abstract interpreter over
        this network's config (symbolic shapes + policy-resolved
        dtypes, no tracing).  Returns the issue list;
        ``netcheck.errors(...)`` filters the trace-fatal subset.  The
        reference verified its proto config before any kernel ran;
        this is that check for the rebuild."""
        from ..analysis import netcheck
        from ..core.dtypes import current_policy, dtype_name

        pol = current_policy()
        return netcheck.check_model(
            self.config, policy=(dtype_name(pol.compute_dtype),
                                 dtype_name(pol.output_dtype)))

    def _collect_specs(self, layers, declared) -> None:
        for layer in layers:
            for spec in layer.param_specs():
                if spec.name in declared:
                    d = declared[spec.name]
                    if not d.dims:
                        d.dims = spec.dims
                    d.size = d.size or spec.size
                    spec = d
                if spec.name in self.param_specs:
                    prev = self.param_specs[spec.name]
                    enforce(prev.dims == spec.dims,
                            f"shared parameter {spec.name} shape mismatch: "
                            f"{prev.dims} vs {spec.dims}")
                    continue
                self.param_specs[spec.name] = spec

    # ------------------------------------------------------------- params
    def init_params(self, seed: int = 1) -> Dict[str, jax.Array]:
        key = jax.random.PRNGKey(seed)
        params = {}
        for i, (name, spec) in enumerate(sorted(self.param_specs.items())):
            params[name] = init_parameter(jax.random.fold_in(key, i), spec)
        return params

    def init_buffers(self) -> Dict[str, jax.Array]:
        buffers: Dict[str, jax.Array] = {}
        for coll in [self.layers, *[g.layers for g in self.groups.values()]]:
            for layer in coll.values():
                if hasattr(layer, "buffer_specs"):
                    buffers.update(layer.buffer_specs())
        return buffers

    def lr_scales(self, params: Dict[str, jax.Array]) -> Dict[str, float]:
        """Per-parameter learning-rate scale (ParameterConfig.learning_rate);
        0 for static parameters."""
        return {
            n: 0.0 if n in self.static_params
            else self.param_specs[n].learning_rate
            for n in params
        }

    def _ancestors(self, targets) -> Set[str]:
        """Main-graph layers (transitively) needed to produce ``targets``
        — inference pruning, the ``core.prune`` / capi
        create-for-inference equivalent.  Group out-links pull in the
        whole group: its in-links, memory boot layers, and every outer
        value its step layers read."""
        needed: Set[str] = set()
        stack = [t for t in targets]
        seen: Set[str] = set()
        while stack:
            v = stack.pop()
            if v in seen:
                continue
            seen.add(v)
            base = v.split(".", 1)[0]
            if base not in self.layers:
                base = v
            if base in self.layers:
                needed.add(base)
                stack.extend(self.layers[base].conf.input_names())
                continue
            gname = self.group_of.get(v)
            if gname is None:
                continue
            grp = self.groups.get(gname)
            sub = grp.sub if grp is not None else self.gen_groups[gname]
            stack.extend(sub.in_links)
            # beam-search groups read encoder context as static inputs
            # (deliberately NOT in_links, dsl.py GeneratedInput wiring)
            stack.extend(sub.generator.get("static_inputs", ()))
            step_layers = (grp.layers if grp is not None
                           else self._decoders[gname].group.layers)
            inner = set(step_layers) | set(sub.layer_names)
            mem_links = set()
            for m in sub.memories:
                mem_links.add(m.get("link_name",
                                    m["layer_name"] + "@pre"))
                if m.get("boot_layer_name"):
                    stack.append(m["boot_layer_name"])
            for lyr in step_layers.values():
                for iname in lyr.conf.input_names():
                    head = iname.split(".", 1)[0]
                    if head not in inner and iname not in mem_links \
                            and iname not in sub.in_links:
                        stack.append(iname)
        return needed

    # ------------------------------------------------------------ forward
    def forward(self, params: Dict[str, jax.Array], feed: Dict[str, Any],
                buffers: Optional[Dict[str, jax.Array]] = None,
                is_training: bool = True,
                rng: Optional[jax.Array] = None,
                only: Optional[Sequence[str]] = None
                ) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
        """Run all layers; returns (all outputs by name, updated buffers).

        ``only``: restrict execution to the ancestors of these value
        names — data layers outside the cone need no feed (inference on
        a training config)."""
        ctx = ForwardContext(is_training=is_training, rng=rng,
                             buffers=buffers or {})
        values: Dict[str, Any] = {}
        done_groups: Set[str] = set()
        needed = self._ancestors(only) if only is not None else None
        # conv→BN pairs active for THIS call: the conv is skipped and the
        # BN executes the fused op — unless the conv's own value was
        # explicitly requested (it then must exist standalone)
        targets = set(only) if only is not None else set()
        fuse = {bn: cv for bn, cv in self._conv_bn_fuse.items()
                if (needed is None or bn in needed) and cv not in targets}
        fused_convs = set(fuse.values())
        # BNs whose apply pass defers into their consuming conv this
        # call (forward fusion) — inactive when the BN's own value is an
        # explicit target (it must then materialize standalone)
        defer = {bn for cv, bn in self._bn_conv_fuse.items()
                 if (needed is None or cv in needed) and bn not in targets}
        for name in self.order:
            if needed is not None and name not in needed:
                continue
            if name in fused_convs:
                continue  # produced inside its batch-norm partner
            layer = self.layers[name]
            if layer.conf.type == "data":
                if name not in feed:
                    raise ConfigError(f"missing feed for data layer {name!r}")
                values[name] = feed[name]
                continue
            # run any recurrent group whose inputs are all ready lazily:
            # groups appear in order via their output layers.
            # jax.named_scope threads the layer name into XLA's op_name
            # metadata so the compiled executable's fused regions key
            # back to THIS layer (observe/costmodel.py attribution),
            # and outside it the layer's type, by which a device trace
            # is summed (ops/scopes.py); scope cost is trace-time only,
            # nothing per step.
            with layer_stack.guard(name), \
                    jax.named_scope(layer.conf.type), jax.named_scope(name):
                if name in defer:
                    # forward conv+BN fusion: publish (z, a, c) — the
                    # consuming conv applies the affine in its input
                    # pipeline (no activation materialized here)
                    inputs = self._gather(layer.conf.input_names(),
                                          params, values, ctx,
                                          done_groups)
                    values[name] = layer.forward_deferred(params, inputs,
                                                          ctx)
                    continue
                src = fuse.get(name)
                if src is not None:
                    conv = self.layers[src]
                    cinputs = self._gather(conv.conf.input_names(),
                                           params, values, ctx,
                                           done_groups)
                    out = cast_layer_output(
                        layer, layer.forward_fused(params, conv,
                                                   cinputs, ctx))
                else:
                    inputs = self._gather(layer.conf.input_names(),
                                          params, values, ctx,
                                          done_groups)
                    if name in self._cost_logit_alias:
                        # hand the cost its producer's logits when the
                        # graph exposed them (None → cost falls back to
                        # probs)
                        layer._logits_value = values.get(
                            self._cost_logit_alias[name])
                    out = cast_layer_output(
                        layer, layer.forward(params, inputs, ctx))
            if isinstance(out, dict):
                for k, v in out.items():
                    values[name if k == "out" else f"{name}.{k}"] = v
            else:
                values[name] = out
        # declared outputs that are group out-links with no downstream
        # consumer still need their group to run
        for name in (self.output_names if only is None else only):
            gname = self.group_of.get(name)
            if name in values or gname is None or gname in done_groups:
                continue
            grp = self.groups.get(gname)
            out_links = grp.out_links if grp is not None \
                else self.gen_groups[gname].out_links
            if name in out_links:
                self._run_producer(name, params, values, ctx, done_groups)
        ctx.buffers.update(ctx.new_buffers)
        return values, ctx.buffers

    def _gather(self, names, params, values, ctx, done_groups):
        """Resolve input values, running lazy group producers on demand."""
        vals = []
        for iname in names:
            if iname not in values:
                self._run_producer(iname, params, values, ctx, done_groups)
            vals.append(values[iname])
        return vals

    def _run_producer(self, name: str, params, values, ctx, done_groups):
        """Produce a value coming from a recurrent-group output link."""
        group_name = self.group_of.get(name)
        if group_name is None or group_name in done_groups:
            raise ConfigError(f"layer input {name!r} has no producer")
        group = self.groups.get(group_name)
        if group is None:
            sm = self.gen_groups.get(group_name)
            if sm is None:
                raise ConfigError(f"no producer for group {group_name!r}")
            dec = self._decoders.get(group_name)
            if dec is None:   # decoders are prebuilt in __init__
                from .beam_search import BeamSearchDecoder
                dec = self._decoders[group_name] = \
                    BeamSearchDecoder(sm, self.config)
            bundle = dec.generate(params, values, ctx)
            for link in sm.out_links:
                values[link] = bundle
        else:
            group.run(params, values, ctx)
        done_groups.add(group_name)

    # --------------------------------------------------------------- loss
    def loss(self, params: Dict[str, jax.Array], feed: Dict[str, Any],
             buffers: Optional[Dict[str, jax.Array]] = None,
             is_training: bool = True, rng: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, Tuple[Dict[str, Any], Dict[str, jax.Array]]]:
        """Scalar objective = mean per-example total cost (TrainerInternal
        ``Argument::sum`` / batchSize convention)."""
        values, new_buffers = self.forward(params, feed, buffers,
                                           is_training, rng)
        enforce(self.cost_layers, "network has no cost layer")
        total = None
        for cname in self.cost_layers:
            out = values[cname]
            v = value_of(out)
            c = jnp.sum(v) / v.shape[0]
            total = c if total is None else total + c
        return total, (values, new_buffers)

    def outputs(self, values: Dict[str, Any]) -> Dict[str, Any]:
        return {n: values[n] for n in self.output_names if n in values}
