"""The names of the program's sublayers in a device trace.

Every op of a compiled step carries JAX's ``op_name``: the stack of
``jax.named_scope`` names it was traced under, with the transformations
and the primitive (``jit(step)/transpose(jvp(batch_norm))/res2a_bn/mul``).
A profiler window keeps it beside the op's HLO line
(``utils/profiler.py::last_window_ops``, the event metadata's
``tf_op``), so device time can be summed by these names
(``profiler.scope_seconds``) whatever XLA called the fusion.  The
constants below are the one table of them; ``PERF.md`` §3 says which
metric reads each.  A scope is metadata: entering one changes no
compiled program (``tests/test_scopes.py``).  A fusion carries the name
of ONE of its ops: where XLA fuses across two sublayers (a batch norm's
backward reductions into the conv's beside it, the optimizer's update
into a weight gradient) the time reads under the one it named, and the
copies XLA makes of its own (``copy-start``, ``slice-done`` …) carry
none.

They are entered **inline** with ``jax.named_scope`` in the bodies that
are there, never through a wrapper of this module: JAX writes the Python
traceback into every op's location, and one frame more above a jitted
step costs its set-up 6-15 % (PERF.md §6, PR 30).

**The served decoder** (``serving/model.py``, ``ops/pallas_moe.py``;
prefill and decode alike).  A layer's paths start at ``L<i>``, the
layer's index in the plan; the names under ``FFN`` are entered inside
it.  The default plan's paged decode call stands under **no** scope:
an unnamed ``pallas_call`` takes its HLO name from the scope it is
traced under, and the benchmark finds that kernel by the ``%_lambda_``
of the step's jit (see ``model.py::_decode_impl``).  Kernels that pass
``name=`` keep their name under any scope (``ops/kernels.py``).

**The layer engine** (``layers/network.py``): a layer runs under its
type (``batch_norm``, ``exconv``, ``fc``, ``pool``, ``addto`` …, the
config's own word) and inside it under its name, which
``observe/costmodel.py`` keys its modelled regions on; the trainer's
update runs under ``OPTIMIZER`` and its checks under ``HEALTH``.
"""

from __future__ import annotations

EMBED = "embed"                          # token and position rows
# L<i>/mixer: attention, or the gated short convolution or the mamba
# mixer in its place
MIXER_NORM = "L{}/mixer/norm"            # the pre-norm
QKV = "L{}/mixer/qkv"                    # projections, rotary, head norms
CACHE_WRITE = "L{}/mixer/cache_write"    # the rows into their pages
ATTEND = "L{}/mixer/attend"              # the kernel and what feeds only it
MIXER_OUT = "L{}/mixer/out"              # gate, wo, post-norm, residual
CONV = "L{}/mixer/conv"                  # a conv layer whole, state ops too
SSM = "L{}/mixer/ssm"                    # a mamba layer whole, state ops too
# L<i>/ffn and, inside it:
FFN = "L{}/ffn"
FFN_NORM = "norm"                        # pre-norm (post-norm: its last op)
DENSE = "dense"                          # GELU or SwiGLU feed-forward
ROUTE = "route"                          # scores, top-k, weights
SORT = "sort"                            # choices by expert, sizes, gather
EXPERTS = "experts"                      # the two grouped kernels
COMBINE = "combine"                      # rows back, mask, weighted sum
SHARED = "shared"                        # the shared expert
HEAD = "head"                            # last token, norm, logits, argmax
CACHE_LAYOUT = "cache_layout"            # the pools' free reshapes
# trainer/trainer.py
OPTIMIZER = "optimizer"
HEALTH = "health"

#: constant → scope name (a ``{}`` stands for the layer's index)
SCOPE_NAMES = {k: v for k, v in globals().items()
               if k.isupper() and isinstance(v, str)}
