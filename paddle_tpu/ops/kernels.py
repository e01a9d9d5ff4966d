"""The names of the Pallas kernels, and the account of their work.

**Names.**  Every ``pl.pallas_call`` in ``paddle_tpu/ops/pallas_*.py``
passes ``name=`` one of the constants below.  In the installed JAX
``name=`` wraps the call in ``jax.named_scope(name)`` and sets Mosaic's
``kernel_name``, so the compiled HLO instruction — and with it the op
event in a device trace — reads ``%<name>.N = … custom-call(…)``
whatever layer scope or ``jax.jit(lambda …)`` the call sits in (shown
on the chip, PR 25: ``%probe_bwd.1``, where the unnamed call beside it
read ``%transpose_jvp_exconv_2__.1``).  JAX wraps the scope in the
transformation the call was traced under — ``jvp(flash_fwd)`` reaches
the HLO as ``%jvp_flash_fwd_.1``, a transposed one as
``%transpose_jvp_flash_bwd_dq__.1`` — so :func:`instruction_pattern` is
how a kernel is found.  A benchmark's trace reduction finds a kernel by
this name; renaming one is a change to every metric that reads it.

**Work.**  :func:`record_kernel_work` ticks
``pallas_kernel_work_total{kernel, kind}`` (``kind`` ∈ ``calls``,
``flops``, ``bytes``; the flash forward adds ``pairs`` and
``pairs_interior``) once per traced call — trace-time, like the
``*_dispatch_total`` counters: once per compiled program per call site.
It counts what the *op* is, from the shapes at the call (a 3×3 conv's
``2·N·H·W·9·Cin·Cout``; each operand and result once), never what the
kernel's loop re-does, so the number is the same work whatever
implements it.  ``flops ÷ calls`` is the mean work of one executed
kernel as long as every traced call runs once per step.
"""

from __future__ import annotations

import math
import re
from typing import Iterable

from ..observe import counter

# ops/pallas_conv.py — 3×3 stride-1 conv with the neighbouring
# BatchNorm's per-channel affine in its pipeline
CONV_BN_FWD = "conv_bn_fwd"              # act(a·z + c) → conv
CONV_BN_DX = "conv_bn_dx"                # BN backward affine → dX, dz
CONV_BN_FWD_BWD = "conv_bn_fwd_bwd"      # backward of conv_bn_fwd
CONV_BN_CHAIN_BWD = "conv_bn_chain_bwd"  # both affines, one dX pass
# ops/pallas_attention.py
FLASH_FWD = "flash_fwd"                  # pair-table (block-sparse) grid
FLASH_FWD_PACKED = "flash_fwd_packed"    # the same grid over segments
FLASH_FWD_GRID = "flash_fwd_grid"        # legacy full grid
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
FLASH_BWD_DQ_GRID = "flash_bwd_dq_grid"
FLASH_BWD_DKV_GRID = "flash_bwd_dkv_grid"
#: ``paged_decode_attention`` takes it where its caller asks (the
#: decoders with a layer plan do); the default plan's call stays unnamed
#: while the benchmark's ``paged_decode_roofline.serve`` finds that
#: kernel by the ``_lambda_`` of ``serving/model.py``'s jit (see the
#: call site)
PAGED_DECODE = "paged_decode"
#: its block mode: the queries of a diffusion step's block see the whole
#: block and every position before it
BLOCK_DECODE = "block_decode"
#: decode over a latent cache: one compressed row a token serves every
#: query head as its key and, in its leading lanes, as its value
LATENT_DECODE = "latent_decode"
#: a chunk of a prompt that continues a prefill: its queries see every
#: position of their row up to their own, through the row's pages
CHUNK_PREFILL = "chunk_prefill"
# ops/pallas_ssm.py — Mamba's selective scan over whole prompts
SSM_SCAN = "ssm_scan"
# ops/pallas_moe.py
MOE_GMM = "moe_gmm"                      # grouped matmul, rows by expert
# ops/pallas_embedding.py
EMBEDDING_GATHER = "embedding_gather"
# ops/pallas_lstm.py, ops/pallas_gru.py (whole-sequence and H-blocked)
LSTM_FWD = "lstm_fwd"
LSTM_BWD = "lstm_bwd"
LSTM_FWD_BLOCKED = "lstm_fwd_blocked"
LSTM_BWD_BLOCKED = "lstm_bwd_blocked"
LSTM_DW_BLOCKED = "lstm_dw_blocked"
GRU_FWD = "gru_fwd"
GRU_BWD = "gru_bwd"
GRU_FWD_BLOCKED = "gru_fwd_blocked"
GRU_BWD_BLOCKED = "gru_bwd_blocked"
GRU_DW_BLOCKED = "gru_dw_blocked"

#: constant → kernel name: the one table (``tests/test_kernel_names.py``
#: holds every ``pallas_call`` site to it)
KERNEL_NAMES = {k: v for k, v in globals().items()
                if k.isupper() and isinstance(v, str)}


def instruction_pattern(name: str) -> str:
    """The regex that finds kernel ``name`` at the head of an HLO
    instruction line, which is also its op event's name in a device
    trace: the name, behind any transformation wrappers
    (``jvp_``, ``transpose_`` …), before the ``.N`` of its instance."""
    return rf"%(?:[a-z]+_)*{re.escape(name)}_*(?:\.\d+)? = "


def _nbytes(a) -> int:
    return math.prod(a.shape) * a.dtype.itemsize


def record_kernel_work(kernel: str, flops: float, operands: Iterable,
                       results: Iterable, **more: float) -> None:
    """Tick one traced call of ``kernel``: its logical ``flops`` and the
    bytes of ``operands`` and ``results`` (arrays or
    ``ShapeDtypeStruct``s), each counted once; ``more`` are further
    kinds a kernel counts of itself (the flash forward's ``pairs`` and
    ``pairs_interior``)."""
    work = counter(
        "pallas_kernel_work_total",
        "logical work of the Pallas kernels traced into compiled "
        "programs, by kernel name (trace-time; kind = calls | flops | "
        "bytes; flops and bytes are the op's, from its shapes)")
    work.inc(kernel=kernel, kind="calls")
    work.inc(float(flops), kernel=kernel, kind="flops")
    work.inc(float(sum(_nbytes(a) for a in (*operands, *results))),
             kernel=kernel, kind="bytes")
    for kind, value in more.items():
        work.inc(float(value), kernel=kernel, kind=kind)
