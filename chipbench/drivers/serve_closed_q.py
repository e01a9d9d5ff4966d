"""``serve_closed``, judged on how the gap is spread over the served
tokens and not on its worst token alone.

Everything of a run is ``serve_closed``'s (its load generator, warm-up,
window, sample of finished requests, the reference pass): this file
only replaces the reduction of that pass.  Where a model routes tokens
to experts, a score that the stated precision rounds across a near-tie
of the top-k picks another expert, the token's feed-forward output
jumps, the layers behind it route differently in turn, and the worst of
some hundred served tokens then reads what a fault would read on a
dense model, while nine tokens in ten are untouched.  So beside the
maximum (``served_logit_gap``, kept under the same name) the run states
the gap's quantiles over the served tokens, its mean, and the share of
tokens that are not the reference's own first choice; the cell's
``limits/<cell>.json`` names which of them it holds.  A fault (a layer
that attends without its window, a dropped selection bias, a lower
precision) moves every token and shows in the quantiles.

``planted`` gains ``stated_precision`` beside ``control``: the reference
itself computed in the precision the configuration states (bfloat16
operands), put in the program's place.  It has to read ``correct``: what
the configuration's own precision does to the reference is no fault of
a program.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import harness as H
from chipbench import reftrain

base = H.load_module("drivers", "serve_closed")

QUANTILES = {"p50": 50.0, "p90": 90.0, "p99": 99.0}
#: a gap under this is a tie of two logits at float32's rounding
TIE = 1e-6


def served_gaps(ref, weights, sizes, sample: List[Dict],
                cast: str = "none") -> Dict[str, float]:
    """``serve_closed.served_gaps``' pass (each sampled prompt with its
    served tokens once through the plain reference; with ``cast`` the
    token that the reference computed in ``cast`` puts first is judged
    in the served token's place), reduced to the spread of the
    per-token gap ``best - reference logit of the token``."""
    import jax

    total = max(len(r["prompt"]) + len(r["tokens"]) for r in sample)
    t = -(-total // 128) * 128
    n = max(len(r["tokens"]) for r in sample)
    tokens = np.zeros((len(sample), t), np.int32)
    positions = np.zeros((len(sample), n), np.int32)
    valid = np.zeros((len(sample), n), bool)
    served = np.zeros((len(sample), n), np.int32)
    for i, r in enumerate(sample):
        seq = r["prompt"] + r["tokens"]
        tokens[i, :len(seq)] = seq
        k = len(r["tokens"])
        positions[i, :k] = len(r["prompt"]) - 1 + np.arange(k)
        valid[i, :k] = True
        served[i, :k] = r["tokens"]
    # a sample padded with a repeat of its first request counts it once
    for i in range(1, len(sample)):
        if any(sample[i] is sample[j] for j in range(i)):
            valid[i] = False
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(weights, sizes, tokens, positions)
        if cast != "none":
            served = ref.logits_at(weights, sizes, tokens, positions,
                                   reftrain.CASTS[cast]).argmax(axis=-1)
    best = want.max(axis=-1)
    got = np.take_along_axis(want, served[..., None], axis=-1)[..., 0]
    gaps = (best - got)[valid]
    out = {"served_logit_gap": float(gaps.max()),
           "served_logit_gap_mean": float(gaps.mean()),
           "served_tokens_off_best_share": float((gaps > TIE).mean()),
           "served_tokens_compared": int(valid.sum())}
    for name, q in QUANTILES.items():
        out[f"served_logit_gap_{name}"] = float(np.percentile(gaps, q))
    return out


def _with_this_reduction(fn, *args):
    real = base.served_gaps
    base.served_gaps = served_gaps
    try:
        return fn(*args)
    finally:
        base.served_gaps = real


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    out = _with_this_reduction(base.run, ctx)
    control = out["planted"]["control"]
    config = ctx["cell"].config          # the control reads its cast here

    def stated_precision():
        lower = config["control_precision"]
        config["control_precision"] = config["stated_precision"]
        try:
            return _with_this_reduction(control)
        finally:
            config["control_precision"] = lower

    out["planted"] = {
        "control": lambda: _with_this_reduction(control),
        "stated_precision": stated_precision}
    return out
