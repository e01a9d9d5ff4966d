"""``serve_closed`` for a model that generates by diffusion over blocks:
each served token is judged at the denoising step that revealed it, and
the schedule of the steps is held.

Everything of a run is ``serve_closed``'s (its load generator, window,
sample of finished requests) but four things, swapped in for the run:

- the warm-up: the prefill programs at the padded lengths of the
  prompts' whole blocks, and the block step;
- the traffic's ids are drawn from 2 up to the configuration's mask id,
  which no prompt holds;
- a finished request's record keeps its blocks as the server logged
  them (``Request.blocks``: each block as it started and after each
  denoising step, each step's confidences, whether it was committed);
- the reference pass (:func:`served_steps`).  Each sampled request's
  committed sequence (its prompt's whole blocks, then every block but
  its last) and every denoising step it took, the block as it stood
  before the step (masked where a later step revealed), go once through
  the plain reference (``reference/<config>.py::run``: the steps see the
  committed sequence before their block, and themselves).  Judged at
  each position a step revealed: the gap by which the served token's
  logit lies below the reference's best (its mean, its p99 and the
  share of tokens off the best, as the routed cells hold them; the
  worst token observed only), and the gap by which the position's
  log-confidence lies below that of the least confident position the
  reference itself would have revealed at that step
  (``served_reveal_conf_gap_*``); at each position a step could reveal,
  how far the confidence the program reported (the log of its top
  probability) lies from the reference's (``served_conf_err_*``: the
  logits compared, not only their argmax).  **The schedule is held**:
  ``served_blocks_off_schedule`` counts the blocks whose steps did not
  reveal what the configured schedule gives or changed what an earlier
  step revealed, or that were not committed before the next began
  (:func:`replay_plan`); its limit is 0.

``planted`` holds ``control`` and ``stated_precision`` as
``serve_closed_q``'s do: the reference computed in the precision below
the configuration's, and in the configuration's own, in the program's
place: at each step the token it puts first is judged at each position
the program revealed, and the positions it is most confident of are
judged as the revealed ones.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from chipbench import harness as H
from chipbench import reftrain

base = H.load_module("drivers", "serve_closed")
routed = H.load_module("drivers", "serve_closed_q")

#: a gap under this is a tie of two logits at float32's rounding
TIE = routed.TIE


def reveal_schedule(masked: int, steps: int) -> List[int]:
    """The configured schedule, restated here (the program's is not
    read): step s reveals ⌊m/steps⌋ + (s < m mod steps), a step that
    would reveal none left out."""
    return [n for n in (masked // steps + (s < masked % steps)
                        for s in range(steps)) if n]


def warm(server, mix: Dict, vocab: int) -> None:
    """Every program the window can reach: a prefill at B in
    1..admit_cap x each padded length of the prompts' whole blocks, and
    the fixed-width block step."""
    import jax

    model = server.model
    k, v = server._k_pool, server._v_pool
    b = model.block_length
    pads = sorted({max(-(-(int(n) // b * b) // 16) * 16, 16)
                   for n in base.prompt_lens(mix)})
    for rows in range(1, int(mix["admit_cap"]) + 1):
        for t_pad in pads:
            out = model.prefill(
                k, v, np.full((rows, t_pad), base.FIRST_PROMPT_ID, np.int32),
                np.full((rows,), t_pad, np.int32),
                np.zeros((rows, server.max_pages), np.int32))
            del out
    width = server.max_batch
    out = model.block_step(k, v, np.full((width, b), -1, np.int32),
                           np.zeros((width, server.max_pages), np.int32),
                           np.zeros((width,), np.int32),
                           np.zeros((width,), bool),
                           np.zeros((width,), np.int32))
    jax.block_until_ready(out[2])
    del out


class ClosedLoop(base.ClosedLoop):
    """The load generator, keeping a finished request's blocks."""

    def poll(self) -> None:
        for c in self.clients:
            if c.req is not None and c.req.done.is_set():
                c.rec["blocks"] = list(c.req.blocks)
        super().poll()


def replay_plan(r: Dict, sizes) -> Dict[str, Any]:
    """One finished request's record → the committed sequence, every
    denoising step's block as it stood before the step, the positions
    each step revealed and the ids they were given, and how many blocks
    are off the schedule: a step that revealed other than the schedule's
    count, changed or masked a position an earlier step revealed, a
    block that did not start where the one before it ended, with the
    prompt's last ids and masks elsewhere, or that ended with a mask, and
    every block but the last uncommitted."""
    b, steps = int(sizes["block_length"]), int(sizes["denoising_steps"])
    prompt, blocks = list(r["prompt"]), r["blocks"]
    given = len(prompt) % b
    seq = prompt[:len(prompt) - given]
    starts, states, revealed, served, confs, off = [], [], [], [], [], 0
    for n, blk in enumerate(blocks):
        st = np.asarray(blk["states"])
        first = prompt[len(seq):] if n == 0 else []
        fresh = np.asarray(first + [-1] * (b - len(first)))
        want = reveal_schedule(b - len(first), steps)
        shown = (st[:-1] < 0) & (st[1:] >= 0)
        kept = (st[:-1] < 0) | (st[1:] == st[:-1])
        off += int(len(st) != len(want) + 1 or blk["start"] != len(seq)
                   or (st[0] != fresh).any() or (st[-1] < 0).any()
                   or not kept.all() or shown.sum(axis=1).tolist() != want
                   or (n + 1 < len(blocks) and not blk["committed"]))
        for s in range(min(len(want), len(st) - 1)):
            starts.append(blk["start"])
            states.append(st[s])
            revealed.append(shown[s])
            served.append(st[-1])
            confs.append(blk["confs"][s])
        if n + 1 < len(blocks):
            seq += st[-1].tolist()
    return {"seq": seq, "starts": starts, "blocks": np.asarray(states),
            "revealed": np.asarray(revealed), "served": np.asarray(served),
            "confs": np.asarray(confs), "off_schedule": off}


def _summary(at=()):
    """A reduction of a request's step logits [N, B, V] on the device:
    per position the best logit, the log-sum-exp and the argmax, and the
    logit of each id array in ``at`` (one a request), ``at<j>``."""
    import jax
    import jax.numpy as jnp

    def reduce(logits, req):
        out = {"best": logits.max(axis=-1),
               "lse": jax.nn.logsumexp(logits, axis=-1),
               "top": jnp.argmax(logits, axis=-1)}
        for j, ids in enumerate(at):
            out[f"at{j}"] = jnp.take_along_axis(
                logits, jnp.asarray(ids[req])[..., None], axis=-1)[..., 0]
        return out
    return reduce


def served_steps(ref, weights, sizes, sample: List[Dict],
                 cast: str = "none") -> Dict[str, float]:
    """The reference pass over each sampled request's steps (see the
    module's docstring).  With ``cast`` the reference computed in
    ``cast`` stands in the program's place: at each step its most
    confident masked positions, as many as the program revealed, each
    set to its own argmax."""
    import jax

    plans = [replay_plan(r, sizes) for r in sample]
    # a sample padded with a repeat of its first request counts it once
    keep = [not any(sample[i] is sample[j] for j in range(i))
            for i in range(len(sample))]
    asked = [{"seq": p["seq"], "starts": p["starts"], "blocks": p["blocks"]}
             for p in plans]
    picks = [p["revealed"] for p in plans]
    tokens = [p["served"] for p in plans]
    confs = [p["confs"] for p in plans]
    with jax.default_matmul_precision("highest"):
        if cast != "none":
            stood = ref.run(weights, sizes, asked, reftrain.CASTS[cast],
                            reduce=_summary())
            picks, tokens, confs = [], [], []
            for plan, (_, c) in zip(plans, stood):
                confs.append(c["best"] - c["lse"])
                score = np.where(plan["blocks"] < 0, confs[-1], -np.inf)
                rank = np.argsort(np.argsort(-score, axis=1, kind="stable"),
                                  axis=1)
                picks.append(rank < plan["revealed"].sum(axis=1,
                                                         keepdims=True))
                tokens.append(c["top"])
        want = ref.run(weights, sizes, asked, reduce=_summary([tokens]))
    gaps, conf_gaps, conf_errs, off = [], [], [], 0
    for i, (plan, picked, (_, w)) in enumerate(zip(plans, picks, want)):
        if not keep[i]:
            continue
        off += plan["off_schedule"]
        gaps.append((w["best"] - w["at0"])[picked])
        masked = plan["blocks"] < 0
        conf_errs.append(np.abs(confs[i] - (w["best"] - w["lse"]))[masked])
        # the least confident of what the reference itself would reveal
        conf = np.where(masked, w["best"] - w["lse"], -np.inf)
        k = plan["revealed"].sum(axis=1)
        bar = np.take_along_axis(np.sort(conf, axis=1)[:, ::-1],
                                 np.maximum(k - 1, 0)[:, None], axis=1)
        conf_gaps.append(np.maximum(bar - conf, 0.0)[picked])
    gaps, conf_gaps, conf_errs = (np.concatenate(a) for a in
                                  (gaps, conf_gaps, conf_errs))
    out = {"served_logit_gap": float(gaps.max()),
           "served_logit_gap_mean": float(gaps.mean()),
           "served_tokens_off_best_share": float((gaps > TIE).mean()),
           "served_reveal_conf_gap_mean": float(conf_gaps.mean()),
           "served_reveal_conf_gap_max": float(conf_gaps.max()),
           "served_reveals_off_best_share": float((conf_gaps > TIE).mean()),
           "served_conf_err_mean": float(conf_errs.mean()),
           "served_conf_err_max": float(conf_errs.max()),
           "served_blocks_off_schedule": float(off),
           "served_tokens_compared": int(gaps.size)}
    for name, q in routed.QUANTILES.items():
        out[f"served_logit_gap_{name}"] = float(np.percentile(gaps, q))
        out[f"served_reveal_conf_gap_{name}"] = float(
            np.percentile(conf_gaps, q))
        out[f"served_conf_err_{name}"] = float(np.percentile(conf_errs, q))
    return out


def _with_these(fn, *args, mask_id: int):
    """``fn(*args)`` with this driver's warm-up, ids, records and
    reference pass in ``serve_closed``'s place."""
    real = (base.warm, base.request_plan, base.ClosedLoop, base.served_gaps)
    plan = base.request_plan
    base.warm, base.ClosedLoop, base.served_gaps = warm, ClosedLoop, \
        served_steps
    base.request_plan = lambda mix, vocab, seed: plan(
        mix, min(vocab, mask_id), seed)
    try:
        return fn(*args)
    finally:
        base.warm, base.request_plan, base.ClosedLoop, base.served_gaps = \
            real


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    config = ctx["cell"].config           # the control reads its cast here
    sizes = config["rehearsal"]["sizes"] if ctx["rehearsal"] \
        else config["sizes"]
    mask_id = int(sizes["mask_token_id"])
    out = _with_these(base.run, ctx, mask_id=mask_id)
    control = out["planted"]["control"]

    def stated_precision():
        lower = config["control_precision"]
        config["control_precision"] = config["stated_precision"]
        try:
            return _with_these(control, mask_id=mask_id)
        finally:
            config["control_precision"] = lower

    out["planted"] = {
        "control": lambda: _with_these(control, mask_id=mask_id),
        "stated_precision": stated_precision}
    return out
