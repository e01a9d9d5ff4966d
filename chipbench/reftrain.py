"""The plain reference's training loop, and the measures that compare a
program's first steps with it.

The reference follows the first three steps from the same weights on the
same batches: its loss at each step, the per-leaf norm of the first
gradient as the optimizer gets it (after clipping and weight decay), and
the per-leaf norm of the parameters' change after the three.  Per leaf:
the gap between the program's norm and the reference's, against the
reference's norm of that leaf or of the median leaf, whichever is
larger; summed up as the worst leaf, the median leaf, the leaf nine in
ten read better than, and the worst leaf of two dimensions or more (a
cell's ``limits/<cell>.json`` names the ones it holds).  Leaves whose
reference gradient is under a thousandth of the median leaf's (a conv
bias before batch norm, a key's bias under softmax) move by round-off
alone and are left out of the change.
"""

from __future__ import annotations

import statistics
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

STEPS = 3
NOUGHT = 1e-3            # of the median leaf's gradient norm


def fp8(a):
    """Round to float8 (e4m3) and back: the control's operands where the
    configuration multiplies in bfloat16."""
    return a.astype(ml_dtypes.float8_e4m3fn).astype(jnp.float32)


def bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


CASTS = {"none": lambda a: a, "fp8": fp8, "bf16": bf16}


def _effective_grad(g, p, opt):
    t = float(opt.get("clip", 0.0))
    if t > 0:
        g = jnp.clip(g, -t, t)
    wd = float(opt.get("l2", 0.0))
    return g + wd * p if wd else g


def _update(p, g, slot, opt, count):
    lr = float(opt["lr"])
    if opt["method"] == "momentum":
        v = float(opt["momentum"]) * slot[0] - lr * g
        return p + v, (v,)
    if opt["method"] == "adam":
        b1, b2 = float(opt["beta1"]), float(opt["beta2"])
        eps = float(opt["epsilon"])
        m = b1 * slot[0] + (1 - b1) * g
        v = b2 * slot[1] + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** count)
        vhat = v / (1 - b2 ** count)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), (m, v)
    raise ValueError(f"no reference for optimizer {opt['method']!r}")


def _slots(p, opt):
    n = {"momentum": 1, "adam": 2}[opt["method"]]
    return tuple(jnp.zeros_like(p) for _ in range(n))


def follow(loss_fn: Callable, params: Dict[str, np.ndarray],
           batches: Sequence[Dict[str, np.ndarray]], opt: Dict,
           cast: str = "none") -> Dict:
    """Three plain steps → losses, first-gradient norms, change norms."""
    q = CASTS[cast]
    put = jnp.asarray

    def step(p, slots, batch, count):
        loss, grads = jax.value_and_grad(
            lambda pp: loss_fn(pp, batch, q))(p)
        new_p, new_s, gnorm = {}, {}, {}
        for k in p:
            g = _effective_grad(grads[k], p[k], opt)
            gnorm[k] = jnp.sqrt(jnp.sum(jnp.square(g)))
            new_p[k], new_s[k] = _update(p[k], g, slots[k], opt, count)
        return new_p, new_s, loss, gnorm

    with jax.default_matmul_precision("highest"):
        jstep = jax.jit(step, static_argnums=3, donate_argnums=(0, 1))
        p = {k: put(v) for k, v in params.items()}
        slots = {k: _slots(v, opt) for k, v in p.items()}
        losses: List[float] = []
        grad_norms: Dict[str, float] = {}
        for i in range(STEPS):
            batch = {k: put(v) for k, v in batches[i].items()}
            p, slots, loss, gnorm = jstep(p, slots, batch, i + 1)
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(v) for k, v in gnorm.items()}
        change = jax.jit(lambda a, b: {
            k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(
            p, {k: put(v) for k, v in params.items()})
        change_norms = {k: float(v) for k, v in change.items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms,
            "matrices": [k for k, v in params.items() if np.ndim(v) >= 2]}


def leaf_gaps(got: Dict[str, float], want: Dict[str, float],
              leaves: Sequence[str]) -> List[float]:
    """Per leaf: |got - want| / max(want, median want), ascending."""
    med = statistics.median(want[k] for k in leaves)
    gaps = [abs(got[k] - want[k]) / max(want[k], med, 1e-30)
            for k in leaves]
    if any(g != g for g in gaps):
        return [float("nan")] * len(gaps)
    return sorted(gaps)


def _quantile(sorted_vals: List[float], q: float) -> float:
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(q * len(sorted_vals)))]


def compare(program: Dict, reference: Dict) -> Dict[str, float]:
    """The numbers a training cell compares (each gets a limit)."""
    names = list(reference["grad_norms"])
    med_g = statistics.median(reference["grad_norms"].values())
    moved = [k for k in names
             if reference["grad_norms"][k] >= NOUGHT * med_g]
    out = {}
    for i in range(STEPS):
        ref = reference["losses"][i]
        out[f"loss_gap_step{i + 1}"] = \
            abs(program["losses"][i] - ref) / max(abs(ref), 1e-30)
    g = leaf_gaps(program["grad_norms"], reference["grad_norms"], names)
    c = leaf_gaps(program["change_norms"], reference["change_norms"], moved)
    # the worst leaf of all: read and printed, compared only where a
    # cell's limits name it (one small vector's gradient can be a sum of
    # terms that all but cancel, and then it is rounding noise)
    out["grad_norm_gap"], out["change_norm_gap"] = g[-1], c[-1]
    # steady from seed to seed: the median leaf and the leaf that nine
    # in ten read better than
    out["grad_norm_gap_p50"] = _quantile(g, 0.5)
    out["grad_norm_gap_p90"] = _quantile(g, 0.9)
    out["change_norm_gap_p50"] = _quantile(c, 0.5)
    out["change_norm_gap_p90"] = _quantile(c, 0.9)
    # the worst leaf of two dimensions or more (kernels and matrices:
    # thousands of terms to a norm), each against its own kind's median
    mats = [k for k in reference.get("matrices", ()) if k in names]
    if mats:
        out["grad_norm_gap_matrices"] = leaf_gaps(
            program["grad_norms"], reference["grad_norms"], mats)[-1]
        out["change_norm_gap_matrices"] = leaf_gaps(
            program["change_norms"], reference["change_norms"],
            [k for k in mats if k in moved] or mats)[-1]
    return out
