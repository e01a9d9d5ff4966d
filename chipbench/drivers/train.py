"""The training driver: one compiled step with its state, driven from
the seed through its first three steps (which the plain reference then
follows), handed as the same object to the measured window.

Traffic parameters (``traffic/<mix>.json``): ``batch``, ``host_batches``
(seeded host batches cycled through the trainer's normal feed),
``in_flight`` (steps dispatched ahead of the last fenced one),
``trace_seconds`` (the traced stretch of a ``--trace 1`` window).
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import harness as H
from chipbench import reftrain, tracing, weights as W


def make_batches(feed_spec: Dict, batch: int, n: int, seed: int
                 ) -> List[Dict[str, np.ndarray]]:
    """``n`` host batches from the seed; every row differs."""
    out = []
    for i in range(n):
        rng = np.random.default_rng([int(seed) % (2 ** 63), 7919, i])
        b = {}
        for name, s in feed_spec.items():
            shape = (batch, *s["shape"])
            if s["draw"] == "normal":
                b[name] = rng.standard_normal(shape, dtype=np.float32)
            elif s["draw"] == "randint":
                b[name] = rng.integers(int(s.get("low", 0)), int(s["high"]),
                                       shape).astype(s["dtype"])
            else:
                raise ValueError(f"unknown draw {s['draw']!r}")
        out.append(b)
    return out


def program_leaves(leaf_map, weights):
    return {leaf_map[k]: v for k, v in weights.items()}


def place(host_leaves):
    import jax.numpy as jnp

    return {k: jnp.asarray(v) for k, v in host_leaves.items()}


def install_weights(trainer, leaf_map, weights):
    """Hand the benchmark's weights to the trainer before its first
    step (what ``Trainer.load`` does from a checkpoint)."""
    import jax.numpy as jnp

    want = program_leaves(leaf_map, weights)
    have = trainer.params
    if set(want) != set(have):
        raise RuntimeError(
            "leaf names differ: only in the program "
            f"{sorted(set(have) - set(want))[:5]}, only in the reference "
            f"{sorted(set(want) - set(have))[:5]}")
    for k in have:
        if tuple(have[k].shape) != tuple(want[k].shape):
            raise RuntimeError(f"leaf {k}: program {have[k].shape}, "
                               f"reference {want[k].shape}")
    trainer.params = place({k: want[k] for k in have})
    trainer.opt_state = trainer.optimizer.init_state(trainer.params)


def _leaf_norms(tree: Dict) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)
    return {k: float(v) for k, v in norms.items()}


def first_grad_norms(trainer, opt: Dict, leaf_map) -> Dict[str, float]:
    """Per-leaf norm of the first gradient as the optimizer got it,
    worked out from its state after one step: momentum keeps
    v1 = -lr*g, Adam keeps m1 = (1-beta1)*g."""
    _, slots = trainer.opt_state
    names = sorted(trainer.params)       # slots follow the flattened dict
    first = _leaf_norms({n: s[0] for n, s in zip(names, slots)})
    if opt["method"] == "momentum":
        scale = 1.0 / float(opt["lr"])
    elif opt["method"] == "adam":
        scale = 1.0 / (1.0 - float(opt["beta1"]))
    else:
        raise ValueError(f"no state rule for {opt['method']!r}")
    back = {v: k for k, v in leaf_map.items()}
    return {back[n]: first[n] * scale for n in names}


def change_norms(trainer, leaf_map, weights):
    import jax
    import jax.numpy as jnp

    back = {v: k for k, v in leaf_map.items()}
    p0 = program_leaves(leaf_map, weights)
    diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k] - b[k]))) for k in a})
    out = diff(trainer.params,
               place({k: p0[k] for k in trainer.params}))
    return {back[k]: float(v) for k, v in out.items()}


def drive(step, feeds, seconds: float, in_flight: int):
    """Dispatch steps for ``seconds``; at most ``in_flight`` beyond the
    last fenced one; the last is fenced.  → (steps, elapsed seconds,
    host time of each fence)."""
    import jax

    pending, fences, n = [], [], 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if len(pending) >= in_flight:
            jax.block_until_ready(pending.pop(0))
            fences.append(time.perf_counter())
        pending.append(step(feeds[n % len(feeds)]))
        n += 1
    for loss in pending:
        jax.block_until_ready(loss)
        fences.append(time.perf_counter())
    return n, time.perf_counter() - t0, fences


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import jax

    cell, seed = ctx["cell"], ctx["seed"]
    cfg, mix = cell.config, cell.traffic
    sizes, opt, feed_spec = cfg["sizes"], cfg["optimizer"], cfg["feed"]
    batch = int(mix["batch"])
    if ctx["rehearsal"]:
        toy = cfg["rehearsal"]
        batch = int(toy["batch"])
        sizes, feed_spec = toy.get("sizes", sizes), toy.get("feed",
                                                            feed_spec)
    ref = H.load_module("reference", cell.config_name, ctx["here"])
    system = H.load_module("systems", cell.config_name, ctx["here"])

    weights = W.make(ref.param_spec(sizes), seed)
    batches = make_batches(feed_spec, batch, int(mix["host_batches"]),
                           seed)
    trainer, leaf_map = system.build(sizes, opt, ctx["chips"])
    install_weights(trainer, leaf_map, weights)

    # the first steps, through the window's own call and feed
    program: Dict[str, Any] = {"losses": []}
    for i in range(reftrain.STEPS):
        loss = trainer.train_one_batch(batches[i])
        program["losses"].append(float(jax.block_until_ready(loss)))
        if i == 0:
            program["grad_norms"] = first_grad_norms(trainer, opt,
                                                     leaf_map)
    program["change_norms"] = change_norms(trainer, leaf_map, weights)
    order = batches[reftrain.STEPS:] + batches[:reftrain.STEPS]
    step = trainer.train_one_batch
    jax.block_until_ready(step(order[-1]))       # settle before timing

    seconds = min(ctx["seconds"], 2.0) if ctx["rehearsal"] \
        else ctx["seconds"]
    traced: Dict[str, Any] = {}
    t_setup = time.perf_counter()
    if ctx["trace"]:
        with tracing.traced(ctx, traced):
            drive(step, order, float(mix["trace_seconds"]),
                  int(mix["in_flight"]))
        seconds = max(1.0, seconds - (time.perf_counter() - t_setup))
    t0 = time.perf_counter()
    n, elapsed, fences = drive(step, order, seconds, int(mix["in_flight"]))
    t1 = time.perf_counter()
    memory_peak = H.memory_peak_bytes(ctx["chips"])
    counters = H.dispatch_rows()

    # free the program's state, then follow the plain reference
    del step, trainer
    gc.collect()
    t_ref = time.perf_counter()
    loss_fn = lambda p, b, q: ref.loss(p, b, sizes, q)
    follow = lambda w, bs, **kw: reftrain.follow(loss_fn, w, bs, opt, **kw)
    reference = follow(weights, batches[:reftrain.STEPS])
    numbers = reftrain.compare(program, reference)
    checks = H.hold(cell, numbers)

    # for the readings and the tests, not for a run: the reference put
    # in the program's place, one precision below the configuration's
    # (the control), and on half of the batch, the mean taken over the
    # rest (a fault); each gives the numbers that the cell compares
    def control():
        return reftrain.compare(follow(weights, batches[:reftrain.STEPS],
                                       cast=cfg["control_precision"]),
                                reference)

    def half_batch():
        half = [{k: v[:batch // 2] for k, v in b.items()}
                for b in batches[:reftrain.STEPS]]
        return reftrain.compare(follow(weights, half), reference)

    # read, not compared: where a run reads far off, these say why
    numbers["longest_fence_gap_s"] = max(
        (b - a for a, b in zip(fences, fences[1:])), default=0.0)
    numbers["compiles_in_window"] = ctx["meter"].compiles_between(t0, t1)
    return {
        "cell": cell, "ctx": ctx, "checks": checks, "numbers": numbers,
        "attempted": n, "failed": 0,
        "setup_s": t_setup - ctx["t_process"],
        "work": n * batch, "window_s": elapsed, "window": (t0, t1),
        "step_fences": fences, "batch": batch,
        "memory_peak_bytes": memory_peak, "counters": counters,
        "meter": ctx["meter"], "trace": traced or None,
        "reference_s": time.perf_counter() - t_ref,
        "program": program, "reference": reference,
        "planted": {"control": control, "half_batch": half_batch},
    }
