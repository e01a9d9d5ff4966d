"""The closed-loop serving driver: a fixed number of clients over
``InferenceServer.submit``/``result``, in process; each sends its next
request when its reply returns.

Traffic parameters (``traffic/<mix>.json``): ``clients``, ``blocks``
(each a fixed multiset of ``prompt_lens`` and ``output_budgets``; a
cycle offers every block once; the seed permutes the blocks, the
requests of a block and the pairing of lengths with budgets, and draws
the token ids),
``admit_cap`` (requests that may wait for admission at once: bounds the
prefill batch, so the set of prefill shapes is closed), ``stagger_s``
(clients start that far apart), the server's ``max_batch``, ``n_pages``,
``page_size``, ``poll_s``, ``sample_requests`` (requests the reference
re-runs), ``trace_seconds``.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from chipbench import harness as H
from chipbench import reftrain, tracing, weights as W

FIRST_PROMPT_ID = 2          # 0 pads, 1 ends a request


def request_plan(mix: Dict, vocab: int, seed: int
                 ) -> Iterator[Tuple[List[int], int]]:
    """(prompt ids, output budget), for ever: each cycle is the whole
    multiset of lengths and budgets, block by block, in an order drawn
    from the seed."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 104729])
    blocks = mix["blocks"]
    while True:
        for b in rng.permutation(len(blocks)):
            lens = blocks[b]["prompt_lens"]
            budgets = blocks[b]["output_budgets"]
            for i, j in zip(rng.permutation(len(lens)),
                            rng.permutation(len(budgets))):
                prompt = rng.integers(FIRST_PROMPT_ID, vocab, lens[i])
                yield prompt.tolist(), int(budgets[j])


def prompt_lens(mix: Dict) -> List[int]:
    return [n for b in mix["blocks"] for n in b["prompt_lens"]]


class Client:
    __slots__ = ("start_at", "req", "rec", "ready_at")

    def __init__(self, start_at: float):
        self.start_at = start_at
        self.req = None
        self.rec = None
        self.ready_at = start_at


class ClosedLoop:
    """The load generator: one polling thread (the caller's), no think
    time.  ``records`` holds one dict per request ever submitted."""

    def __init__(self, server, plan, mix: Dict, t_start: float):
        self.server, self.plan = server, plan
        self.cap = int(mix["admit_cap"])
        self.clients = [Client(t_start + i * float(mix["stagger_s"]))
                        for i in range(int(mix["clients"]))]
        self.records: List[Dict[str, Any]] = []
        self.pool_peak = 0
        self.closing = False
        self.count = server.generated_tokens     # as last polled
        self.tick_at = t_start                   # when it last moved
        self.longest_gap = 0.0                   # between two moves

    def poll(self) -> None:
        now = time.perf_counter()
        n = self.server.generated_tokens
        if n != self.count:
            self.longest_gap = max(self.longest_gap, now - self.tick_at)
            self.count, self.tick_at = n, now
        queued = 0
        for c in self.clients:
            r = c.req
            if r is None:
                continue
            if r.done.is_set():
                c.rec.update(state=r.state, tokens=list(r.tokens),
                             t_first=r.t_first, t_done=r.t_done,
                             error=r.error)
                c.ready_at = r.t_done if r.t_done is not None else now
                c.req = c.rec = None
            elif r.state == "queued":
                queued += 1
        self.pool_peak = max(self.pool_peak, self.server.pool.used_pages())
        if self.closing:
            return
        for c in sorted((c for c in self.clients if c.req is None),
                        key=lambda c: c.ready_at):
            if now < c.start_at or queued >= self.cap:
                continue
            prompt, budget = next(self.plan)
            c.req = self.server.submit(prompt, budget)
            c.rec = {"prompt": prompt, "budget": budget,
                     "t_ready": c.ready_at, "t_submit": c.req.t_submit,
                     "state": "in_flight", "tokens": None,
                     "t_first": None, "t_done": None, "req": c.req}
            self.records.append(c.rec)
            queued += 1

    def run_for(self, seconds: float, poll_s: float) -> None:
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            self.poll()
            time.sleep(poll_s)

    def run_to_edge(self, poll_s: float, patience_s: float = 60.0
                    ) -> float:
        """Poll on until a launch has just emitted its tokens, and give
        that instant: an edge of the window.  Both edges lie there, so
        the window holds whole launches whatever a step takes (at 0.25 s
        a step, an edge that cuts one moves the rate by 0.6 %)."""
        start, end = self.count, time.perf_counter() + patience_s
        while self.count == start:
            if time.perf_counter() > end:
                raise H.BenchError(
                    f"the server emitted nothing in {patience_s:g} s")
            time.sleep(poll_s)
            self.poll()
        while True:                  # the launch's last token is out
            n = self.server.generated_tokens
            time.sleep(poll_s)
            if self.server.generated_tokens == n:
                self.poll()
                return time.perf_counter()

    def emitted(self) -> int:
        """Tokens the clients hold or see coming: finished requests'
        tokens plus what the in-flight ones have produced so far."""
        n = 0
        for rec in self.records:
            if rec["tokens"] is not None:
                n += len(rec["tokens"])
            else:
                n += len(rec["req"].tokens)
        return n

    def close(self) -> None:
        """Stop sending; note how far the in-flight requests came."""
        self.closing = True
        self.poll()
        now = time.perf_counter()
        for rec in self.records:
            if rec["tokens"] is None:
                r = rec["req"]
                rec.update(n_at_close=len(r.tokens), t_first=r.t_first,
                           t_close=now)


def warm(server, mix: Dict, vocab: int) -> None:
    """Every program the window can reach, through the model's own
    entry: prefill at B in 1..admit_cap x each padded prompt length, and
    the fixed-width decode step.  The pools are not donated, so the
    server's own stay as they are."""
    import jax

    model = server.model
    k, v = server._k_pool, server._v_pool
    pads = sorted({-(-int(n) // 16) * 16 for n in prompt_lens(mix)})
    for b in range(1, int(mix["admit_cap"]) + 1):
        for t_pad in pads:
            out = model.prefill(
                k, v, np.full((b, t_pad), FIRST_PROMPT_ID, np.int32),
                np.full((b,), t_pad, np.int32),
                np.zeros((b, server.max_pages), np.int32))
            del out
    width = server.max_batch
    out = model.decode(k, v, np.full((width,), FIRST_PROMPT_ID, np.int32),
                       np.zeros((width, server.max_pages), np.int32),
                       np.ones((width,), np.int32), np.zeros((width,), bool))
    jax.block_until_ready(out[2])
    del out


def served_gaps(ref, weights, sizes, sample: List[Dict],
                cast: str = "none") -> Dict[str, float]:
    """Run the plain reference once over each sampled prompt with its
    served tokens.  → the widest gap by which a served token's logit
    lies below the reference's best.  With ``cast`` the control stands
    in the program's place: at each position of the same prompts and
    tokens, the token that the reference computed in ``cast`` puts
    first is the one judged."""
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
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(weights, sizes, tokens, positions)
        if cast != "none":
            served = ref.logits_at(weights, sizes, tokens, positions,
                                   reftrain.CASTS[cast]).argmax(axis=-1)
    best = want.max(axis=-1)
    got = np.take_along_axis(want, served[..., None], axis=-1)[..., 0]
    return {"served_logit_gap": float(np.max((best - got)[valid])),
            "served_tokens_compared": int(valid.sum())}


def pick_sample(finished: List[Dict], k: int, seed: int) -> List[Dict]:
    """The longest finished request and k-1 others drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rng = np.random.default_rng([int(seed) % (2 ** 63), 15485863])
    rest = list(rng.permutation(order[1:]))[:max(0, k - 1)]
    picked = [finished[order[0]]] + [finished[i] for i in rest]
    while len(picked) < k:                   # fixed batch: one program
        picked.append(picked[0])
    return picked


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cell, seed = ctx["cell"], ctx["seed"]
    cfg, mix = cell.config, dict(cell.traffic)
    sizes = cfg["sizes"]
    if ctx["rehearsal"]:
        sizes = cfg["rehearsal"]["sizes"]
        mix.update(cfg["rehearsal"]["mix"])
    ref = H.load_module("reference", cell.config_name, ctx["here"])
    system = H.load_module("systems", cell.config_name, ctx["here"])
    vocab = int(sizes["vocab_size"])

    weights = W.make(ref.param_spec(sizes), seed)
    model, server = system.build(sizes, mix, weights)
    server.start()
    try:
        warm(server, mix, vocab)
        loop = ClosedLoop(server, request_plan(mix, vocab, seed), mix,
                          time.perf_counter())
        ramp = int(mix["clients"]) * float(mix["stagger_s"]) + 1.0
        loop.run_for(ramp, float(mix["poll_s"]))

        seconds = min(ctx["seconds"], 3.0) if ctx["rehearsal"] \
            else ctx["seconds"]
        traced: Dict[str, Any] = {}
        t_setup = time.perf_counter()
        if ctx["trace"]:
            with tracing.traced(ctx, traced):
                loop.run_for(float(mix["trace_seconds"]),
                             float(mix["poll_s"]))
            traced["span"] = (t_setup, time.perf_counter())
            seconds = max(1.0, seconds - (time.perf_counter() - t_setup))
        poll_s = float(mix["poll_s"])
        t0 = loop.run_to_edge(poll_s)
        tok0, seen0 = server.generated_tokens, loop.emitted()
        loop.longest_gap = 0.0
        loop.run_for(seconds, poll_s)
        t1 = loop.run_to_edge(poll_s)
        tok1, seen1 = server.generated_tokens, loop.emitted()
        longest_gap = loop.longest_gap
        loop.close()
        memory_peak = H.memory_peak_bytes(ctx["chips"])
        counters = H.dispatch_rows()
        pool_peak_share = 100.0 * loop.pool_peak / server.pool.capacity
    finally:
        server.stop()
    if abs((tok1 - tok0) - (seen1 - seen0)) > 2 * int(mix["max_batch"]):
        raise H.BenchError(
            f"the server counted {tok1 - tok0} tokens in the window, its "
            f"clients {seen1 - seen0}")
    records = loop.records
    for rec in records:
        rec.pop("req", None)
    finished = [r for r in records if r["state"] == "done"]
    failed = [r for r in records if r["state"] == "failed"]

    # free the program's state, then run the plain reference
    del model, server, loop
    gc.collect()
    t_ref = time.perf_counter()
    sample = pick_sample(finished, int(mix["sample_requests"]), seed)
    numbers: Dict[str, float] = {}
    if sample:
        numbers = served_gaps(ref, weights, sizes, sample)
    checks = H.hold(cell, numbers)

    # for the readings and the tests, not for a run: the reference one
    # precision below the configuration's, put in the program's place
    def control():
        return served_gaps(ref, weights, sizes, sample,
                           cfg["control_precision"])

    # read, not compared: where a run reads far off, these say why
    numbers["longest_emit_gap_s"] = longest_gap
    numbers["compiles_in_window"] = ctx["meter"].compiles_between(t0, t1)
    return {
        "cell": cell, "ctx": ctx, "checks": checks, "numbers": numbers,
        "attempted": len(finished) + len(failed), "failed": len(failed),
        "setup_s": t_setup - ctx["t_process"],
        "work": tok1 - tok0, "window_s": t1 - t0, "window": (t0, t1),
        "requests": records, "sizes": sizes, "mix": mix,
        "pool_peak_share": pool_peak_share,
        "memory_peak_bytes": memory_peak, "counters": counters,
        "meter": ctx["meter"], "trace": traced or None,
        "reference_s": time.perf_counter() - t_ref,
        "planted": {"control": control},
    }
