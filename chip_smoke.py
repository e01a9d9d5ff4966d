#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

One process, one command, nothing downloaded, every input made from a
seed, every file written under ``--out`` (default
``chiprun_out/chip_smoke`` next to this script):

    python chip_smoke.py              # CLI LSTM job, T=2048 transformer
                                      # steps, HTTP server, kernel checks
    python chip_smoke.py --all        # + one train step each of ResNet-50,
                                      # seq2seq, LSTM hidden 1280, sparse CTR;
                                      # the paged decode kernel timed at the
                                      # serve cells' rows (decode_walk);
                                      # the fused 3×3 conv+BN kernels timed
                                      # by tile at ResNet-50's stage
                                      # shapes (conv_walk)
    python chip_smoke.py --only decode_walk   # just the named phases
    python chip_smoke.py --fsdp --hlo # transformer under FSDP; dump the
                                      # compiled steps and print what each
                                      # Mosaic call sees per chip
    python chip_smoke.py --rehearsal  # CPU sandbox: tiny sizes, kernels in
                                      # interpret mode, can never pass

It drives the main path through the entry points a user calls
(``paddle_tpu.cli.main``, ``Trainer.train_one_batch``,
``InferenceServer`` over HTTP) at the full width of the models the repo
benchmarks, then checks what came out by the repo's own means: finite
losses that fall, every request answered, the server's batch-invariance
contract, the trace-time dispatch counters (the fused kernel was the
path taken, no fallback ``reason``), no ``pallas_call`` built with
``interpret=True``, and each kernel family against the reference the CPU
tests use, now on Mosaic numerics.

Exit status 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", ...}}`` only when JAX's
default device is a TPU and every phase passed.  It never sets
``jax_platforms``.  Every time it prints is information, not a benchmark
number.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import os
import re
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request
from typing import NamedTuple, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))

#: Stated tolerance of the kernel-vs-reference checks: max |got − want|
#: over max |want|.  The MXU multiplies in bf16 (8 mantissa bits) on both
#: sides, in different orders, and the references run parts of their
#: pipeline in bf16 outright.
REL_TOL = 3e-2

DISPATCH_COUNTERS = ("rnn_dispatch_total", "attention_dispatch_total",
                     "conv_dispatch_total", "embedding_dispatch_total")

FULL = {
    "lstm_cli": {"config_args": "", "batches": 5},
    "transformer": {"vocab": 30000, "dim": 512, "heads": 8, "layers": 4,
                    "ffn": 2048, "seq": 2048, "batch": 16, "block": 512,
                    "steps": 8},
    "server": {"vocab": 4000, "dim": 256, "heads": 8, "layers": 4,
               "ffn": 1024, "context": 512, "max_batch": 8,
               "n_pages": 512, "page": 16, "max_new": 32,
               # B·T_pad ≤ 512 for every admission round: each prefill
               # bucket tiles as one packed block
               "prompt_lens": (64, 16, 40, 80, 96)},
    "kernels": {"lstm": (128, 100, 512),
                # dense reference scores are [B, H, T, T] f32: B=4 fits
                "flash": (4, 2048, 8, 64, 512),
                "packed": (5, 96, 8, 32),
                # tables wider than the pages one loop step of the
                # kernel takes (32 of 16, 8 of 64): rows can end beyond
                # a chunk's edge
                "decode": (8, 8, 32, 512, 16, 48),
                # the routed decoder's row: 32 heads over 4 K/V heads of
                # 128, pages of 64; (tokens, experts, a token, dim, width)
                "decode_gqa": (8, 32, 4, 128, 128, 64, 12),
                # the latent decoder's row: 32 heads over rows of 512 +
                # 64 numbers in 640 lanes, pages of 64, 24 slots a row
                "decode_latent": (8, 32, 512, 64, 256, 64, 24),
                "moe": (256, 16, 4, 512, 256)},
    # the paged decode kernel at the serve cells' own rows (decode_walk):
    # (query heads, K/V heads, head lanes, page, pool dtype, window,
    # prompts a row is drawn from, the most tokens generated behind one)
    "decode_walk": {
        "batch": 16, "live_rows": 11, "slots": 128, "pool_pages": 4096,
        "layers": 24, "reps": 20, "chunks": (1, 2, 4, 8, 16), "seed": 36,
        "shapes": {
            "dense": (32, 32, 64, 16, "float32", 0,
                      (128, 128, 128, 256, 256, 384, 512), 192),
            "trinity_window": (32, 4, 128, 64, "bfloat16", 2048,
                               (1024, 1024, 2048, 2048, 4096, 6144), 256),
            "trinity_full": (32, 4, 128, 64, "bfloat16", 0,
                             (1024, 1024, 2048, 2048, 4096, 6144), 256),
            "lfm2": (32, 8, 64, 64, "bfloat16", 0,
                     (1024, 2048, 2048, 3072, 4096), 192)}},
    "resnet": {"depth": 50, "image": 224, "batch": 128, "classes": 1000},
    # the fused 3×3 conv+BN kernels at ResNet-50's stage shapes
    # (conv_walk): (map side, channels), the cell's batch
    "conv_walk": {"batch": 128, "layers": 8, "reps": 5, "seed": 38,
                  "shapes": ((56, 64), (28, 128), (14, 256), (7, 512))},
    "seq2seq": {"B": 128, "S_LEN": 30, "T_LEN": 30, "V": 30000, "E": 512,
                "H": 512},
    "lstm1280": {"vocab": 30000, "hidden": 1280, "batch": 128, "seq": 100},
    "sparse": {"rows": 1_000_000, "dim": 128, "batch": 512, "seq": 25},
}

REHEARSAL = {
    "lstm_cli": {"config_args": "batch_size=8,hidden_size=128",
                 "batches": 2},
    "transformer": {"vocab": 500, "dim": 64, "heads": 2, "layers": 1,
                    "ffn": 128, "seq": 256, "batch": 8, "block": 128,
                    "steps": 3},
    "server": {"vocab": 512, "dim": 64, "heads": 4, "layers": 1,
               "ffn": 128, "context": 128, "max_batch": 4,
               "n_pages": 64, "page": 16, "max_new": 6,
               "prompt_lens": (12, 5, 20)},
    "kernels": {"lstm": (8, 6, 128), "flash": (2, 256, 2, 32, 128),
                "packed": (3, 32, 2, 32), "decode": (4, 2, 32, 32, 16, 4),
                "decode_gqa": (4, 4, 2, 16, 32, 16, 4),
                "decode_latent": (4, 4, 32, 8, 32, 16, 4),
                "moe": (16, 4, 2, 32, 32)},
    "decode_walk": {
        "batch": 4, "live_rows": 3, "slots": 8, "pool_pages": 40,
        "layers": 2, "reps": 1, "chunks": (1,), "seed": 36,
        "shapes": {
            "trinity_window": (4, 2, 32, 4, "bfloat16", 8, (8, 12, 20), 8)}},
    "resnet": {"depth": 8, "image": 32, "batch": 8, "classes": 10},
    "conv_walk": {"batch": 4, "layers": 1, "reps": 1, "seed": 38,
                  "shapes": ((8, 64), (4, 128))},
    "seq2seq": {"B": 8, "S_LEN": 6, "T_LEN": 6, "V": 200, "E": 128,
                "H": 128},
    "lstm1280": {"vocab": 500, "hidden": 640, "batch": 8, "seq": 6},
    "sparse": {"rows": 4096, "dim": 128, "batch": 8, "seq": 4},
}


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------- meters
class Meter:
    """What the process compiled and dispatched, read as deltas per
    phase: JAX's own compile events (``jax.monitoring``), every
    ``pallas_call`` built (kernel name + ``interpret`` flag) and the
    repo's trace-time dispatch counters."""

    #: host-side tracing and lowering to MLIR: paid on every run
    TRACE_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                          "/jax/core/compile/jaxpr_to_mlir_module_duration")
    #: XLA + Mosaic compilation: what the persistent cache saves
    BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        from jax.experimental import pallas as pl

        self.trace_lower_s = 0.0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.pallas_calls = []          # (kernel, interpret)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        real = pl.pallas_call

        def spy(kernel, *a, **kw):
            fn = getattr(kernel, "func", kernel)
            self.pallas_calls.append(
                (f"{fn.__module__.rsplit('.', 1)[-1]}.{fn.__name__}",
                 bool(kw.get("interpret", False))))
            return real(kernel, *a, **kw)

        pl.pallas_call = spy            # ops call pl.pallas_call(...)

    def _on_duration(self, event, secs, **_):
        if event in self.TRACE_LOWER_EVENTS:
            self.trace_lower_s += secs
        elif event == self.BACKEND_EVENT:
            self.backend_compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @staticmethod
    def dispatch_samples():
        from paddle_tpu import observe

        out = {}
        for name in DISPATCH_COUNTERS:
            m = observe.REGISTRY.find(name)
            for s in (m.samples() if m is not None else ()):
                key = (name,) + tuple(sorted(s["labels"].items()))
                out[key] = s["value"]
        return out

    def snapshot(self):
        return {"trace_lower_s": self.trace_lower_s,
                "backend_compile_s": self.backend_compile_s,
                "hits": self.cache_hits, "misses": self.cache_misses,
                "n_pallas": len(self.pallas_calls),
                "dispatch": self.dispatch_samples()}

    def delta(self, before):
        """(compile stats, dispatch rows, pallas calls) since ``before``."""
        now = self.dispatch_samples()
        rows = []
        for key, v in sorted(now.items()):
            d = v - before["dispatch"].get(key, 0.0)
            if d:
                rows.append({"counter": key[0], **dict(key[1:]),
                             "count": int(d)})
        calls = self.pallas_calls[before["n_pallas"]:]
        return ({"trace_lower_s": round(
                     self.trace_lower_s - before["trace_lower_s"], 2),
                 "backend_compile_s": round(
                     self.backend_compile_s - before["backend_compile_s"],
                     2),
                 "cache_hits": self.cache_hits - before["hits"],
                 "cache_misses": self.cache_misses - before["misses"]},
                rows, calls)


class Expect(NamedTuple):
    """What a phase's dispatch table must look like: every
    ``(counter, path)`` in ``want`` ticked, every other row is one of
    ``may``, no ``reason`` label set bar the stated ``reasons``, and each
    kernel in ``kernels`` built at least ``kernel_count`` times."""
    want: Sequence
    may: Sequence = ()
    reasons: Sequence = ()
    kernels: Sequence = ()
    kernel_count: int = 0


def check_dispatch(rows, calls, expect, rehearsal):
    """The phase's headline kernels were the path taken (see
    :class:`Expect`), and no kernel was built for the interpreter."""
    for counter, path in expect.want:
        check(any((r["counter"], r["path"]) == (counter, path)
                  for r in rows),
              f"{counter}: path {path!r} was not taken: {rows}")
    expected = set(expect.want) | set(expect.may)
    for r in rows:
        check((r["counter"], r["path"]) in expected,
              f"{r['counter']}: unexpected path {r['path']!r}: {r}")
        check(not r["reason"] or r["reason"] in expect.reasons,
              f"{r['counter']}: fallback reason set: {r}")
    for k in expect.kernels:
        k, need = k if isinstance(k, tuple) else (k, expect.kernel_count)
        n = sum(1 for c, _ in calls if c == k)
        check(n >= need, f"{k} built {n}× < {need}")
    if not rehearsal:
        bad = sorted({k for k, interp in calls if interp})
        check(not bad, f"pallas_call built with interpret=True: {bad}")


def kernel_census(calls):
    out = {}
    for k, interp in calls:
        key = k + ("[interpret]" if interp else "")
        out[key] = out.get(key, 0) + 1
    return out


def mosaic_calls_of(trainer, feed, path):
    """Dump the compiled train step and list what each Mosaic custom
    call and collective in it looks like PER CHIP (the SPMD-partitioned
    module is the per-device program)."""
    import re

    from paddle_tpu.observe import costmodel

    compiled = trainer._train_step.lower(
        *costmodel._step_args(trainer, feed)).compile()
    text = compiled.as_text()
    with open(path, "w") as f:
        f.write(text)
    kernels, collectives = {}, {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = .+? ([\w\-]+)\(", line)
        if not m:
            continue
        name, op = m.group(1), m.group(2)
        if op == "custom-call" and "tpu_custom_call" in line:
            # the operand shapes ARE what one chip's kernel is handed
            ops = re.search(
                r"operand_layout_constraints=\{(.*?)\}(?:, [a-z_]+=|$)", line)
            shapes = re.sub(r"\{[\d,]*\}", "", ops.group(1)) if ops else "?"
            key = f"{re.sub(r'[.][0-9]+$', '', name)}({shapes[:150]})"
            kernels[key] = kernels.get(key, 0) + 1
        elif op in ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute",
                    "all-gather-start", "all-reduce-start",
                    "collective-permute-start"):
            collectives[op] = collectives.get(op, 0) + 1
    return {"hlo": path, "mosaic_calls": kernels,
            "collectives": collectives}


def laid_over(trainer, feed):
    """How the batch is laid over the devices: (devices, per-device
    shard shape of the first feed leaf)."""
    import jax

    leaf = jax.tree_util.tree_leaves(trainer._shard_feed(feed))[0]
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return {"devices": 1, "global": list(leaf.shape),
                "per_device": list(leaf.shape)}
    return {"devices": len(sh.device_set), "global": list(leaf.shape),
            "per_device": list(sh.shard_shape(leaf.shape))}


def train_steps(trainer, feed, steps):
    """``steps`` fenced steps on one repeated batch → (losses, seconds
    per step).  The first step holds the compile."""
    import jax

    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = float(jax.block_until_ready(trainer.train_one_batch(feed)))
        secs.append(time.perf_counter() - t0)
        losses.append(loss)
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss: {losses}")
    return losses, secs


def steady(secs):
    rest = sorted(secs[1:]) or secs
    return rest[len(rest) // 2]


# ------------------------------------------------------------- phases
def phase_lstm_cli(S, ctx):
    """The README quick-start line: 2×LSTM through ``paddle train
    --job=time`` (config file → parse_config → NeuralNetwork → Trainer)."""
    from paddle_tpu import cli

    save_dir = os.path.join(ctx.out, "lstm_cli")
    os.makedirs(save_dir, exist_ok=True)
    argv = ["train", "--config=" + os.path.join(HERE, "benchmark", "rnn.py"),
            "--job=time", f"--test_period={S['batches']}",
            "--save_dir=" + save_dir]
    if S["config_args"]:
        argv.append("--config_args=" + S["config_args"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli.main(argv)
    check(rc == 0, f"cli.main returned {rc}")
    line = json.loads(buf.getvalue().strip().splitlines()[-1])
    check(line.get("job") == "time", f"no --job=time line: {line}")
    check(line["platform"] == ctx.device["platform"]
          and line["count"] == ctx.device["count"],
          f"time line names another device: {line}")
    check(math.isfinite(line["loss"]), f"non-finite loss: {line}")
    return ({"time_line": line, "warmup_incl_compile_s": line["warmup_s"],
             "steady_step_s": round(line["ms_per_batch"] / 1e3, 5)},
            Expect([("rnn_dispatch_total", "fused")]))


def _transformer_trainer(S, fsdp):
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.models import transformer_text_classifier
    from paddle_tpu.trainer.trainer import Trainer

    cfg = transformer_text_classifier(
        vocab_size=S["vocab"], model_dim=S["dim"], num_heads=S["heads"],
        num_layers=S["layers"], ffn_dim=S["ffn"], num_classes=2,
        max_len=S["seq"], causal=True, block_q=S["block"],
        block_k=S["block"])
    kw = {}
    if fsdp:
        from paddle_tpu.parallel.rule_tables import zoo_fsdp_rules
        kw = {"fsdp": True, "fsdp_rules": zoo_fsdp_rules("transformer")}
    # a small Adam rate: at 1e-3 (and still at 2e-4) the first updates
    # of the d=512 model overshoot — loss 0.72 → 9.6 → 2.5 → … on the
    # chip, the same shape on the CPU with dense XLA attention — and
    # "lower at the end" would hold by luck; at 2e-5 it falls steadily
    trainer = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="adam", learning_rate=2e-5,
        gradient_clipping_threshold=25.0), seed=0, **kw)
    rng = np.random.RandomState(0)
    b, t = S["batch"], S["seq"]
    feed = {"data": SequenceBatch(
                jnp.asarray(rng.randint(0, S["vocab"], (b, t)), jnp.int32),
                jnp.full((b,), t, jnp.int32)),
            "label": jnp.asarray(rng.randint(0, 2, (b,)), jnp.int32)}
    return trainer, feed


def phase_transformer(S, ctx):
    """``models.transformer_text_classifier`` through the API path:
    ``Trainer.train_one_batch`` on one repeated batch."""
    trainer, feed = _transformer_trainer(S, ctx.args.fsdp)
    losses, secs = train_steps(trainer, feed, S["steps"])
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    out = {"losses": [round(x, 5) for x in losses], "fsdp": ctx.args.fsdp,
           "mesh": dict(trainer.mesh.shape),
           "batch_laid_over": laid_over(trainer, feed),
           "first_step_incl_compile_s": round(secs[0], 2),
           "steady_step_s": round(steady(secs), 5)}
    if ctx.args.hlo:
        out["per_chip"] = mosaic_calls_of(
            trainer, feed, os.path.join(ctx.out, "transformer.step.hlo.txt"))
    # both backward kernels once per layer; the forward kernel's call is
    # jitted on its own since PR 32 and is built once a program
    return out, Expect(
        [("attention_dispatch_total", "block_sparse")],
        kernels=[("pallas_attention._fa_pair_kernel", 1),
                 "pallas_attention._bwd_dq_pair_kernel",
                 "pallas_attention._bwd_dkv_pair_kernel"],
        kernel_count=S["layers"])


def _post(port, path, body, timeout=180.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:      # 4xx carries the server's
        return json.loads(e.read())          # {"error": ...} body


def phase_server(S, ctx):
    """``DecoderModel`` → ``InferenceServer``/``PagePool`` → HTTP: a
    prompt served alone, then concurrent waves; every request answers,
    none fails, decode runs at batch > 1, and the lone prompt's tokens
    equal its tokens inside the batch."""
    import numpy as np

    from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                          init_decoder_params)
    from paddle_tpu.serving.server import InferenceServer

    cfg = DecoderConfig(vocab=S["vocab"], dim=S["dim"], heads=S["heads"],
                        layers=S["layers"], ffn=S["ffn"],
                        max_context=S["context"], eos_id=1)
    model = DecoderModel(init_decoder_params(cfg, seed=0), cfg)
    rng = np.random.RandomState(0)
    # ids from 2: never the eos id, so only generation can end a request
    prompts = [rng.randint(2, cfg.vocab, n).tolist()
               for n in S["prompt_lens"]]
    max_new = S["max_new"]
    srv = InferenceServer(model, max_batch=S["max_batch"],
                          n_pages=S["n_pages"],
                          page_size=S["page"]).start()
    try:
        port = srv.start_http(0)

        def generate(prompt):
            t0 = time.perf_counter()
            r = _post(port, "/v1/generate",
                      {"prompt": prompt, "max_new_tokens": max_new})
            check("tokens" in r, f"request failed: {r}")
            check(1 <= len(r["tokens"]) <= max_new
                  and (len(r["tokens"]) == max_new
                       or r["tokens"][-1] == cfg.eos_id),
                  f"bad token count: {r}")
            return r["tokens"], time.perf_counter() - t0

        solo, solo_cold_s = generate(prompts[0])
        solo2, solo_s = generate(prompts[0])
        check(solo2 == solo, "the same lone prompt gave different tokens")

        def wave():
            results = [None] * len(prompts)
            errors = []

            def one(i):
                try:
                    results[i] = generate(prompts[i])
                except Exception as e:  # noqa: BLE001 - joined below
                    errors.append(f"{type(e).__name__}: {e}")

            threads = [threading.Thread(target=one, args=(i,),
                                        name=f"smoke-client-{i}")
                       for i in range(len(prompts))]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            peak = 0
            while any(th.is_alive() for th in threads):
                peak = max(peak, srv.stats()["active"])
                time.sleep(0.0005)
            for th in threads:
                th.join()
            check(not errors, f"wave requests failed: {errors}")
            return ([r[0] for r in results], peak,
                    time.perf_counter() - t0)

        tokens1, peak1, wave_cold_s = wave()
        tokens2, peak2, wave_s = wave()
        check(tokens1 == tokens2, "two identical waves gave different "
                                  "tokens")
        check(max(peak1, peak2) > 1,
              f"decode never ran at batch > 1 (peak active {peak1}, "
              f"{peak2})")
        check(tokens1[0] == solo,
              "batch invariance broken: prompt 0 alone gave "
              f"{solo}, in the batch {tokens1[0]}")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            health = json.loads(r.read())
        n_req = 2 + 2 * len(prompts)
        check(health.get("status") == "ok" and health["served"] == n_req
              and health["active"] == 0 and health["queue_depth"] == 0,
              f"/healthz after {n_req} requests: {health}")
    finally:
        srv.stop()
    return ({"requests": n_req, "peak_active": max(peak1, peak2),
             "tokens_per_request": [len(t) for t in tokens1],
             "devices": 1,
             "first_request_incl_compile_s": round(solo_cold_s, 2),
             "steady_request_s": round(solo_s, 4),
             "first_wave_incl_compile_s": round(wave_cold_s, 2),
             "steady_wave_s": round(wave_s, 4)},
            Expect([("attention_dispatch_total", "packed"),
                    ("attention_dispatch_total", "decode")]))


def _rel_err(got, want):
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(np.all(np.isfinite(g)), "kernel output not finite")
        check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
        worst = max(worst, float(np.max(np.abs(g - w)))
                    / (float(np.max(np.abs(w))) or 1.0))
    return worst


_HLO_SHAPE = r"\b[a-z]+[0-9]*\[([\d,]*)\]"
_HLO_MOVE = re.compile(
    r"(?:ROOT )?%[\w.\-]+ = (.+?) (copy|copy-start|transpose|reshape|slice"
    r"|dynamic-slice|dynamic-update-slice)\((.*)")


def _moved_shapes(result: str, op: str, operands: str):
    """The dims of what an instruction of :data:`_HLO_MOVE` moves: a
    slice's result, a dynamic-update-slice's update (its first operand
    is written in place where the step donates it, and copied by a
    ``copy`` of its own where not), else the result and the operands."""
    if op in ("slice", "dynamic-slice"):
        return re.findall(_HLO_SHAPE, result)
    if op == "dynamic-update-slice":
        return re.findall(_HLO_SHAPE, operands)[1:2]
    return re.findall(_HLO_SHAPE, result + operands)


def decode_step_checks(compiled, pool_elems, patterns=None):
    """What the benchmark's traced run needs of a compiled step of the
    server (its prefill or its decode step, pools donated): where
    ``patterns`` are given, an instruction that one of them finds (None:
    the accepted metric ``paged_decode_roofline.serve``'s own pattern;
    for a decoder with a layer plan the pattern of the kernel's own
    name); and the pools updated in place: no ``copy``, ``slice``,
    ``dynamic-slice`` or ``dynamic-update-slice``, and no relayout
    (``transpose``, a ``reshape`` the compiler could not make a
    bitcast), that moves a layer's pool of elements or more
    (:func:`_moved_shapes`), in the entry computation or inside a
    fusion."""
    from jax._src.lib import xla_client as xc

    if patterns is None:
        with open(os.path.join(HERE, "chipbench", "metrics",
                               "paged_decode_roofline.serve.json")) as f:
            patterns = json.load(f)["args"]["patterns"]
    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    calls, moves, found = 0, [], set()
    for line in map(str.strip, text.splitlines()):
        hit = {pat for pat in patterns if re.search(pat, line)}
        calls += bool(hit)
        found |= hit
        m = _HLO_MOVE.match(line)
        if m and any(math.prod(int(n) for n in dims.split(",") if n)
                     >= pool_elems for dims in _moved_shapes(*m.groups())):
            moves.append(line[:160])
    # under whatever scopes the step enters (ops/scopes.py): the default
    # plan's call keeps the name it inherits only under none
    check(found == set(patterns),
          "no instruction of the compiled step matches "
          f"{sorted(set(patterns) - found)}: the traced run of the "
          "benchmark cannot find that kernel")
    check(not moves, "the compiled step copies, slices or relayouts a "
                     f"layer's pool or more: {moves}")
    return {"decode_calls": calls, "pool_sized_moves": len(moves)}


def phase_kernels(S, ctx):
    """Each kernel family on the path against the reference the CPU
    tests use, on this backend's numerics, within ``REL_TOL``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.ops import pallas_attention as pa
    from paddle_tpu.ops import pallas_lstm, recurrent_ops
    from paddle_tpu.utils import FLAGS

    rng = np.random.RandomState(0)
    errs = {}

    def randn(*shape, dtype=jnp.float32, scale=1.0):
        return (jnp.asarray(rng.randn(*shape).astype(np.float32)) * scale
                ).astype(dtype)

    @contextlib.contextmanager
    def dense_attention():            # the CPU tests' flash reference
        FLAGS.set("flash_kernel", False)
        try:
            yield
        finally:
            FLAGS.set("flash_kernel", True)

    @contextlib.contextmanager
    def scan_lstm():                  # the lax.scan recurrence
        real = pallas_lstm.fused_ok
        pallas_lstm.fused_ok = lambda *_: False
        try:
            yield
        finally:
            pallas_lstm.fused_ok = real

    # fused LSTM forward + backward vs the scan
    b, t, h = S["lstm"]
    xw = randn(b, t, 4 * h, scale=0.3)
    w_hh = randn(h, 4 * h, scale=0.05)
    lens = jnp.asarray(rng.randint(t // 2, t + 1, (b,)), jnp.int32)
    cot = randn(b, t, h)

    def lstm_loss(xw, w):
        out, _ = recurrent_ops.lstm_sequence(
            SequenceBatch(xw, lens), None, w, None)
        return jnp.sum(out.data.astype(jnp.float32) * cot)

    lstm = lambda: jax.jit(jax.value_and_grad(lstm_loss, (0, 1)))(xw, w_hh)
    got = lstm()
    with scan_lstm():
        want = lstm()
    errs["lstm_fused_fwd_bwd"] = _rel_err(got, want)

    # flash attention forward + backward vs dense
    b, t, nh, d, blk = S["flash"]
    q, k, v, cot = (randn(b, t, nh, d, dtype=jnp.bfloat16)
                    for _ in range(4))
    lens = jnp.asarray(rng.randint(t // 2, t + 1, (b,)), jnp.int32)

    def flash_loss(q, k, v):
        o = pa.flash_attention(q, k, v, lens, True, blk, blk)
        return jnp.sum(o.astype(jnp.float32) * cot.astype(jnp.float32))

    flash = lambda: jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2)))(
        q, k, v)
    flash_qkv = (q, k, v)
    got = flash()
    with dense_attention():
        want = flash()
    errs["flash_fwd_bwd"] = _rel_err(got, want)

    # packed prefill (the server's [1, B·T] layout) vs dense
    b, t, nh, d = S["packed"]
    q, k, v = (randn(1, b * t, nh, d) for _ in range(3))
    seg = pa.segments_from_lengths(
        jnp.asarray(rng.randint(max(1, t // 2), t + 1, (b,)), jnp.int32),
        b, t)
    packed = lambda: jax.jit(lambda q, k, v: pa.flash_attention_packed(
        q, k, v, seg, causal=True, slot=t))(q, k, v)
    got = packed()
    with dense_attention():
        want = packed()
    errs["packed_prefill"] = _rel_err(got, want)

    def decode_lengths(rows, slots, page, *call):
        """Seeded lengths whose first three lie at, one under and one
        over the edge of the chunk the kernel takes a loop step at
        ``call``'s shapes (its gauge says which)."""
        jax.eval_shape(pa.paged_decode_attention, *call,
                       jnp.ones((rows,), jnp.int32))
        edge = page * pages_a_step(call[1])
        lens = rng.randint(1, slots * page, (rows,))
        lens[:3] = np.minimum((edge, edge - 1, edge + 1), slots * page)
        return jnp.asarray(lens, jnp.int32)

    # paged decode vs the dense gather reference
    b, nh, d, n_pages, page, max_pages = S["decode"]
    q = randn(b, 1, nh, d)
    kp, vp = randn(n_pages, page, nh, d), randn(n_pages, page, nh, d)
    pidx = jnp.asarray(rng.permutation(n_pages - 1)[:b * max_pages]
                       .reshape(b, max_pages) + 1, jnp.int32)
    lens = decode_lengths(b, max_pages, page, q, kp, vp, pidx)
    errs["paged_decode"] = _rel_err(
        jax.jit(pa.paged_decode_attention)(q, kp, vp, pidx, lens),
        jax.jit(pa.paged_decode_reference)(q, kp, vp, pidx, lens))

    # the same with query heads that share K/V heads under a window of
    # two pages, the pool stored in bfloat16, one row a token
    bg, hg, g, dg, pages_g, page_g, slots_g = S["decode_gqa"]
    qg = randn(bg, 1, hg, dg)
    kg, vg = (randn(pages_g, page_g, g * dg, dtype=jnp.bfloat16)
              for _ in range(2))
    pidx_g = jnp.asarray(rng.permutation(pages_g - 1)[:bg * slots_g]
                         .reshape(bg, slots_g) + 1, jnp.int32)
    lens_g = decode_lengths(bg, slots_g, page_g, qg, kg, vg, pidx_g)
    errs["paged_decode_gqa_window"] = _rel_err(
        jax.jit(lambda *a: pa.paged_decode_attention(
            *a, window=2 * page_g))(qg, kg, vg, pidx_g, lens_g),
        jax.jit(lambda *a: pa.paged_decode_reference(
            *a, window=2 * page_g))(
            qg, kg.reshape(pages_g, page_g, g, dg),
            vg.reshape(pages_g, page_g, g, dg), pidx_g, lens_g))

    # decode over a latent cache: one row a token is every head's key
    # and, in its leading lanes, its value; bfloat16, whole lane tiles
    bl, hl, rank, rope, pages_l, page_l, slots_l = S["decode_latent"]
    wl = -(-(rank + rope) // 128) * 128
    ql = randn(bl, hl, wl, dtype=jnp.bfloat16)
    rows_l = randn(pages_l, page_l, wl, dtype=jnp.bfloat16)
    lens_l = jnp.asarray(rng.randint(1, slots_l * page_l, (bl,)), jnp.int32)
    pidx_l = jnp.asarray(rng.permutation(pages_l - 1)[:bl * slots_l]
                         .reshape(bl, slots_l) + 1, jnp.int32)
    latent = lambda fn: jax.jit(lambda *a: fn(*a, rank, wl ** -0.5))(
        ql, rows_l, pidx_l, lens_l)
    errs["latent_decode"] = _rel_err(
        latent(pa.latent_decode_attention),
        latent(pa.latent_decode_reference))

    # routed experts: the grouped matmul over rows sorted by expert vs
    # every expert on every token
    from paddle_tpu.ops import pallas_moe

    t_moe, e, top_k, dm, f = S["moe"]
    xm = randn(t_moe, dm)
    rw, rb = randn(dm, e, scale=dm ** -0.5), randn(e, scale=0.1)
    wg, wu = (randn(e, dm, f, dtype=jnp.bfloat16, scale=dm ** -0.5)
              for _ in range(2))
    wd = randn(e, f, dm, dtype=jnp.bfloat16, scale=f ** -0.5)

    def every_expert(x):
        ex, wt = pallas_moe.route(x, rw, rb, top_k, 2.826)
        weight = jnp.zeros((t_moe, e)).at[
            jnp.arange(t_moe)[:, None], ex].set(wt)
        mm = lambda a, w, eq: jnp.einsum(
            eq, a.astype(jnp.bfloat16), w,
            preferred_element_type=jnp.float32)
        hid = jax.nn.silu(mm(x, wg, "td,edf->etf")) \
            * mm(x, wu, "td,edf->etf")
        return jnp.einsum("etd,te->td", mm(hid, wd, "etf,efd->etd"), weight)

    errs["routed_experts"] = _rel_err(
        jax.jit(lambda x: pallas_moe.routed_experts(
            x, rw, rb, wg, wu, wd, top_k=top_k, route_scale=2.826)[0])(xm),
        jax.jit(every_expert)(xm))

    # the steps as the server compiles them, small, pools donated: the
    # benchmark's traced run must find the kernel in the decode step,
    # and neither step may move a layer's pool.  A rehearsal has no
    # Mosaic call to look at
    server_steps = {}
    from paddle_tpu.ops import kernels as K

    if not ctx.args.rehearsal:
        from paddle_tpu.serving.model import (DecoderConfig, DecoderModel,
                                              init_decoder_params)

        def steps_of(cfg, n_pages, page, pidx, lens, patterns):
            model = DecoderModel(init_decoder_params(cfg, seed=0), cfg)
            pools = [p.array for p in model.new_pools(n_pages, page)]
            rows, t = pidx.shape[0], 128
            # a layer of the smallest cache (the conv state, where kept)
            layer = min(p[0].size for p in pools)
            return {
                "decode": decode_step_checks(model._decode.lower(
                    model.params, *pools, jnp.zeros((rows,), jnp.int32),
                    # the ids of the launch before and which of them
                    # each row is fed (routed counts ride behind them)
                    jnp.zeros((rows + (2 if model.routed_layers else 0),),
                              jnp.int32),
                    jnp.full((rows,), -1, jnp.int32),
                    pidx, lens, jnp.ones((rows,), bool)).compile(),
                    layer, patterns),
                "prefill": decode_step_checks(model._prefill.lower(
                    model.params, *pools, jnp.zeros((1, t), jnp.int32),
                    jnp.full((1,), t, jnp.int32), pidx[:1]).compile(),
                    layer, ())}

        server_steps["default_plan"] = steps_of(
            DecoderConfig(vocab=512, dim=nh * d, heads=nh, layers=2,
                          ffn=2 * nh * d, max_context=max_pages * page),
            n_pages, page, pidx, lens, None)
        # and of a decoder with a layer plan: grouped K/V heads, a window
        # layer and a full one, routed experts, bfloat16 storage; its
        # kernel is found by the name it is given
        server_steps["planned"] = steps_of(
            DecoderConfig(
                vocab=512, dim=dm, heads=hg, layers=2, ffn=2 * dm,
                max_context=slots_g * page_g, kv_heads=g, head_dim=dg,
                window=2 * page_g, experts=e, top_k=top_k, expert_ffn=f,
                route_scale=2.826, pos_embed=False, storage="bfloat16",
                plan=("window+rope+qknorm+gate+postnorm/swiglu",
                      "full+qknorm+gate+postnorm/routed+shared")),
            pages_g, page_g, pidx_g, lens_g,
            [K.instruction_pattern(name) + ".*tpu_custom_call"
             for name in (K.PAGED_DECODE, K.MOE_GMM)])
        # and of a latent plan: one pool of compressed rows, donated
        server_steps["latent"] = steps_of(
            DecoderConfig(
                vocab=512, dim=dm, heads=hl, layers=2, ffn=2 * dm,
                max_context=slots_l * page_l, q_rank=3 * rank // 4,
                kv_rank=rank, nope_dim=rank // 4, rope_dim=rope,
                v_dim=rank // 4, rope_interleave=True, experts=e,
                top_k=top_k, expert_ffn=f, route_scale=2.5,
                pos_embed=False, storage="bfloat16",
                plan=("latent+rope/swiglu", "latent+rope/routed+shared")),
            pages_l, page_l, pidx_l, lens_l,
            [K.instruction_pattern(K.LATENT_DECODE) + ".*tpu_custom_call"])
        # and of a plan with conv layers: their state is one more
        # donated cache, a place a page ([L, P, 2, dim]: 4096 places make
        # a layer of it larger than any weight, which XLA may well
        # prefetch whole), beside K/V pools that hold the one layer that
        # attends: 8 heads over 2 K/V heads of 64, half a lane tile a head
        pages_c, page_c, slots_c = 4096, 64, 2
        server_steps["conv"] = steps_of(
            DecoderConfig(
                vocab=512, dim=dm, heads=8, layers=3, ffn=2 * dm,
                max_context=slots_c * page_c, kv_heads=2, experts=e,
                top_k=top_k, expert_ffn=f, pos_embed=False,
                storage="bfloat16",
                plan=("conv/swiglu", "full+rope+qknorm/routed",
                      "conv/routed")),
            pages_c, page_c,
            jnp.asarray(rng.permutation(pages_c - 1)[:bg * slots_c]
                        .reshape(bg, slots_c) + 1, jnp.int32),
            jnp.asarray(rng.randint(1, slots_c * page_c, (bg,)), jnp.int32),
            [K.instruction_pattern(K.PAGED_DECODE) + ".*tpu_custom_call"])

    # the trace can name the kernels: a Mosaic call compiles to an
    # instruction named after ops/kernels.py's table (its ``name=``),
    # whatever scope or jitted lambda it sits in — the op events of a
    # device trace carry that instruction's line.  A rehearsal has no
    # Mosaic call to look at, only the scope in the lowering's locations
    from paddle_tpu.ops import nn_ops

    z = randn(2, 8, 8, 64, dtype=jnp.bfloat16)
    w = randn(3, 3, 64, 64, dtype=jnp.bfloat16, scale=0.05)
    a, c = randn(64), randn(64)
    with jax.named_scope("__exconv_0__"):     # a layer's scope around it
        lowered = {
            K.CONV_BN_FWD: jax.jit(
                lambda z, a, c, w: nn_ops.affine_act_conv2d(z, a, c, w)
            ).lower(z, a, c, w),
            K.FLASH_FWD: jax.jit(lambda q, k, v: pa.flash_attention(
                q, k, v, None, True, blk, blk)).lower(*flash_qkv)}
    for name, low in lowered.items():
        if ctx.args.rehearsal:
            found = name in low.as_text(debug_info=True)
        else:
            found = re.search(K.instruction_pattern(name)
                              + ".*tpu_custom_call",
                              low.compile().as_text())
        check(found, f"no instruction named {name!r} in the compiled "
                     "program: the device trace cannot name the kernel")

    errs = {k: round(e, 5) for k, e in errs.items()}
    bad = {k: e for k, e in errs.items() if not e <= REL_TOL}
    check(not bad, f"kernel != reference beyond {REL_TOL}: {bad} "
                   f"(all: {errs})")
    # no Expect: the references tick their own fallback labels by design
    return {"rel_err": errs, "tolerance": REL_TOL,
            "named_kernels": sorted(lowered),
            "server_steps": server_steps}, None


def pages_a_step(k_pages):
    """Pages a loop step the paged decode kernel takes over pool
    ``k_pages``, as the last call traced over it told its gauge."""
    from paddle_tpu import observe

    width = math.prod(k_pages.shape[2:])
    return int(observe.REGISTRY.find("paged_decode_pages_per_step").value(
        page=str(k_pages.shape[1]), width=str(width + -width % 128),
        dtype=k_pages.dtype.name))


def decode_walk_inputs(S):
    """The serve cells' decode rows, seeded: for each shape of
    ``S["shapes"]`` its name, window, a query ``[B, 1, H, D]``, one
    layer's K and V pool as stored, a page table and two sets of
    lengths: ``cell`` (a step's live rows, each a prompt of the cell's
    plus part of its budget, beside idle slots of length 1, as the
    server pads them) and ``one_page_a_row`` (next to nothing live)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(S["seed"])
    b, slots, n_pages = S["batch"], S["slots"], S["pool_pages"]
    for shape, (h, g, d, page, dtype, window, prompts, budget) \
            in S["shapes"].items():
        q = jnp.asarray(rng.randn(b, 1, h, d), jnp.float32)
        kp, vp = (jnp.asarray(rng.randn(n_pages, page, g * d), dtype)
                  for _ in range(2))
        pidx = jnp.asarray(rng.permutation(n_pages - 1)[:b * slots]
                           .reshape(b, slots) + 1, jnp.int32)
        drawn = np.ones(b, np.int64)
        drawn[:S["live_rows"]] = np.minimum(
            rng.choice(prompts, S["live_rows"])
            + rng.randint(0, budget + 1, S["live_rows"]), slots * page)
        yield shape, window, q, kp, vp, pidx, {
            "cell": drawn, "one_page_a_row": np.minimum(drawn, page)}


def per_call_us(call, layers, reps, q, *rest):
    """Microseconds one ``call(q, *rest)`` takes as one of ``layers`` in
    a row inside one program, each fed the result before it, as a decode
    step's layers are."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda q, *rest: jax.lax.fori_loop(
        0, layers, lambda _, o: call(q + o * 1e-9, *rest),
        jnp.zeros_like(q)))
    jax.block_until_ready(f(q, *rest))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(q, *rest)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / layers * 1e6


def phase_decode_walk(S, ctx):
    """Microseconds a call of the paged decode kernel by shape, live
    pages and pages a loop step (ISSUE 36's step 0, kept: the decode
    kernels' ``[H, 1]`` statistics, ROADMAP S6, start from these
    numbers): :func:`decode_walk_inputs`' rows at the chunk
    ``_pages_per_step`` gives and at every chunk of ``S["chunks"]``,
    each against the dense reference and the bandwidth's floor for the
    live pages' K and V."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.observe import costmodel
    from paddle_tpu.ops import kernels as K
    from paddle_tpu.ops import pallas_attention as pa

    peak_bytes_s = costmodel.detect_peaks()["bw"]
    table = []
    for shape, window, q, kp, vp, pidx, fills in decode_walk_inputs(S):
        n_pages, page, width = kp.shape
        g = width // q.shape[3]
        # the rule's choice, as the call's gauge says it
        jax.eval_shape(lambda *a: pa.paged_decode_attention(
            *a, window=window), q, kp, vp, pidx, jnp.ones(q.shape[:1], int))
        chosen = pages_a_step(kp)
        as_heads = lambda a: a.reshape(n_pages, page, g, -1)
        for fill, lens in fills.items():
            first = np.maximum(lens - window, 0) // page if window else 0
            live = int(np.sum(-(-lens // page) - first))
            row = {"shape": shape, "fill": fill, "live_pages": live,
                   # K and V of the live pages once at the bandwidth
                   "bandwidth_floor_us": round(
                       live * page * 2 * width * kp.dtype.itemsize
                       / peak_bytes_s * 1e6, 2),
                   "rule_chunk": chosen, "us_a_call": {}, "rel_err": {}}
            lens = jnp.asarray(lens, jnp.int32)
            want = jax.jit(lambda *a: pa.paged_decode_reference(
                *a, window=window))(q, as_heads(kp), as_heads(vp), pidx,
                                    lens)
            for chunk in sorted({chosen, *S["chunks"]}):
                call = lambda *a: pa._paged_decode(
                    *a, window, chunk, K.PAGED_DECODE)
                row["rel_err"][chunk] = round(_rel_err(
                    jax.jit(call)(q, kp, vp, pidx, lens), want), 5)
                row["us_a_call"][chunk] = round(per_call_us(
                    call, S["layers"], S["reps"], q, kp, vp, pidx, lens), 2)
            table.append(row)
            print(json.dumps(row), flush=True)
            bad = {c: e for c, e in row["rel_err"].items()
                   if not e <= REL_TOL}
            check(not bad, f"{shape}/{fill}: kernel != reference beyond "
                           f"{REL_TOL} at chunks {bad}")
    return {"decode_walk": table}, None


def conv_tile_of(kernel, h, w, cin, cout):
    """The tile a fused 3×3 conv+BN kernel took at these shapes, as the
    last call traced there told its ``conv_bn_tile`` gauge."""
    from paddle_tpu import observe
    from paddle_tpu.ops import pallas_conv as pc

    want = {"kernel": kernel, "h": str(h), "w": str(w), "cin": str(cin),
            "cout": str(cout)}
    for smp in observe.REGISTRY.find("conv_bn_tile").samples():
        lab = smp["labels"]
        if all(lab[k] == v for k, v in want.items()):
            return pc.ConvTile(int(smp["value"]), lab["k"], int(lab["rows"]))
    raise SmokeFailure(f"no conv_bn_tile for {want}")


def chained_call_us(call, layers, reps, x, *rest):
    """Microseconds one ``call(x, *rest)`` takes as one of ``layers`` in
    a row inside one program, each fed the result of the one before (of
    x's shape and dtype): nothing else runs between the calls."""
    import jax

    f = jax.jit(lambda x, *rest: jax.lax.fori_loop(
        0, layers, lambda _, o: call(o, *rest), x))
    jax.block_until_ready(f(x, *rest))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(x, *rest)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps / layers * 1e6


def conv_walk_variants(rule, h, n):
    """Tiles of the walk: the rule's, and the rule's with each of its
    choices moved one step (the other contraction forms, half and twice
    the images a step, half and twice the band)."""
    out = {"rule": rule}
    for k in ("c", "9c"):
        out[f"rule_k={k}"] = rule._replace(k=k)
    for nb in (rule.nb // 2, rule.nb * 2):
        if 1 <= nb <= n and n % nb == 0:
            out[f"rule_nb={nb}"] = rule._replace(nb=nb)
    for rows in (rule.rows // 2, rule.rows * 2):
        if 1 <= rows <= h:
            out[f"rule_rows={rows}"] = rule._replace(rows=rows)
    seen, uniq = set(), {}             # one entry a distinct tile
    for name, t in out.items():
        if t not in seen:
            seen.add(t)
            uniq[name] = t
    return uniq


@contextlib.contextmanager
def conv_tile_fixed(tile):
    """Every fused 3×3 call traced inside takes ``tile`` in place of the
    rule's."""
    from paddle_tpu.ops import pallas_conv as pc

    rule = pc._conv_tile
    pc._conv_tile = lambda *a, **kw: tile
    try:
        yield
    finally:
        pc._conv_tile = rule


def phase_conv_walk(S, ctx):
    """Microseconds a call of the fused 3×3 conv+BN kernels
    (``conv_bn_fwd``, ``conv_bn_fwd_bwd``) at ResNet-50's four stage
    shapes, by tile (kept beside decode_walk): the tiles of
    :func:`conv_walk_variants` and XLA's unfused composition, each
    against that composition's numbers and the MXU's floor."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.observe import costmodel
    from paddle_tpu.ops import kernels as K
    from paddle_tpu.ops import pallas_conv as pc

    peak = costmodel.detect_peaks()["flops"]
    n, bf16 = S["batch"], jnp.bfloat16
    rng = np.random.RandomState(S["seed"])
    table = []

    def composed(z, ci, w):
        x = jnp.maximum(ci[0] * z.astype(jnp.float32) + ci[1], 0.0)
        return pc._conv3x3(x.astype(w.dtype), w).astype(z.dtype)

    for hw, c in S["shapes"]:
        z = jnp.asarray(rng.randn(n, hw, hw, c) * 0.5, bf16)
        dy = jnp.asarray(rng.randn(n, hw, hw, c), bf16)
        w = jnp.asarray(rng.randn(3, 3, c, c) / math.sqrt(9 * c), bf16)
        ci = jnp.zeros((8, c), jnp.float32).at[0].set(
            jnp.asarray(rng.rand(c) + 0.5, jnp.float32)).at[1].set(
            jnp.asarray(rng.randn(c) * 0.3, jnp.float32))
        calls = {
            K.CONV_BN_FWD: (
                lambda z, ci, w: pc._fwd_call(z, ci, w, z.dtype, True),
                composed, (z, ci)),
            K.CONV_BN_FWD_BWD: (
                lambda g, z, ci, w: pc._fwd_bwd_call(g, z, ci, w, True)[0],
                lambda g, z, ci, w: jax.vjp(
                    lambda z_: composed(z_, ci, w), z)[1](g)[0],
                (dy, z, ci))}
        for kernel, (fused, xla, args) in calls.items():
            jax.eval_shape(fused, *args, w)
            rule = conv_tile_of(kernel, hw, hw, c, c)
            row = {"shape": f"{hw}x{hw}x{c}", "kernel": kernel, "batch": n,
                   "rule": rule._asdict(),
                   "floor_us": round(pc._conv_flops(n, hw, hw, c, c)
                                     / peak * 1e6, 2),
                   "us_a_call": {}, "rel_err": {}}
            want = jax.jit(lambda *a: xla(*a, w))(*args)
            row["us_a_call"]["xla"] = round(chained_call_us(
                xla, S["layers"], S["reps"], *args, w), 2)
            for name, t in conv_walk_variants(rule, hw, n).items():
                with conv_tile_fixed(t):        # a new function: traced anew
                    row["rel_err"][name] = round(_rel_err(
                        jax.jit(lambda *a: fused(*a))(*args, w), want), 5)
                    row["us_a_call"][name] = round(chained_call_us(
                        fused, S["layers"], S["reps"], *args, w), 2)
            table.append(row)
            print(json.dumps(row), flush=True)
            bad = {k: e for k, e in row["rel_err"].items()
                   if not e <= REL_TOL}
            check(not bad, f"{row['shape']} {kernel}: kernel != XLA's "
                           f"composition beyond {REL_TOL} at {bad}")
    return {"conv_walk": table}, None


# ------------------------------------------------------ --all phases
def _one_step(trainer, feed, ctx, name):
    losses, secs = train_steps(trainer, feed, 2)
    out = {"losses": [round(x, 5) for x in losses],
           "batch_laid_over": laid_over(trainer, feed),
           "first_step_incl_compile_s": round(secs[0], 2),
           "second_step_s": round(secs[1], 5)}
    if ctx.args.hlo:
        out["per_chip"] = mosaic_calls_of(
            trainer, feed, os.path.join(ctx.out, f"{name}.step.hlo.txt"))
    return out


def _adam_trainer(cfg, lr=1e-3):
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.trainer.trainer import Trainer

    return Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="adam", learning_rate=lr,
        gradient_clipping_threshold=25.0), seed=0)


def phase_lstm1280(S, ctx):
    """2×LSTM past the single-block gate: the hidden-blocked tier."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import lstm_text_classifier

    cfg = lstm_text_classifier(vocab_size=S["vocab"], embed_dim=128,
                               hidden_size=S["hidden"], lstm_num=2,
                               num_classes=2)
    rng = np.random.RandomState(0)
    b, t = S["batch"], S["seq"]
    feed = {"data": SequenceBatch(
                jnp.asarray(rng.randint(0, S["vocab"], (b, t)), jnp.int32),
                jnp.asarray(rng.randint(t // 2, t + 1, (b,)), jnp.int32)),
            "label": jnp.asarray(rng.randint(0, 2, (b,)), jnp.int32)}
    return (_one_step(_adam_trainer(cfg), feed, ctx, "lstm1280"),
            Expect([("rnn_dispatch_total", "fused_blocked")]))


def seq2seq_setup(B, S_LEN, T_LEN, V, E, H):
    """The seq2seq trainer and one feed."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import ParamAttr, StepInput, config_scope
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.data.feeder import integer_value_sequence
    from paddle_tpu.utils import FLAGS
    from paddle_tpu.v2.networks import simple_attention, simple_gru

    FLAGS.set("bf16_activations", True)
    # the demo/seqToseq training topology
    with config_scope():
        src = dsl.data("source", integer_value_sequence(V))
        trg = dsl.data("target", integer_value_sequence(V))
        trg_next = dsl.data("target_next", integer_value_sequence(V))
        src_emb = dsl.embedding(src, size=E, name="src_emb",
                                param_attr=ParamAttr(name="_src_emb"),
                                vocab_size=V)
        fwd = simple_gru(src_emb, size=H, name="enc_fwd")
        bwd = simple_gru(src_emb, size=H, name="enc_bwd", reverse=True)
        enc = dsl.concat([fwd, bwd], name="enc_seq")
        enc_proj = dsl.fc(enc, size=H, act=dsl.LinearActivation(),
                          bias_attr=False, name="enc_proj")
        boot = dsl.fc(dsl.last_seq(bwd), size=H,
                      act=dsl.TanhActivation(), name="dec_boot")
        trg_emb = dsl.embedding(trg, size=E, name="trg_emb",
                                param_attr=ParamAttr(name="_trg_emb"),
                                vocab_size=V)

        def step(e, ep, b, w):
            mem = dsl.memory(name="dec_gru", size=H, boot_layer=b)
            context = simple_attention(e, ep, mem.out, name="att")
            inp = dsl.fc([context, w], size=H * 3,
                         act=dsl.LinearActivation(), bias_attr=False,
                         name="dec_inproj")
            hidden = dsl.gru_step_layer(inp, mem.out, size=H,
                                        name="dec_gru")
            return dsl.fc(hidden, size=V, act=dsl.SoftmaxActivation(),
                          name="dec_prob")

        probs = dsl.recurrent_group(
            step, [enc, enc_proj, boot, StepInput(trg_emb)],
            name="decoder")
        cost = dsl.classification_cost(probs, trg_next)
        cfg = dsl.topology(cost)

    trainer = _adam_trainer(cfg, lr=5e-4)
    rng = np.random.RandomState(0)

    def ids(t):
        return SequenceBatch(
            jnp.asarray(rng.randint(2, V, (B, t)).astype(np.int32)),
            jnp.asarray(np.full((B,), t, np.int32)))

    feed = {"source": ids(S_LEN), "target": ids(T_LEN),
            "target_next": ids(T_LEN)}
    return trainer, feed


def phase_seq2seq(S, ctx):
    """bi-GRU encoder + attention-GRU decoder."""
    trainer, feed = seq2seq_setup(**S)
    return (_one_step(trainer, feed, ctx, "seq2seq"),
            Expect([("rnn_dispatch_total", "fused")]))


def phase_resnet(S, ctx):
    """ResNet train step: the conv+BN fused pairs."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.config import dsl
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.models.image import resnet, resnet_cifar10

    img_px, n_cls = S["image"], S["classes"]
    with dsl.config_scope():
        img = dsl.data("image", dense_vector(3 * img_px * img_px),
                       height=img_px, width=img_px)
        lab = dsl.data("label", integer_value(n_cls))
        probs = resnet(img, depth=50, num_classes=n_cls) \
            if S["depth"] == 50 \
            else resnet_cifar10(img, depth=S["depth"], num_classes=n_cls)
        cfg = dsl.topology(dsl.classification_cost(probs, lab))
    rng = np.random.RandomState(0)
    feed = {"image": jnp.asarray(rng.randn(
                S["batch"], 3 * img_px * img_px).astype(np.float32)),
            "label": jnp.asarray(rng.randint(0, n_cls, (S["batch"],)),
                                 jnp.int32)}
    # bottlenecks (ResNet-50) resolve to the forward-fused 3×3 kernel,
    # the rehearsal's basic blocks to the chain kernel — a conv+BN pair,
    # gated to the XLA composition on a mesh.  Stride-2 and off-tile
    # convs are statically gated the same way: expected, named reasons
    headline = "pallas3x3" if S["depth"] == 50 \
        else "chain" if ctx.device["count"] == 1 else "unfused"
    return (_one_step(_adam_trainer(cfg), feed, ctx, "resnet"),
            Expect([("conv_dispatch_total", headline)],
                   may=[("conv_dispatch_total", p) for p in
                        ("pallas3x3", "gemm1x1", "chain", "fused",
                         "unfused")],
                   reasons=("off-tile shape/stride/layout",
                            "multi-device mesh (BN statistics span the "
                            "batch)")))


def phase_sparse(S, ctx):
    """CTR-shaped sparse_update table with D % 128 == 0: the sparse
    exchange's row-gather kernel."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.config import dsl
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.data.feeder import integer_value, integer_value_sequence

    with dsl.config_scope():
        x = dsl.data("ids", integer_value_sequence(S["rows"]))
        lab = dsl.data("label", integer_value(2))
        emb = dsl.embedding(x, size=S["dim"], param_attr=dsl.ParamAttr(
            name="_slot_emb.w", sparse_update=True, initial_std=0.02))
        pooled = dsl.pooling(emb, pooling_type=dsl.SumPooling())
        tower = dsl.fc(pooled, size=32, act=dsl.ReluActivation())
        pred = dsl.fc(tower, size=2, act=dsl.SoftmaxActivation())
        cfg = dsl.topology(dsl.classification_cost(pred, lab))
    rng = np.random.RandomState(0)
    b, t = S["batch"], S["seq"]
    feed = {"ids": SequenceBatch(
                jnp.asarray(rng.randint(0, S["rows"], (b, t)), jnp.int32),
                jnp.full((b,), t, jnp.int32)),
            "label": jnp.asarray(rng.randint(0, 2, (b,)), jnp.int32)}
    out = _one_step(_adam_trainer(cfg), feed, ctx, "sparse")
    # the kernel vetoes itself off the chip (the interpreter is a
    # numerics harness, not a runtime tier) and on a mesh (the gather
    # stays with the SPMD partitioner, trainer.py): named, expected
    reason = "no_tpu" if ctx.device["platform"] != "tpu" \
        else "sharded" if ctx.device["count"] > 1 else ""
    return out, Expect(
        [("embedding_dispatch_total", "dense" if reason else "kernel")],
        reasons=(reason,))


DEFAULT_PHASES = (("lstm_cli", phase_lstm_cli),
                  ("transformer", phase_transformer),
                  ("server", phase_server),
                  ("kernels", phase_kernels))
ALL_PHASES = (("resnet", phase_resnet), ("seq2seq", phase_seq2seq),
              ("lstm1280", phase_lstm1280), ("sparse", phase_sparse),
              ("decode_walk", phase_decode_walk),
              ("conv_walk", phase_conv_walk))


class Ctx:
    def __init__(self, args, device, out, meter):
        self.args, self.device, self.out, self.meter = \
            args, device, out, meter


def run_phase(name, fn, sizes, ctx):
    """Run one phase; any exception fails it (and the run) but the
    remaining phases still run, so one report shows every break."""
    before = ctx.meter.snapshot()
    t0 = time.perf_counter()
    rec = {"phase": name}
    try:
        report, expect = fn(sizes, ctx)
        rec.update(report)
        compile_stats, rows, calls = ctx.meter.delta(before)
        rec.update(compile_stats)
        rec["dispatch"] = rows
        rec["pallas_calls"] = kernel_census(calls)
        if expect is not None:
            check_dispatch(rows, calls, expect, ctx.args.rehearsal)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001 - the report carries it
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"[:4000]
        traceback.print_exc(file=sys.stderr)
    rec["wall_s"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(rec), flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--all", action="store_true",
                    help="also one train step each of ResNet-50, seq2seq, "
                         "LSTM hidden 1280 and a sparse CTR table, the "
                         "paged decode kernel's microseconds a call and "
                         "the fused 3x3 conv+BN kernels' by tile")
    ap.add_argument("--fsdp", action="store_true",
                    help="transformer phase under FSDP with "
                         "zoo_fsdp_rules('transformer')")
    ap.add_argument("--hlo", action="store_true",
                    help="dump each trainer phase's compiled step under "
                         "--out and print what every Mosaic call and "
                         "collective in it looks like per chip")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU sandbox: every size shrunk, kernels in "
                         "interpret mode; can never print the pass line")
    ap.add_argument("--only", default="",
                    help="comma-separated phase names: run just these "
                         "(of the --all set too); the pass line still "
                         "needs them to pass")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"))
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(HERE, "paddle_tpu")):
        print("chip_smoke: no paddle_tpu/ beside this script — it proves "
              "the program, not itself", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)

    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "jaxlib": jaxlib.__version__,
                      "libtpu": libtpu_version,
                      "python": sys.version.split()[0]}), flush=True)
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearsal:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run.  "
              "--rehearsal exercises the phases on the CPU at toy sizes "
              "and cannot pass.", file=sys.stderr)
        return 2
    if args.rehearsal:
        print("chip_smoke: REHEARSAL — toy sizes"
              + ("" if on_chip else ", Pallas kernels in interpret mode")
              + "; this run cannot print the pass line", flush=True)

    from paddle_tpu.core.device import ensure_compile_cache

    os.makedirs(args.out, exist_ok=True)
    cache_dir = ensure_compile_cache()
    print(json.dumps({
        "compile_cache": cache_dir,
        "placed_by": "JAX_COMPILATION_CACHE_DIR"
        if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "checkout",
        "entries_at_start": len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0}), flush=True)

    sizes = REHEARSAL if args.rehearsal else FULL
    phases = list(DEFAULT_PHASES) + (list(ALL_PHASES) if args.all else [])
    if args.only:
        only = args.only.split(",")
        phases = [(n, f) for n, f in DEFAULT_PHASES + ALL_PHASES
                  if n in only]
        if len(phases) != len(only):
            ap.error(f"--only {args.only}: phases are "
                     f"{[n for n, _ in DEFAULT_PHASES + ALL_PHASES]}")
    ctx = Ctx(args, device, args.out, Meter())
    t0 = time.perf_counter()
    records = [run_phase(name, fn, sizes[name], ctx)
               for name, fn in phases]
    phases_ok = bool(records) and all(r["ok"] for r in records)
    report = {"device": device, "rehearsal": args.rehearsal,
              "phases": records, "phases_ok": phases_ok,
              "trace_lower_s_total": round(ctx.meter.trace_lower_s, 2),
              "backend_compile_s_total": round(
                  ctx.meter.backend_compile_s, 2),
              "cache_hits": ctx.meter.cache_hits,
              "cache_misses": ctx.meter.cache_misses,
              "wall_s": round(time.perf_counter() - t0, 2)}
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({k: v for k, v in report.items() if k != "phases"}),
          flush=True)
    if not phases_ok:
        failed = [r["phase"] for r in records if not r["ok"]]
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    if args.rehearsal:
        # a rehearsal proves nothing about the chip
        print(json.dumps({"ok": False, "rehearsal": True,
                          "phases_ok": True, "device": device}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
