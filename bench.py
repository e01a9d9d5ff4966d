"""Headline benchmarks, matched to BASELINE.json's primary metrics.

Six workloads (the first printed line is the driver-parsed metric):

1. **LSTM text classifier** training ms/batch — the reference RNN
   benchmark (``benchmark/paddle/rnn/rnn.py`` via ``paddle train
   --job=time``): 2×LSTM + fc, hidden=512, batch=128, T=100; reference
   261 ms/batch on 1× K40m (``benchmark/README.md:124-126``).
2. **ResNet-50** training samples/sec/chip (BASELINE.json primary 1) —
   224² ImageNet shapes from the ``benchmark/paddle/image`` contract;
   compared against published P40 ResNet-50 fp32 training throughput
   (~95 images/sec, the BASELINE.md "P40" yardstick).
3. **seq2seq** training tokens/sec (BASELINE.json primary 2) — bi-GRU
   encoder + Bahdanau-attention GRU decoder (the ``demo/seqToseq`` /
   WMT14 model at benchmark scale); the reference never published a
   number ("will be added later", ``benchmark/README.md:141``), so
   vs_baseline keys off the same P40-class yardstick via the reference
   4-GPU LSTM row scaled to tokens (documented below).
4. **transformer** training tokens/sec at T=2048 — the flash-attention
   kernel's product surface (``scaled_dot_product_attention`` layer);
   no reference yardstick exists (2017 codebase), MFU is the figure.
   Round 19 rebuilt this into an A/B lane: a causal-T=2048 row (dense
   XLA [small scale only] vs the legacy fetch-every-block grid vs
   block-sparse, each stamping ms/batch, tokens/sec, MFU and the
   attributed attention-region HBM bytes), a padded-vs-packed
   mixed-length row, and a paged-KV decode microbench row
   (``--attention_small`` for CPU shapes).  See :func:`bench_attention`.
5. **LSTM hidden=1280** ms/batch — the baseline's big-hidden row
   (1007 ms on K40m, ``benchmark/README.md:124-126``).  Round 8's
   hidden-blocked tier (``ops/pallas_lstm.py``) carries this row on
   the fused path; every RNN line stamps the runtime-resolved
   ``path`` (``fused_blocked|fused|scan``) so the artifact records
   which tier actually ran.
6. **LSTM hidden=2048** ms/batch — blocked-tier scaling row (no
   published reference number; the K40m table stops at 1280).
7. **input pipeline A/B** (round 11) — sync vs ``--prefetch_depth``
   prefetched training over recordio-backed readers on the LSTM /
   ResNet-50 / transformer rows; headline value is the worst
   prefetch-mode ``input_bound_ratio`` (target < 0.05).  See
   :func:`bench_pipeline`; ``--pipeline_small`` for CPU-scale shapes.
8. **precision A/B** (round 12) — ``--precision=fp32`` vs ``bf16``
   (fp32 masters + bf16 compute + dynamic loss scaling) on the LSTM /
   ResNet-50 / transformer train rows, headline = second-best speedup
   (target ≥ 1.2 on at least two workloads), MFU targets 0.45 / 0.35;
   plus an fp32-vs-int8 serving-artifact row (latency, top-1/loss
   delta).  See :func:`bench_precision`; ``--precision_small`` for
   CPU-scale shapes.  Every emitted JSON line (all lanes) now carries
   a ``precision_policy`` stamp with the resolved per-op dispatch
   dtypes.
9. **tracing overhead A/B** (round 13) — traced (``--trace_jsonl`` +
   flight recorder) vs untraced training on a small LSTM row, both
   modes per-step fenced; stamps ``trace_overhead_us_per_step``
   (enabled tax) and ``trace_disabled_us_per_step`` (the no-op span
   machinery, acceptance < 50 µs/step).  See :func:`bench_observe`.

Each train step is ONE jitted XLA computation (fwd + autodiff bwd +
Adam).  Timing chains K steps inside one ``lax.scan`` program (see
:func:`_scan_time_ms`), which leaves one dispatch and one host sync per
sample; ``timing_self_check`` is the relative spread of the warm K-step
samples.  MFU is an exact-MAC FLOP count over the detected chip's peak
(``observe/costmodel.detect_peaks``).

Every emitted json line carries the **run-mode band**: ``attempts``
(the per-attempt metric values — one entry for single-shot workloads),
``median`` and ``spread`` ((max−min)/min across attempts), and for the
resnet workload the per-attempt MFUs with a fast/slow ``modes`` count
(threshold 0.35, the PERF_NOTES bimodality).  A best-of number alone
hid the ResNet slow-mode miss in round 5; the band keeps the
bimodality visible in the artifact.

Round 7 adds two traffic-visibility fields: every line carries an
``hbm_gb_per_step`` estimate (XLA compiled cost analysis, "bytes
accessed" — deltas across ``--conv_bn_fuse_fwd`` on/off track the
forward-fusion traffic cut without an xprof session), and ``--profile``
dumps a per-workload ``jax.profiler`` trace (path on the JSON line as
``trace_dir``).

Round 16 adds ``--attribution_diff OLD NEW``: a pure-host replay mode
that diffs two ``--roofline_dump`` reports per region (FLOPs / HBM
bytes / roofline verdict / MFU / bwd_frac, with add/remove/rename
detection — ``observe/costmodel.attribution_diff``) and emits the
machine-readable delta ``--check`` gates on — every kernel PR ships
verified before/after attribution.
"""

import argparse
import json
import sys
import time
from functools import partial as _partial

import jax
import numpy as np

from paddle_tpu import observe
from paddle_tpu.observe import benchgate
from paddle_tpu.observe import costmodel
from paddle_tpu.observe import memory as omem
from paddle_tpu.utils import FLAGS

TRAIN_FLOP_FACTOR = 3.0       # fwd + bwd ≈ 3× fwd matmul FLOPs

# --profile: per-workload jax.profiler trace dump directory (None = off)
PROFILE_DIR = None

#: Fields `_finish` stamps on a row — composite lanes and the resnet
#: best-of merge copy exactly this set from the attempt that carried
#: the analysis.
PERF_STAMP_FIELDS = (
    "hbm_gb_per_step", "regions", "regions_elided", "flop_agreement",
    "opaque_custom_calls", "hbm_peak_bytes", "hbm_in_use_bytes",
    "hbm_categories", "mfu_est", "mfu_source", "flops_per_step",
    "trace_dir",
)


def _finish(r, tag, trainer, feed, step_ms=None, hint_flops=None):
    """Attach the performance-observatory stamp to a result line:

    - ``regions``: the per-fused-region FLOPs / HBM-bytes / roofline
      attribution of the compiled train step, keyed to network layer
      names (observe/costmodel.py); ``hbm_gb_per_step`` stays the
      whole-step XLA 'bytes accessed' figure round 7 introduced;
    - ``hbm_peak_bytes`` / ``hbm_in_use_bytes`` / ``hbm_categories``:
      the device-memory accounting snapshot (observe/memory.py —
      params / opt_state / buffers / data attribution by buffer
      identity);
    - ``mfu_est``: THE shared MFU implementation
      (:func:`paddle_tpu.observe.costmodel.step_mfu` — executed-step
      FLOPs over time x detected peak x chips), replacing the
      per-workload hand formulas; those formulas survive only as
      ``hint_flops``, the analytic fallback for steps whose FLOPs hide
      inside opaque Pallas custom calls (``mfu_source`` says which
      source produced the number);
    - under ``--profile`` a jax.profiler trace of a few production
      steps (path on the line as ``trace_dir``).

    The cost analysis is memoized per ``tag`` — it is a property of the
    lowering, identical across timing attempts."""
    report = costmodel.analyze_trainer_step(trainer, feed,
                                            cache_key=tag)
    if report is not None:
        r["hbm_gb_per_step"] = round(report["xla_bytes"] / 1e9, 2) \
            if report["xla_bytes"] else None
        r["regions"] = report["regions"]
        r["regions_elided"] = report["regions_elided"]
        r["flop_agreement"] = report["flop_agreement"]
        if report["opaque_custom_calls"]:
            r["opaque_custom_calls"] = report["opaque_custom_calls"]
    else:
        r["hbm_gb_per_step"] = None
        r["regions"] = None
    snap = omem.sample(trainer, feed)
    r["hbm_peak_bytes"] = snap["peak_bytes"]
    r["hbm_in_use_bytes"] = snap["in_use_bytes"]
    r["hbm_categories"] = snap["categories"]
    if step_ms is not None:
        r.update(costmodel.step_mfu(
            trainer, feed, step_ms / 1e3, devices=_n_chips(trainer),
            fallback_flops=hint_flops, cache_key=tag))
    if PROFILE_DIR:
        import os

        d = os.path.join(PROFILE_DIR, tag)
        os.makedirs(d, exist_ok=True)
        with jax.profiler.trace(d):
            for _ in range(3):
                trainer.train_one_batch(feed)
        r["trace_dir"] = d
    return r


def _scan_time_ms(trainer, feed, iters=256, max_tries=3, tol=0.2):
    """Device ms/step via K steps CHAINED INSIDE one jitted lax.scan.

    Scanning K train steps inside one XLA program leaves exactly one
    dispatch + one D2H sync per measurement, so per-dispatch host
    latency (the same order as a small step such as the LSTM's) stays
    out of the figure; ms/step is the K-step vs 1-step program
    difference divided by K-1.  ``timing_self_check`` is the relative
    spread of the warm K-step samples — host jitter shows up there, and
    the measurement retries on disagreement or a non-positive
    difference.  The same batch is re-fed every step (timing only; the
    per-step math is production-identical).
    """
    import jax.numpy as jnp
    from jax import lax

    # build + place state exactly as train_one_batch would
    trainer.train_one_batch(feed)
    raw = trainer._raw_step
    sfeed = trainer._shard_feed(feed)
    rng = jax.random.PRNGKey(0)
    progress = jnp.zeros((), jnp.float32)
    # --precision=bf16 trainers thread the loss-scale state through the
    # step; carry it in the scan so the timed program is the production
    # mixed-precision step (finite-check, select, scale update included)
    # --precision=bf16 threads the loss-scale state through the step
    # and --health_interval threads the health accumulator; carry both
    # in the scan so the timed program is the production step.  Every
    # step variant returns (params, opt, buffers, loss, *extras) with
    # the extras mirroring the trailing inputs (Trainer._step_extras,
    # the one definition of the order), so carry plumbing is uniform:
    # out[:3] + out[4:].
    def k_steps(k):
        def body(carry, _):
            out = raw(*carry[:3], sfeed, rng, progress, *carry[3:])
            return (out[:3] + out[4:]), out[3]

        @_partial(jax.jit, donate_argnums=(0,))
        def run(carry):
            carry, losses = lax.scan(body, carry, None, length=k)
            return carry, losses[-1]
        return run

    def snapshot():
        state = (trainer.params, trainer.opt_state, trainer.buffers) \
            + trainer._step_extras()
        return jax.tree_util.tree_map(lambda x: x.copy(), state)

    def samples(run, n=3, drop_first=True):
        times = []
        for _ in range(n):   # first sample pays the compile
            carry = snapshot()
            t0 = time.perf_counter()
            carry, loss = run(carry)
            float(loss)
            times.append((time.perf_counter() - t0) * 1000.0)
        return times[1:] if drop_first else times

    def one_step_time():
        # the already-compiled single-step program shares the dispatch +
        # sync fixed costs with the scan programs; using it as the
        # baseline saves one scan(1) compile per workload
        def one(carry):
            out = trainer._train_step(*carry[:3], sfeed, rng, progress,
                                      *carry[3:])
            return out[:3] + out[4:], out[3]
        return min(samples(one, drop_first=False))

    one = one_step_time()
    run = k_steps(1 + iters)     # compiled once, reused across retries
    for _ in range(max_tries):
        warm = samples(run)
        ms = (min(warm) - one) / iters
        spread = (max(warm) - min(warm)) / max(min(warm), 1e-3)
        if ms > 0 and spread <= tol:
            return ms, spread
        one = min(one, one_step_time())   # re-baseline
    return max(ms, 1e-3), spread


def _with_band(r, values=None, mfus=None, fast_mfu=0.35):
    """Attach the run-mode band fields to a result dict: per-attempt
    values, median, relative spread, and (when per-attempt MFUs are
    known) the fast/slow mode census.  Single-shot workloads report a
    one-entry band — honest about having sampled one process mode."""
    vals = [r["value"]] if values is None else list(values)
    r["attempts"] = [round(float(v), 3) for v in vals]
    r["median"] = round(float(np.median(vals)), 3)
    r["spread"] = round((max(vals) - min(vals)) / max(min(vals), 1e-9), 3)
    if mfus is not None:
        r["attempt_mfus"] = [round(float(m), 3) for m in mfus]
        r["modes"] = {"fast": int(sum(m >= fast_mfu for m in mfus)),
                      "slow": int(sum(m < fast_mfu for m in mfus))}
    return r


def _mk_trainer(cfg, lr=2e-3, clip=25.0, l2=0.0, mesh=None):
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.trainer.trainer import Trainer

    net = NeuralNetwork(cfg)
    return Trainer(net, opt_config=OptimizationConfig(
        learning_method="adam", learning_rate=lr, l2_weight_decay=l2,
        gradient_clipping_threshold=clip), mesh=mesh, seed=0)


def _n_chips(trainer):
    mesh = getattr(trainer, "mesh", None)
    return int(mesh.devices.size) if mesh is not None else 1


def _bench_lstm_row(hidden, baseline_ms, metric, iters=256):
    """One LSTM text-classifier row (bs=128, 2×LSTM, T=100) at the given
    hidden size against the matching K40m baseline (BASELINE.md:18)."""
    # AMP-style mixed precision (--bf16_activations): activations stored
    # bf16, params/losses fp32 — measured 5.68 → 5.35 ms/batch here.
    # (seq2seq keeps it off: the attention group path measured slower.)
    FLAGS.set("bf16_activations", True)
    from paddle_tpu.core.device import build_mesh, set_mesh
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import lstm_text_classifier

    B, T, H, V, E = 128, 100, hidden, 30000, 128
    devices = jax.devices()
    mesh = build_mesh({"data": len(devices)}, devices)
    set_mesh(mesh)
    cfg = lstm_text_classifier(vocab_size=V, embed_dim=E, hidden_size=H,
                               lstm_num=2, num_classes=2)
    trainer = _mk_trainer(cfg, l2=8e-4, mesh=mesh)  # reference rnn.py decay

    rng = np.random.RandomState(0)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(rng.randint(0, V, (B, T)).astype(np.int32)),
                jax.numpy.asarray(
                    rng.randint(T // 2, T + 1, (B,)).astype(np.int32))),
            "label": jax.numpy.asarray(rng.randint(0, 2, (B,)).astype(np.int32))}

    ms, agree = _scan_time_ms(trainer, feed, iters=iters)
    n = _n_chips(trainer)
    # analytic fwd matmul FLOPs: layer1 x-proj [B,E]→[B,4H] + h-proj
    # [B,H]→[B,4H], layer2 both projections from H; per timestep, ×T —
    # the MFU fallback when the fused Pallas path hides the FLOPs from
    # XLA (the shared implementation in observe/costmodel.py decides)
    fwd = 2 * B * T * (E * 4 * H + H * 4 * H + H * 4 * H + H * 4 * H)
    r = {
        "metric": metric,
        "value": round(ms, 3),
        "unit": f"ms/batch (bs=128, hidden={H}, 2xLSTM, T=100)",
        "devices": n,
        "timing_self_check": round(agree, 3),
        "path": _rnn_path("lstm", B, H),
    }
    if baseline_ms is None:
        r["vs_baseline_note"] = ("no published reference number at "
                                 f"hidden={H}; the K40m table stops "
                                 "at 1280")
    else:
        r["vs_baseline"] = round(baseline_ms / ms, 3)
    return _finish(_with_band(r), f"lstm{H}", trainer, feed,
                   step_ms=ms, hint_flops=TRAIN_FLOP_FACTOR * fwd)


def _rnn_path(kind, b, h):
    """Runtime-resolved RNN lowering for a (batch, hidden) shape —
    the SAME predicate ops/recurrent_ops.py dispatches on (it sees the
    --fused_rnn_hblock kill switch), so the artifact records which
    tier this process actually ran, not what a doc comment claims."""
    from paddle_tpu.ops import pallas_gru, pallas_lstm

    tier = (pallas_gru if kind == "gru" else pallas_lstm).fused_tier(b, h)
    return tier or "scan"


def bench_lstm():
    return _bench_lstm_row(512, 261.0, "lstm_text_cls_ms_per_batch")


def bench_lstm_1280():
    """The baseline's hidden=1280/bs=128 row (1007 ms on K40m) — the
    round-8 hidden-blocked tier carries it on the fused path (the JSON
    line's ``path`` field says which tier actually ran; with
    ``--fused_rnn_hblock=false`` it reads ``scan`` and measures the
    pre-blocking fallback gap)."""
    return _bench_lstm_row(1280, 1007.0, "lstm_text_cls_1280_ms_per_batch",
                           iters=64)


def bench_lstm_2048():
    """Blocked-tier scaling row: H=2048 doubles the streamed-weight
    traffic per step vs 1280 while the [B, H] VMEM state stays cheap,
    so ms/batch should scale with w_hh bytes — visible against the
    1280 row in the same artifact."""
    return _bench_lstm_row(2048, None, "lstm_text_cls_2048_ms_per_batch",
                           iters=32)


def _bench_resnet_once(extras=True):
    FLAGS.set("bf16_activations", True)   # see bench_lstm note
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.models.image import resnet

    B, IMG, NCLASS = 128, 224, 1000  # 128 measured best/chip (64: 2483/s, 256: 2472/s)
    with config_scope():
        img = dsl.data("image", dense_vector(3 * IMG * IMG),
                       height=IMG, width=IMG)
        lab = dsl.data("label", integer_value(NCLASS))
        probs = resnet(img, depth=50, num_classes=NCLASS)
        cost = dsl.classification_cost(probs, lab)
        cfg = dsl.topology(cost)
    trainer = _mk_trainer(cfg, lr=1e-3)

    rng = np.random.RandomState(0)
    feed = {"image": jax.numpy.asarray(
                rng.randn(B, 3 * IMG * IMG).astype(np.float32)),
            "label": jax.numpy.asarray(
                rng.randint(0, NCLASS, (B,)).astype(np.int32))}

    ms, agree = _scan_time_ms(trainer, feed, iters=40)
    n = _n_chips(trainer)
    sps_chip = B / (ms / 1e3) / n
    # 3.858 GMACs fwd @224²: exact conv+fc MAC count of THIS config
    # (summed from the parsed topology; the model is ResNet-50 v1) —
    # the analytic fallback when Pallas conv custom calls hide FLOPs
    hint = TRAIN_FLOP_FACTOR * 3.858e9 * 2 * B
    mfu = costmodel.step_mfu(trainer, feed, ms / 1e3, devices=n,
                             fallback_flops=hint, cache_key="resnet")
    r = {
        "metric": "resnet50_samples_per_sec_per_chip",
        "value": round(sps_chip, 1),
        "unit": f"samples/sec/chip (bs={B}, 224x224, train step)",
        "vs_baseline": round(sps_chip / 95.0, 3),  # published P40 fp32 ~95/s
        **mfu,
        "devices": n,
        "timing_self_check": round(agree, 3),
    }
    # the traffic estimate is a property of the LOWERING, identical
    # across attempts — compute it (and any --profile trace) once
    return _finish(r, "resnet", trainer, feed, step_ms=ms,
                   hint_flops=hint) if extras else r


def bench_resnet():
    """Up to 5 fresh compiles; the headline is still the best attempt
    but EVERY attempt lands in the artifact.  Repeated runs are bimodal
    (~2700 vs ~3000 samples/s with per-run self-checks ≤0.015): the
    per-PROCESS compile/chip state, not step-timing noise, decides which
    mode a run lands in — this is the round-4 driver-2702 vs
    builder-2908 gap, and a bare best-of number hid the slow-mode MFU
    miss in round 5.  The band fields (attempts / median / spread /
    per-attempt MFUs / fast-slow mode census) keep the bimodality
    visible.  Each attempt rebuilds the trainer after
    jax.clear_caches(); attempts stop early once the 0.35-MFU target is
    met, and the attempt count is reported.  (One attempt ≈ 2–3.5 min;
    the elapsed-time guard below keeps the workload under ~9-10 min
    worst case.)"""
    results = []
    t0 = time.perf_counter()
    for attempt in range(5):
        results.append(_bench_resnet_once(extras=not results))
        # stop early on target met, or when another ~2-3.5 min attempt
        # would push the workload past ~12-13 minutes total.  Five
        # attempts: the slow mode clusters in time (shared-chip
        # contention), so P(all slow) shrinks fast with retries while
        # early-stop keeps the common case at one or two attempts.
        if max(r["mfu_est"] for r in results) >= 0.35 \
                or time.perf_counter() - t0 > 10 * 60:
            break
        jax.clear_caches()
    best = dict(max(results, key=lambda r: r["value"]))
    best["best_of_attempts"] = len(results)
    for k in PERF_STAMP_FIELDS:         # extras live on attempt 0
        if k in results[0] and k not in ("mfu_est", "mfu_source",
                                         "flops_per_step"):
            best[k] = results[0][k]     # mfu_* stay the best attempt's
    return _with_band(best, [r["value"] for r in results],
                      [r["mfu_est"] for r in results])


def seq2seq_setup(B=128, S_LEN=30, T_LEN=30, V=30000, E=512, H=512,
                  bf16_activations=True):
    """Build the seq2seq benchmark trainer + feed (shared by the bench
    and the profiling harness)."""
    FLAGS.set("bf16_activations", bf16_activations)
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import ParamAttr, StepInput, config_scope
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.data.feeder import integer_value_sequence
    from paddle_tpu.v2.networks import simple_attention, simple_gru

    # the demo/seqToseq training topology at benchmark scale
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

    trainer = _mk_trainer(cfg, lr=5e-4)
    rng = np.random.RandomState(0)
    feed = {
        "source": SequenceBatch(
            jax.numpy.asarray(rng.randint(2, V, (B, S_LEN)).astype(np.int32)),
            jax.numpy.asarray(np.full((B,), S_LEN, np.int32))),
        "target": SequenceBatch(
            jax.numpy.asarray(rng.randint(2, V, (B, T_LEN)).astype(np.int32)),
            jax.numpy.asarray(np.full((B,), T_LEN, np.int32))),
        "target_next": SequenceBatch(
            jax.numpy.asarray(rng.randint(2, V, (B, T_LEN)).astype(np.int32)),
            jax.numpy.asarray(np.full((B,), T_LEN, np.int32))),
    }
    return trainer, feed


def bench_seq2seq():
    # B=128 measured best on v5e (64: 176k tok/s, 128: 228k, 256: 216k)
    B, S_LEN, T_LEN, V, E, H = 128, 30, 30, 30000, 512, 512
    trainer, feed = seq2seq_setup(B, S_LEN, T_LEN, V, E, H)

    ms, agree = _scan_time_ms(trainer, feed, iters=128)
    n = _n_chips(trainer)
    tokens_per_sec = B * T_LEN / (ms / 1e3)
    # analytic fwd matmuls (the MFU fallback): encoder 2×GRU (3H gates
    # from E and H) over S_LEN; decoder per step: attention proj +
    # inproj (2H+E→3H) + GRU (H→3H) + softmax H→V
    enc = 2 * 2 * B * S_LEN * (E * 3 * H + H * 3 * H)
    dec = 2 * B * T_LEN * ((2 * H + E) * 3 * H + H * 3 * H + H * V)
    return _finish(_with_band({
        "metric": "seq2seq_tokens_per_sec",
        "value": round(tokens_per_sec, 0),
        "unit": f"target tokens/sec (bs={B}, src=trg=30, hid=512, attn)",
        # the reference never published a seq2seq number
        # ("will be added later", benchmark/README.md:141); no yardstick
        # is honest, so vs_baseline is intentionally absent — MFU is the
        # comparable figure
        "vs_baseline_note": "no published reference seq2seq number",
        "devices": n,
        "timing_self_check": round(agree, 3),
        "path": _rnn_path("gru", B, H),
    }), "seq2seq", trainer, feed, step_ms=ms,
        hint_flops=TRAIN_FLOP_FACTOR * (enc + dec))


# --attention_small: CPU-runnable shapes for the attention A/B lane
ATTENTION_SMALL = False


def _attention_shapes():
    """(B, T, D, HEADS, L, F, V) for the attention lane."""
    if ATTENTION_SMALL:
        return 2, 512, 128, 4, 2, 256, 2000
    # B swept with the Pallas backward: 8 → 432k, 16 → 463k (best),
    # 32 → 427k tokens/s (pre-Pallas-backward, B=16 lost to B=8 —
    # the dense einsum backward's HBM pressure)
    return 16, 2048, 512, 8, 4, 2048, 30000


def _attention_workload(causal=False, packed=False, mixed_lengths=False,
                        seed=0):
    """Build one transformer trainer + feed for the attention lane.
    ``mixed_lengths`` draws ragged valid lengths in [T/4, T] (the
    padded/packed A/B input); returns (trainer, feed, analytic fwd
    FLOPs, valid-token count)."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer_text_classifier

    B, T, D, HEADS, L, F, V = _attention_shapes()
    # block = T/4 at small scale so the causal grid is 4×4 there too —
    # the skip fraction under measure (10/16 live pairs) matches the
    # bench-scale T=2048 row's, just narrower
    blk = 128 if ATTENTION_SMALL else 512
    cfg = transformer_text_classifier(
        vocab_size=V, model_dim=D, num_heads=HEADS, num_layers=L,
        ffn_dim=F, num_classes=2, max_len=T, causal=causal,
        packed=packed, block_q=blk, block_k=blk)
    trainer = _mk_trainer(cfg, lr=1e-3)
    rng = np.random.RandomState(seed)
    if mixed_lengths:
        lengths = rng.randint(T // 4, T + 1, (B,)).astype(np.int32)
    else:
        lengths = np.full((B,), T, np.int32)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(rng.randint(0, V, (B, T)).astype(np.int32)),
                jax.numpy.asarray(lengths)),
            "label": jax.numpy.asarray(rng.randint(0, 2, (B,)).astype(np.int32))}
    # analytic fwd MACs/layer (MFU fallback — the flash-attention
    # Pallas kernel hides its FLOPs from XLA): qkv B·T·D·3D + scores
    # B·T²·D + p·v B·T²·D + out-proj B·T·D·D + ffn B·T·2·D·F
    fwd = 2 * L * B * T * (3 * D * D + 2 * T * D + D * D + 2 * D * F)
    return trainer, feed, fwd, int(lengths.sum())


def _attn_region_bytes(report):
    """Attributed HBM bytes of the attention regions (attn0..attnL-1)
    of one cost report — the per-mode number the block-sparse A/B
    exists to move (and --attribution_diff --check pins)."""
    if not report:
        return None
    return round(sum(r["bytes"] for r in report.get("regions", ())
                     if r["region"].startswith("attn")), 1)


def _attention_mode_flags(mode):
    """Flag combo per A/B mode — same vocabulary as the
    ``attention_dispatch_total{path}`` counter."""
    return {
        "dense": {"flash_kernel": False, "flash_block_sparse": True},
        "legacy": {"flash_kernel": True, "flash_block_sparse": False},
        "block_skip": {"flash_kernel": True, "flash_block_sparse": True},
    }[mode]


def _attention_ab_row(workload, modes, builds, iters, tokens_of):
    """Time one workload under each mode's flag combo; every mode entry
    carries ms/batch, tokens/sec, the shared-implementation MFU and the
    attributed attention-region HBM bytes."""
    row = {"workload": workload}
    for mode in modes:
        for flag, val in _attention_mode_flags(mode).items():
            FLAGS.set(flag, val)
        trainer, feed, fwd, tokens = builds()
        ms, agree = _scan_time_ms(trainer, feed, iters=iters)
        n = _n_chips(trainer)
        hint = TRAIN_FLOP_FACTOR * fwd
        tag = f"attention-{workload}-{mode}"
        mfu = costmodel.step_mfu(trainer, feed, ms / 1e3, devices=n,
                                 fallback_flops=hint, cache_key=tag)
        report = costmodel.analyze_trainer_step(trainer, feed,
                                                cache_key=tag)
        row[mode] = {
            "ms_per_batch": round(ms, 3),
            "tokens_per_sec": round(tokens_of(tokens) / (ms / 1e3), 0),
            "timing_self_check": round(agree, 3),
            "attn_region_bytes": _attn_region_bytes(report),
            **{k: mfu[k] for k in ("mfu_est", "mfu_source")},
        }
        del trainer
        jax.clear_caches()
    return row


def _attention_decode_row():
    """Decode-shape microbench: the paged-KV decode primitive
    (``ops/pallas_attention.paged_decode_attention``) over a
    partially-filled cache — ms/decode-call and queries/sec, the
    numbers ROADMAP item 1's serving loop will inherit."""
    from paddle_tpu.ops.pallas_attention import paged_decode_attention

    if ATTENTION_SMALL:
        B, H, D, page, n_max, P, calls = 8, 4, 32, 64, 4, 64, 20
    else:
        B, H, D, page, n_max, P, calls = 64, 8, 64, 128, 16, 1024, 50
    rng = np.random.RandomState(0)
    kpg = jax.numpy.asarray(rng.randn(P, page, H, D).astype(np.float32))
    vpg = jax.numpy.asarray(rng.randn(P, page, H, D).astype(np.float32))
    pidx = jax.numpy.asarray(
        rng.randint(0, P, (B, n_max)).astype(np.int32))
    lengths_np = rng.randint(page, page * n_max + 1, (B,))
    lengths = jax.numpy.asarray(lengths_np.astype(np.int32))
    q = jax.numpy.asarray(rng.randn(B, 1, H, D).astype(np.float32))
    step = jax.jit(paged_decode_attention)
    step(q, kpg, vpg, pidx, lengths).block_until_ready()   # compile
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        step(q, kpg, vpg, pidx, lengths).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3)
    ms = float(np.median(times))
    return {
        "workload": "decode_paged",
        "decode": {"ms_per_call": round(ms, 3)},
        "queries_per_sec": round(B / (ms / 1e3), 1),
        "kv_tokens": int(lengths_np.sum()),
        "shape": {"batch": B, "heads": H, "head_dim": D,
                  "page_size": page, "pages_per_row": n_max,
                  "pool_pages": P},
    }


def bench_attention():
    """Attention lane (`--only attention`, reworked round 19):

    - headline: transformer encoder training tokens/sec at long context
      (T=2048) on the DEFAULT path (block-sparse flash) — the metric the
      previous rounds carried, so the trajectory stays comparable;
    - ``causal_t2048`` A/B row: dense XLA (small scale only — the [T,T]
      scores don't fit at bench scale, which is the point of flash) vs
      the legacy fetch-every-block grid vs block-skip, each stamping
      ms/batch, tokens/sec, MFU and the attributed attention-region HBM
      bytes — the same number the committed roofline dumps pin via
      ``--attribution_diff --check``;
    - ``padded_mixed`` A/B row: ragged lengths in [T/4, T], padded
      per-row lowering vs sequence packing (``packed=True`` layer attr;
      tokens/sec counts VALID tokens only);
    - ``decode_paged`` row: the paged-KV decode primitive microbench.

    The reference predates transformers, so like seq2seq there is no
    published yardstick; MFU is the comparable figure."""
    saved = {k: FLAGS.get(k) for k in
             ("flash_kernel", "flash_block_sparse", "attention_packing",
              "bf16_activations")}
    FLAGS.set("bf16_activations", True)
    iters = 8 if ATTENTION_SMALL else 32
    try:
        causal_modes = ["legacy", "block_skip"]
        if ATTENTION_SMALL:
            causal_modes.insert(0, "dense")
        causal_row = _attention_ab_row(
            "causal_t2048", causal_modes,
            lambda: _attention_workload(causal=True), iters,
            tokens_of=lambda tokens: tokens)

        FLAGS.set("flash_kernel", True)
        FLAGS.set("flash_block_sparse", True)
        # the packed mode must actually pack: a process-level
        # --attention_packing=false would silently turn the A/B into
        # padded-vs-padded (the layer kill switch reverts the attr)
        FLAGS.set("attention_packing", True)
        padded_row = {"workload": "padded_mixed"}
        for mode, packed in (("padded", False), ("packed", True)):
            trainer, feed, fwd, tokens = _attention_workload(
                mixed_lengths=True, packed=packed, seed=1)
            ms, agree = _scan_time_ms(trainer, feed, iters=iters)
            tag = f"attention-padded_mixed-{mode}"
            report = costmodel.analyze_trainer_step(trainer, feed,
                                                    cache_key=tag)
            padded_row[mode] = {
                "ms_per_batch": round(ms, 3),
                "valid_tokens_per_sec": round(tokens / (ms / 1e3), 0),
                "timing_self_check": round(agree, 3),
                "attn_region_bytes": _attn_region_bytes(report),
            }
            del trainer
            jax.clear_caches()
        padded_row["packing_speedup"] = round(
            padded_row["padded"]["ms_per_batch"]
            / max(padded_row["packed"]["ms_per_batch"], 1e-9), 3)

        decode_row = _attention_decode_row()

        # ---- headline: the default path at full length (trajectory
        # metric; re-built so the A/B flag churn can't leak into it)
        trainer, feed, fwd, tokens = _attention_workload(causal=False)
        ms, agree = _scan_time_ms(trainer, feed, iters=iters)
        n = _n_chips(trainer)
        tokens_per_sec = tokens / (ms / 1e3)
        B, T, D, HEADS, L, F, V = _attention_shapes()
        r = _finish(_with_band({
            "metric": "transformer_tokens_per_sec",
            "value": round(tokens_per_sec, 0),
            "unit": f"tokens/sec (bs={B}, T={T}, d={D}, {L}L/{HEADS}H, "
                    "block-sparse flash attention)",
            "vs_baseline_note": "reference predates transformers; no "
                                "published number",
            "devices": n,
            "timing_self_check": round(agree, 3),
            "scale": "small" if ATTENTION_SMALL else "bench",
            "rows": [causal_row, padded_row, decode_row],
        }), "attention", trainer, feed, step_ms=ms,
            hint_flops=TRAIN_FLOP_FACTOR * fwd)
        r["attn_region_bytes"] = _attn_region_bytes(
            costmodel.analyze_trainer_step(trainer, feed,
                                           cache_key="attention"))
        return r
    finally:
        for k, v in saved.items():
            FLAGS.set(k, v)


# --serving_small: CPU-runnable decoder shapes for the serving lane
SERVING_SMALL = False


def _serving_shapes():
    """(cfg, n_requests, prompt_len_range, max_new, max_batch,
    pool_pages, page_size, timed_passes) for the serving lane."""
    from paddle_tpu.serving.model import DecoderConfig

    if SERVING_SMALL:
        return (DecoderConfig(vocab=512, dim=64, heads=4, layers=2,
                              ffn=128, max_context=128, eos_id=1),
                12, (4, 24), 8, 4, 64, 16, 2)
    return (DecoderConfig(vocab=4000, dim=256, heads=8, layers=4,
                          ffn=1024, max_context=512, eos_id=1),
            48, (16, 96), 32, 8, 512, 16, 3)


def _serving_mode_run(model, prompts, max_new, max_batch, pool_pages,
                      page, continuous, passes):
    """Drive the full request stream through one
    :class:`~paddle_tpu.serving.server.InferenceServer` mode.  One
    untimed pass pays the per-(B, T)-bucket XLA compiles; each timed
    pass submits every request up front (open loop) and waits them all
    out — sustained req/s is completions over wall, TTFT lands in a
    bench-owned reservoir histogram (the p99 the SLO gate reads).
    Returns (mode summary dict, per-pass req/s list, generated tokens
    of the last pass — the kill-switch equality witness)."""
    from paddle_tpu.serving.server import InferenceServer

    mode = "continuous" if continuous else "sequential"
    hist = observe.histogram(
        "bench_serve_ttft_seconds",
        "serving-lane submit-to-first-token reservoir, by mode")
    lat = observe.histogram(
        "bench_serve_latency_seconds",
        "serving-lane submit-to-last-token reservoir, by mode")
    srv = InferenceServer(model, max_batch=max_batch, n_pages=pool_pages,
                          page_size=page, continuous=continuous).start()
    try:
        for r in [srv.submit(p, max_new) for p in prompts]:  # warm pass
            srv.result(r, timeout=600.0)
        walls, tokens = [], None
        for _ in range(passes):
            t0 = time.perf_counter()
            reqs = [srv.submit(p, max_new) for p in prompts]
            tokens = [srv.result(r, timeout=600.0) for r in reqs]
            walls.append(time.perf_counter() - t0)
            for r in reqs:
                hist.observe(r.ttft_s, mode=mode)
                lat.observe(r.latency_s, mode=mode)
    finally:
        srv.stop()
    rps = [len(prompts) / w for w in walls]
    return {
        "req_per_sec": round(float(np.median(rps)), 3),
        "p99_ms": round(hist.sample_quantile(0.99, mode=mode) * 1e3, 3),
        "p50_ttft_ms": round(
            hist.sample_quantile(0.5, mode=mode) * 1e3, 3),
        "p99_latency_ms": round(
            lat.sample_quantile(0.99, mode=mode) * 1e3, 3),
    }, rps, tokens


def bench_serving():
    """Serving lane (`--only serving`, round 20): sustained req/s of the
    continuous-batching :class:`InferenceServer` vs the same loop with
    the ``--serve_continuous=false`` kill switch (sequential
    single-request serving) — the machine-checked A/B the baseline
    gate replays.  One deterministic mixed-length request stream runs
    through BOTH modes; the lane also asserts the two modes generated
    byte-identical tokens (the kill-switch contract), so the perf
    number and the correctness witness travel on one line.

    Headline value: continuous-mode req/s.  ``p99_ms`` per mode is the
    submit-to-first-token p99 read from a reservoir histogram
    (``Histogram.sample_quantile`` — the SLO sensor); with
    ``--serve_slo_ms > 0`` the line records whether the p99 met it.
    The observatory stamp is trainer-free: region attribution via
    ``costmodel.analyze_fn`` on the jitted decode step, HBM census via
    ``observe.memory.sample`` over the live params + KV pools."""
    from paddle_tpu.serving.model import DecoderModel, init_decoder_params

    cfg, n_req, (lo, hi), max_new, max_batch, pool_pages, page, passes \
        = _serving_shapes()
    model = DecoderModel(init_decoder_params(cfg, seed=0), cfg)
    rng = np.random.RandomState(0)
    # token ids start at 2: never the eos id, so prompt content cannot
    # end a request early — only generation (identical in both modes)
    prompts = [rng.randint(2, cfg.vocab,
                           rng.randint(lo, hi + 1)).tolist()
               for _ in range(n_req)]

    cont, cont_rps, cont_tokens = _serving_mode_run(
        model, prompts, max_new, max_batch, pool_pages, page,
        continuous=True, passes=passes)
    seq, seq_rps, seq_tokens = _serving_mode_run(
        model, prompts, max_new, max_batch, pool_pages, page,
        continuous=False, passes=passes)
    if cont_tokens != seq_tokens:
        raise RuntimeError(
            "serving kill-switch contract violated: continuous and "
            "sequential modes generated different tokens")

    r = _with_band({
        "metric": "serving_req_per_sec",
        "value": cont["req_per_sec"],
        "unit": f"req/s ({n_req} mixed prompts T in [{lo},{hi}], "
                f"max_new={max_new}, batch={max_batch}, "
                f"{cfg.layers}L/{cfg.heads}H d={cfg.dim})",
        "devices": 1,
        "scale": "small" if SERVING_SMALL else "bench",
        "rows": [{"workload": "mixed_prompts",
                  "continuous": cont, "sequential": seq}],
        "continuous_speedup": round(
            cont["req_per_sec"] / max(seq["req_per_sec"], 1e-9), 3),
        "tokens_equal": True,
        "vs_baseline_note": "reference ships a C inference API, no "
                            "request-serving loop; sequential mode is "
                            "the internal yardstick",
    }, values=cont_rps)
    slo_ms = float(FLAGS.get("serve_slo_ms"))
    if slo_ms > 0:
        r["slo_ms"] = slo_ms
        r["slo_met"] = bool(cont["p99_ms"] <= slo_ms)

    return _decoder_observatory_stamp(r, model, cfg, max_batch,
                                      pool_pages, page,
                                      cache_key="serving-decode")


def _decoder_observatory_stamp(r, model, cfg, max_batch, pool_pages,
                               page, cache_key):
    """Trainer-free observatory stamp shared by the serving and rollout
    lanes: attribute ONE jitted decode step at the lane's batch width
    (the loop's steady-state program) via ``costmodel.analyze_fn``,
    HBM census via ``observe.memory.sample`` over the live params + KV
    pools, and the decode step's own MFU."""
    import types as _types

    from paddle_tpu.serving.model import _decode_impl

    # the arrays themselves: this lane's own jit donates nothing
    k_pool, v_pool = (p.array for p in model.new_pools(pool_pages, page))
    max_pages = min(pool_pages - 1,
                    (cfg.max_context + page - 1) // page)
    b = max_batch
    sargs = (model.params, k_pool, v_pool,
             jax.numpy.zeros((b,), jax.numpy.int32),
             jax.numpy.ones((b, max_pages), jax.numpy.int32),
             jax.numpy.full((b,), page, jax.numpy.int32),
             jax.numpy.ones((b,), bool))

    def _step(p, kp, vp, tk, pi, ln, ac):
        with jax.named_scope("decode_step"):
            return _decode_impl(p, kp, vp, tk, pi, ln, ac, cfg)

    report = costmodel.analyze_fn(_step, sargs, known=["decode_step"],
                                  cache_key=cache_key)
    if report is not None:
        r["hbm_gb_per_step"] = round(report["xla_bytes"] / 1e9, 2) \
            if report["xla_bytes"] else None
        r["regions"] = report["regions"]
        r["regions_elided"] = report["regions_elided"]
        r["flop_agreement"] = report["flop_agreement"]
        if report["opaque_custom_calls"]:
            r["opaque_custom_calls"] = report["opaque_custom_calls"]
    else:
        r["hbm_gb_per_step"] = None
        r["regions"] = None
    snap = omem.sample(_types.SimpleNamespace(params=model.params),
                       {"k_pool": k_pool, "v_pool": v_pool})
    r["hbm_peak_bytes"] = snap["peak_bytes"]
    r["hbm_in_use_bytes"] = snap["in_use_bytes"]
    r["hbm_categories"] = snap["categories"]
    # MFU of the decode step itself (timed directly — wall req/s mixes
    # scheduling with math; MFU is about the math).  The paged kernels
    # are opaque custom calls, so the analytic matmul count is the
    # usual fallback, exactly as step_mfu decides for training lanes.
    step_j = jax.jit(_step)
    jax.block_until_ready(step_j(*sargs))
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(step_j(*sargs))
        times.append(time.perf_counter() - t0)
    step_s = float(np.median(times))
    d = cfg.dim
    hint = 2.0 * b * (cfg.layers * (4 * d * d + 2 * d * cfg.ffn)
                      + d * cfg.vocab)
    flops, source = 0.0, "costmodel"
    if report is not None:
        flops = report["flops_per_step"]
    if report is None or (report["opaque_custom_calls"]
                          and hint > flops):
        flops, source = hint, "analytic-fallback"
    r["mfu_est"] = round(costmodel.mfu(flops, step_s, 1), 3)
    r["mfu_source"] = source
    r["flops_per_step"] = round(flops, 1)
    r["decode_step_ms"] = round(step_s * 1e3, 3)
    return r


# --rollout_small: CPU-runnable shapes for the hot-swap lane
ROLLOUT_SMALL = False


def _rollout_shapes():
    """(cfg, n_requests, prompt_len_range, max_new, max_batch,
    pool_pages, page_size, timed_passes) for the rollout lane — the
    serving-lane decoder tiers (the swap A/B needs two int8 exports
    of it).  eos_id=-1 (unreachable for argmax) so BOTH checkpoints
    generate exactly max_new tokens per request — the two windows
    compare identical token volume, not two models' different greedy
    stopping points."""
    from paddle_tpu.serving.model import DecoderConfig

    if ROLLOUT_SMALL:
        # 3 timed pass-pairs, not 2: continuous batching admits by
        # thread timing, so a pass can randomly form a packed-prefill
        # bucket the warmup never compiled — one XLA cold compile in a
        # window is a 10x outlier on CPU, and the median over 3 ratios
        # shrugs it off where a mean over 2 cannot.
        return (DecoderConfig(vocab=512, dim=64, heads=4, layers=2,
                              ffn=128, max_context=128, eos_id=-1),
                12, (4, 24), 8, 4, 64, 16, 3)
    return (DecoderConfig(vocab=4000, dim=256, heads=8, layers=4,
                          ffn=1024, max_context=512, eos_id=-1),
            48, (16, 96), 32, 8, 512, 16, 3)


def _rollout_pass(srv, prompts, max_new, swap_art=None):
    """One open-loop pass over the request stream; with ``swap_art``
    a real hot-swap (build + verify + probe + flip) lands inside the
    measurement window, after submission while the batch decodes.
    Returns (wall_s, ttft list, swap report or None, failed count)."""
    from paddle_tpu.serving import rollout as ro

    t0 = time.perf_counter()
    reqs = [srv.submit(p, max_new) for p in prompts]
    rep = None
    if swap_art is not None:
        rep = ro.swap_from_artifact(srv, swap_art)
        if rep["result"] != "ok":
            raise RuntimeError(f"hot-swap failed mid-bench: {rep}")
    failed, ttfts = 0, []
    for r in reqs:
        try:
            srv.result(r, timeout=600.0)
            ttfts.append(r.ttft_s)
        except Exception:       # noqa: BLE001 — counted, asserted zero
            failed += 1
    return time.perf_counter() - t0, ttfts, rep, failed


def bench_rollout():
    """Rollout lane (`--only rollout`, round 23): sustained req/s and
    TTFT p99 of the continuous-batching server while a zero-downtime
    hot-swap lands inside the measurement window, vs the same request
    stream at steady state.  Each timed swap window swaps to a
    genuinely DIFFERENT artifact (two int8 exports of the serving
    decoder, alternated), so every window pays a full off-thread
    build + digest verify + probe plus the decode-boundary pointer
    flip.

    Headline: swap-window TTFT p99 over steady TTFT p99 (lower is
    better, 1.0 = swaps are free).  The gate also bands the per-mode
    ``req_per_sec`` / ``p99_ms`` rows; the zero-downtime contract —
    every request in every window completes — is asserted outright
    (``failed_requests`` stays informational at 0), and the swap
    report's ``pause_s`` (the only moment the decode loop is not
    decoding) rides along in ms."""
    import os
    import shutil
    import tempfile

    from paddle_tpu.serving.loader import artifact_digest, read_manifest
    from paddle_tpu.serving.model import (DecoderModel, export_decoder,
                                          init_decoder_params)
    from paddle_tpu.serving.server import InferenceServer

    cfg, n_req, (lo, hi), max_new, max_batch, pool_pages, page, passes \
        = _rollout_shapes()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, cfg.vocab,
                           rng.randint(lo, hi + 1)).tolist()
               for _ in range(n_req)]
    tmp = tempfile.mkdtemp(prefix="bench-rollout-")
    try:
        arts = []
        for seed in (0, 1):
            # canonical model-<digest12> names: the canary bake's
            # rollback resolves its predecessor by that convention
            d0 = os.path.join(tmp, f"stage-{seed}")
            export_decoder(
                {k: np.asarray(v) for k, v in
                 init_decoder_params(cfg, seed=seed).items()},
                cfg, d0, quantize="int8")
            dig = artifact_digest(read_manifest(d0))
            d = os.path.join(tmp, f"model-{dig[:12]}")
            os.rename(d0, d)
            arts.append(d)
        mdl = DecoderModel.from_artifact(arts[0])
        srv = InferenceServer(
            mdl, max_batch=max_batch,
            n_pages=pool_pages, page_size=page, continuous=True,
            model_version=artifact_digest(
                read_manifest(arts[0]))).start()
        try:
            # deterministically compile EVERY packed-prefill bucket the
            # admission loop can form — (b, ceil(T/16)*16) for
            # b <= max_batch, T <= the longest prompt.  Continuous
            # batching admits by thread timing, so which buckets a
            # pass forms is luck; an uncompiled one landing in a timed
            # window is a multi-second XLA cold compile — a 10x
            # outlier that has nothing to do with the swap under test.
            # Both artifacts share the config, so the shared
            # _jitted_steps cache makes one compile cover both models.
            mp = min(pool_pages - 1, (cfg.max_context + page - 1) // page)
            kp, vp = mdl.new_pools(pool_pages, page)
            t_hi = min(-(-hi // 16) * 16, cfg.max_context)
            for b in range(1, max_batch + 1):
                for t in range(16, t_hi + 1, 16):
                    mdl.prefill(kp, vp,
                                np.ones((b, t), np.int32),
                                np.full((b,), t, np.int32),
                                np.ones((b, mp), np.int32))
            del kp, vp
            # untimed warmup: a full swap cycle — the probe's bucket
            # plus the admission patterns a drain window produces (a
            # paused-then-resumed queue admits in groupings steady
            # state never forms)
            _rollout_pass(srv, prompts, max_new)
            _rollout_pass(srv, prompts, max_new, swap_art=arts[1])
            _rollout_pass(srv, prompts, max_new, swap_art=arts[0])
            current = 0
            steady_w, steady_t = [], []
            swap_w, swap_t, reports = [], [], []
            degr, failed = [], 0
            for _ in range(passes):
                w, t, _, f = _rollout_pass(srv, prompts, max_new)
                steady_w.append(w)
                steady_t.append(t)
                failed += f
                current = 1 - current
                w, t, rep, f = _rollout_pass(srv, prompts, max_new,
                                             swap_art=arts[current])
                swap_w.append(w)
                swap_t.append(t)
                reports.append(rep)
                failed += f
                degr.append(
                    float(np.percentile(swap_t[-1], 99))
                    / max(float(np.percentile(steady_t[-1], 99)),
                          1e-9))
            # canary-bake sub-lane (ISSUE 20): the bake must catch a
            # seeded-slow artifact (manifest debug_prefill_delay_ms)
            # and auto-roll-back, and must PROMOTE a clean one — with
            # zero failed requests either way.  The windowed TTFT
            # baseline is already warm from the timed passes above.
            from paddle_tpu.observe import REGISTRY as _reg
            from paddle_tpu.serving import rollout as ro

            # the seeded regression must clear the bake's 2x verdict
            # over the LIVE 60s window — which at this point holds the
            # timed passes' open-loop queue waits, so the delay is
            # sized off the measured window, not a magic constant
            _h = _reg.find("serve_ttft_seconds")
            base_p99 = (_h.window_quantile(0.99, 60.0)
                        if _h is not None else None) or 0.1
            delay_ms = int(max(2.5 * base_p99, 0.5) * 1e3)
            slow = os.path.join(tmp, "art-slow")
            export_decoder(
                {k: np.asarray(v) for k, v in
                 init_decoder_params(cfg, seed=2).items()},
                cfg, slow, quantize="int8",
                extra_meta={"debug_prefill_delay_ms": delay_ms})
            factor = 2.0
            bakes = {"bad": delay_ms / 1e3 + 2.5, "good": 2.5}
            canary_failed, canary_reports = 0, {}
            for tag, art in (("bad", slow), ("good", arts[1 - current])):
                # requests decode THROUGH the bake, so the canary's
                # windowed p99 is judged on live traffic
                reqs = [srv.submit(p, max_new) for p in prompts]
                canary_reports[tag] = ro.swap_from_artifact(
                    srv, art, canary=True, bake_s=bakes[tag],
                    canary_factor=factor)
                for q in reqs:
                    try:
                        srv.result(q, timeout=600.0)
                    except Exception:   # noqa: BLE001 — asserted zero
                        canary_failed += 1
        finally:
            srv.stop()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if failed:
        raise RuntimeError(
            f"zero-downtime contract violated: {failed} request(s) "
            "failed during the rollout lane")
    bad, good = canary_reports["bad"], canary_reports["good"]
    if bad.get("result") != "rolled_back" or \
            bad.get("canary", {}).get("rollback") != "ok":
        raise RuntimeError(
            "canary bake failed to roll back the seeded-slow "
            f"artifact: {bad}")
    if good.get("canary", {}).get("result") != "promoted":
        raise RuntimeError(
            f"canary bake failed to promote a clean artifact: {good}")
    if canary_failed:
        raise RuntimeError(
            f"zero-downtime contract violated: {canary_failed} "
            "request(s) failed during the canary bakes")

    def _mode(walls, ttfts):
        flat = [x for t in ttfts for x in t]
        return {
            "req_per_sec": round(float(np.median(
                [n_req / w for w in walls])), 3),
            "p99_ms": round(float(np.percentile(flat, 99)) * 1e3, 3),
            "p50_ttft_ms": round(
                float(np.percentile(flat, 50)) * 1e3, 3),
        }

    r = _with_band({
        "metric": "rollout_swap_p99_degradation",
        "value": float(np.median(degr)),
        "unit": "x steady TTFT p99 (swap in window; lower is better)",
        "devices": 1,
        "scale": "small" if ROLLOUT_SMALL else "bench",
        "rows": [{"workload": "live_swap",
                  "steady": _mode(steady_w, steady_t),
                  "swap": _mode(swap_w, swap_t)},
                 # the GOOD bake's windowed p99 vs its pre-swap
                 # baseline window — gated like any serving tail; the
                 # detection outcomes themselves are asserted above
                 # (a lane that stops detecting regressions errors,
                 # and an errored lane regresses unconditionally)
                 {"workload": "canary_bake",
                  "steady": {"p99_ms": round(float(
                      good["canary"]["baseline_p99_s"] or 0.0)
                      * 1e3, 3)},
                  "swap": {"p99_ms": round(float(
                      good["canary"]["p99_s"] or 0.0) * 1e3, 3)}}],
        "failed_requests": failed,
        "swaps": len(reports),
        "inflight_policy": str(FLAGS.get("rollout_inflight")),
        "swap_pause_ms_p50": round(float(np.median(
            [r["pause_s"] for r in reports])) * 1e3, 3),
        "swap_build_ms_p50": round(float(np.median(
            [r["build_s"] for r in reports])) * 1e3, 3),
        "swap_total_ms_p50": round(float(np.median(
            [r["swap_s"] for r in reports])) * 1e3, 3),
        "vs_baseline_note": "reference reloads by restarting the "
                            "serving process; the in-place hot-swap "
                            "is the yardstick-free rebuild surface",
    }, values=degr)
    r["canary"] = {
        "bake_s": bakes, "factor": factor,
        "injected_delay_ms": delay_ms,
        "failed_requests": canary_failed,
        "bad_bake": {
            "result": bad["result"],               # "rolled_back"
            "rollback": bad["canary"]["rollback"],
            "reason": bad["canary"]["reason"],
            "p99_ms": round(float(
                bad["canary"]["p99_s"] or 0.0) * 1e3, 3),
            "baseline_p99_ms": round(float(
                bad["canary"]["baseline_p99_s"] or 0.0) * 1e3, 3)},
        "good_bake": {"result": good["canary"]["result"]},  # promoted
    }
    r["perf_stamp_of"] = "decode_step"
    return _decoder_observatory_stamp(
        r, DecoderModel(init_decoder_params(cfg, seed=0), cfg), cfg,
        max_batch, pool_pages, page, cache_key="rollout-decode")


# --multichip_small: CPU-runnable shapes for the FSDP scaling lane
MULTICHIP_SMALL = False


def _multichip_shapes():
    """(T, D, heads, layers, ffn, V, per-chip batch, scan iters) for
    the multichip lane's transformer-zoo row.  Small-scale dims are all
    divisible by 8 so every rule-table entry actually shards on the
    8-virtual-device CPU mesh tier-1 replays."""
    if MULTICHIP_SMALL:
        return 16, 64, 2, 1, 128, 1024, 4, 8
    return 128, 512, 8, 4, 2048, 30000, 16, 32


def _multichip_trainer(n_devices, fsdp, batch, seed=0):
    """One transformer-zoo trainer on a ``data=n`` mesh (FSDP on/off)
    plus its fixed-seed feed.  Installs the mesh as the process global
    (the trainer's feed sharding reads it)."""
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.core.device import build_mesh, set_mesh
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.models import transformer_text_classifier
    from paddle_tpu.parallel import transformer_fsdp_rules
    from paddle_tpu.trainer.trainer import Trainer

    T, D, HEADS, L, F, V, _, _ = _multichip_shapes()
    devices = jax.devices()[:n_devices]
    mesh = build_mesh({"data": len(devices)}, devices)
    set_mesh(mesh)
    cfg = transformer_text_classifier(
        vocab_size=V, model_dim=D, num_heads=HEADS, num_layers=L,
        ffn_dim=F, num_classes=2, max_len=T)
    trainer = Trainer(
        NeuralNetwork(cfg),
        opt_config=OptimizationConfig(
            learning_method="adam", learning_rate=1e-3,
            gradient_clipping_threshold=25.0),
        mesh=mesh, seed=0, fsdp=fsdp,
        fsdp_rules=transformer_fsdp_rules())
    rng = np.random.RandomState(seed)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(
                    rng.randint(0, V, (batch, T)).astype(np.int32)),
                jax.numpy.asarray(np.full((batch,), T, np.int32))),
            "label": jax.numpy.asarray(
                rng.randint(0, 2, (batch,)).astype(np.int32))}
    return trainer, feed


def _multichip_mode_run(n, fsdp, batch, iters, keep=False):
    """Time one (chip count, FSDP mode, global batch) cell and read the
    per-chip HBM category gauges off it.  ``params_bytes_per_chip`` /
    ``opt_state_bytes_per_chip`` are the lane's whole point: under FSDP
    they must shrink with the chip count while replicated mode pays the
    full model everywhere.  (Informational fields — the gate's series
    key is ``samples_per_sec``.)  ``keep=True`` also returns the live
    trainer/feed so the lane can attach the observatory stamp to one
    representative cell."""
    trainer, feed = _multichip_trainer(n, fsdp, batch)
    ms, agree = _scan_time_ms(trainer, feed, iters=iters)
    cats = omem.account(trainer, feed)["categories"]
    res = {
        "samples_per_sec": round(batch / (ms / 1e3), 3),
        "step_ms": round(ms, 3),
        "params_bytes_per_chip": int(cats.get("params", 0)),
        "opt_state_bytes_per_chip": int(cats.get("opt_state", 0)),
        "timing_self_check": round(agree, 4),
    }
    return (res, trainer, feed) if keep else res


def bench_multichip():
    """Multi-chip FSDP scaling lane (`--only multichip`, round 21).

    Weak scaling (fixed per-chip batch) and strong scaling (fixed
    global batch) of the transformer-zoo train step over ``data`` =
    1/2/4/8 chips with ``--fsdp`` on — params AND Adam slots sharded
    over the mesh (``parallel/rule_tables.py`` transformer table) —
    plus a replicated A/B at the widest mesh, so the artifact carries
    samples/sec AND the per-chip ``hbm_category_bytes`` win on one
    line.  On CPU the 8 "chips" are virtual devices sharing the same
    cores, so throughput scaling is about program correctness (the
    collectives run) rather than speedup; the HBM columns are exact
    either way.

    The lane also replays the kill-switch contract every run:
    ``--fsdp`` on a 1-chip mesh must be byte-for-byte the replicated
    program (3 fixed-seed steps, params compared exactly) — the same
    pin tests/test_fsdp.py holds.
    """
    from paddle_tpu.core import device as _dev

    T, D, HEADS, L, F, V, per_chip, iters = _multichip_shapes()
    saved_mesh = _dev._mesh
    n_avail = len(jax.devices())
    chip_counts = [n for n in (1, 2, 4, 8) if n <= n_avail]
    max_n = chip_counts[-1]
    global_batch = per_chip * max_n
    try:
        rows, weak, strong = [], {}, {}
        stamp_tr = stamp_feed = None
        for n in chip_counts:
            out = _multichip_mode_run(n, True, per_chip * n, iters,
                                      keep=(n == 1))
            if n == 1:
                # the 1-chip cell carries the observatory stamp: its
                # step is the plain single-device program the cost
                # model attributes exactly
                weak[n], stamp_tr, stamp_feed = out
            else:
                weak[n] = out
            rows.append({"workload": f"weak_d{n}", "fsdp": weak[n]})
        # the FSDP win's denominator: full replication at the widest mesh
        repl = _multichip_mode_run(max_n, False, global_batch, iters)
        rows[-1]["replicated"] = repl
        for n in chip_counts:
            # weak@max_n IS the fixed-global-batch point — reuse it
            strong[n] = weak[n] if n == max_n else \
                _multichip_mode_run(n, True, global_batch, iters)
            if n != max_n:
                rows.append({"workload": f"strong_d{n}",
                             "fsdp": strong[n]})

        # kill-switch contract: --fsdp on a 1-chip mesh is the SAME
        # program as --fsdp=false — byte-identical params after 3
        # fixed-seed steps
        t_on, feed1 = _multichip_trainer(1, True, per_chip, seed=1)
        t_off, _ = _multichip_trainer(1, False, per_chip, seed=1)
        for _ in range(3):
            t_on.train_one_batch(feed1)
            t_off.train_one_batch(feed1)
        for a, b in zip(jax.tree_util.tree_leaves(t_on.params),
                        jax.tree_util.tree_leaves(t_off.params)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise RuntimeError(
                    "fsdp kill-switch contract violated: --fsdp on a "
                    "1-chip mesh diverged from --fsdp=false")

        fsdp_bytes = (weak[max_n]["params_bytes_per_chip"]
                      + weak[max_n]["opt_state_bytes_per_chip"])
        repl_bytes = (repl["params_bytes_per_chip"]
                      + repl["opt_state_bytes_per_chip"])
        sps = weak[max_n]["samples_per_sec"]
        line = _with_band({
            "metric": "multichip_samples_per_sec",
            "value": sps,
            "unit": f"samples/s (weak scaling, {max_n} chips × batch "
                    f"{per_chip}, transformer {L}L/{HEADS}H d={D} "
                    f"T={T}, fsdp)",
            "devices": max_n,
            "scale": "small" if MULTICHIP_SMALL else "bench",
            "rows": rows,
            "weak_scaling_eff": round(
                sps / max(weak[1]["samples_per_sec"] * max_n, 1e-9), 3),
            "strong_scaling_eff": round(
                strong[max_n]["samples_per_sec"]
                / max(strong[1]["samples_per_sec"] * max_n, 1e-9), 3),
            "fsdp_hbm_win": round(repl_bytes / fsdp_bytes, 2)
            if fsdp_bytes else 0.0,
            "kill_switch_equal": True,
            "vs_baseline_note": "reference's multi-device story is "
                                "MultiGradientMachine thread-per-GPU "
                                "replication — no sharded optimizer "
                                "state; FSDP per-chip bytes are the "
                                "new capability under measure",
            "perf_stamp_of": "weak_d1.fsdp",
        }, values=[sps])
        return _finish(line, "multichip_weak_d1", stamp_tr, stamp_feed,
                       step_ms=weak[1]["step_ms"])
    finally:
        _dev._mesh = saved_mesh


# --sparse_small: CPU-runnable shapes for the sparse embedding lane
SPARSE_SMALL = False


def _sparse_shapes():
    """(lookup-scan table sizes, lookup dim, ids per lookup batch,
    train table rows, train emb dim, train batch, train seq len, scan
    iters) for the sparse embedding lane.  The lookup dim stays
    lane-aligned (128) so the TPU dispatch would take the kernel path
    at these exact shapes; the train rows hit the 10⁶ CPU scale the
    exchange A/B is pinned at (10⁷ at bench scale)."""
    if SPARSE_SMALL:
        return (10 ** 4, 10 ** 5, 10 ** 6), 128, 4096, 10 ** 6, 16, \
            256, 8, 8
    return (10 ** 5, 10 ** 6, 10 ** 7), 128, 8192, 10 ** 7, 64, \
        1024, 16, 32


def _sparse_trainer(vocab, emb_dim, batch, seq_len, mesh, seed=0):
    """One ctr-shaped trainer (sparse_update embedding → sum-pool →
    relu tower → softmax head) over ``vocab`` rows, plus its
    fixed-seed feed.  Whether the step runs the sparse exchange or the
    legacy dense gradient is read off ``--sparse_grads`` at build
    time — the lane flips the flag between constructions for the A/B."""
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.data.feeder import integer_value, \
        integer_value_sequence
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.trainer.trainer import Trainer

    with config_scope():
        x = dsl.data("ids", integer_value_sequence(vocab))
        lab = dsl.data("label", integer_value(2))
        emb = dsl.embedding(x, size=emb_dim, param_attr=dsl.ParamAttr(
            name="_slot_emb.w", sparse_update=True, initial_std=0.02))
        pooled = dsl.pooling(emb, pooling_type=dsl.SumPooling())
        tower = dsl.fc(pooled, size=32, act=dsl.ReluActivation())
        pred = dsl.fc(tower, size=2, act=dsl.SoftmaxActivation())
        cfg = dsl.topology(dsl.classification_cost(pred, lab))
    trainer = Trainer(
        NeuralNetwork(cfg),
        opt_config=OptimizationConfig(
            learning_method="adam", learning_rate=1e-3,
            gradient_clipping_threshold=25.0),
        mesh=mesh, seed=0)
    rng = np.random.RandomState(seed)
    feed = {"ids": SequenceBatch(
                jax.numpy.asarray(rng.randint(
                    0, vocab, (batch, seq_len)).astype(np.int32)),
                jax.numpy.asarray(np.full((batch,), seq_len,
                                          np.int32))),
            "label": jax.numpy.asarray(
                rng.randint(0, 2, (batch,)).astype(np.int32))}
    return trainer, feed


def _time_call_ms(fn, *args, reps=5):
    """Median warm-call wall ms of ``fn(*args)`` (first call pays the
    compile and is dropped)."""
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _sparse_lookup_row(vocab, dim, n_ids):
    """One lookup-throughput row at table size ``vocab``: the
    production sparse composite (dedup → touched-row gather → inverse
    lookup, ``parallel/sparse.py``) against the dense ``take`` over
    the raw id stream.  Gate keys are ``lookups_per_sec`` only —
    ``call_ms`` rides along informationally (a second ``_ms`` series
    per mode would shadow it in the gate)."""
    from paddle_tpu.ops import pallas_embedding as pemb
    from paddle_tpu.parallel import sparse as psparse

    rng = np.random.RandomState(vocab % (2 ** 31))
    table = jax.numpy.zeros((vocab, dim), jax.numpy.float32)
    ids = jax.numpy.asarray(
        rng.randint(0, vocab, (n_ids,)).astype(np.int32))

    @jax.jit
    def sparse_lookup(table, ids):
        rows = psparse.unique_rows_sorted(ids, n_ids, vocab)
        block = pemb.gather_rows(table, rows)
        return psparse.lookup_rows(rows, block, ids)

    @jax.jit
    def dense_lookup(table, ids):
        return jax.numpy.take(table, ids, axis=0, mode="clip")

    sparse_ms = _time_call_ms(sparse_lookup, table, ids)
    dense_ms = _time_call_ms(dense_lookup, table, ids)
    row = {
        "workload": f"lookup_v{vocab}",
        "sparse": {
            "lookups_per_sec": round(n_ids / (sparse_ms / 1e3), 1),
            "call_ms": round(sparse_ms, 4)},
        "dense": {
            "lookups_per_sec": round(n_ids / (dense_ms / 1e3), 1),
            "call_ms": round(dense_ms, 4)},
    }
    del table
    return row


def bench_sparse():
    """Sparse embedding lane (`--only sparse`, round 22).

    Three measurements on one line:

    - lookup throughput vs table size — the production sparse
      composite (``unique_rows_sorted`` → ``gather_rows`` →
      ``lookup_rows``) against the dense ``take`` over the raw id
      stream, one row per table size (on CPU the gather dispatch takes
      the ``no_tpu`` XLA fallback; the shapes are exactly the kernel's
      capable shapes so a TPU run exercises the Pallas path);
    - the dense-vs-sparse-exchange TRAIN A/B at the 10⁶-row CPU scale
      (``--sparse_grads`` flipped between trainer builds):
      samples/sec plus ``exchanged_grad_bytes`` — the fixed-capacity
      (rows, values) payload against the dense [V, D] gradient — with
      the traffic win stamped on the line;
    - the kill-switch contracts, replayed every run and raising (=
      lane failure) on violation: ``--embedding_kernel`` on/off
      byte-identical gathers (interpret-mode kernel vs XLA at tiny
      shapes), and ``--sparse_grads`` on/off parameter trajectories
      rtol-close after 3 fixed-seed steps (close, not bit-equal: the
      scatter-add accumulates in a different order than the dense
      update).
    """
    from paddle_tpu.core import device as _dev
    from paddle_tpu.core.device import build_mesh, set_mesh
    from paddle_tpu.ops import pallas_embedding as pemb
    from paddle_tpu.parallel import sparse as psparse

    scan, dim, n_ids, v_train, emb_dim, batch, seq_len, iters = \
        _sparse_shapes()
    saved_mesh = _dev._mesh
    saved_sparse = bool(FLAGS.sparse_grads)
    try:
        mesh = build_mesh({"data": 1}, jax.devices()[:1])
        set_mesh(mesh)
        rows = [_sparse_lookup_row(v, dim, n_ids) for v in scan]

        # ---- train A/B: sparse exchange vs legacy dense gradient
        FLAGS.set("sparse_grads", True)
        tr_sp, feed = _sparse_trainer(v_train, emb_dim, batch,
                                      seq_len, mesh)
        sp_ms, _ = _scan_time_ms(tr_sp, feed, iters=iters)
        cap = batch * seq_len       # auto capacity = batch id count
        sp_bytes = psparse.exchange_payload_bytes(cap, emb_dim)
        FLAGS.set("sparse_grads", False)
        tr_d, _ = _sparse_trainer(v_train, emb_dim, batch, seq_len,
                                  mesh)
        d_ms, _ = _scan_time_ms(tr_d, feed, iters=iters)
        d_bytes = v_train * emb_dim * 4
        rows.append({
            "workload": f"train_v{v_train}",
            "sparse": {
                "samples_per_sec": round(batch / (sp_ms / 1e3), 3),
                "step_ms": round(sp_ms, 3),
                "exchanged_grad_bytes": int(sp_bytes)},
            "dense": {
                "samples_per_sec": round(batch / (d_ms / 1e3), 3),
                "step_ms": round(d_ms, 3),
                "exchanged_grad_bytes": int(d_bytes)},
        })
        del tr_d

        # ---- kill-switch contracts (every run, violation raises)
        rng = np.random.RandomState(7)
        t_small = jax.numpy.asarray(
            rng.randn(32, 128).astype(np.float32))
        r_small = jax.numpy.asarray(
            rng.randint(0, 32, (8,)).astype(np.int32))
        FLAGS.set("embedding_kernel_interpret", True)
        a = np.asarray(pemb.gather_rows(t_small, r_small))
        FLAGS.set("embedding_kernel", False)
        b = np.asarray(pemb.gather_rows(t_small, r_small))
        FLAGS.set("embedding_kernel", True)
        FLAGS.set("embedding_kernel_interpret", False)
        if not np.array_equal(a, b):
            raise RuntimeError(
                "embedding kernel kill-switch contract violated: "
                "--embedding_kernel on/off gathers differ")

        FLAGS.set("sparse_grads", True)
        eq_sp, eq_feed = _sparse_trainer(1024, emb_dim, 16, seq_len,
                                         mesh, seed=3)
        FLAGS.set("sparse_grads", False)
        eq_d, _ = _sparse_trainer(1024, emb_dim, 16, seq_len, mesh,
                                  seed=3)
        for _ in range(3):
            eq_sp.train_one_batch(eq_feed)
            eq_d.train_one_batch(eq_feed)
        for name in eq_sp.params:
            if not np.allclose(np.asarray(eq_sp.params[name]),
                               np.asarray(eq_d.params[name]),
                               rtol=1e-4, atol=1e-6):
                raise RuntimeError(
                    "sparse exchange equivalence violated: "
                    f"--sparse_grads on/off diverged on {name!r}")
        FLAGS.set("sparse_grads", True)

        headline = rows[len(scan) - 1]["sparse"]["lookups_per_sec"]
        line = _with_band({
            "metric": "sparse_embedding",
            "value": headline,
            "unit": f"lookups/s (sparse composite, {scan[-1]:.0e}-row "
                    f"table, d={dim}, {n_ids} ids)",
            "scale": "small" if SPARSE_SMALL else "bench",
            "rows": rows,
            "exchange_traffic_win": round(d_bytes / sp_bytes, 1),
            "kill_switch_equal": True,
            "sparse_dense_equiv": True,
            "vs_baseline_note": "reference ships sparse tables to "
                                "parameter servers row by row "
                                "(SparseRemoteParameterUpdater); here "
                                "the fixed-capacity (rows, values) "
                                "exchange rides the jitted step and "
                                "the dense [V, D] gradient is never "
                                "materialized",
            "perf_stamp_of": f"train_v{v_train}.sparse",
        }, values=[headline])
        return _finish(line, "sparse_train", tr_sp, feed,
                       step_ms=sp_ms)
    finally:
        FLAGS.set("sparse_grads", saved_sparse)
        _dev._mesh = saved_mesh


# --pipeline_small: CPU-runnable shapes for the prefetch A/B lane
PIPELINE_SMALL = False


def _write_pipeline_dataset(tmp, tag, samples, records_per_chunk=256):
    """Pickle raw samples into a recordio file (the framework's own
    dataset-cache convention) so the A/B reader pays real disk IO +
    unpickle per sample, like a production input pipeline."""
    import os
    import pickle

    from paddle_tpu.data import recordio as rio

    path = os.path.join(tmp, f"{tag}.recordio")
    with rio.Writer(path, max_records_per_chunk=records_per_chunk) as w:
        for s in samples:
            w.write(pickle.dumps(s))
    return path


def _pipeline_ab(trainer, reader, feeder, n_batches, batch_size,
                 prefetch_depth):
    """Run 2 passes synchronous (depth=0) then 2 passes prefetched;
    report pass-2 (warm) ms/batch and the input_bound_ratio gauge of
    each mode.  The same trainer carries over so the prefetch run
    reuses the compiled step — the A/B isolates the input pipeline."""
    old_depth = FLAGS.prefetch_depth
    old_save = FLAGS.save_dir
    FLAGS.set("save_dir", "")        # timing run: no checkpoints
    res = {}
    try:
        for mode, depth in (("sync", 0), ("prefetch", prefetch_depth)):
            FLAGS.set("prefetch_depth", depth)
            marks = {}

            def handler(e, marks=marks):
                from paddle_tpu.trainer import events as ev
                if isinstance(e, (ev.BeginPass, ev.EndPass)):
                    marks[(type(e).__name__, e.pass_id)] = \
                        time.perf_counter()

            trainer.train(reader, num_passes=2, feeder=feeder,
                          event_handler=handler)
            warm_s = marks[("EndPass", 1)] - marks[("BeginPass", 1)]
            res[mode] = {
                "ms_per_batch": round(warm_s / n_batches * 1e3, 3),
                "input_bound_ratio": round(
                    observe.gauge("input_bound_ratio").value(), 4),
                "samples_per_sec": round(
                    n_batches * batch_size / warm_s, 1),
            }
    finally:
        FLAGS.set("prefetch_depth", old_depth)
        FLAGS.set("save_dir", old_save)
    return res


def _pipeline_lstm(tmp):
    """LSTM text-classifier row (bench_lstm's config; --pipeline_small
    shrinks it to CPU scale).  Raw samples are (token-list, label) —
    convert pays the pad/stack, the reader pays disk IO + unpickle."""
    import pickle

    from paddle_tpu.data import reader as R
    from paddle_tpu.data.feeder import (DataFeeder, integer_value,
                                        integer_value_sequence)
    from paddle_tpu.models import lstm_text_classifier

    if PIPELINE_SMALL:
        B, T, H, V, E, NB = 32, 64, 128, 4000, 64, 8
    else:
        B, T, H, V, E, NB = 128, 100, 512, 30000, 128, 12
    FLAGS.set("bf16_activations", True)
    cfg = lstm_text_classifier(vocab_size=V, embed_dim=E, hidden_size=H,
                               lstm_num=2, num_classes=2)
    trainer = _mk_trainer(cfg, l2=8e-4)
    rng = np.random.RandomState(0)
    samples = [(rng.randint(0, V, (T,)).astype(np.int32).tolist(),
                int(rng.randint(0, 2))) for _ in range(NB * B)]
    path = _write_pipeline_dataset(tmp, "lstm", samples)
    feeder = DataFeeder([("data", integer_value_sequence(V)),
                         ("label", integer_value(2))])

    def reader():
        import paddle_tpu.data.recordio as rio
        return R.batch(
            lambda: (pickle.loads(r) for r in rio.reader(path)), B)()

    return trainer, reader, feeder, NB, B


def _pipeline_resnet(tmp):
    """ResNet-50 row (bench_resnet's config): uint8 images on disk,
    convert densifies to float32 — the decode-ish host work a vision
    input pipeline pays per batch."""
    import pickle

    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data import reader as R
    from paddle_tpu.data.feeder import (DataFeeder, dense_vector,
                                        integer_value)
    from paddle_tpu.models.image import resnet, resnet_cifar10

    if PIPELINE_SMALL:
        # ResNet-50's final 7x7 pool needs a 224^2 input; the small lane
        # subs the repo's cifar resnet (same conv+BN block family)
        B, IMG, NCLASS, NB = 32, 32, 10, 6
    else:
        B, IMG, NCLASS, NB = 128, 224, 1000, 6
    FLAGS.set("bf16_activations", True)
    with config_scope():
        img = dsl.data("image", dense_vector(3 * IMG * IMG),
                       height=IMG, width=IMG)
        lab = dsl.data("label", integer_value(NCLASS))
        if PIPELINE_SMALL:
            probs = resnet_cifar10(img, depth=20, num_classes=NCLASS)
        else:
            probs = resnet(img, depth=50, num_classes=NCLASS)
        cost = dsl.classification_cost(probs, lab)
        cfg = dsl.topology(cost)
    trainer = _mk_trainer(cfg, lr=1e-3)
    rng = np.random.RandomState(0)
    samples = [(rng.randint(0, 256, (3 * IMG * IMG,), dtype=np.uint8),
                int(rng.randint(0, NCLASS))) for _ in range(NB * B)]
    path = _write_pipeline_dataset(tmp, "resnet", samples,
                                   records_per_chunk=B)
    feeder = DataFeeder([("image", dense_vector(3 * IMG * IMG)),
                         ("label", integer_value(NCLASS))])

    def reader():
        import paddle_tpu.data.recordio as rio
        return R.batch(
            lambda: (pickle.loads(r) for r in rio.reader(path)), B)()

    return trainer, reader, feeder, NB, B


def _pipeline_transformer(tmp):
    """Transformer row (bench_attention's config) at long context."""
    import pickle

    from paddle_tpu.data import reader as R
    from paddle_tpu.data.feeder import (DataFeeder, integer_value,
                                        integer_value_sequence)
    from paddle_tpu.models import transformer_text_classifier

    if PIPELINE_SMALL:
        B, T, D, HEADS, L, F, V, NB = 4, 256, 128, 4, 2, 256, 4000, 6
    else:
        B, T, D, HEADS, L, F, V, NB = 16, 2048, 512, 8, 4, 2048, 30000, 6
    FLAGS.set("bf16_activations", True)
    cfg = transformer_text_classifier(
        vocab_size=V, model_dim=D, num_heads=HEADS, num_layers=L,
        ffn_dim=F, num_classes=2, max_len=T)
    trainer = _mk_trainer(cfg, lr=1e-3)
    rng = np.random.RandomState(0)
    samples = [(rng.randint(0, V, (T,)).astype(np.int32).tolist(),
                int(rng.randint(0, 2))) for _ in range(NB * B)]
    path = _write_pipeline_dataset(tmp, "transformer", samples,
                                   records_per_chunk=4 * B)
    feeder = DataFeeder([("data", integer_value_sequence(V)),
                         ("label", integer_value(2))])

    def reader():
        import paddle_tpu.data.recordio as rio
        return R.batch(
            lambda: (pickle.loads(r) for r in rio.reader(path)), B)()

    return trainer, reader, feeder, NB, B


def bench_pipeline():
    """Async-input-pipeline A/B (round 11): each workload trains from a
    recordio file on disk — reader IO + unpickle + DataFeeder convert
    on the host — twice: `--prefetch_depth=0` (the synchronous loop)
    vs the async pipeline.  The JSON line carries per-workload warm
    ms/batch, the input_bound_ratio of each mode, and the acceptance
    verdict `ratio_ok` (prefetch ratio < 0.05); the headline value is
    the WORST prefetch-mode ratio across workloads, so the parsed
    metric is the acceptance bound itself."""
    import tempfile

    depth = max(FLAGS.prefetch_depth, 2)
    rows = []
    stamp = {}
    with tempfile.TemporaryDirectory(prefix="ptpu-bench-pipeline-") \
            as tmp:
        for tag, build in (("lstm_text_cls", _pipeline_lstm),
                           ("resnet50", _pipeline_resnet),
                           ("transformer", _pipeline_transformer)):
            trainer, reader, feeder, nb, b = build(tmp)
            ab = _pipeline_ab(trainer, reader, feeder, nb, b, depth)
            speedup = ab["sync"]["ms_per_batch"] \
                / max(ab["prefetch"]["ms_per_batch"], 1e-9)
            rows.append({
                "workload": tag, **ab,
                "speedup": round(speedup, 3),
                "ratio_ok": ab["prefetch"]["input_bound_ratio"] < 0.05,
            })
            if tag == "lstm_text_cls":
                # the lane's perf stamp (regions/memory/MFU) describes
                # its first workload — the LSTM row, re-fed one
                # converted batch from the same recordio reader
                feed = feeder.convert(next(iter(reader())))
                _finish(stamp, "pipeline", trainer, feed,
                        step_ms=ab["prefetch"]["ms_per_batch"])
    worst = max(r["prefetch"]["input_bound_ratio"] for r in rows)
    r = {
        "metric": "input_pipeline_bound_ratio_max",
        "value": worst,
        "unit": ("worst input_bound_ratio across workloads with the "
                 "async pipeline on (target < 0.05; per-row sync-vs-"
                 f"prefetch A/B at depth={depth}, "
                 f"{'small' if PIPELINE_SMALL else 'bench'} scale)"),
        "target": 0.05,
        "passed": all(r["ratio_ok"] for r in rows),
        "prefetch_depth": depth,
        "reader_workers": FLAGS.reader_workers,
        "scale": "small" if PIPELINE_SMALL else "bench",
        "rows": rows,
        "perf_stamp_of": "lstm_text_cls",
        **stamp,
    }
    return _with_band(r)


# --precision_small: CPU-runnable shapes for the fp32/bf16 A/B lane
PRECISION_SMALL = False


def _prec_lstm():
    """LSTM text-classifier precision-A/B workload (bench_lstm's config
    minus the bf16_activations override — precision is the only knob)."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import lstm_text_classifier

    if PRECISION_SMALL:
        B, T, H, V, E = 16, 32, 128, 2000, 32
    else:
        B, T, H, V, E = 128, 100, 512, 30000, 128
    cfg = lstm_text_classifier(vocab_size=V, embed_dim=E, hidden_size=H,
                               lstm_num=2, num_classes=2)
    trainer = _mk_trainer(cfg, l2=8e-4)
    rng = np.random.RandomState(0)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(rng.randint(0, V, (B, T)).astype(np.int32)),
                jax.numpy.asarray(np.full((B,), T, np.int32))),
            "label": jax.numpy.asarray(
                rng.randint(0, 2, (B,)).astype(np.int32))}
    fwd = 2 * B * T * (E * 4 * H + 3 * H * 4 * H)
    return trainer, feed, fwd


def _prec_resnet():
    """ResNet-50 precision-A/B workload (cifar ResNet-20 on the small
    lane — same conv+BN block family at CPU scale)."""
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.models.image import resnet, resnet_cifar10

    if PRECISION_SMALL:
        B, IMG, NCLASS = 8, 32, 10
        fwd_per_img = 41e6 * 2        # cifar resnet20 MACs, approximate
    else:
        B, IMG, NCLASS = 128, 224, 1000
        fwd_per_img = 3.858e9 * 2     # exact conv+fc MACs of this config
    with config_scope():
        img = dsl.data("image", dense_vector(3 * IMG * IMG),
                       height=IMG, width=IMG)
        lab = dsl.data("label", integer_value(NCLASS))
        if PRECISION_SMALL:
            probs = resnet_cifar10(img, depth=20, num_classes=NCLASS)
        else:
            probs = resnet(img, depth=50, num_classes=NCLASS)
        cost = dsl.classification_cost(probs, lab)
        cfg = dsl.topology(cost)
    trainer = _mk_trainer(cfg, lr=1e-3)
    rng = np.random.RandomState(0)
    feed = {"image": jax.numpy.asarray(
                rng.randn(B, 3 * IMG * IMG).astype(np.float32)),
            "label": jax.numpy.asarray(
                rng.randint(0, NCLASS, (B,)).astype(np.int32))}
    return trainer, feed, fwd_per_img * B


def _prec_transformer():
    """Transformer precision-A/B workload (bench_attention's config)."""
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer_text_classifier

    if PRECISION_SMALL:
        B, T, D, HEADS, L, F, V = 4, 128, 64, 4, 2, 128, 2000
    else:
        B, T, D, HEADS, L, F, V = 16, 2048, 512, 8, 4, 2048, 30000
    cfg = transformer_text_classifier(
        vocab_size=V, model_dim=D, num_heads=HEADS, num_layers=L,
        ffn_dim=F, num_classes=2, max_len=T)
    trainer = _mk_trainer(cfg, lr=1e-3)
    rng = np.random.RandomState(0)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(rng.randint(0, V, (B, T)).astype(np.int32)),
                jax.numpy.asarray(np.full((B,), T, np.int32))),
            "label": jax.numpy.asarray(
                rng.randint(0, 2, (B,)).astype(np.int32))}
    fwd = 2 * L * B * T * (3 * D * D + 2 * T * D + D * D + 2 * D * F)
    return trainer, feed, fwd


def _precision_serving_row():
    """fp32 vs int8-weights-only artifact A/B: per-call latency plus
    top-1 / loss delta on a FIXED synthetic eval slice (seeded data and
    labels, identical for both artifacts — the delta isolates
    quantization, per the Gemma-on-TPU measurement template)."""
    import tempfile

    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.layers import NeuralNetwork
    from paddle_tpu.serving import ServedModel, export_network

    DIM, NCLASS, B, CALLS = (64, 10, 32, 10) if PRECISION_SMALL \
        else (784, 10, 128, 30)
    with config_scope():
        img = dsl.data_layer("img", dense_vector(DIM))
        lbl = dsl.data_layer("label", integer_value(NCLASS))
        h1 = dsl.fc_layer(img, size=4 * DIM, act=dsl.ReluActivation())
        h2 = dsl.fc_layer(h1, size=4 * DIM, act=dsl.ReluActivation())
        pred = dsl.fc_layer(h2, size=NCLASS,
                            act=dsl.SoftmaxActivation(),
                            name="prediction")
        cfg = dsl.topology(dsl.classification_cost(pred, lbl))
    net = NeuralNetwork(cfg)
    params = net.init_params(7)
    rng = np.random.RandomState(0)
    x = rng.randn(B, DIM).astype(np.float32)
    labels = rng.randint(0, NCLASS, (B,))

    def artifact_size(d):
        import os
        return sum(os.path.getsize(os.path.join(d, f))
                   for f in os.listdir(d))

    def bench_artifact(d):
        m = ServedModel.load(d)
        for _ in range(3):                      # warmup / compile
            m(img=x)
        times = []
        for _ in range(CALLS):
            t0 = time.perf_counter()
            probs = m(img=x)["prediction"]
            times.append((time.perf_counter() - t0) * 1e3)
        probs = np.asarray(probs, np.float32)
        ce = float(np.mean(-np.log(
            np.maximum(probs[np.arange(B), labels], 1e-9))))
        return (round(float(np.median(times)), 3), probs.argmax(1), ce,
                artifact_size(d))

    with tempfile.TemporaryDirectory(prefix="ptpu-bench-prec-") as tmp:
        d32 = tmp + "/fp32"
        d8 = tmp + "/int8"
        export_network(net, params, {"img": x}, d32)
        export_network(net, params, {"img": x}, d8, quantize="int8")
        ms32, top32, ce32, sz32 = bench_artifact(d32)
        ms8, top8, ce8, sz8 = bench_artifact(d8)
    return {
        "workload": "serving_int8",
        "fp32": {"ms_per_call": ms32, "loss": round(ce32, 5),
                 "artifact_bytes": sz32},
        "int8": {"ms_per_call": ms8, "loss": round(ce8, 5),
                 "artifact_bytes": sz8},
        "latency_ratio": round(ms8 / max(ms32, 1e-9), 3),
        "top1_delta": round(float((top32 != top8).mean()), 4),
        "loss_delta": round(abs(ce32 - ce8), 5),
        "size_ratio": round(sz8 / max(sz32, 1), 3),
        "batch": B,
    }


def bench_precision():
    """Precision A/B lane (`--only precision`, round 12): each training
    workload runs the SAME step twice — `--precision=fp32` (full fp32,
    legacy bf16 knobs forced off) vs `--precision=bf16` (fp32 masters,
    bf16 compute, dynamic loss scaling) — timed by the in-scan method,
    so the bf16 number pays the full mixed-precision tax (cast, finite
    check, scale update).  Headline value is the SECOND-BEST bf16/fp32
    speedup across the three workloads: value ≥ 1.2 ⟺ the "bf16 ≥ 1.2×
    on at least two of {LSTM, ResNet-50, transformer}" acceptance bound.
    MFU targets ride each row (ResNet-50 ≥ 0.45, transformer ≥ 0.35 —
    ROADMAP item 3).  A serving row A/Bs the fp32 vs int8 weights-only
    artifact (latency + top-1/loss delta on a fixed eval slice)."""
    saved = {k: FLAGS.get(k)
             for k in ("precision", "use_bf16", "bf16_activations")}
    iters = 16 if PRECISION_SMALL else 64
    workloads = [("lstm_text_cls", _prec_lstm, None),
                 ("resnet50" if not PRECISION_SMALL
                  else "resnet20_cifar", _prec_resnet, 0.45),
                 ("transformer", _prec_transformer, 0.35)]
    rows = []
    stamp = {}
    try:
        # the legacy knobs would make the "fp32" lane bf16 on TPU;
        # force them off so --precision is the only variable
        FLAGS.set("use_bf16", False)
        FLAGS.set("bf16_activations", False)
        for tag, build, mfu_target in workloads:
            per = {}
            for prec in ("fp32", "bf16"):
                FLAGS.set("precision", prec)
                trainer, feed, fwd_flops = build()
                ms, agree = _scan_time_ms(trainer, feed, iters=iters)
                n = _n_chips(trainer)
                hint = TRAIN_FLOP_FACTOR * fwd_flops
                mfu = costmodel.step_mfu(
                    trainer, feed, ms / 1e3, devices=n,
                    fallback_flops=hint,
                    cache_key=f"precision-{tag}-{prec}")
                per[prec] = {"ms_per_batch": round(ms, 3), **mfu,
                             "timing_self_check": round(agree, 3)}
                if tag == workloads[-1][0] and prec == "bf16":
                    # lane perf stamp: the last workload's bf16 step
                    # (analysis BEFORE the trainer is torn down)
                    _finish(stamp, f"precision-{tag}-{prec}", trainer,
                            feed, step_ms=ms, hint_flops=hint)
                del trainer
                jax.clear_caches()
            speedup = per["fp32"]["ms_per_batch"] \
                / max(per["bf16"]["ms_per_batch"], 1e-9)
            row = {"workload": tag, **per,
                   "speedup": round(speedup, 3),
                   "speedup_ok": speedup >= 1.2}
            if mfu_target is not None:
                row["mfu_target"] = mfu_target
                row["mfu_ok"] = per["bf16"]["mfu_est"] >= mfu_target
            rows.append(row)
        FLAGS.set("precision", "fp32")
        serving = _precision_serving_row()
    finally:
        for k, v in saved.items():
            FLAGS.set(k, v)
    speedups = sorted(r["speedup"] for r in rows)
    return _with_band({
        "metric": "precision_bf16_speedup_2nd_best",
        "value": round(speedups[-2], 3),
        "unit": ("second-best bf16/fp32 step-throughput speedup across "
                 "{LSTM, ResNet, transformer} (target ≥ 1.2 ⟺ at least "
                 "two workloads pass; "
                 f"{'small' if PRECISION_SMALL else 'bench'} scale)"),
        "target": 1.2,
        "passed": sum(r["speedup_ok"] for r in rows) >= 2,
        "scale": "small" if PRECISION_SMALL else "bench",
        "rows": rows,
        "serving": serving,
        "perf_stamp_of": f"{workloads[-1][0]}.bf16",
        **stamp,
    })


def bench_observe():
    """Tracing-overhead A/B (`--only observe`, round 13): the SAME
    small LSTM row steps untraced (the production default — no sink, no
    port, `span()` is a shared no-op) vs traced (JSONL sink + flight
    recorder), per-step fenced in BOTH modes so the delta is tracing
    cost, not fencing asymmetry.  `trace_overhead_us_per_step` is the
    enabled-mode tax; `trace_disabled_us_per_step` measures the no-op
    span machinery directly (span count of one hot-path step × the
    measured per-call cost) — the <50 µs/step acceptance bound of the
    disabled-mode contract.  The traced run's file is parsed back
    (`json.load`) to certify the Chrome trace-event stream.

    Round 17 adds the fleet A/B on the SAME row: the identical LSTM
    lane steps with a live fleet push client (reporter thread POSTing
    one frame per interval to an in-process aggregator) vs without —
    `fleet_overhead_us_per_step` is the wall-clock tax the push plane
    steals from the step loop (the client itself runs off-thread; the
    bound is GIL/scheduler steal), with the work-based upper bound
    `fleet_push_cpu_us_per_step` (measured push duration × pushes /
    steps) stamped alongside.  Both the disabled-trace and the
    enabled-fleet taxes gate `passed` at 50 µs/step."""
    import json as _json
    import os as _os
    import tempfile

    from paddle_tpu.core.device import build_mesh, set_mesh
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import lstm_text_classifier
    from paddle_tpu.observe import trace

    # deliberately small: the A/B resolves a per-step tax of tens of µs,
    # so the step itself must be a few ms, not hundreds (CPU boxes run
    # the scan tier here; the tax being measured is host-side anyway)
    B, T, H, V, E = 16, 16, 64, 500, 32
    devices = jax.devices()
    mesh = build_mesh({"data": 1}, devices[:1])
    set_mesh(mesh)
    cfg = lstm_text_classifier(vocab_size=V, embed_dim=E, hidden_size=H,
                               lstm_num=2, num_classes=2)
    trainer = _mk_trainer(cfg, mesh=mesh)
    rng = np.random.RandomState(0)
    feed = {"data": SequenceBatch(
                jax.numpy.asarray(rng.randint(0, V, (B, T)).astype(np.int32)),
                jax.numpy.asarray(
                    rng.randint(T // 2, T + 1, (B,)).astype(np.int32))),
            "label": jax.numpy.asarray(
                rng.randint(0, 2, (B,)).astype(np.int32))}

    def measure_ms(steps=60, warmup=8):
        for _ in range(warmup):
            float(trainer.train_one_batch(feed))
        t0 = time.perf_counter()
        for _ in range(steps):
            float(trainer.train_one_batch(feed))   # float() = fence
        return (time.perf_counter() - t0) / steps * 1e3

    trace_path = _os.path.join(tempfile.mkdtemp(prefix="ptpu-bench-obs-"),
                               "trace.json")
    # interleave attempts so drift (thermal, competing load) hits both
    # modes equally; per-mode median is the row value
    off_ms, on_ms = [], []
    for _ in range(5):
        trace.disable()
        off_ms.append(measure_ms())
        trace.enable(jsonl_path=trace_path,
                     ring_size=FLAGS.get("trace_ring_size"))
        on_ms.append(measure_ms())
    trace.disable()
    with open(trace_path) as f:
        events = _json.load(f)
    overhead_us = (float(np.median(on_ms)) - float(np.median(off_ms))) \
        * 1e3

    # disabled-mode contract: measure the no-op span() directly and
    # scale by one step's span count (train_step, feed, step_dispatch,
    # input_wait + one spare for pipeline/fence variants)
    spans_per_step = 5
    n_calls = 20000
    t0 = time.perf_counter()
    for _ in range(n_calls):
        with trace.span("bench_noop"):
            pass
    disabled_us = (time.perf_counter() - t0) / n_calls * 1e6 \
        * spans_per_step

    # ---- fleet push A/B (round 17): same lane, push client on vs off.
    # The client runs on the reporter thread, so the per-step tax is
    # scheduler/GIL steal, not step-path work; interleaved like the
    # trace A/B so drift hits both modes equally.  The bench CRANKS
    # the push interval (0.1 s vs the 10 s production default) so the
    # tax is resolvable at all — overhead scales linearly with push
    # frequency (cost-per-push × step-time ÷ interval), so the
    # headline `fleet_overhead_us_per_step` is the raw A/B scaled back
    # to the default interval; the raw cranked-interval number and the
    # work-based bound (all push wall time ÷ steps) ride along.
    from paddle_tpu.observe.fleet import FleetAggregator

    FLEET_BENCH_INTERVAL_S = 0.1
    default_interval_s = 10.0    # utils/flags.py metrics_interval_s
    agg = FleetAggregator(0).start()
    fleet_off_ms, fleet_on_ms = [], []
    push_hist = observe.REGISTRY.histogram("fleet_push_seconds")
    try:
        for _ in range(5):
            # BOTH modes run with a live reporter sink (devnull JSONL)
            # so observe.active() — and with it the trainer's
            # metrics-sink step fence — is symmetric; the delta is
            # push-client cost alone, the same discipline as the
            # traced-vs-untraced A/B above
            for on, acc in ((False, fleet_off_ms),
                            (True, fleet_on_ms)):
                rep = observe.MetricsReporter(
                    path=_os.devnull,
                    interval_s=FLEET_BENCH_INTERVAL_S,
                    fleet_addr=agg.addr if on else None)
                rep.start()
                acc.append(measure_ms())
                rep.stop()
        topo = agg.state.topology()
        fleet_frames = sum(p["frames"] for p in topo["procs"].values())
        fleet_rollup = agg.state.rollup()["status"]
    finally:
        agg.stop()
    fleet_ab_us = (float(np.median(fleet_on_ms))
                   - float(np.median(fleet_off_ms))) * 1e3
    fleet_overhead_us = fleet_ab_us \
        * (FLEET_BENCH_INTERVAL_S / default_interval_s)
    # work-based upper bound: ALL push wall time (build + POST, off-
    # thread) charged to the enabled windows' steps (60 × 5 attempts)
    fleet_push_cpu_us = push_hist.sum() / (60 * 5) * 1e6

    return _finish(_with_band({
        "metric": "observe_trace_overhead_us_per_step",
        "value": round(overhead_us, 1),
        "unit": ("traced − untraced per-step wall time, µs (LSTM "
                 f"bs={B} hidden={H} T={T}, fenced both modes)"),
        "trace_overhead_us_per_step": round(overhead_us, 1),
        "trace_disabled_us_per_step": round(disabled_us, 2),
        "disabled_target_us": 50.0,
        "fleet_overhead_us_per_step": round(fleet_overhead_us, 2),
        "fleet_ab_us_per_step_cranked": round(fleet_ab_us, 1),
        "fleet_push_interval_s": FLEET_BENCH_INTERVAL_S,
        "fleet_default_interval_s": default_interval_s,
        "fleet_push_cpu_us_per_step": round(fleet_push_cpu_us, 2),
        "fleet_target_us": 50.0,
        "fleet_frames": fleet_frames,
        "fleet_rollup": fleet_rollup,
        "passed": disabled_us < 50.0
        and abs(fleet_overhead_us) < 50.0,
        "ms_untraced": [round(v, 3) for v in off_ms],
        "ms_traced": [round(v, 3) for v in on_ms],
        "ms_fleet_off": [round(v, 3) for v in fleet_off_ms],
        "ms_fleet_on": [round(v, 3) for v in fleet_on_ms],
        "trace_events": len(events),
        "trace_file_valid": all(
            k in e for e in events
            for k in ("ph", "ts", "dur", "pid", "tid", "name")),
        "devices": _n_chips(trainer),
        # per-mode attempt lists above carry the variability; the
        # signed per-attempt deltas would make the band's relative
        # spread meaningless, so the band is the median alone
    }), "observe", trainer, feed,
        step_ms=float(np.median(off_ms)))


def _precision_stamp():
    """Active precision policy + resolved per-op dispatch dtypes,
    stamped on EVERY emitted JSON line (the round-8 `path`-field
    pattern): artifacts are self-describing across fp32/bf16 A/Bs."""
    from paddle_tpu.core.dtypes import dispatch_dtypes

    return dispatch_dtypes()


def _workload_metrics(before):
    """Per-workload telemetry merged onto the emitted JSON line: counter
    DELTAS across the workload (dispatch-tier decisions, recompiles,
    reconnects — which code path produced this number, not just the
    timing) plus current gauges (fused-pair census, input-bound ratio,
    fenced samples/sec when a sink is attached)."""
    now = observe.REGISTRY.flat(kinds=("counter",))
    out = {k: round(v - before.get(k, 0.0), 6)
           for k, v in now.items() if v != before.get(k, 0.0)}
    out.update({k: round(v, 6)
                for k, v in observe.REGISTRY.flat(kinds=("gauge",)).items()})
    return out


def _read_jsonl_lines(path):
    out = []
    with open(path) as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                d = json.loads(raw)
            except ValueError:
                continue                # log noise between rows
            if isinstance(d, dict) and ("metric" in d or "error" in d):
                out.append(d)
    return out


def _run_gate(lines, args):
    """``--check`` / ``--check_report_only``: judge this run's lines
    against ``--baseline`` and return the process exit code.  The human
    diff table goes to stderr — stdout stays the machine-parsed JSONL
    stream (the driver reads the FIRST line)."""
    baseline = benchgate.load_baseline(args.baseline)
    res = benchgate.compare(lines, baseline)
    for row in res.regressions:
        observe.counter(
            "bench_regressions_total",
            "bench series that tripped the perf-regression gate "
            "(--check vs the committed baseline)").inc(
            series=row["series"])
    print(benchgate.render_table(res, args.baseline), file=sys.stderr,
          flush=True)
    if res.ok or args.check_report_only:
        return 0
    return 2


def main(argv=None):
    # persistent compile cache: JAX_COMPILATION_CACHE_DIR when the
    # environment sets it, else <checkout>/.jax_cache
    from paddle_tpu.core.device import ensure_compile_cache
    ensure_compile_cache()

    lanes = ["lstm", "resnet", "seq2seq", "attention", "lstm1280",
             "lstm2048", "pipeline", "precision", "observe", "serving",
             "multichip", "sparse", "rollout"]
    ap = argparse.ArgumentParser()
    ap.add_argument("--only",
                    help="run a subset of lanes (comma-separated): "
                         + ",".join(lanes))
    ap.add_argument("--pipeline_small", action="store_true",
                    help="run the input-pipeline A/B lane at CPU-"
                         "runnable shapes (the JSON line records "
                         "scale='small'); default is bench scale")
    ap.add_argument("--precision_small", action="store_true",
                    help="run the fp32/bf16 precision A/B lane at CPU-"
                         "runnable shapes (the JSON line records "
                         "scale='small'); default is bench scale")
    ap.add_argument("--attention_small", action="store_true",
                    help="run the attention A/B lane (dense/legacy/"
                         "block-skip, padded/packed, paged decode) at "
                         "CPU-runnable shapes (T=512; the JSON line "
                         "records scale='small'); default is the bench "
                         "T=2048 scale, where the dense mode is "
                         "skipped ([T,T] scores do not fit)")
    ap.add_argument("--serving_small", action="store_true",
                    help="run the serving continuous-vs-sequential A/B "
                         "lane with a CPU-sized decoder (the JSON line "
                         "records scale='small'); default is bench "
                         "scale")
    ap.add_argument("--rollout_small", action="store_true",
                    help="run the hot-swap rollout lane (steady vs "
                         "swap-in-window req/s + TTFT p99) with a CPU-"
                         "sized decoder (the JSON line records "
                         "scale='small'); default is bench scale")
    ap.add_argument("--multichip_small", action="store_true",
                    help="run the FSDP weak/strong scaling lane at CPU-"
                         "runnable transformer shapes over the virtual-"
                         "device mesh (the JSON line records "
                         "scale='small'); default is bench scale")
    ap.add_argument("--sparse_small", action="store_true",
                    help="run the sparse embedding lane (lookup "
                         "throughput vs table size + the dense-vs-"
                         "sparse-exchange train A/B) at CPU-runnable "
                         "shapes — 10\u2076-row train table (the JSON "
                         "line records scale='small'); default is the "
                         "bench 10\u2077 scale")
    ap.add_argument("--profile", action="store_true",
                    help="dump a jax.profiler trace of a few production "
                         "train steps per workload (see --profile_dir); "
                         "the trace path lands on the workload's JSON "
                         "line as trace_dir")
    ap.add_argument("--profile_dir", default="./profiles",
                    help="root directory for --profile trace dumps")
    # ---- perf-regression gate (observe/benchgate.py)
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="baseline document for --check / context for "
                         "--write-baseline (benchmark/baselines/*.json)")
    ap.add_argument("--check", action="store_true",
                    help="after the run (or --from_jsonl replay), "
                         "compare every series against --baseline: "
                         "human diff table on stderr, "
                         "bench_regressions_total per tripped series, "
                         "exit 2 on regression")
    ap.add_argument("--check_report_only", action="store_true",
                    help="with --check: print the diff table but "
                         "always exit 0 (CI report mode)")
    ap.add_argument("--write-baseline", "--write_baseline",
                    dest="write_baseline", default=None, metavar="FILE",
                    help="write this run's lines as a baseline "
                         "document (median ± spread-derived tolerance "
                         "per series) for future --check runs")
    ap.add_argument("--from_jsonl", default=None, metavar="FILE",
                    help="replay previously-emitted bench JSON lines "
                         "instead of executing workloads — re-gate an "
                         "old run's lines without a multi-minute run")
    # ---- attribution diff (observe/costmodel.py): machine-checked
    # before/after roofline attribution for kernel PRs
    ap.add_argument("--attribution_diff", nargs=2, default=None,
                    metavar=("OLD", "NEW"),
                    help="diff two --roofline_dump reports per region "
                         "(FLOPs, HBM bytes, roofline verdict, MFU, "
                         "bwd_frac; add/remove/rename detection): "
                         "machine-readable JSON delta on stdout, human "
                         "table on stderr; with --check, exit 2 when "
                         "any region's bytes or time estimate "
                         "regressed beyond --attribution_tolerance")
    ap.add_argument("--attribution_tolerance", type=float, default=0.05,
                    help="fractional growth in a region's HBM bytes or "
                         "time estimate (or the step totals) that "
                         "counts as a regression for --attribution_diff "
                         "--check (default 0.05)")
    # framework flags ride the same CLI (e.g. --fused_rnn_hblock=false
    # for an A/B of the blocked RNN tier against the scan path, or
    # --metrics_jsonl/--log_level for the telemetry satellites)
    args = ap.parse_args(FLAGS.parse_argv(
        sys.argv[1:] if argv is None else list(argv)))
    if FLAGS.get("log_level"):
        from paddle_tpu.utils import set_log_level
        set_log_level(FLAGS.get("log_level"))
    # a bench run pushing to a fleet aggregator registers as its own
    # role — a bench box must never impersonate a trainer in the rollup
    observe.fleet.set_identity(role="bench")
    observe.start_from_flags()
    if args.profile:
        global PROFILE_DIR
        PROFILE_DIR = args.profile_dir
    if args.pipeline_small:
        global PIPELINE_SMALL
        PIPELINE_SMALL = True
    if args.precision_small:
        global PRECISION_SMALL
        PRECISION_SMALL = True
    if args.attention_small:
        global ATTENTION_SMALL
        ATTENTION_SMALL = True
    if args.serving_small:
        global SERVING_SMALL
        SERVING_SMALL = True
    if args.rollout_small:
        global ROLLOUT_SMALL
        ROLLOUT_SMALL = True
    if args.multichip_small:
        global MULTICHIP_SMALL
        MULTICHIP_SMALL = True
    if args.sparse_small:
        global SPARSE_SMALL
        SPARSE_SMALL = True
    if args.attribution_diff:
        # pure-host replay of two committed dumps: no workload runs, no
        # backend touched — the kernel-PR verification loop stays fast
        old = costmodel.load_report(args.attribution_diff[0])
        new = costmodel.load_report(args.attribution_diff[1])
        diff = costmodel.attribution_diff(
            old, new, tolerance=args.attribution_tolerance)
        print(json.dumps(diff), flush=True)
        print(costmodel.render_diff_table(diff), file=sys.stderr,
              flush=True)
        if (args.check and not args.check_report_only
                and not diff["ok"]):
            return 2
        return 0
    if (args.check or args.check_report_only) and not args.baseline:
        ap.error("--check requires --baseline FILE")

    lines = []
    failed_lanes = []
    if args.from_jsonl:
        lines = _read_jsonl_lines(args.from_jsonl)
    else:
        benches = {"lstm": bench_lstm, "resnet": bench_resnet,
                   "seq2seq": bench_seq2seq,
                   "attention": bench_attention,
                   "lstm1280": bench_lstm_1280,
                   "lstm2048": bench_lstm_2048,
                   "pipeline": bench_pipeline,
                   "precision": bench_precision,
                   "observe": bench_observe,
                   "serving": bench_serving,
                   "multichip": bench_multichip,
                   "sparse": bench_sparse,
                   "rollout": bench_rollout}
        order = [t.strip() for t in args.only.split(",") if t.strip()] \
            if args.only else lanes
        unknown = [t for t in order if t not in benches]
        if unknown:
            ap.error(f"unknown lane(s) {unknown}; choose from {lanes}")
        for name in order:
            try:
                before = observe.REGISTRY.flat(kinds=("counter",))
                r = benches[name]()
                r["precision_policy"] = _precision_stamp()
                r["metrics"] = _workload_metrics(before)
            except Exception as e:      # noqa: BLE001 — report, don't die
                if name == order[0] and not (args.check
                                             or args.write_baseline):
                    raise               # the parsed line must be honest
                r = {"metric": name, "error": str(e)}
                failed_lanes.append(name)
            print(json.dumps(r), flush=True)
            lines.append(r)

    if args.write_baseline:
        doc = benchgate.write_baseline(
            args.write_baseline, lines,
            meta={"scale": ("small" if PIPELINE_SMALL
                            or PRECISION_SMALL
                            or ATTENTION_SMALL
                            or SERVING_SMALL
                            or MULTICHIP_SMALL
                            or SPARSE_SMALL
                            or ROLLOUT_SMALL else "bench"),
                  "argv": sys.argv[1:] if argv is None else list(argv)})
        print(f"wrote baseline {args.write_baseline} "
              f"({len(doc['series'])} series)", file=sys.stderr,
              flush=True)
    rc = _run_gate(lines, args) \
        if args.check or args.check_report_only else 0
    if failed_lanes:
        # every other lane's line is already printed; a lane that raised
        # must still fail the run
        print(f"bench: lane(s) raised: {failed_lanes}", file=sys.stderr,
              flush=True)
        return rc or 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
