"""Unified telemetry layer: registry semantics, export schema, and the
instrumented-path contracts (dispatch tiers, trainer step split).

The registry tests are pure stdlib; the dispatch/trainer tests drive
the real ops/trainer on the CPU harness and pin the counters against
the same predicates the dispatch uses — the counter must record what
actually ran, not what a doc comment claims.
"""

import json
import math
import threading

import numpy as np
import pytest

from paddle_tpu import observe
from paddle_tpu.observe import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    MetricsReporter,
    REGISTRY,
)
from paddle_tpu.utils.logger import reset_warn_once, warn_once
from paddle_tpu.utils.stat import StatSet


# ------------------------------------------------------------- registry
def test_counter_monotonic_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2.5)
    c.inc(3, kind="a")
    assert c.value() == 3.5
    assert c.value(kind="a") == 3
    assert c.total() == 6.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.total() == 6.5   # the rejected inc left no trace


def test_registry_get_or_create_and_type_collision():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("q_depth")
    g.set(5)
    g.inc(2)
    g.dec()
    assert g.value() == 6
    g.set(0.25, shard="0")
    assert g.value(shard="0") == 0.25


def test_histogram_bucket_boundaries():
    """Prometheus ``le`` convention: a bucket counts values <= its upper
    bound; +Inf catches the overflow."""
    h = MetricsRegistry().histogram("lat", buckets=(0.1, 0.5, 1.0))
    for v in (0.05, 0.1, 0.100001, 0.5, 0.9, 7.0):
        h.observe(v)
    assert h.cumulative_buckets() == [
        (0.1, 2),          # 0.05, 0.1 (boundary is inclusive)
        (0.5, 4),          # + 0.100001, 0.5
        (1.0, 5),          # + 0.9
        (math.inf, 6),     # + 7.0
    ]
    assert h.count() == 6
    assert h.sum() == pytest.approx(0.05 + 0.1 + 0.100001 + 0.5 + 0.9 + 7.0)


def test_histogram_time_context():
    h = MetricsRegistry().histogram("t", buckets=(10.0,))
    with h.time():
        pass
    assert h.count() == 1 and 0 <= h.sum() < 10


def test_histogram_quantile_interpolation():
    """Prometheus ``histogram_quantile`` semantics: linear interpolation
    inside the bucket the rank lands in."""
    h = MetricsRegistry().histogram("lat", buckets=(1.0, 2.0))
    for _ in range(50):
        h.observe(0.5)
    for _ in range(50):
        h.observe(1.5)
    assert h.quantile(0.5) == pytest.approx(1.0)   # rank 50 = bucket edge
    assert h.quantile(0.75) == pytest.approx(1.5)  # halfway into (1, 2]
    assert h.quantile(1.0) == pytest.approx(2.0)
    assert h.quantiles() == {
        "p50": pytest.approx(1.0),
        "p95": pytest.approx(1.9),
        "p99": pytest.approx(1.98),
    }


def test_histogram_quantile_overflow_clamps_to_last_finite_bound():
    """The +Inf bucket has no width to interpolate over — ranks landing
    there clamp to the last finite bound instead of reporting inf."""
    h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0))
    for v in (0.05, 0.2, 0.9, 5.0, 7.0, 9.0):
        h.observe(v)
    assert h.quantile(0.5) == pytest.approx(1.0)
    assert h.quantile(0.99) == 1.0          # 3 of 6 live past the bound
    assert math.isfinite(h.quantiles()["p99"])


def test_histogram_quantile_empty_and_labeled_series():
    h = MetricsRegistry().histogram("lat", buckets=(1.0,))
    assert h.quantile(0.5) is None and h.quantiles() == {}
    h.observe(0.25, op="read")
    assert h.quantile(0.5, op="read") == pytest.approx(0.5)
    assert h.quantile(0.5) is None           # unlabeled series untouched


def test_quantiles_exported_on_samples_and_prometheus_dump():
    """Satellite: p50/p95/p99 ride every histogram export — the JSONL
    samples and the ``/metrics`` Prometheus text (``<name>_q`` gauge
    family with a summary-style ``quantile`` label)."""
    reg = MetricsRegistry()
    h = reg.histogram("step_seconds", buckets=(0.1, 1.0))
    for _ in range(100):
        h.observe(0.05)
    s = h.samples()[0]
    assert set(s["quantiles"]) == {"p50", "p95", "p99"}
    assert s["quantiles"]["p50"] == pytest.approx(0.05)
    txt = reg.prometheus_text()
    assert "# TYPE step_seconds_q gauge" in txt
    assert 'step_seconds_q{quantile="0.50"}' in txt
    assert 'step_seconds_q{quantile="0.95"}' in txt
    assert 'step_seconds_q{quantile="0.99"}' in txt


def test_concurrent_increments_from_threads():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("hh", buckets=(0.5, 1.0))

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == 8000
    assert h.count() == 8000
    assert h.cumulative_buckets()[0] == (0.5, 8000)


# --------------------------------------------------------------- export
def test_jsonl_schema_round_trip(tmp_path):
    """One flush = one self-describing line: every metric type plus the
    StatSet timer table survive a json round trip with values intact."""
    reg = MetricsRegistry()
    reg.counter("c", "help c").inc(3, kind="x")
    reg.gauge("g").set(0.5)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    stat = StatSet("test")
    with stat.timer("unit"):
        pass
    path = str(tmp_path / "m.jsonl")
    rep = MetricsReporter(path, registry=reg, stat=stat)
    rep.flush()
    rep.flush()

    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["seq"] for ln in lines] == [0, 1]
    assert all("ts" in ln for ln in lines)
    by_name = {m["name"]: m for m in lines[0]["metrics"]}
    assert by_name["c"]["type"] == "counter"
    assert by_name["c"]["help"] == "help c"
    assert by_name["c"]["samples"] == [
        {"labels": {"kind": "x"}, "value": 3}]
    assert by_name["g"]["samples"][0]["value"] == 0.5
    hs = by_name["h"]["samples"][0]
    assert hs["count"] == 1 and hs["sum"] == 1.5
    assert hs["buckets"] == [[1.0, 0], [2.0, 1], ["+Inf", 1]]
    timers = {t["name"]: t for t in lines[0]["timers"]}
    assert timers["unit"]["count"] == 1
    assert timers["unit"]["min"] <= timers["unit"]["max"]
    assert timers["unit"]["avg"] == pytest.approx(
        timers["unit"]["total"] / timers["unit"]["count"])


def test_prometheus_text_dump():
    reg = MetricsRegistry()
    reg.counter("c_total", "a counter").inc(2, op="x")
    reg.histogram("lat_seconds", buckets=(0.1,)).observe(0.05)
    stat = StatSet()
    with stat.timer("fwd"):
        pass
    txt = MetricsReporter(registry=reg, stat=stat).prometheus_text()
    assert "# HELP c_total a counter" in txt
    assert "# TYPE c_total counter" in txt
    assert 'c_total{op="x"} 2' in txt
    assert 'lat_seconds_bucket{le="0.1"} 1' in txt
    assert 'lat_seconds_bucket{le="+Inf"} 1' in txt
    assert "lat_seconds_count 1" in txt
    assert "# TYPE paddle_tpu_timer_seconds summary" in txt
    assert 'paddle_tpu_timer_seconds_count{name="fwd"} 1' in txt


def test_reporter_attach_active_and_stop(tmp_path):
    path = str(tmp_path / "sink.jsonl")
    assert observe.active() is False
    observe.attach(path, interval_s=999)
    try:
        assert observe.active() is True
        observe.counter("attached_c").inc()
    finally:
        observe.stop_global()
    assert observe.active() is False
    lines = [json.loads(ln) for ln in open(path)]  # stop() final-flushes
    assert any(m["name"] == "attached_c"
               for ln in lines for m in ln["metrics"])


def test_flat_compact_form():
    reg = MetricsRegistry()
    reg.counter("a").inc(2, k="v")
    reg.gauge("b").set(1.5)
    reg.histogram("h").observe(1)   # histograms excluded from flat()
    assert reg.flat() == {'a{k="v"}': 2, "b": 1.5}


# ------------------------------------------------------------ warn_once
def test_warn_once_logs_once_per_key():
    reset_warn_once()
    hits = []
    import logging

    class Grab(logging.Handler):
        def emit(self, record):
            hits.append(record.getMessage())

    h = Grab()
    logging.getLogger("paddle_tpu").addHandler(h)
    try:
        assert warn_once("k1", "message %d", 1) is True
        assert warn_once("k1", "message %d", 2) is False
        assert warn_once("k2", "other") is True
    finally:
        logging.getLogger("paddle_tpu").removeHandler(h)
    assert hits == ["message 1", "other"]
    reset_warn_once()
    assert warn_once("k1", "message %d", 3) is True


def test_stat_min_column_printed():
    stat = StatSet("s")
    with stat.timer("op"):
        pass
    out = []
    stat.print_all_status(log=out.append)
    assert "min(ms)" in out[1]
    # one row per item, all five stat columns present
    assert len(out) == 3 and len(out[2].split()) == 6


# ----------------------------------------------- dispatch-tier counters
def _lstm_once(b, h, t=3, **kw):
    import jax.numpy as jnp

    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.ops.recurrent_ops import lstm_sequence

    rng = np.random.RandomState(0)
    seq = SequenceBatch(
        jnp.asarray(rng.randn(b, t, 4 * h).astype(np.float32)),
        jnp.asarray(np.full((b,), t, np.int32)))
    w_hh = jnp.asarray(rng.randn(h, 4 * h).astype(np.float32) * 0.01)
    return lstm_sequence(seq, None, w_hh, **kw)


@pytest.mark.parametrize("b,h", [(8, 128), (8, 100)])
def test_rnn_dispatch_counter_matches_tier_predicate(b, h):
    """The ``rnn_dispatch_total`` path label must agree with the SAME
    predicate the dispatch lowers through (``pallas_lstm.fused_tier``)
    — (8,128) resolves fused, (8,100) is off lane tiling → scan."""
    from paddle_tpu.ops import pallas_lstm

    expect = pallas_lstm.fused_tier(b, h) or "scan"
    c = REGISTRY.counter("rnn_dispatch_total")
    before = sum(s["value"] for s in c.samples()
                 if s["labels"].get("kind") == "lstm")
    _lstm_once(b, h)
    after = [s for s in c.samples() if s["labels"].get("kind") == "lstm"]
    assert sum(s["value"] for s in after) == before + 1
    hit = [s for s in after if s["labels"]["path"] == expect]
    assert hit, f"no sample for expected path {expect!r}: {after}"
    if expect == "scan":
        assert "128" in hit[0]["labels"]["reason"]   # lane-tiling reason


def test_rnn_dispatch_counter_nondefault_activation_reason():
    _lstm_once(8, 128, gate_act="sigmoid", cell_act="relu",
               out_act="tanh")
    c = REGISTRY.counter("rnn_dispatch_total")
    assert c.value(kind="lstm", path="scan",
                   reason="non-default activations") == 1


# ------------------------------------------------- trainer instrumentation
def _tiny_trainer(seed=0):
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.config.model_config import OptimizationConfig
    from paddle_tpu.data.feeder import DataFeeder, dense_vector, \
        integer_value
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.trainer.trainer import Trainer

    with config_scope():
        x = dsl.data("x", dense_vector(8))
        lab = dsl.data("label", integer_value(2))
        p = dsl.fc(x, size=2, act=dsl.SoftmaxActivation())
        cost = dsl.classification_cost(p, lab)
        cfg = dsl.topology(cost)
    tr = Trainer(NeuralNetwork(cfg), opt_config=OptimizationConfig(
        learning_method="momentum", momentum=0.9, learning_rate=0.05),
        seed=seed)
    feeder = DataFeeder([("x", dense_vector(8)),
                         ("label", integer_value(2))])
    return tr, feeder


def _batch(rng, n=4):
    return [(rng.randn(8).astype(np.float32), int(rng.randint(0, 2)))
            for _ in range(n)]


def test_trainer_step_metrics_with_sink(tmp_path):
    """With a sink attached the step is fenced: the host-feed +
    device-blocked split exists, sums to within tolerance of the
    end-to-end step histogram, and the step/sample counters tick."""
    tr, feeder = _tiny_trainer()
    rng = np.random.RandomState(0)
    # warm up OUTSIDE the measured window so the one-time XLA compile
    # doesn't dominate the step histogram the split is checked against
    tr.train_one_batch(feeder.convert(_batch(rng)))
    assert REGISTRY.counter("jit_recompiles").value() >= 1
    REGISTRY.reset()
    observe.attach(str(tmp_path / "m.jsonl"), interval_s=999)
    try:
        for _ in range(3):
            tr.train_one_batch(feeder.convert(_batch(rng)))
    finally:
        observe.stop_global()
    assert REGISTRY.counter("train_steps").value() == 3
    assert REGISTRY.counter("train_samples").value() == 12
    step = REGISTRY.histogram("train_step_seconds")
    feed = REGISTRY.histogram("train_host_feed_seconds")
    dev = REGISTRY.histogram("train_device_blocked_seconds")
    assert step.count() == feed.count() == dev.count() == 3
    # the split covers the step: parts never exceed the total, and what
    # is left over is the dispatch segment (bounded on warm steps)
    assert feed.sum() + dev.sum() <= step.sum() + 1e-6
    assert REGISTRY.gauge("train_samples_per_sec").value() > 0


def test_trainer_unfenced_without_sink():
    """No sink → no device fencing: the device-blocked histogram stays
    empty (the step would otherwise serialize the dispatch pipeline),
    while the cheap counters still tick."""
    tr, feeder = _tiny_trainer()
    rng = np.random.RandomState(0)
    assert observe.active() is False
    tr.train_one_batch(feeder.convert(_batch(rng)))
    assert REGISTRY.counter("train_steps").value() == 1
    assert REGISTRY.histogram("train_device_blocked_seconds").count() == 0
    assert REGISTRY.histogram("train_step_seconds").count() == 1


def test_train_loop_input_bound_ratio():
    tr, feeder = _tiny_trainer()
    rng = np.random.RandomState(0)

    def reader():
        for _ in range(3):
            yield _batch(rng)

    import paddle_tpu.utils.flags as _f
    saved = _f.FLAGS.get("save_dir")
    _f.FLAGS.set("save_dir", "")      # no checkpoint side effects
    try:
        tr.train(reader, num_passes=1, feeder=feeder)
    finally:
        _f.FLAGS.set("save_dir", saved)
    ratio = REGISTRY.gauge("input_bound_ratio").value()
    assert 0.0 <= ratio <= 1.0
    assert REGISTRY.histogram("data_reader_wait_seconds").count() == 3
    assert REGISTRY.histogram("data_feed_convert_seconds").count() == 3


def test_network_fused_pair_census_resnet():
    """The build-time census gauge must equal the peephole tables — and
    on ResNet-50 those resolve 16 Pallas-3×3 + 16 GEMM-1×1 forward
    pairs (the round-7 resolution; the bwd entries are all evicted
    into fwd chains)."""
    from paddle_tpu.config import dsl
    from paddle_tpu.config.dsl import config_scope
    from paddle_tpu.data.feeder import dense_vector, integer_value
    from paddle_tpu.layers.network import NeuralNetwork
    from paddle_tpu.models.image import resnet

    with config_scope():
        img = dsl.data("image", dense_vector(3 * 224 * 224),
                       height=224, width=224)
        lab = dsl.data("label", integer_value(1000))
        probs = resnet(img, depth=50, num_classes=1000)
        cost = dsl.classification_cost(probs, lab)
        cfg = dsl.topology(cost)
    net = NeuralNetwork(cfg)
    g = REGISTRY.gauge("network_conv_bn_fused_pairs")
    assert g.value(direction="fwd", kernel="3x3") == 16
    assert g.value(direction="fwd", kernel="1x1") == 16
    assert len(net._bn_conv_fuse) == 32
    assert g.value(direction="bwd", kernel="3x3") \
        == len(net._conv_bn_fuse) == 0


# --------------------------------------------- bounded sample reservoir
def test_histogram_reservoir_bounded_over_a_million_observations():
    """Retention is CAPPED: a 10^6-observation series keeps at most
    sample_cap raw samples, and the reservoir quantiles still land
    within tolerance of the true distribution — the long-training-run
    memory contract."""
    h = Histogram("step_seconds", buckets=(0.5, 1.0), sample_cap=1024)
    rng = np.random.RandomState(7)
    # uniform [0, 100): true p50 = 50, p99 = 99 — far past the last
    # finite bucket bound, where bucket interpolation clamps to 1.0
    for v in rng.uniform(0.0, 100.0, size=1_000_000):
        h.observe(float(v))
    assert h.count() == 1_000_000
    assert h.retained_samples() <= 1024
    assert h.sample_quantile(0.5) == pytest.approx(50.0, rel=0.08)
    assert h.sample_quantile(0.99) == pytest.approx(99.0, rel=0.08)
    # the bucket path still clamps (unchanged legacy semantics)
    assert h.quantile(0.99) == 1.0


def test_histogram_reservoir_exact_under_cap():
    h = Histogram("lat", sample_cap=64)
    for v in [1.0, 2.0, 3.0, 4.0, 5.0]:
        h.observe(v)
    assert h.retained_samples() == 5
    assert h.sample_quantile(0.0) == 1.0
    assert h.sample_quantile(0.5) == 3.0
    assert h.sample_quantile(1.0) == 5.0
    assert h.sample_quantile(0.25) == 2.0       # exact order stats


def test_histogram_reservoir_per_label_series_and_disable():
    h = Histogram("lat", sample_cap=8)
    h.observe(1.0, op="read")
    h.observe(9.0, op="write")
    assert h.sample_quantile(0.5, op="read") == 1.0
    assert h.sample_quantile(0.5, op="write") == 9.0
    assert h.sample_quantile(0.5) is None       # unlabeled untouched
    off = Histogram("lat_off", sample_cap=0)
    for v in range(100):
        off.observe(float(v))
    assert off.retained_samples() == 0
    assert off.sample_quantile(0.5) is None     # caller falls back
    assert off.quantile(0.5) is not None        # ...to the bucket path


def test_registry_histogram_passes_sample_cap():
    reg = MetricsRegistry()
    h = reg.histogram("x_seconds", sample_cap=16)
    for v in range(64):
        h.observe(float(v))
    assert h.retained_samples() == 16
    assert reg.histogram("x_seconds") is h      # get-or-create intact
