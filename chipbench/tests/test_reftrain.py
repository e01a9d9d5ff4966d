"""The reference's training loop takes the optimizer's steps as a hand
count does, and the measures read what they say."""

import numpy as np
import pytest

from chipbench import reftrain


def _hand(opt, w0, xs):
    """Three steps of loss = mean((w . x)^2) / 2 by hand, in float64."""
    w, slot, first = np.float64(w0), None, None
    for i, x in enumerate(xs, 1):
        g = np.mean((x @ w)[:, None] * x, axis=0)
        if opt.get("clip"):
            g = np.clip(g, -opt["clip"], opt["clip"])
        g = g + opt.get("l2", 0.0) * w
        first = g if first is None else first
        if opt["method"] == "momentum":
            v = opt["momentum"] * (slot if slot is not None else 0.0) \
                - opt["lr"] * g
            w, slot = w + v, v
        else:
            m0, v0 = slot if slot is not None else (0.0, 0.0)
            m = opt["beta1"] * m0 + (1 - opt["beta1"]) * g
            v = opt["beta2"] * v0 + (1 - opt["beta2"]) * g * g
            w = w - opt["lr"] * (m / (1 - opt["beta1"] ** i)) / (
                np.sqrt(v / (1 - opt["beta2"] ** i)) + opt["epsilon"])
            slot = (m, v)
    return np.linalg.norm(first), np.linalg.norm(w - w0)


@pytest.mark.parametrize("opt", [
    {"method": "momentum", "momentum": 0.9, "lr": 0.05, "l2": 0.01},
    {"method": "adam", "lr": 0.01, "beta1": 0.9, "beta2": 0.999,
     "epsilon": 1e-8, "clip": 0.5}])
def test_follow_takes_the_optimizers_steps(opt):
    rng = np.random.default_rng(3)
    w0 = rng.standard_normal(6).astype(np.float32)
    xs = [rng.standard_normal((5, 6)).astype(np.float32) for _ in range(3)]
    loss = lambda p, b, q: 0.5 * ((q(b["x"]) @ q(p["w"])) ** 2).mean()
    got = reftrain.follow(loss, {"w": w0}, [{"x": x} for x in xs], opt)
    g1, change = _hand(opt, w0, xs)
    assert got["grad_norms"]["w"] == pytest.approx(g1, rel=1e-4)
    assert got["change_norms"]["w"] == pytest.approx(change, rel=1e-4)
    assert got["losses"][0] == pytest.approx(
        0.5 * np.mean((xs[0] @ w0) ** 2), rel=1e-4)


def test_measures():
    want = {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-6}
    got = {"a": 1.1, "b": 2.0, "c": 2.0, "tiny": 0.5}
    gaps = reftrain.leaf_gaps(got, want, list(want))
    # against the leaf's own norm or the median leaf's (1.5), whichever
    # is larger: a: .1/1.5, b: 0, c: 2/4, tiny: ~.5/1.5
    assert gaps == pytest.approx(sorted([0.1 / 1.5, 0.0, 0.5, 0.5 / 1.5]),
                                 rel=1e-3)
    same = {"losses": [1.0, 1.0, 1.0], "grad_norms": want,
            "change_norms": want}
    frozen = {"losses": [1.0, 1.0, 1.0],
              "grad_norms": {k: 0.0 for k in want},
              "change_norms": {k: 0.0 for k in want}}
    numbers = reftrain.compare(frozen, same)
    # a state left unchanged reads 1 on the leaves that moved
    assert numbers["grad_norm_gap"] == pytest.approx(1.0)
    assert numbers["change_norm_gap_p50"] == pytest.approx(1.0)
    assert numbers["loss_gap_step1"] == 0.0
    assert reftrain.compare(same, same)["grad_norm_gap_p90"] == 0.0


def test_casts_round_as_named():
    x = np.float32(1.0 + 2.0 ** -5)
    assert float(reftrain.CASTS["fp8"](np.asarray(x))) == 1.0
    assert float(reftrain.CASTS["bf16"](np.asarray(x))) == float(x)
    assert float(reftrain.CASTS["none"](np.asarray(x))) == float(x)
