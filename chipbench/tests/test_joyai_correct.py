"""``correct`` of ``joyai_serve_closed_c12`` at its rehearsal sizes: the
sound run reads true and the two faults that latent attention invites
read false, each planted under the timed path and judged by
``H.decide`` over the cell's own limits.  The fp8 control is held to
those limits on the chip, at the widths they were set at (PERF.md §4);
at a hidden size of 64 and a vocabulary of 256 its logits move by less
than a near-tie, so here it only has to read several times the sound
run's gap."""

import numpy as np

from chipbench import harness as H

MAN = H.manifest()
CELL = "joyai_serve_closed_c12"


def drive(seed=11):
    cell = H.Cell(MAN, CELL)
    driver = H.load_module("drivers", cell.traffic["driver"])
    ctx = H.context(cell, {"device": {"platform": "cpu"}, "peaks": {}},
                    seed, 1.0, rehearsal=True, chips=1)
    return driver.run(ctx)


def system():
    return H.load_module("systems", H.Cell(MAN, CELL).config_name)


def test_sound_run_is_correct_and_the_control_is_not():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    # the longest finished request lies past several pages, and most of
    # what is compared came from absorbed decode steps over them
    longest = max(len(r["prompt"]) + len(r["tokens"])
                  for r in run["requests"] if r["state"] == "done")
    assert longest > 8 * int(run["mix"]["page_size"])
    assert run["numbers"]["served_tokens_compared"] > 40
    assert H.decide(run["checks"]), run["checks"]
    planted = H.planted(run)
    mean = lambda row: row["numbers"]["served_logit_gap_mean"]
    assert mean(planted["control"]) > max(
        0.03, 3 * run["numbers"]["served_logit_gap_mean"]), planted
    # the reference itself in the stated precision is no fault
    assert planted["stated_precision"]["correct"], \
        planted["stated_precision"]


def test_half_split_rotation_where_interleaved_is_stated(monkeypatch):
    mod = system()
    real = mod.decoder_config
    monkeypatch.setattr(
        mod, "decoder_config",
        lambda sizes: real(sizes)._replace(rope_interleave=False))
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]


def test_the_latent_norm_dropped(monkeypatch):
    """The key/value latent cached and attended without its norm's gain
    (set to one: the latent norms' gains are drawn 1 + N(0, 0.3²))."""
    mod = system()
    real = mod.build

    def build(sizes, mix, weights):
        return real(sizes, mix, {
            k: np.ones_like(v) if k.endswith("kv_a_norm") else v
            for k, v in weights.items()})

    monkeypatch.setattr(mod, "build", build)
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]
