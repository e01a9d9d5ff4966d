"""``correct`` of ``trinity_serve_closed_c12`` at its rehearsal sizes:
the sound run reads true; the fp8 control and the two faults that the
configuration's mechanisms invite read false, each planted under the
timed path and judged by ``H.decide`` over the cell's own limits."""

from chipbench import harness as H

MAN = H.manifest()
CELL = "trinity_serve_closed_c12"


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
    # the longest finished request lies past the window and past
    # several pages
    sizes, mix = run["sizes"], run["mix"]
    longest = max(len(r["prompt"]) + len(r["tokens"])
                  for r in run["requests"] if r["state"] == "done")
    assert longest > int(sizes["sliding_window"]) + 3 * int(mix["page_size"])
    assert run["numbers"]["served_tokens_compared"] > 0
    assert H.decide(run["checks"]), run["checks"]
    control = H.planted(run)["control"]
    assert not control["correct"], control


def test_sliding_layers_that_attend_without_the_window(monkeypatch):
    mod = system()
    real = mod.decoder_config
    monkeypatch.setattr(
        mod, "decoder_config",
        lambda sizes: real(sizes)._replace(window=10 ** 6))
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]


def test_the_selection_bias_dropped(monkeypatch):
    mod = system()
    real = mod.build

    def build(sizes, mix, weights):
        return real(sizes, mix, {
            k: 0 * v if k.endswith("router_bias") else v
            for k, v in weights.items()})

    monkeypatch.setattr(mod, "build", build)
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]
