"""``correct`` of ``lfm2_serve_closed_c12`` at its rehearsal sizes: the
sound run reads true and the three faults that a fixed-size state beside
the pages invites read false, each planted under the timed path and
judged by ``H.decide`` over the cell's own limits.  The fp8 control is
held to those limits on the chip, at the widths they were set at
(PERF.md §4); at a hidden size of 64 and a vocabulary of 256 its logits
move by less than a near-tie, so here it only has to read several times
the sound run's gap."""

import numpy as np

from chipbench import harness as H

MAN = H.manifest()
CELL = "lfm2_serve_closed_c12"


def drive(seed=11):
    cell = H.Cell(MAN, CELL)
    driver = H.load_module("drivers", cell.traffic["driver"])
    ctx = H.context(cell, {"device": {"platform": "cpu"}, "peaks": {}},
                    seed, 1.0, rehearsal=True, chips=1)
    return driver.run(ctx)


def system():
    return H.load_module("systems", H.Cell(MAN, CELL).config_name)


def with_server(monkeypatch, plant):
    """Build the system as the cell does, then let ``plant(model,
    server)`` wrap what it will."""
    mod = system()
    real = mod.build

    def build(sizes, mix, weights):
        model, server = real(sizes, mix, weights)
        plant(model, server)
        return model, server

    monkeypatch.setattr(mod, "build", build)


def test_sound_run_is_correct_and_the_control_is_not():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    # rows moved while they decoded: requests of three lengths and
    # three budgets finish apart, and the longest lies past many pages
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


def test_decode_that_starts_from_a_zeroed_state(monkeypatch):
    """The prefill's state is lost: the first decode steps of every
    request sum over zeros where the prompt's last two z lay."""
    def plant(model, server):
        real = model.launch_prefill

        def launch_prefill(*args):
            out = real(*args)
            state, tables = args[model.n_pools - 1], args[-1]
            state.array = state.array.at[:, np.asarray(tables)[:, 0]].set(0)
            return out
        model.launch_prefill = launch_prefill

    with_server(monkeypatch, plant)
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]


def test_a_state_read_by_batch_row_after_a_compaction(monkeypatch):
    """A store indexed by the row in the batch: when a request ends and
    the rows behind it move up, each reads what the row's former
    occupant left there."""
    def plant(model, server):
        real = model.launch_decode
        before = []

        def launch_decode(*args):
            state = args[model.n_pools - 1]
            places = np.asarray(args[model.n_pools + 1])[:, 0].tolist()
            for row, place in enumerate(places):
                was = before[row] if row < len(before) else 0
                if place and was and was != place and place in before:
                    state.array = state.array.at[:, place].set(
                        state.array[:, was])
            before[:] = places
            return real(*args)
        model.launch_decode = launch_decode

    with_server(monkeypatch, plant)
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]


def test_the_thirds_of_in_proj_in_another_order(monkeypatch):
    """``in_proj``'s result read as C, B, u: the gate goes through the
    convolution and an input gates what comes out."""
    mod = system()
    real = mod.build

    def build(sizes, mix, weights):
        swapped = {}
        for k, v in weights.items():
            if k.endswith(".in_proj"):
                b, c, u = np.split(v, 3, axis=1)
                v = np.concatenate([c, b, u], axis=1)
            swapped[k] = v
        return real(sizes, mix, swapped)

    monkeypatch.setattr(mod, "build", build)
    run = drive()
    assert run["failed"] == 0
    assert not H.decide(run["checks"]), run["checks"]
