"""``correct`` of ``sdar_serve_closed_c12`` at its rehearsal sizes: the
sound run reads true and the five faults that generation by diffusion
over blocks invites read false, each planted under the timed path and
judged by ``H.decide`` over the cell's own limits.  The fp8 control is
held to those limits on the chip, at the widths they were set at
(PERF.md §4); at a hidden size of 64 and a vocabulary of 256 it moves
the logits by less than it does there, so here its confidences only
have to lie several times as far from the reference's as the sound
run's do."""

import numpy as np
import pytest

from chipbench import harness as H

MAN = H.manifest()
CELL = "sdar_serve_closed_c12"


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


def assert_incorrect():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    assert not H.decide(run["checks"]), run["checks"]
    return run


def test_sound_run_is_correct_and_the_control_is_not():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    done = [r for r in run["requests"] if r["state"] == "done"]
    # every remainder of a prompt mod 4, budgets that end mid-block, and
    # requests that left the batch while others went on
    assert {len(r["prompt"]) % 4 for r in done} == {0, 1, 2, 3}
    assert len(run["requests"]) > 2 * int(run["mix"]["max_batch"])
    # the longest of four sampled requests reveals 12 or more positions,
    # each other 4 or more
    assert run["numbers"]["served_tokens_compared"] > 20
    assert run["numbers"]["served_blocks_off_schedule"] == 0
    assert H.decide(run["checks"]), run["checks"]
    planted = H.planted(run)
    err = lambda row: row["numbers"]["served_conf_err_mean"]
    assert err(planted["control"]) > max(0.03, 3 * err(run)), planted
    # the reference itself in the stated precision is no fault
    assert planted["stated_precision"]["correct"], \
        planted["stated_precision"]


def test_a_causal_tile_where_the_block_is_bidirectional(monkeypatch):
    """The block step's queries see their block only up to themselves:
    the paged kernel's causal tail in the place of its block mode."""
    from paddle_tpu.serving import model as decoder
    real = decoder.paged_decode_attention
    monkeypatch.setattr(decoder, "paged_decode_attention",
                        lambda *a, block=False, **kw: real(*a, **kw))
    decoder._jitted_block_step.cache_clear()
    try:
        assert_incorrect()
    finally:
        monkeypatch.undo()
        decoder._jitted_block_step.cache_clear()


def test_no_commit_pass(monkeypatch):
    """A block's last denoising step is followed by the next block: the
    K/V its passes wrote while positions were still masked are kept."""
    from paddle_tpu.serving import server
    real = server.InferenceServer._enter_block

    def enter(self, r, at, fresh):
        real(self, r, at, fresh)
        if r.todo and r.todo[-1] == 0:
            r.todo.pop()

    monkeypatch.setattr(server.InferenceServer, "_enter_block", enter)
    assert_incorrect()


def test_sigmoid_where_softmax_routing_is_stated(monkeypatch):
    mod = system()
    real = mod.decoder_config
    monkeypatch.setattr(mod, "decoder_config",
                        lambda sizes: real(sizes)._replace(
                            route_score="sigmoid"))
    assert_incorrect()


def test_all_four_tokens_revealed_in_one_step(monkeypatch):
    from paddle_tpu.serving import server
    monkeypatch.setattr(server, "reveal_schedule",
                        lambda masked, steps: (masked,))
    run = assert_incorrect()
    assert run["numbers"]["served_blocks_off_schedule"] > 0


def test_the_block_read_by_batch_row_after_a_compaction(monkeypatch):
    """A launch fed from the one before takes each row's block from the
    row of the same batch index there, not from the row its request
    had: once rows move, a row reads another request's block.  Rows move
    when a request leaves the batch, which a one-second rehearsal does
    only now and then, so here every planning also turns the active
    rows by one place (the sound program follows a row wherever it
    goes: ``src``)."""
    from paddle_tpu.serving import server
    real_plan = server.InferenceServer._plan_blocks

    def plan(self):
        self._active[:] = self._active[1:] + self._active[:1]
        return real_plan(self)

    monkeypatch.setattr(server.InferenceServer, "_plan_blocks", plan)

    def plant(model, server):
        real = model.launch_block_step

        def launch(*args):
            *head, prev, src = args
            src = np.where(np.asarray(src) >= 0,
                           np.arange(len(src), dtype=np.int32), -1)
            return real(*head, prev, src)
        model.launch_block_step = launch

    with_server(monkeypatch, plant)
    run = assert_incorrect()
    assert run["numbers"]["served_blocks_off_schedule"] > 0


def test_rows_that_move_between_launches_are_followed():
    """The sound program under the same turning of the rows reads
    ``correct``: ``src`` finds each row's block wherever it went."""
    from paddle_tpu.serving import server
    mp = pytest.MonkeyPatch()
    real_plan = server.InferenceServer._plan_blocks

    def plan(self):
        self._active[:] = self._active[1:] + self._active[:1]
        return real_plan(self)

    mp.setattr(server.InferenceServer, "_plan_blocks", plan)
    try:
        run = drive()
    finally:
        mp.undo()
    assert run["failed"] == 0 and H.decide(run["checks"]), run["checks"]
