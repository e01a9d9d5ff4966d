"""``correct`` comes out false when it should: the control (the
reference computed one precision below the configuration's, put in the
program's place) and each fault a cell can have, planted under the
timed path.  The harness's look for a chip is skipped (``rehearsal``);
the rest of a run is driven as it is on the chip, at a size a test run
can hold, and every verdict is the harness's own: ``H.decide`` over the
cell's own limits, the ones a run on the chip is held to."""

import pytest

from chipbench import harness as H

MAN = H.manifest()


def drive(workload, seed=11):
    cell = H.Cell(MAN, workload)
    driver = H.load_module("drivers", cell.traffic["driver"])
    ctx = H.context(cell, {"device": {"platform": "cpu"}, "peaks": {}},
                    seed, 1.0, rehearsal=True, chips=1)
    return driver.run(ctx)


# ------------------------------------------------------------------ serving
def test_serve_sound_run_is_correct_and_the_control_is_not():
    run = drive("serve_closed_c12")
    assert run["failed"] == 0 and run["attempted"] > 0
    assert run["numbers"]["served_tokens_compared"] > 0
    assert H.decide(run["checks"]), run["checks"]
    control = H.planted(run)["control"]
    assert not control["correct"], control


def test_serve_token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.serving.server import InferenceServer

    real = InferenceServer._emit_token
    count = [0]

    def emit(self, r, token):
        count[0] += 1
        if count[0] % 5 == 0:
            token = (token + 17) % self.model.cfg.vocab
        return real(self, r, token)

    monkeypatch.setattr(InferenceServer, "_emit_token", emit)
    assert not H.decide(drive("serve_closed_c12")["checks"])


# ----------------------------------------------------------------- training
def test_train_sound_run_is_correct_control_and_half_batch_are_not():
    run = drive("resnet50_train_b128")
    assert H.decide(run["checks"]), run["checks"]
    # each has to fail one of the cell's numbers, not each of them
    for name, got in H.planted(run).items():
        assert not got["correct"], (name, got["compared"])


def test_train_step_that_returns_its_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.trainer.trainer import Trainer

    real = Trainer.train_one_batch

    def frozen(self, feed, placed=False):
        keep = jax.tree_util.tree_map(jnp.copy,
                                      (self.params, self.opt_state))
        loss = real(self, feed, placed)
        self.params, self.opt_state = keep
        return loss

    monkeypatch.setattr(Trainer, "train_one_batch", frozen)
    run = drive("resnet50_train_b128")
    n = run["numbers"]
    # nothing moved: the gaps of both norms read 1 by their measure
    assert n["grad_norm_gap"] == pytest.approx(1.0)
    assert n["change_norm_gap"] == pytest.approx(1.0)
    assert not H.decide(run["checks"])


def test_train_half_of_the_batch_left_out(monkeypatch):
    from paddle_tpu.trainer.trainer import Trainer

    real = Trainer.train_one_batch

    def halved(self, feed, placed=False):
        n = len(next(iter(feed.values())))
        return real(self, {k: v[:n // 2] for k, v in feed.items()}, placed)

    monkeypatch.setattr(Trainer, "train_one_batch", halved)
    assert not H.decide(drive("resnet50_train_b128")["checks"])
