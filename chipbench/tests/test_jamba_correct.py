"""``correct`` of ``jamba_serve_closed_c12`` at its rehearsal sizes: the
sound run reads true and the five faults that a scanned state in slots
of its own invites read false, each planted under the timed path and
judged by ``H.decide`` over the cell's own limits.  The fp8 control is
held to those limits on the chip, at the widths they were set at
(PERF.md §4); at a hidden size of 64 and a vocabulary of 256 its logits
move by less than a near-tie, so here it only has to read several times
the sound run's gap."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness as H

MAN = H.manifest()
CELL = "jamba_serve_closed_c12"


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


@pytest.fixture
def program(monkeypatch):
    """Replace one function of the served decoder for a run: the steps
    are traced anew with it, and again without it after the test."""
    from paddle_tpu.serving import model as decoder

    def plant(name, fn):
        monkeypatch.setattr(decoder, name, fn)
        decoder._jitted_steps.cache_clear()
    yield decoder, plant
    monkeypatch.undo()
    decoder._jitted_steps.cache_clear()


def assert_incorrect():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    assert not H.decide(run["checks"]), run["checks"]


def test_sound_run_is_correct_and_the_control_is_not():
    run = drive()
    assert run["failed"] == 0 and run["attempted"] > 0
    # rows moved while they decoded: requests of three lengths and three
    # budgets finish apart, and every slot was taken more than once
    longest = max(len(r["prompt"]) + len(r["tokens"])
                  for r in run["requests"] if r["state"] == "done")
    assert longest > 8 * int(run["mix"]["page_size"])
    assert len(run["requests"]) > 2 * int(run["mix"]["max_batch"])
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
    """The prefill's scan state is lost: every request decodes as if its
    prompt had been three tokens long (the convolution's window kept)."""
    def plant(model, server):
        real = model.launch_prefill

        def launch_prefill(*args, slots):
            out = real(*args, slots=slots)
            h = args[model.n_pools - 1]
            h.array = h.array.at[:, np.asarray(slots)].set(0)
            return out
        model.launch_prefill = launch_prefill

    with_server(monkeypatch, plant)
    assert_incorrect()


def test_a_state_read_by_batch_row_instead_of_its_slot(monkeypatch):
    """A step handed each row's batch index for its slot: after the
    first compaction a row reads what another request left, or what the
    prefill of no request wrote."""
    def plant(model, server):
        real = model.launch_decode

        def launch_decode(*args, slots):
            return real(*args, slots=np.arange(len(slots), dtype=np.int32))
        model.launch_decode = launch_decode

    with_server(monkeypatch, plant)
    assert_incorrect()


def test_a_scan_that_drops_its_carry_at_a_chunk_boundary(program):
    """The scan begins again from zeros every 8 positions, as a chunked
    kernel whose carry between chunks is lost would."""
    decoder, plant = program
    real = decoder.selective_scan

    def dropped(u, delta, a, b, c, d, h0, chunk=8):
        t = u.shape[1]
        cut = lambda x, s: x[:, s:s + chunk]
        out = [real(cut(u, s), cut(delta, s), a, cut(b, s), cut(c, s), d,
                    h0 if s == 0 else jnp.zeros_like(h0))
               for s in range(0, t, chunk)]
        return jnp.concatenate([y for y, _ in out], axis=1), out[-1][1]

    plant("selective_scan", dropped)
    assert_incorrect()


def test_the_delta_b_c_norms_dropped(program):
    """δ, B and C straight from ``x_proj``, neither normed nor scaled by
    their gains (drawn 1 + N(0, 0.1²))."""
    decoder, plant = program

    def unnormed(uc, params, i, cfg):
        p = lambda leaf: params[f"l{i}.{leaf}"]
        r, n = cfg.dt_rank, cfg.ssm_state
        dbc = decoder.weight_matmul(uc, p("x_proj"))
        delta = decoder.weight_matmul(dbc[..., :r], p("dt_proj"))
        return jax.nn.softplus(delta + p("dt_bias")), \
            dbc[..., r:r + n], dbc[..., r + n:]

    plant("_mamba_dbc", unnormed)
    assert_incorrect()


def test_u_and_z_swapped(monkeypatch):
    """``in_proj``'s halves read as z, u: the gate goes through the
    convolution and the scan, and the scan's input gates what comes
    out."""
    mod = system()
    real = mod.build

    def build(sizes, mix, weights):
        swapped = {}
        for k, v in weights.items():
            if k.endswith(".in_proj"):
                u, z = np.split(v, 2, axis=1)
                v = np.concatenate([z, u], axis=1)
            swapped[k] = v
        return real(sizes, mix, swapped)

    monkeypatch.setattr(mod, "build", build)
    assert_incorrect()
