"""The harness's run at a CPU size, past its look for a chip: sound runs
come out correct against the float32 reference, and a timed path broken
underneath, or the control in the program's place, comes out not correct."""
import functools
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny
from bench.tests.tiny import tiny_cell

SEED = 2_147_483_659      # over 31 bits, as a benchmark check's seeds may be
# the program's LayerNorm hard-codes eps 1e-6, OLMo's is 1e-5: the sound
# run of olmo-1b here is at the program's eps, and the published one is
# test_published_olmo_eps_departs
PROGRAM_EPS = {"olmo-1b": 1e-6}


def _run(cell, seed=SEED):
    return harness.run_cell(cell, seed, 0.2, False, t0=time.perf_counter())


@pytest.mark.parametrize("config", ["qwen3-0.6b", "olmo-1b"])
def test_sound_run_is_correct(config):
    res = _run(tiny_cell(config, norm_eps=PROGRAM_EPS.get(config)))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s_per_chip",
                                   "peak_hbm_gb"}
    assert res["device"]["platform"] == "cpu"


def test_published_olmo_eps_departs():
    """Against OLMo's published LayerNorm (eps 1e-5), in float32 where
    round-off reads under 1e-5, the program (eps 1e-6) is not correct."""
    res = _run(tiny_cell("olmo-1b"))
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > 20 * tiny.LIMITS["grad_gap"]


def test_checked_copies_read_as_the_live_state():
    """The host copy of the parameters after the checked steps, placed
    again and unpacked, reads the same norms as the state itself."""
    cell = tiny_cell()
    trainer, model = harness.build(cell)
    harness.seed_trainer(trainer, cell, model, SEED)
    prog = harness.checked_steps(trainer)
    read = harness.read_checked(trainer, cell, model, SEED, prog)
    live = jax.device_get(trainer.bench_delta(trainer.state["params"],
                                              model.key_words(SEED)))
    assert read["delta"].keys() == live.keys()
    for k in live:
        np.testing.assert_array_equal(read["delta"][k], live[k])
    assert len(read["loss"]) == harness.N_CHECK_STEPS


def _broken_jitted(monkeypatch, breaker):
    from repro.train.step import TrainStepBundle

    def jitted(self, phase, donate=True):
        return jax.jit(breaker(functools.partial(self.step_fn, phase=phase)))

    monkeypatch.setattr(TrainStepBundle, "jitted", jitted)


def test_state_returned_unchanged_is_caught(monkeypatch):
    def breaker(step):
        def broken(state, batch):
            _, rotated, metrics = step(state, batch)
            return state, rotated, metrics
        return broken

    _broken_jitted(monkeypatch, breaker)
    res = _run(tiny_cell())
    assert not res["correct"]
    assert res["checks"]["delta_gap"]["value"] > 0.5


def test_half_batch_is_caught(monkeypatch):
    def breaker(step):
        def broken(state, batch):
            half = jax.tree.map(lambda x: x[:, : x.shape[1] // 2], batch)
            new, _, metrics = step(state, half)
            return new, batch, metrics
        return broken

    _broken_jitted(monkeypatch, breaker)
    res = _run(tiny_cell())
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > 0.05


def test_control_in_the_programs_place_is_not_correct():
    """bench/control.py's readings on three seeds: the program is under
    every limit, and the reference at the precision below the stated one
    (bf16 operands for this float32 cell) and half of each batch left out
    each exceed one limit or more."""
    from bench import control

    cell = tiny_cell()
    lim = cell.limits
    for r in control.readings(cell, [3, SEED, 4_000_000_007],
                              log=lambda s: None):
        assert all(r["program"][k] <= lim[k] for k in lim), r
        for variant in ("control", "half_batch"):
            assert any(r[variant][k] > lim[k] for k in lim), (variant, r)
