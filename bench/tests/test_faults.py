"""A whole run of each cell, past the harness's look for a chip, with the
timed path broken underneath: ``correct`` has to come out false."""

import time

import pytest

from bench import harness
from bench.tests.small import small_cell
from bench.tests.test_apps import ONE_CHIP_CELLS

SEED = 2**31 + 99


def _alter_one_answer(job):
    def broken(tokens):
        import jax.numpy as jnp

        ok, ov, dropped = job(tokens)
        first = jnp.argmax((ok != harness.check.PAD).ravel())
        return ok, ov.ravel().at[first].add(1).reshape(ov.shape), dropped
    return broken


def _half_input_left_out(job):
    def broken(tokens):
        half = tokens.shape[0] // 2
        return job(tokens.at[half:].set(0))
    return broken


def _state_unchanged(job):
    def broken(tokens):
        import jax.numpy as jnp

        ok, ov, dropped = job(tokens)
        return jnp.full_like(ok, harness.check.PAD), jnp.zeros_like(ov), \
            dropped
    return broken


def _drops_a_pair(job):
    def broken(tokens):
        ok, ov, dropped = job(tokens)
        return ok, ov, dropped + 1
    return broken


def _run(cell, monkeypatch=None, fault=None):
    import jax

    if fault is not None:
        real = harness.build_entry

        def build(cell, devices):
            plan, job, sharding = real(cell, devices)
            return plan, jax.jit(fault(job)), sharding

        monkeypatch.setattr(harness, "build_entry", build)
    r = harness.run(cell, SEED, 0.2, False, jax.devices(),
                    time.perf_counter())
    r.pop("_log")
    return r


@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_sound_run_is_correct(name):
    r = _run(small_cell(name))
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"job_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("fault", [_alter_one_answer, _half_input_left_out,
                                   _state_unchanged, _drops_a_pair])
@pytest.mark.parametrize("name", ONE_CHIP_CELLS)
def test_broken_run_is_not_correct(name, fault, monkeypatch):
    r = _run(small_cell(name), monkeypatch, fault)
    assert not r["correct"]
    assert r["failed"] == r["attempted"]
