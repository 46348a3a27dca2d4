"""The check that decides ``correct`` fails the control and every
planted fault of the timed path, and passes the program.

Each case drives the whole harness at a CPU size, with the look for a
chip skipped and the engine's replay executor broken underneath."""

import dataclasses

import jax.numpy as jnp
import pytest

from bench import harness
from bench.control import HalfWidthDevice

from conftest import CELLS, small_spec


def _run(cell, make_device=harness.make_chip_device, seed=2 ** 31 + 3):
    return harness.run_cell(small_spec(cell), seed, 0.3, False,
                            require_chip=False, make_device=make_device,
                            log=lambda line: None)


def _unchanged(run, states, tables):
    return jnp.asarray(states)


def _half_lanes_left_out(run, states, tables):
    out = run(states, tables)
    w = out.shape[-1] // 2
    return out.at[..., w:].set(jnp.asarray(states)[..., w:])


def _one_answer_bit_flipped(run, states, tables):
    out = run(states, tables)
    return out.at[:, :, :, 0].set(out[:, :, :, 0] ^ 1)


FAULTS = {"state_unchanged": _unchanged,
          "half_the_lanes_left_out": _half_lanes_left_out,
          "answer_altered": _one_answer_bit_flipped}


def _faulty(fault):
    def make(config):
        dev = harness.make_chip_device(config)
        engine = dev.chip()
        plain = engine.executor
        engine.executor = dataclasses.replace(
            plain, run=lambda s, t: fault(plain.run, s, t))
        return dev
    return make


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    r = _run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = _run(cell, make_device=lambda config: HalfWidthDevice())
    assert not r["correct"]
    assert r["failed"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    r = _run(cell, make_device=_faulty(FAULTS[fault]))
    assert not r["correct"], r["checks"]
