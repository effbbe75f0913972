"""``correct`` fails where it must: the control (the reference in
bfloat16 in the program's place) and the faults a one-chip training
cell can have, a round that returns its models unchanged and a local
step that leaves half of each minibatch out.  At a tiny size on the CPU:
each reads at least ten times the sound run on one of the compared
numbers, and a run with a fault planted under the harness is never
``correct``."""
import math

import jax.numpy as jnp
import pytest

import harness
from calibrate import plant_half_batch
from check import NUMBERS, judge, load_limits, readings
from conftest import shrink

WORKLOAD = "resnet20-cifar10.cross_device"
SEPARATION = 10


def _limits():
    lim = load_limits(harness.BENCH, WORKLOAD)
    return lim["limits"] if lim else None


@pytest.fixture(scope="module")
def tiny():
    return shrink(harness.load_cell(WORKLOAD))


@pytest.fixture(scope="module")
def sound(own_cache, tiny):
    return harness.session(WORKLOAD, 77, 0.0, False, require_chip=False,
                           cell=tiny, log=lambda *a: None)


def _separates(values, sound_values):
    return any(values[k] >= SEPARATION * sound_values[k] for k in NUMBERS)


def test_sound_run_compares_a_timed_round(sound, tiny):
    warm = tiny["mix"]["warmup_rounds"]
    assert sound.result["attempted"] >= 1
    assert len(sound.prog_rounds) == len(sound.ref_rounds) == warm + 1
    assert all(math.isfinite(sound.values[k]) for k in NUMBERS)


def test_sound_run_is_within_the_limits(sound):
    limits = _limits()
    ok, table = judge(sound.values, limits)
    assert ok is (limits is not None), table


def test_no_limit_is_never_correct(sound):
    assert judge(sound.values, None)[0] is False
    assert judge(sound.values, {k: None for k in NUMBERS})[0] is False


def test_control_is_not_correct(tiny, sound):
    ctl = harness.reference_rounds(tiny, sound.built, sound.start,
                                   len(sound.ref_rounds), dtype=jnp.bfloat16)
    values = readings(sound.start, ctl, sound.ref_rounds)
    assert _separates(values, sound.values), (values, sound.values)
    assert not judge(values, _limits())[0]


def _unchanged(b):
    run_round = b.runner.run_round

    def same_models(state):
        models = list(state.global_models)
        state = run_round(state)
        state.global_models = models
        return state

    b.runner.run_round = same_models


@pytest.mark.parametrize("plant", [_unchanged, plant_half_batch],
                         ids=["unchanged", "half_batch"])
def test_faults_are_not_correct(own_cache, tiny, sound, plant):
    result = harness.run(WORKLOAD, 77, 0.5, False, require_chip=False,
                         cell=tiny, plant=plant, log=lambda *a: None)
    values = {k: row["value"] for k, row in result["checks"].items()}
    assert _separates(values, sound.values), (values, sound.values)
    assert result["correct"] is False


def test_warming_window_shapes_leaves_the_run_alone(tiny, sound, monkeypatch):
    monkeypatch.setattr(harness, "window_horizon", lambda *a: 8)
    said = []
    warmed = harness.session(WORKLOAD, 77, 0.0, False, require_chip=False,
                             cell=tiny, log=said.append)
    assert any(line.startswith("warmed round") for line in said), said
    assert warmed.values == sound.values
