"""``correct`` fails where it must: the control (the reference in the
configuration's ``control_dtype`` in the program's place) and the faults a one-chip training
cell can have, a round that returns its models unchanged and a local
step that leaves half of each minibatch out.  At a tiny size on the CPU:
each reads at least ten times the sound run on one of the compared
numbers, and a run with a fault planted under the harness is never
``correct``."""
import math

import pytest

import harness
from calibrate import plant_half_batch, unchanged
from check import NUMBERS, judge, load_limits, readings, reduce
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
    assert len(sound.prog.kd_losses) == len(sound.ref.kd_losses) == warm + 1
    assert all(math.isfinite(sound.values[k]) for k in NUMBERS)


def test_sound_run_is_within_the_limits(sound):
    limits = _limits()
    ok, table = judge(sound.values, limits)
    assert ok is (limits is not None), table


def test_no_limit_is_never_correct(sound):
    assert judge(sound.values, None)[0] is False
    assert judge(sound.values, {k: None for k in NUMBERS})[0] is False


def test_control_is_not_correct(tiny, sound):
    ctl = reduce(sound.start, harness.reference_rounds(
        tiny, sound.built, sound.start, len(sound.ref.kd_losses),
        dtype="control_dtype"))
    values = readings(ctl, sound.ref)
    assert _separates(values, sound.values), (values, sound.values)
    assert not judge(values, _limits())[0]


def test_unchanged_norms_are_not_correct(sound):
    values = readings(unchanged(sound.prog), sound.ref)
    assert values["first_round_gap"] == values["stuck_share"] == 1.0
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
    """The same numbers as a run that warms nothing, and the teacher
    bank's ring back where it was: each leaf's sharding, and committed to
    its device where it was (as a ring placed across chips is)."""
    import jax
    monkeypatch.setattr(harness, "window_horizon", lambda *a: 8)
    warm = harness.warm_window_shapes
    placed = []

    def watched(b, state, horizon, log=print):
        def where():
            return [(a.sharding, a.committed) for a in
                    jax.tree.leaves(state.ensemble.export_state()[0])]
        ring, meta = state.ensemble.export_state()
        state.ensemble.import_state(
            jax.device_put(ring, jax.devices()[0]), meta)
        del ring
        before = where()
        ran = warm(b, state, horizon, log)
        placed.append((before, where()))
        return ran

    monkeypatch.setattr(harness, "warm_window_shapes", watched)
    said = []
    warmed = harness.session(WORKLOAD, 77, 0.0, False, require_chip=False,
                             cell=tiny, log=said.append)
    assert any(line.startswith("warmed round") for line in said), said
    assert warmed.values == sound.values
    [(before, after)] = placed
    assert before and all(committed for _, committed in before)
    assert after == before
