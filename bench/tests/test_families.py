"""A cell's model comes from its configuration's family, found by name:
the lookup and its refusal, a family of another kind of model (the
token-sequence family under ``tests/families/``) run end to end through
the unchanged harness, and the round reference in blocks against the
reference all at once."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from calibrate import plant_half_batch
from check import NUMBERS, readings, reduce
from cells import TOKENS
from conftest import shrink, token_cell
from reference import Reference

WORKLOAD = "resnet20-cifar10.cross_device"


@pytest.mark.parametrize("cfg", [
    {"name": "lm-a", "family": "no-such-family"},
    {"name": "lm-b"}], ids=["missing_module", "no_family_key"])
def test_a_configuration_without_its_family_module_is_refused(cfg):
    with pytest.raises(SystemExit) as e:
        harness.family_of(cfg)
    said = str(e.value)
    assert cfg["name"] in said
    assert os.path.join(harness.FAMILIES, f"{cfg.get('family')}.py") in said


def test_a_family_module_without_a_name_is_refused(tmp_path, monkeypatch):
    with open(os.path.join(harness.FAMILIES, "resnet.py")) as f:
        text = f.read()
    (tmp_path / "partial.py").write_text(
        text.replace("def round_flops(", "def round_flops_elsewhere("))
    monkeypatch.setattr(harness, "FAMILIES", str(tmp_path))
    with pytest.raises(SystemExit,
                       match=r"partial\.py has no \['round_flops'\]"):
        harness.family_of({"name": "lm-c", "family": "partial"})


def test_the_token_family_is_whole(families):
    assert harness.family_of({"name": "t", "family": "tokens"}).shrink


@pytest.mark.parametrize("pins", [{}, {"kd_head_fusion": True}],
                         ids=["logits", "head_fused"])
def test_token_session_is_correct(own_cache, families, pins):
    cell = token_cell(pins)
    job, cfg = harness.job_of(cell), cell["config"]
    rows = job["server_batch"] * cfg["seq"]
    seen = {}

    def look(b):
        seen["head_fused"] = b.runner._kd_pipeline().head_fused
        params = b.task.init_fn(jax.random.PRNGKey(0))
        seen["logits"] = b.task.logits_fn(params,
                                          b.task.server_batches[0]).shape

    s = harness.session(TOKENS, 2 ** 31 + 77, 0.5, False,
                        require_chip=False, cell=cell, plant=look,
                        log=lambda *a: None)
    assert s.result["correct"] is True, s.result["checks"]
    assert s.result["attempted"] >= 1 and s.result["failed"] == 0
    assert seen["logits"] == (rows, cfg["vocab"])
    assert seen["head_fused"] is bool(pins)


def test_token_half_batch_is_not_correct(own_cache, families):
    s = harness.session(TOKENS, 2 ** 31 + 78, 0.0, False,
                        require_chip=False, cell=token_cell(),
                        plant=plant_half_batch, log=lambda *a: None)
    assert s.result["correct"] is False
    limits = s.result["checks"]
    assert any(row["value"] > row["limit"] for row in limits.values()
               if row["limit"] is not None), limits


def _cell(family):
    cell = (token_cell() if family == "tokens"
            else shrink(harness.load_cell(WORKLOAD)))
    # every client each round: groups of two and three clients, so that
    # Eq. 2 adds clients within and across blocks
    cell["mix"]["job"]["participation"] = 1.0
    return cell


@pytest.fixture(scope="module")
def all_at_once(families):
    """Per family: the cell, its build, start models and the reference's
    rounds with every client and teacher in one program."""
    out = {}
    for family in ("resnet", "tokens"):
        cell = _cell(family)
        b = harness.build(cell, 41, log=lambda *a: None)
        start = harness.initial_models(cell, b)
        out[family] = (cell, b, start, _reference(cell, b, start, None, None))
    return out


def _reference(cell, b, start, clients, teachers):
    family = harness.family_of(cell["config"])
    ref = Reference(family.plain_model(cell["config"]), b.job,
                    clients=clients, teachers=teachers)
    return list(ref.run(start, b.client_data, b.server_x, b.sizes,
                        b.schedule_seed, cell["mix"]["warmup_rounds"] + 1))


# Blocks vmap the local training over fewer clients and add Eq. 2's
# weighted sum in another order: float32 rounding, which local SGD and
# KD carry on from round to round (ResNet-8's worst leaf drifts by 1.6%
# over four rounds on the CPU, the token model's by 2e-7).  So the
# agreement asked for is the one ``correct`` rests on: each number that
# ``check.py`` compares, of the blocked reference against the whole one,
# stays under a tenth of the cell's limit, and blocks cannot move a
# judgement.
@pytest.mark.parametrize("family", ["resnet", "tokens"])
@pytest.mark.parametrize("clients,teachers", [(1, 1), (3, 3)])
def test_reference_in_blocks_agrees(all_at_once, family, clients, teachers):
    cell, b, start, whole = all_at_once[family]
    blocked = _reference(cell, b, start, clients, teachers)
    values = readings(reduce(start, blocked), reduce(start, whole))
    limits = cell["limits"]["limits"]
    for name in NUMBERS:
        assert values[name] <= limits[name] / 10, (name, values)


# The teachers' logits in blocks: the same float32 sum in another order,
# a few ulps of the mean logit.
@pytest.mark.parametrize("family", ["resnet", "tokens"])
def test_teacher_blocks_sum_the_whole_bank(all_at_once, family):
    cell, b, start, _ = all_at_once[family]
    model = harness.family_of(cell["config"]).plain_model(cell["config"])
    B = b.job["server_batch"]
    nb = len(b.server_x) // B
    server = jnp.asarray(b.server_x[:nb * B].reshape(
        (nb, B) + b.server_x.shape[1:]))
    bank = [start, [jax.tree.map(lambda a: 0.5 * a, m) for m in start]]
    whole = np.asarray(Reference(model, b.job)._teacher_logits(bank, server))
    for teachers in (1, 3):
        got = Reference(model, b.job, teachers=teachers)._teacher_logits(
            bank, server)
        np.testing.assert_allclose(np.asarray(got), whole, rtol=1e-6,
                                   atol=1e-6 * np.max(np.abs(whole)))
