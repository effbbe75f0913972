"""The round reference streams through the host and each side of the
comparison is reduced to leaf norms as its rounds arrive: the
reference's device memory stays within its module's budget at every
program call, a side holds at most three sets of K models, and the
numbers from norm vectors are those from whole models."""
import math
import weakref

import jax
import numpy as np
import pytest

import check
import harness
import reference
from conftest import shrink, token_cell

WORKLOAD = "resnet20-cifar10.cross_device"


def _live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def _nbytes(tree, itemsize=None) -> int:
    return sum(a.size * (itemsize or a.dtype.itemsize)
               for a in jax.tree.leaves(tree))


# Wide enough that one model (131,328 parameters, 0.5 MB in float32)
# weighs as much as a logit buffer and far more than the data, so that a
# copy too many shows.
@pytest.mark.parametrize("clients,teachers", [(1, 1), (None, None)])
def test_reference_holds_its_device_budget(families, clients, teachers):
    cell = token_cell()
    cell["config"].update(vocab=128, d_model=256)
    cfg, job = cell["config"], harness.job_of(cell)
    b = harness.build(cell, 2 ** 31 + 5, log=lambda *a: None)
    start = harness.initial_models(cell, b)
    ref = reference.Reference(
        harness.family_of(cfg).plain_model(cfg), b.job,
        dtype=cfg["reference_dtype"], clients=clients, teachers=teachers)
    seen = []

    def watched(name, program):
        def call(*args):
            seen.append((name, "in", _live_bytes()))
            out = jax.block_until_ready(program(*args))
            seen.append((name, "out", _live_bytes()))
            return out
        return call

    for name in ("_local_sum", "_teacher_share", "_kd"):
        setattr(ref, name, watched(name, getattr(ref, name)))

    model = _nbytes(start[0], itemsize=4)
    B, S, V = job["server_batch"], cfg["seq"], cfg["vocab"]
    nb = len(b.server_x) // B
    data = (sum(x.nbytes + y.nbytes for x, y in b.client_data)
            + b.server_x[:nb * B].nbytes)
    logits = nb * B * S * V * 4
    # a block's rows, step masks and weights, and the KD losses
    small = 64 * 1024
    bank = job["K"] * job["R"]
    # the module docstring's budget: max(3, teachers a block) models, or
    # max(2K, teachers a block) where the round's clients run at once
    group = 3 if clients else 2 * job["K"]
    allowed = max(group, teachers or bank) * model + data + 2 * logits \
        + small
    base = _live_bytes()
    rounds = list(ref.run(start, b.client_data, b.server_x, b.sizes,
                          b.schedule_seed, job["R"] + 2))
    assert len(rounds) == job["R"] + 2
    calls = {name for name, _, _ in seen}
    assert calls == {"_local_sum", "_teacher_share", "_kd"}
    over = [(name, when, (n - base) / model) for name, when, n in seen
            if n - base > allowed]
    assert not over, (allowed / model, over)


def test_a_side_holds_three_sets_of_models():
    def models(v):
        return [{"w": np.full(3, v, np.float32)} for _ in range(4)]

    start = models(0.0)
    side = check.Side(start)
    alive = []
    for t in range(1, 6):
        record = {"models": models(float(t)), "kd_loss_first": 1.0,
                  "kd_loss_last": 0.5}
        alive.append(weakref.ref(record["models"][0]["w"]))
        side.add(record)
        del record
        held = [i for i, r in enumerate(alive, 1) if r() is not None]
        assert held == list(range(max(1, t - 1), t + 1)), held
    norms = side.close()
    assert all(r() is None for r in alive)
    assert np.allclose(norms.update, 1.0 * math.sqrt(3))
    assert np.allclose(norms.change, 5.0 * math.sqrt(3))
    assert np.allclose(norms.first, 1.0 * math.sqrt(3))
    assert len(norms.first) == 3 and len(norms.update) == 4


def _whole_readings(start, prog_rounds, ref_rounds) -> dict:
    """The compared numbers from every round's whole models, as they
    were computed before the sides were reduced as rounds arrive."""
    kd = 0.0
    for p, r in zip(prog_rounds, ref_rounds):
        for key in ("kd_loss_first", "kd_loss_last"):
            a, b = p[key], r[key]
            g = abs(a - b) / max(abs(b), 1e-30) if a is not None else math.inf
            kd = max(kd, g if math.isfinite(g) else math.inf)

    def before(rounds):
        return rounds[-2]["models"] if len(rounds) > 1 else start

    norms = check._leaf_norms
    upd, upd_worst, _ = check._gaps(
        norms(prog_rounds[-1]["models"], before(prog_rounds)),
        norms(ref_rounds[-1]["models"], before(ref_rounds)))
    chg, chg_worst, stuck = check._gaps(
        norms(prog_rounds[-1]["models"], start),
        norms(ref_rounds[-1]["models"], start))
    first, _, _ = check._gaps(
        norms(prog_rounds[0]["models"][1:], start[1:]),
        norms(ref_rounds[0]["models"][1:], start[1:]))
    return {"first_round_gap": first, "stuck_share": stuck,
            "update_gap": upd, "change_gap": chg,
            "update_worst_leaf": upd_worst, "change_worst_leaf": chg_worst,
            "kd_loss_gap": kd}


@pytest.mark.parametrize("family", ["resnet", "tokens"])
def test_norms_read_as_whole_models(own_cache, families, monkeypatch,
                                    family):
    cell = (token_cell() if family == "tokens"
            else shrink(harness.load_cell(WORKLOAD)))
    fed = []
    add = check.Side.add

    def spy(self, record):
        if not fed or fed[-1][0] is not self:
            fed.append((self, []))
        fed[-1][1].append(record)
        add(self, record)

    monkeypatch.setattr(check.Side, "add", spy)
    s = harness.session(cell["name"], 2 ** 31 + 99, 0.0, False,
                        require_chip=False, cell=cell, log=lambda *a: None)
    (prog, prog_rounds), (ref, ref_rounds) = fed
    assert prog.start is ref.start is s.start
    assert len(prog_rounds) == len(ref_rounds) == \
        cell["mix"]["warmup_rounds"] + 1
    assert _whole_readings(s.start, prog_rounds, ref_rounds) == s.values
