"""The two programs at the ends of local training (``core/engine.py``).

``start_state`` builds a bucket's start params and optimiser state in one
program; ``finish_round`` takes every bucket's trained params in round
order and runs Eq. 2 in another.  Both only move and average values, so
each must equal the eager per-leaf code it replaced bit for bit, compile
once per bucket size (not per step count), and a round that takes them
must equal, to the bit, the same round with the eager end."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import TraceGuard
from repro.core import engine as eng
from repro.core.aggregation import fedavg_aggregate_grouped
from repro.core.faults import FaultPlan
from repro.core.fedsdd import make_runner
from repro.core.tasks import classification_task
from repro.optim.optimizers import sgd, with_fedprox
from repro.utils.pytree import tree_stack

K = 3


def assert_trees_equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def models(k=K, seed=0):
    """``k`` congruent pytrees of distinct random values (a small MLP's
    shapes, a nested dict as the engine's models are)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4 * k)
    it = iter(keys)
    return [{"dense": {"w": jax.random.normal(next(it), (6, 5)),
                       "b": jax.random.normal(next(it), (5,))},
             "head": {"w": jax.random.normal(next(it), (5, 3)),
                      "b": jax.random.normal(next(it), (3,))}}
            for _ in range(k)]


@pytest.fixture(scope="module")
def task():
    return classification_task(model="mlp", num_clients=8, alpha=0.5,
                               num_train=320, num_server=256, seed=0)


# -------------------------------------------------------- start program
@pytest.mark.parametrize("optimizer", [
    sgd(0.1, momentum=0.9), with_fedprox(sgd(0.1, momentum=0.9), 0.01)],
    ids=["sgd_momentum", "fedprox"])
def test_start_program_equals_eager_stack_gather_init(optimizer):
    globals_ = models()
    group_of = np.array([2, 0, 1, 1, 0, 2, 2])
    e = eng.VectorizedClientEngine(None, optimizer)
    w0, s0 = e.start_state(globals_, group_of)
    gid = jnp.asarray(group_of)
    want_w0 = jax.tree.map(lambda x: x[gid], tree_stack(globals_))
    assert_trees_equal(w0, want_w0)
    assert_trees_equal(s0, jax.vmap(optimizer.init)(want_w0))


# ---------------------------------------------------------- end program
def bucket(cids, group_of, sizes, order, trained):
    plan = types.SimpleNamespace(
        cids=np.asarray(cids), group_of=np.asarray(group_of),
        sizes=np.asarray(sizes), order=np.asarray(order))
    return (plan, trained, None, None)


def round_buckets(gids_round, sizes_round, seed):
    """A round's clients, in group-major round order, split into two
    batch-size buckets whose rows are NOT in round order."""
    C = len(gids_round)
    rows = [np.arange(C)[::2][::-1], np.arange(C)[1::2]]
    trained = models(C, seed)
    out = []
    for r in rows:
        out.append(bucket(
            cids=100 + r, group_of=gids_round[r], sizes=sizes_round[r],
            order=r, trained=tree_stack([trained[i] for i in r])))
    return out


def eager_reorder(buckets):
    """The eager per-leaf reassembly the programs replaced."""
    order = np.concatenate([b[0].order for b in buckets])
    inv = np.argsort(order)
    perm = jnp.asarray(inv)
    stacked = jax.tree.map(
        lambda *xs: jnp.concatenate(xs)[perm] if len(xs) > 1
        else xs[0][perm], *[b[1] for b in buckets])
    gids = np.concatenate([b[0].group_of for b in buckets])[inv]
    sizes = np.concatenate([b[0].sizes for b in buckets])[inv]
    return stacked, gids, sizes


def eager_end(results):
    """The eager code the end program replaced: each result (one train
    phase) reassembled per leaf, the overlap executor's results merged
    into round order, Eq. 2, and the K-way unstack."""
    parts = []
    for buckets in results:
        orders = np.sort(np.concatenate([b[0].order for b in buckets]))
        parts.append((*eager_reorder(buckets), orders))
    if len(parts) == 1:
        stacked, gids, sizes, _ = parts[0]
    else:
        inv = np.argsort(np.concatenate([p[3] for p in parts]))
        perm = jnp.asarray(inv)
        stacked = jax.tree.map(lambda *xs: jnp.concatenate(xs)[perm],
                               *[p[0] for p in parts])
        gids = np.concatenate([p[1] for p in parts])[inv]
        sizes = np.concatenate([p[2] for p in parts])[inv]
    agg = fedavg_aggregate_grouped(stacked, sizes, gids, K)
    return stacked, agg, eng.unstack_models(agg), gids, sizes


@pytest.mark.parametrize("route", ["pallas", "segment"])
@pytest.mark.parametrize("phases", [1, 2], ids=["one_result", "overlap"])
def test_end_program_equals_eager_reassembly_eq2_unstack(
        route, phases, monkeypatch):
    if route == "pallas":   # uniform groups, the kernel in interpret mode
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
        gids = np.repeat(np.arange(K), 3)
    else:                   # ragged groups: the segment reduction
        monkeypatch.delenv("REPRO_FORCE_PALLAS", raising=False)
        gids = np.array([0, 0, 0, 0, 1, 1, 2, 2, 2])
    sizes = np.array([64, 200, 90, 71, 130, 64, 88, 301, 77])
    buckets = round_buckets(gids, sizes, seed=phases)
    if phases == 1:
        results = [buckets]
    else:   # the overlap executor: groups k>0 first, then group 0
        results = [[], []]
        for plan, trained, _, _ in buckets:
            for part, keep in ((0, plan.group_of != 0),
                               (1, plan.group_of == 0)):
                results[part].append(bucket(
                    plan.cids[keep], plan.group_of[keep], plan.sizes[keep],
                    plan.order[keep],
                    jax.tree.map(lambda x: x[np.flatnonzero(keep)],
                                 trained)))
    every = [b for r in results for b in r]
    agg, models, got_gids, got_sizes, cids = eng.finish_round(every, K)
    stacked, want_agg, want_models, want_gids, want_sizes = \
        eager_end(results)
    assert_trees_equal(agg, want_agg)
    assert_trees_equal(models, want_models)
    np.testing.assert_array_equal(got_gids, want_gids)
    np.testing.assert_array_equal(got_gids, gids)
    np.testing.assert_array_equal(got_sizes, want_sizes)
    np.testing.assert_array_equal(cids, 100 + np.arange(len(gids)))
    # the client stack the masked and robust ends start from
    got_stacked, got_gids, got_sizes = eng.reassemble(every)
    assert_trees_equal(got_stacked, stacked)
    np.testing.assert_array_equal(got_gids, want_gids)
    np.testing.assert_array_equal(got_sizes, want_sizes)


# ------------------------------------------------------------- compiles
def test_each_end_compiles_once_across_step_counts(task):
    """Two rounds of the same clients, the second with twice the local
    steps: the bucket program compiles again, the two ends do not."""
    e = eng.VectorizedClientEngine(task.loss_fn, sgd(0.05, momentum=0.9))
    groups = [np.array([0, 1, 2]), np.array([3, 4, 5])]
    globals_ = [task.init_fn(jax.random.PRNGKey(k)) for k in range(2)]
    rng = np.random.default_rng(0)

    def round_(epochs):
        cfg = types.SimpleNamespace(client_batch=32, local_epochs=epochs)
        entries = eng.build_round_entries(task, cfg, groups, rng)
        rplan = eng.plan_from_entries(task, entries, groups)
        buckets = e.train_round(
            rplan, lambda plan: e.start_state(globals_, plan.group_of))
        out = eng.finish_round(buckets, len(groups))
        jax.block_until_ready(out[0])
        return rplan

    first = round_(1)
    tg = TraceGuard("round 2").watch_programs(e)
    with tg:
        second = round_(2)
    steps = [p.step_mask.shape[1] for p in (*first.plans, *second.plans)]
    assert steps[:len(first.plans)] != steps[len(first.plans):]
    grown = tg.cache_growth()
    assert grown["engine/start"] == 0 and grown["engine/end"] == 0
    assert sum(v for k, v in grown.items()
               if k in ("engine/scan", "engine/stepped")) > 0
    # once per bucket size: the buckets' client counts, not their steps
    assert e._start_fn._cache_size() == len(
        {p.cids.size for p in first.plans})


# ------------------------------------------------------ the whole round
def cell_runner(task, **kw):
    """A small FedSDD runner under the benchmark cell's pins."""
    return make_runner(
        "fedsdd", task, num_clients=8, participation=0.5, K=2, R=2,
        local_epochs=1, client_batch=32, client_lr=0.05, server_lr=0.05,
        distill_steps=3, execution="vectorized", kd_kernel="flash",
        client_sharding="vmap", client_cache_buckets=9, **kw)


@pytest.mark.parametrize("overlap", ["off", "async"])
def test_round_with_the_programs_equals_the_eager_round(task, overlap):
    """A fault plan that never fires sends the round down the eager
    (masked) end with every client surviving, which is the eager code
    the programs replaced: the models must agree to the bit, and
    ``local_eager_ends`` tells the two ends apart."""
    never = FaultPlan(seed=1, dropout=1e-12)
    states = [cell_runner(task, overlap=overlap, faults=f).run(rounds=3)
              for f in (None, never)]
    folded, eager = states
    for a, b in zip(folded.global_models, eager.global_models):
        assert_trees_equal(a, b)
    assert [r["counts"]["local_eager_ends"] for r in folded.history] == \
        [0, 0, 0]
    assert [r["counts"]["local_eager_ends"] for r in eager.history] == \
        [1, 1, 1]
