"""Overlapped round execution vs the back-to-back oracle.

``FedConfig.overlap`` ∈ {async, fused} defers round t's server KD into
round t+1's k>0 local-training phase (core/round_plan.py) — an EXACT
reordering of the dependency graph, so after the drain
(``FederatedRunner.finalize``, called by ``run``) the final state must be
allclose to ``overlap='off'`` for every preset × K × engine combination,
including the clients-source (FedDF) teacher snapshot and the shard_mapped
teacher precompute.  Also covered: the deferred-KD state machine
(pending job, drain, late-patched history records) and the genuinely
fused one-program path (scan step mode on both sides).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.fedsdd import make_runner
from repro.core.tasks import classification_task
from repro.distill import KDPipeline
from repro.utils.pytree import tree_stack

ATOL, RTOL = 2e-4, 2e-4


@pytest.fixture(scope="module")
def task():
    # mlp: the executor's phase mechanics are model-agnostic and the cnn
    # engine-vs-engine parity is already pinned by test_engine_parity —
    # the tiny MLP keeps this matrix inside the quick PR gate
    return classification_task(model="mlp", num_clients=8, alpha=0.5,
                               num_train=320, num_server=256, seed=0)


def small(**kw):
    base = dict(num_clients=8, participation=1.0, local_epochs=1,
                client_lr=0.05, server_lr=0.05, distill_steps=4,
                client_batch=32)
    base.update(kw)
    return base


def assert_models_close(ms_a, ms_b):
    assert len(ms_a) == len(ms_b)
    for a, b in zip(ms_a, ms_b):
        jax.tree.map(lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=RTOL, atol=ATOL), a, b)


def run_overlap(task, preset, overlap, *, rounds=3, **kw):
    r = make_runner(preset, task, overlap=overlap, **small(**kw))
    return r.run(rounds=rounds)


# ----------------------------------------------------------- full matrix
# K=4 (the deferral-eligible shape) is the expensive half — marked slow;
# K=1 (the inline-degenerate shape) stays in the quick gate.
@pytest.mark.parametrize("K", [1, pytest.param(4, marks=pytest.mark.slow)])
@pytest.mark.parametrize("preset", ["fedsdd", "feddf"])
@pytest.mark.parametrize("execution", ["sequential", "vectorized"])
def test_overlap_modes_match_off(task, preset, K, execution):
    off = run_overlap(task, preset, "off", K=K, execution=execution)
    for mode in ("async", "fused"):
        st = run_overlap(task, preset, mode, K=K, execution=execution)
        assert_models_close(off.global_models, st.global_models)
        assert st.pending_kd is None          # run() drained


def test_overlap_matches_sequential_oracle(task):
    """Transitivity anchor: overlapped vectorized equals the all-oracle
    sequential run (off × sequential × legacy-free default config)."""
    oracle = run_overlap(task, "fedsdd", "off", K=4, execution="sequential")
    both = run_overlap(task, "fedsdd", "fused", K=4, execution="vectorized")
    assert_models_close(oracle.global_models, both.global_models)


@pytest.mark.slow
def test_overlap_parity_under_forced_shard_map(task, monkeypatch):
    """The sharded clients-source teacher precompute (shard_map over the
    1-device ('clients',) mesh) + sharded engine must stay a refactoring
    of the vmap path inside the overlapped executor."""
    off = run_overlap(task, "feddf", "off", K=4, execution="vectorized")
    monkeypatch.setenv("REPRO_FORCE_SHARD_MAP", "1")
    st = run_overlap(task, "feddf", "async", K=4, execution="vectorized")
    assert_models_close(off.global_models, st.global_models)


def test_truly_fused_program_runs_and_matches(task, monkeypatch):
    """Scan step mode on both sides => the KD scan and the k>0 bucket
    scans must be emitted as ONE jitted program (FusedKDLocalProgram),
    and still match the oracle."""
    monkeypatch.setenv("REPRO_ENGINE_STEP_MODE", "scan")
    r = make_runner("fedsdd", task, overlap="fused",
                    execution="vectorized", **small(K=2))
    st = r.run(rounds=3)
    fused = r._executor()._fused
    assert fused is not None and fused._fns, \
        "fused overlap never built the combined device program"
    off = run_overlap(task, "fedsdd", "off", K=2, execution="vectorized")
    assert_models_close(off.global_models, st.global_models)


# ------------------------------------------------- deferred-KD mechanics
def test_pending_kd_defers_and_drains(task):
    """Without the drain the last round's KD is still pending and the
    main model is the RAW aggregate; finalize must resolve it to the
    off-mode result and complete the history record."""
    r_off = make_runner("fedsdd", task, overlap="off", **small(K=2))
    off = r_off.run(rounds=2)
    r = make_runner("fedsdd", task, overlap="async", **small(K=2))
    st = r.init_state()
    for _ in range(2):
        st = r.run_round(st)
    assert st.pending_kd is not None
    assert st.pending_kd.round_idx == 2
    rec = st.history[-1]
    assert "kd_steps" not in rec          # record patched only at resolve
    # pre-drain main model is the raw aggregate, NOT the KD output
    diffs = [float(np.abs(np.asarray(a) - np.asarray(b)).max())
             for a, b in zip(jax.tree.leaves(st.global_models[0]),
                             jax.tree.leaves(off.global_models[0]))]
    assert max(diffs) > 0
    st = r.finalize(st)
    assert st.pending_kd is None
    assert rec["kd_steps"] == 4 and "acc_main" in rec
    assert_models_close(off.global_models, st.global_models)


def test_pending_kd_spill_restore_roundtrip(task, tmp_path):
    """Mid-round checkpoint with a deferred KD in flight: spilling the
    PendingKD through fedckpt and restoring it in a FRESH runner must
    drain to exactly the never-interrupted result (the job's inputs are
    persisted; KD re-runs deterministically), with the late KD record
    fields still landing on the restored history record."""
    r_ref = make_runner("fedsdd", task, overlap="async", **small(K=2))
    st_ref = r_ref.init_state()
    for _ in range(2):
        st_ref = r_ref.run_round(st_ref)
    st_ref = r_ref.finalize(st_ref)

    r1 = make_runner("fedsdd", task, overlap="async", **small(K=2))
    st = r1.init_state()
    for _ in range(2):
        st = r1.run_round(st)
    assert st.pending_kd is not None
    path = r1.spill_pending(st, str(tmp_path))
    assert path.endswith("pending_kd_r00002.npz")
    r1._executor().close()
    st.pending_kd = None                  # simulate the process dying
    r2 = make_runner("fedsdd", task, overlap="async", **small(K=2))
    pending = r2.restore_pending(st, path)
    assert pending.round_idx == 2 and pending.dispatched is None
    assert pending.record is st.history[-1]   # rebound to the live record
    st = r2.finalize(st)
    assert st.pending_kd is None
    assert_models_close(st_ref.global_models, st.global_models)
    assert st.history[-1]["kd_steps"] == st_ref.history[-1]["kd_steps"]


def test_pending_kd_spill_none_when_drained(task, tmp_path):
    """spill_pending is a no-op (returns None) once the state is drained —
    nothing to persist, nothing silently written."""
    r = make_runner("fedsdd", task, overlap="async", **small(K=2))
    st = r.run(rounds=2)          # run() drains
    assert r.spill_pending(st, str(tmp_path)) is None
    assert list(tmp_path.iterdir()) == []


def test_overlap_history_matches_off(task):
    """Every round's record (kd losses + eval) must equal the oracle's
    after the drain — late patching changes WHEN, never WHAT."""
    off = run_overlap(task, "fedsdd", "off", K=2)
    ov = run_overlap(task, "fedsdd", "async", K=2)
    assert len(off.history) == len(ov.history)
    for a, b in zip(off.history, ov.history):
        assert a["round"] == b["round"]
        assert a.get("kd_steps") == b.get("kd_steps")
        assert a["acc_main"] == pytest.approx(b["acc_main"], abs=2e-3)
        assert a.get("kd_loss_last") == pytest.approx(
            b.get("kd_loss_last"), rel=1e-3)


def test_overlap_with_warmup_rounds(task):
    """KD-inactive rounds (warmup) emit no pending job; parity holds
    across the activation edge."""
    kw = dict(K=2, distill_warmup_rounds=2)
    off = run_overlap(task, "fedsdd", "off", rounds=4, **kw)
    ov = run_overlap(task, "fedsdd", "async", rounds=4, **kw)
    assert_models_close(off.global_models, ov.global_models)
    assert off.history[0].get("kd_steps") is None
    assert ov.history[0].get("kd_steps") is None
    assert ov.history[-1]["kd_steps"] == 4


def test_overlap_resume_across_run_calls(task):
    """run() drains at its end, so chunked runs (2+2) equal one 4-round
    run — the executor re-primes its pipeline after each drain."""
    whole = run_overlap(task, "fedsdd", "async", rounds=4, K=2)
    r = make_runner("fedsdd", task, overlap="async", **small(K=2))
    st = r.run(rounds=2)
    st = r.run(rounds=2, state=st)
    assert_models_close(whole.global_models, st.global_models)


def test_overlap_requires_fused_pipeline(task):
    with pytest.raises(ValueError, match="overlapped rounds"):
        make_runner("fedsdd", task, overlap="async",
                    kd_pipeline="legacy", **small())


# ------------------------------------------- sharded teacher precompute
def _linear_logits(p, b):
    return b["x"] @ p["w"]


def test_sharded_precompute_matches_vmap(monkeypatch):
    """shard_map teacher precompute == the plain vmapped pass, including
    an M that does not divide the mesh (mask-padded members)."""
    import jax.numpy as jnp

    from repro.launch.mesh import make_client_mesh
    rng = np.random.default_rng(0)
    teachers = [{"w": jnp.asarray(rng.normal(0, 1, (6, 4)), jnp.float32)}
                for _ in range(3)]        # M=3: indivisible by any n>1 mesh
    batches = [{"x": jnp.asarray(rng.normal(0, 1, (8, 6)), jnp.float32)}
               for _ in range(2)]
    plain = KDPipeline(_linear_logits, steps=1, lr=0.1, temperature=3.0)
    stacked_b = plain.batches_for(batches)
    want = plain.precompute_teacher_probs(tree_stack(teachers), stacked_b)
    monkeypatch.setenv("REPRO_FORCE_SHARD_MAP", "1")
    sharded = KDPipeline(_linear_logits, steps=1, lr=0.1, temperature=3.0,
                         mesh=make_client_mesh())
    assert sharded._shard_teachers()
    got = sharded.precompute_teacher_probs(tree_stack(teachers), stacked_b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_overlap_records_round_walltime(task):
    """The executor's phase clock feeds bench_roundtime/scheduler: off
    rounds carry the t_local/t_kd split, every round carries its
    ``fedsdd.round`` span."""
    t = dataclasses.replace(task, eval_fn=None)
    st = run_overlap(t, "fedsdd", "off", rounds=1, K=2)
    rec = st.history[-1]
    assert rec["spans"]["fedsdd.round"] >= rec["t_local"] > 0
    assert rec["t_kd"] > 0
    st = run_overlap(t, "fedsdd", "async", rounds=2, K=2)
    assert all(r["spans"]["fedsdd.round"] > 0 for r in st.history)
    assert "t_kd" not in st.history[-1]   # overlapped rounds don't sync
