"""Program spans and counters (``repro.analysis.spans``) and the round
record they leave: ``spans``, ``span_parents`` and ``counts``."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import allowed_sync, spans, sync_contract
from repro.analysis.spans import (
    collect_round, count, named_program, self_seconds, span,
)
from repro.core.grouping import sample_clients

# the spans a FedSDD round of the vectorized engine opens, KD inline
ROUND_SPANS = (
    "fedsdd.round", "fedsdd.sample", "fedsdd.local.prep",
    "fedsdd.local.dispatch", "fedsdd.local.reassemble", "fedsdd.eq2",
    "fedsdd.bank_push", "fedsdd.wait.local", "fedsdd.kd.teachers",
    "fedsdd.kd.precompute", "fedsdd.kd.scan", "fedsdd.wait.kd",
    "fedsdd.sync",
)


@pytest.fixture
def clock(monkeypatch):
    """``perf_counter`` as seen by the spans: 0, 1, 2, ... per call."""
    ticks = iter(range(1000))
    monkeypatch.setattr(spans.time, "perf_counter", lambda: next(ticks))


@pytest.fixture
def annotations(monkeypatch):
    """The (name, attrs) of every TraceAnnotation the spans open."""
    seen = []

    class Recorder:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans.jax.profiler, "TraceAnnotation", Recorder)
    return seen


def test_nesting_records_parent_links_and_self_time(clock):
    with collect_round(round=1) as col:
        with span("outer"):              # t 0 .. 5
            with span("inner"):          # t 1 .. 2
                pass
            with span("inner"):          # t 3 .. 4
                pass
    assert col.seconds == {"outer": 5, "inner": 2}
    assert col.parents == {"outer": {"": 5}, "inner": {"outer": 2}}
    assert self_seconds(col.seconds, col.parents) == {"outer": 3, "inner": 2}


def test_same_name_under_two_parents_sums(clock):
    with collect_round() as col:
        with span("a"):                  # 0 .. 3
            with span("sync"):           # 1 .. 2
                pass
        with span("b"):                  # 4 .. 7
            with span("sync"):           # 5 .. 6
                pass
    assert col.seconds["sync"] == 2
    assert col.parents["sync"] == {"a": 1, "b": 1}
    assert self_seconds(col.seconds, col.parents) == {"a": 2, "b": 2,
                                                      "sync": 2}


def test_counters_add_up_within_a_round():
    with collect_round() as col:
        count("local_steps", 3)
        count("local_steps", 4)
        count("scan_steps", 10)
    assert col.counts == {"local_steps": 7, "scan_steps": 10}


def test_each_round_collects_afresh_and_restores_the_outer():
    with collect_round(round=1) as first:
        count("n", 1)
        with collect_round(round=2) as second:
            count("n", 5)
            with span("x"):
                pass
        count("n", 1)
    count("n", 100)                      # no round open: dropped
    with span("y"):
        pass
    assert first.counts == {"n": 2} and "x" not in first.seconds
    assert second.counts == {"n": 5} and set(second.seconds) == {"x"}
    assert "y" not in first.seconds and "y" not in second.seconds


def test_spans_outside_a_round_are_harmless(annotations):
    with span("lonely", k=1):
        count("n", 1)
    with pytest.raises(ValueError):
        with span("raises"):
            raise ValueError("boom")
    assert spans._stack() == []          # the stack unwound on the error
    assert annotations == [("lonely", {"k": 1}), ("raises", {})]


def test_worker_thread_spans_keep_their_own_stack():
    """A span on another thread (the async KD worker) has no parent from
    the main thread's stack and leaves that stack as it was."""
    inside = threading.Event()
    release = threading.Event()
    stacks = {}

    def worker():
        with span("fedsdd.kd.scan"):
            with span("fedsdd.sync"):
                stacks["worker"] = list(spans._stack())
                inside.set()
                release.wait(5)

    with collect_round(round=3) as col:
        with span("fedsdd.round"):
            with span("fedsdd.local.dispatch"):
                th = threading.Thread(target=worker)
                th.start()
                assert inside.wait(5)
                stacks["main"] = list(spans._stack())
                release.set()
                th.join()
            with span("fedsdd.eq2"):
                pass
    assert stacks["worker"] == ["fedsdd.kd.scan", "fedsdd.sync"]
    assert stacks["main"] == ["fedsdd.round", "fedsdd.local.dispatch"]
    assert col.parents["fedsdd.kd.scan"].keys() == {""}
    assert col.parents["fedsdd.sync"].keys() == {"fedsdd.kd.scan"}
    assert col.parents["fedsdd.eq2"].keys() == {"fedsdd.round"}
    assert col.parents["fedsdd.local.dispatch"].keys() == {"fedsdd.round"}


def test_round_attributes_are_stamped_on_every_span(annotations):
    with collect_round(round=7):
        with span("fedsdd.round"):
            with allowed_sync("a reason"):
                pass
    assert annotations == [
        ("fedsdd.round", {"round": 7}),
        ("fedsdd.sync", {"round": 7, "reason": "a reason"}),
    ]


def test_allowed_sync_is_the_sync_span(clock):
    with collect_round() as col:
        with span("fedsdd.round"):
            with allowed_sync("one pull"):
                pass
    assert col.parents["fedsdd.sync"] == {"fedsdd.round": 1}


def test_named_program_names_the_module_and_the_scope():
    f = named_program("fedsdd_probe", lambda x, *, k: jnp.sin(x) * k,
                      static_argnames=("k",))
    x = jnp.ones(3)
    np.testing.assert_allclose(f(x, k=2), np.sin(1.0) * 2 * np.ones(3),
                               rtol=1e-6)
    text = f.lower(x, k=2).as_text(debug_info=True)
    assert "module @jit_fedsdd_probe" in text
    # traced inside another program, its ops keep the name
    outer = jax.jit(lambda x: f(x, k=3) + 1)
    assert "fedsdd_probe/sin" in outer.lower(x).as_text(debug_info=True)


# ------------------------------------------------------ the round record
@pytest.fixture(scope="module")
def two_rounds():
    from repro.core.fedsdd import make_runner
    from repro.core.tasks import classification_task
    task = classification_task(model="mlp", num_clients=8, alpha=0.5,
                               num_train=320, num_server=256, seed=0)
    runner = make_runner(
        "fedsdd", task, num_clients=8, participation=0.5, K=2, R=2,
        local_epochs=2, client_batch=32, client_lr=0.05, server_lr=0.05,
        distill_steps=3, execution="vectorized", kd_kernel="flash")
    state = runner.init_state()
    for _ in range(2):
        with sync_contract("round"):     # spans add no host sync
            state = runner.run_round(state)
    return runner, state


def test_round_record_names_every_phase(two_rounds):
    _, state = two_rounds
    for rec in state.history:
        assert set(ROUND_SPANS) <= set(rec["spans"])
        assert rec["spans"]["fedsdd.round"] >= rec["t_local"] > 0
        assert rec["span_parents"]["fedsdd.round"].keys() == {""}
        assert rec["span_parents"]["fedsdd.local.prep"].keys() == \
            {"fedsdd.round"}
        own = self_seconds(rec["spans"], rec["span_parents"])
        assert all(s >= 0 for s in own.values())
        assert "t_round" not in rec


def test_round_counts_the_schedule_steps(two_rounds):
    runner, state = two_rounds
    cfg, store = runner.cfg, runner._store(state)
    for t, rec in enumerate(state.history, start=1):
        rng = np.random.default_rng(cfg.seed * 100_000 + t)
        active = sample_clients(cfg.num_clients, cfg.participation, rng)
        want = 0
        for cid in active:
            n = store.num_examples(int(cid))
            want += cfg.local_epochs * (n // min(cfg.client_batch, n))
        assert rec["counts"]["local_steps"] == want
        assert rec["counts"]["scan_steps"] >= want
