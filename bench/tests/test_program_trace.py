"""The reduction from a profiler trace to the program's spans and
programs (``program_trace.py``), on ``data/fedsdd_rounds.xspace.txt``."""
import os

import pytest

import device_trace as dt
import program_trace as pt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    """The fixture built into an ``.xplane.pb``; a compile 79-85 ms."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "fedsdd_rounds.xspace.txt")) as f:
        text = "".join(line for line in f if not line.startswith("#"))
    path = tmp_path_factory.mktemp("trace") / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    wall = 5_000 * MS            # the harness's clock as the window opened
    return str(path), pt.read(str(path), [(wall + 79 * MS, wall + 85 * MS)],
                              wall)


def test_program_names_come_from_the_module_events():
    assert pt.program_name("jit_fedsdd_eq2(18098313338822878185)") == \
        "fedsdd_eq2"
    assert pt.program_name("jit__lambda_(3)") == "_lambda_"
    assert pt.program_name("copy.3") == "copy.3"


def test_read_finds_spans_programs_and_rounds(trace):
    _, t = trace
    assert t.window == (1_000 * MS, 1_100 * MS)
    assert t.rounds == [(1_000 * MS, 1_045 * MS), (1_050 * MS, 1_095 * MS)]
    assert len(t.modules) == 1 and len(t.modules[0]) == 11
    assert {name for name, _, _ in t.spans} == {
        "fedsdd.round", "fedsdd.local.prep", "fedsdd.local.dispatch",
        "fedsdd.local.reassemble", "fedsdd.eq2", "fedsdd.sync",
        "fedsdd.kd.scan"}
    assert t.compiles == [(1_079 * MS, 1_085 * MS)]


def test_program_seconds_are_the_union_of_their_ops_per_round(trace):
    _, t = trace
    # the bucket scan's fusion lies inside its while: counted once
    assert pt.program_seconds(t) == pytest.approx({
        "fedsdd_bucket_scan": 7.5e-3, "fedsdd_eq2": 2e-3,
        "fedsdd_kd_precompute": 0.5e-3, "fedsdd_kd_scan": 7e-3,
        "concatenate": 0.5e-3, "add": 0.5e-3, "_lambda_": 0.5e-3})


def test_named_programs_share_of_the_busy_time(trace):
    _, t = trace
    assert pt.busy_seconds(t) == pytest.approx(18.5e-3)
    # bucket scan 7.5, Eq. 2 2, precompute 0.5, KD scan 7 ms a round
    assert pt.named_share(t) == pytest.approx(100 * 17 / 18.5)


def test_round_programs_count_executions_inside_rounds(trace):
    _, t = trace
    # 4 in round 1, 5 in round 2; the finite checks fall between rounds
    assert pt.round_programs(t) == 4.5


def test_idle_gaps_are_named_by_the_innermost_covering_span(trace):
    _, t = trace
    assert pt.idle_gaps(t) == [
        ("round 1: fedsdd.local.prep", 10.5 * MS),
        ("round 1: fedsdd.sync", 8 * MS),       # inside fedsdd.eq2
        ("compile", 8 * MS),
        ("round 2: fedsdd.local.prep", 7.5 * MS),
        ("round 2: fedsdd.eq2", 7 * MS),        # 2 ms reassemble, 5 Eq. 2
        ("harness, between rounds", 6 * MS),
        ("round 1: host, unattributed", 5.5 * MS),   # fedsdd.round only
        ("harness, between rounds", 4 * MS),
        ("round 1: fedsdd.local.reassemble", 3 * MS),
        ("round 2: host, unattributed", 2.5 * MS),
    ]


def test_a_span_on_the_worker_thread_covers_a_gap(trace):
    _, t = trace
    spans = [sp for sp in t.spans if sp[0] != pt.ROOT_SPAN]
    gap = (1_080 * MS, 1_084 * MS)
    assert pt.innermost_cover(gap, spans) == "fedsdd.kd.scan"


def test_spans_that_together_cover_half_name_the_largest():
    spans = [("a", 0, 30), ("b", 30, 60), ("c", 60, 100)]
    assert pt.innermost_cover((0, 100), spans) == "c"
    assert pt.innermost_cover((0, 100), [("a", 0, 30)]) is None


def test_idle_attributed_share(trace):
    _, t = trace
    # in-round idle 27.5 + 26.5 ms, of which spans below the round cover
    # 20.5 + 23.5 ms
    assert pt.idle_attributed_share(t) == pytest.approx(100 * 44 / 54)


def test_the_device_numbers_agree_with_device_trace(trace):
    path, t = trace
    s = dt.summarize(path)
    assert s.busy_s == pytest.approx(0.037)
    assert s.rounds == t.rounds and s.window == t.window
