"""The reduction from a profiler trace to device numbers."""
import os

import pytest

import device_trace as dt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_merges_overlaps_and_drops_empty():
    assert dt.union([(5, 7), (0, 2), (1, 3), (9, 9), (6, 8)]) == \
        [(0, 3), (5, 8)]


def test_gaps_are_the_complement_inside_the_window():
    busy = [(0, 3), (5, 8), (12, 20)]
    assert dt.gaps(busy, 1, 15) == [(3, 5), (8, 12)]
    assert dt.gaps([], 0, 4) == [(0, 4)]
    assert dt.gaps([(0, 10)], 2, 6) == []


def test_gaps_are_named_by_what_the_host_did():
    rounds = [(0, 100), (110, 200)]
    compiles = [(20, 35)]
    assert dt.attribute((20, 40), compiles, rounds) == "compile"
    assert dt.attribute((50, 60), compiles, rounds) == \
        "round 1: host, unattributed"
    assert dt.attribute((101, 109), compiles, rounds) == \
        "harness, between rounds"


def test_reduce_two_devices():
    ops = [[("a", 0, 40), ("b", 30, 60), ("a", 80, 100)],
           [("a", 0, 100)]]
    s = dt.reduce_ops(ops, (0, 100), [(0, 100)], [])
    assert s.busy_ns == [80, 100]
    assert s.busy_s == pytest.approx(90e-9)
    assert s.op_ns == {"a": 160, "b": 30}
    assert s.kernel("a") == (160e-9, 3)
    assert s.idle_gaps == [("round 1: host, unattributed", 20)]


def test_trace_file(tmp_path):
    """``data/two_rounds.xspace.txt``, built into an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "two_rounds.xspace.txt")) as f:
        text = "".join(line for line in f if not line.startswith("#"))
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    ms = 1_000_000
    wall = 5_000 * ms            # the harness's clock as the window opened
    compile_wall = [(wall + 41 * ms, wall + 49 * ms)]
    s = dt.summarize_dir(str(tmp_path), compile_wall, wall)
    assert s.devices == 1 and s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.077)
    assert len(s.rounds) == 2
    assert s.kernel("flash_fwd") == (pytest.approx(0.005), 2)
    assert s.kernel("flash_bwd") == (pytest.approx(0.002), 1)
    assert s.kernel("wavg") == (pytest.approx(0.002), 1)
    # 38-52 ms: the compile covers 8 of its 14 ms
    assert s.idle_gaps[0] == ("compile", 14 * ms)
    assert s.idle_gaps[1] == ("harness, between rounds", 6 * ms)
    assert {what for what, _ in s.idle_gaps[2:]} == {
        "round 1: host, unattributed", "round 2: host, unattributed"}
    assert dt.top_ops(s, 1) == [["while.7", pytest.approx(0.068)]]


def test_a_cut_stretch_is_neither_busy_nor_idle():
    ops = [[("a", 0, 40), ("b", 35, 45), ("b", 42, 48), ("a", 60, 100)]]
    s = dt.reduce_ops(ops, (0, 100), [(0, 40), (60, 100)], [],
                      cuts=[(40, 60)])
    assert s.busy_ns == [80]
    assert s.window_s == pytest.approx(80e-9)
    assert s.idle_gaps == []
    assert s.op_ns == {"a": 80, "b": 5}
    assert s.op_count == {"a": 2, "b": 1}


def test_the_copy_to_the_host_is_not_idle(tmp_path):
    """The recorded trace with the stretch between its rounds, 40-50 ms,
    taken by the compared round's copy to the host: it leaves the
    window, and its idle time with it."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "two_rounds.xspace.txt")) as f:
        text = "".join(line for line in f if not line.startswith("#"))
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    ms = 1_000_000
    wall = 5_000 * ms
    s = dt.summarize_dir(str(tmp_path), [(wall + 41 * ms, wall + 49 * ms)],
                         wall, cut_wall=[(wall + 40 * ms, wall + 50 * ms)])
    assert s.window_s == pytest.approx(0.09)
    assert s.busy_s == pytest.approx(0.077)
    assert sorted(ns for _, ns in s.idle_gaps) == \
        [1 * ms, 1 * ms, 1 * ms, 2 * ms, 2 * ms, 6 * ms]
    assert "compile" not in {what for what, _ in s.idle_gaps}
    assert s.idle_gaps[0] == ("harness, between rounds", 6 * ms)
