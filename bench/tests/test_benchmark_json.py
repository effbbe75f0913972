"""BENCHMARK.json against the benchmark's contract of names and files,
and the peak table's refusal of an unknown device."""
import json
import os
import re

import pytest

import harness
from check import NUMBERS, load_limits

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51


def test_names_and_units(bench):
    names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(set(names)) == len(names)


def test_bounds(bench):
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_finds_its_files(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["per_layer"] and len(cell["end_to_end"]) >= 2
        for m in cell["per_layer"]:
            assert m["moves"] in e2e
            assert os.path.exists(os.path.join(
                harness.BENCH, "metrics", f"{m['name']}.py"))
        lim = load_limits(harness.BENCH, w["name"])
        if lim is not None:         # set once the cell is calibrated
            assert set(lim["limits"]) == set(NUMBERS)
    for c in bench["configs"]:
        assert c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        family = harness.family_of(cfg)      # refuses a module it lacks
        assert all(hasattr(family, n) for n in harness.FAMILY_NAMES)


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")


def test_run_seeds_take_any_size():
    assert harness.run_seeds(2 ** 31 + 5) != harness.run_seeds(5)
    assert all(0 <= s < 2 ** 31 for s in harness.run_seeds(2 ** 40))
