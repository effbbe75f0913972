"""The harness end to end on the CPU at a tiny size, and the command's
refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import harness
from check import load_limits
from conftest import ROOT, shrink

WORKLOAD = "resnet20-cifar10.cross_device"
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _check_line(result, cell, trace):
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == KEYS and list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1 and line["device"]["kind"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in
            (cell["per_layer"] if trace else cell["end_to_end"])}
    got = set(line["metrics"])
    if trace:      # the trace's device numbers need a device plane
        assert got == want - {"device_idle_share", "round_mfu"}
    else:
        assert got == want
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    for row in line["checks"].values():
        assert set(row) == {"value", "limit"}
    return line


@pytest.mark.parametrize("trace", [False, True])
def test_tiny_run_prints_the_contract_line(own_cache, trace):
    cell = shrink(harness.load_cell(WORKLOAD))
    result = harness.run(WORKLOAD, 2 ** 31 + 12345, 0.5, trace,
                         require_chip=False, cell=cell)
    line = _check_line(result, cell, trace)
    # sound: correct wherever the cell's limits have been set
    has_limits = load_limits(harness.BENCH, WORKLOAD) is not None
    assert line["correct"] is has_limits


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu():
    out = _command(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _command(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
