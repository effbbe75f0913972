"""Limits are set from readings, and refused where nothing separates."""
import json

import pytest

from calibrate import write_limits
from check import NUMBERS, load_limits

CELL = "cell.a"
FIRST, SECOND, *REST = NUMBERS


def _row(kind, seed, first, others):
    return {"workload": CELL, "kind": kind, "seed": seed, FIRST: first,
            **{name: others for name in (SECOND, *REST)}}


def _rows(tmp_path, control):
    rows = [_row("sound", s, 1e-3 * s, 2e-2) for s in (1, 2, 3)]
    rows += [_row("unchanged", s, 1e-3, 1.0) for s in (1, 2, 3)]
    rows += [_row("control_bf16", 1, control, 2e-2)]
    path = tmp_path / "readings.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return str(path)


def test_limits_sit_between_the_readings(tmp_path):
    out = write_limits(CELL, [_rows(tmp_path, control=0.5)], str(tmp_path))
    lim = out["limits"]
    assert 3e-3 < lim[FIRST] < 0.5
    assert 2e-2 < lim[SECOND] < 1.0
    assert load_limits(str(tmp_path), CELL)["limits"] == lim


def test_a_control_that_passes_every_limit_is_refused(tmp_path):
    with pytest.raises(SystemExit, match="control_bf16 seed 1 passes"):
        write_limits(CELL, [_rows(tmp_path, control=4e-3)], str(tmp_path))


def test_result_lines_are_sound_readings(tmp_path):
    values = {FIRST: 5e-3, SECOND: None, **{name: 3e-2 for name in REST}}
    line = {"correct": False, "checks": {
        name: {"value": v, "limit": None} for name, v in values.items()}}
    runs = tmp_path / "lines.jsonl"
    runs.write_text(json.dumps(line) + "\n")
    out = write_limits(CELL, [_rows(tmp_path, control=0.5), str(runs)],
                       str(tmp_path))
    assert out["readings"][FIRST]["lower"] == 5e-3
    assert out["readings"][FIRST]["sound_seeds"] == 4
    assert out["limits"][SECOND] is None      # a run read no number
