"""The comparison that decides ``correct``: the program's rounds against
the reference's, number by number, each against its limit.

The program's rounds are the set-up's warm-up rounds and the window's
first timed round, all through the window's own ``run_round`` on the
window's runner and state; the timed round runs the window's programs at
the window's shapes with the teacher bank full.  The reference replays
the same rounds from the same initial weights and data.

Two sound computations of these rounds drift apart: local SGD and
above all KD's 125 momentum steps amplify rounding.  On the chip, the
reference started one float32 ulp away reads up to 0.2 on the KD
student's median leaf after one round, and up to 1.8 on the worst leaf
after four; its KD losses move by a fifth.  So the numbers compared are
ones that such drift leaves steady, and each fails one of the control
(the reference in bfloat16) and the faults:

- ``first_round_gap``: the first round's group models that KD does not
  touch (groups 1..K-1: local SGD and Eq. 2 alone), leaf by leaf, the
  median leaf's gap between the program's norm of its change and the
  reference's;
- ``stuck_share``: after the timed round, the share of the leaves of all
  K models that the program moved, since the start, less than half as
  far as the reference did (a leaf left unmoved, as bfloat16 leaves the
  GroupNorm scales whose updates fall under its rounding);
- ``update_gap``: the timed round's change of the K models (the round's
  pseudo-gradient), the median leaf's gap;
- ``change_gap``: the same for the change from the initial models to the
  end of the timed round.

A norm gap is measured against the reference's norm of that leaf or of
the median leaf, whichever is larger.  Leaves that a round leaves
unmoved in the reference (a change under a thousandth of the median
leaf's) are left out, since rounding alone moves them.  ``readings``
also gives, not compared, the worst leaf's gaps and the KD losses' gap.
"""
from __future__ import annotations

import json
import math
import os

import numpy as np

NUMBERS = ("first_round_gap", "stuck_share", "update_gap", "change_gap")
UNMOVED = 1e-3
STUCK = 0.5


def _leaf_norms(a: list, b: list) -> np.ndarray:
    """Per-leaf L2 norms of ``a - b`` over K models, flattened (K*leaves,)."""
    import jax
    out = []
    for ma, mb in zip(a, b):
        for x, y in zip(jax.tree.leaves(ma), jax.tree.leaves(mb)):
            d = np.asarray(x, np.float64) - np.asarray(y, np.float64)
            out.append(math.sqrt(float(np.sum(d * d))))
    return np.asarray(out)


def _gaps(prog: np.ndarray, ref: np.ndarray) -> tuple:
    """``(median, worst, stuck)`` over the leaves the reference moves:
    the median and the worst leaf's norm gap, and the share of leaves
    the program moved less than ``STUCK`` times as far."""
    keep = ref >= UNMOVED * np.median(ref)
    prog, ref = prog[keep], ref[keep]
    floor = float(np.median(ref))
    gaps = np.abs(prog - ref) / np.maximum(np.maximum(ref, floor), 1e-30)
    if not np.all(np.isfinite(gaps)):
        return math.inf, math.inf, 1.0
    return (float(np.median(gaps)), float(np.max(gaps)),
            float(np.mean(prog < STUCK * ref)))


def readings(start: list, prog_rounds: list[dict],
             ref_rounds: list[dict]) -> dict:
    """The compared numbers and the others.  ``start``: the K initial
    models; each round entry holds ``models`` (K host pytrees after the
    round) and the KD losses ``kd_loss_first`` / ``kd_loss_last``."""
    if len(prog_rounds) != len(ref_rounds):
        raise ValueError("the program and the reference ran different "
                         "numbers of rounds")
    kd = 0.0
    for p, r in zip(prog_rounds, ref_rounds):
        for key in ("kd_loss_first", "kd_loss_last"):
            a, b = p[key], r[key]
            g = abs(a - b) / max(abs(b), 1e-30) if a is not None else math.inf
            kd = max(kd, g if math.isfinite(g) else math.inf)

    def before(rounds):
        return rounds[-2]["models"] if len(rounds) > 1 else start

    upd, upd_worst, _ = _gaps(
        _leaf_norms(prog_rounds[-1]["models"], before(prog_rounds)),
        _leaf_norms(ref_rounds[-1]["models"], before(ref_rounds)))
    chg, chg_worst, stuck = _gaps(
        _leaf_norms(prog_rounds[-1]["models"], start),
        _leaf_norms(ref_rounds[-1]["models"], start))
    first, _, _ = _gaps(
        _leaf_norms(prog_rounds[0]["models"][1:], start[1:]),
        _leaf_norms(ref_rounds[0]["models"][1:], start[1:]))
    return {"first_round_gap": first, "stuck_share": stuck,
            "update_gap": upd, "change_gap": chg,
            "update_worst_leaf": upd_worst, "change_worst_leaf": chg_worst,
            "kd_loss_gap": kd}


def load_limits(bench_dir: str, workload: str) -> dict | None:
    """The cell's limits file (``limits/<workload>.json``): ``limits`` by
    number and the readings they were set from; None where none have
    been set."""
    path = os.path.join(bench_dir, "limits", f"{workload}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def set_limit(lower: float, upper: float) -> float | None:
    """A limit between the sound runs' largest reading and the smallest
    reading of the control or a fault, with more room above the lower
    (fresh seeds read higher than a dozen did): two thirds of the way up
    from the lower to the upper on a log scale.  None where the two are
    less than three times apart: the number separates nothing."""
    if not (math.isfinite(lower) and upper >= 3 * lower):
        return None
    if lower <= 0:
        return upper / 3
    return lower ** (1 / 3) * min(upper, 1e6) ** (2 / 3)


def judge(values: dict, limits: dict | None) -> tuple[bool, dict]:
    """``(correct, table)``: each number beside its limit.  A run is never
    correct where no number has a limit; a number whose limit is null is
    printed and not compared (it separates no fault from sound runs)."""
    table = {}
    ok = limits is not None and any(
        limits.get(name) is not None for name in NUMBERS)
    for name in NUMBERS:
        v = values[name]
        lim = None if limits is None else limits.get(name)
        table[name] = {"value": v, "limit": lim}
        if lim is not None and not v <= lim:     # NaN and inf fail too
            ok = False
    return ok, table
