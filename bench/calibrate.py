#!/usr/bin/env python3
"""Readings from which a cell's limits are set (not part of a benchmark
run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds S] \
        [--out FILE]
    python3 bench/calibrate.py --workload <cell> --write-limits FILE

Every seed, in one process, is a whole run of the cell (``harness.session``:
set-up, a window of ``--seconds``, which runs at least the one timed round
that is compared, and the float32 reference), and gives a sound reading.
For a control seed also the reference in bfloat16 in the program's place.
For a fault seed a run of the program with half of every local minibatch
left out (the loss is the mean over the other half).  A round that
leaves its models unchanged reads 1 by the measure and needs no run; it
is written alongside.  One JSON line per reading, to standard output and
``--out``.

``--write-limits FILE...`` sets ``limits/<cell>.json`` from such files,
and from files of the command's result lines, whose compared numbers are
sound readings too: for each
number the largest sound reading (lower), the smallest reading of the
control or of a fault that reads at least ten times the lower, a state
left unchanged at three times (upper), and ``check.set_limit`` between.
It refuses where a reading of the control or of a fault would pass every
limit.
"""
import argparse
import json
import math
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(BENCH), ".jax_cache")


def half_batch(loss_fn):
    """The fault: the first half of each minibatch, the mean over it."""
    def faulty(params, batch):
        return loss_fn(params, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})
    return faulty


def plant_half_batch(built):
    built.task.loss_fn = half_batch(built.task.loss_fn)


def unchanged(start, rounds):
    """Round records of a step that returns its models unchanged."""
    return [dict(r, models=start) for r in rounds]


def read_rows(workload: str, paths) -> list[dict]:
    """Readings from files of calibrate's lines and of runs' result
    lines; a result line (it has ``checks``) is a sound reading."""
    rows = []
    for path in paths:
        for line in open(path):
            if not line.strip():
                continue
            r = json.loads(line)
            if "checks" in r:
                r = {"workload": workload, "kind": "sound",
                     "seed": f"{os.path.basename(path)}:{len(rows)}",
                     **{k: math.inf if c["value"] is None else c["value"]
                        for k, c in r["checks"].items()}}
            if r["workload"] == workload:
                rows.append(r)
    return rows


def write_limits(workload: str, paths, bench_dir: str = BENCH) -> dict:
    """``limits/<workload>.json`` under ``bench_dir`` from files of
    readings (``read_rows``)."""
    from check import NUMBERS, set_limit
    rows = read_rows(workload, paths)
    by_kind: dict = {}
    for r in rows:
        by_kind.setdefault(r["kind"], []).append(r)
    limits, readings_ = {}, {}
    for name in NUMBERS:
        sound = [r[name] for r in by_kind.get("sound", [])]
        lower = max(sound)
        uppers = {}
        for kind, rs in by_kind.items():
            if kind == "sound":
                continue
            low = min(r[name] for r in rs)
            need = 3 if kind in ("control_bf16", "unchanged") else 10
            if low > lower and low >= need * lower:
                uppers[kind] = low
        upper = min(uppers.values()) if uppers else None
        limits[name] = None if upper is None else set_limit(lower, upper)
        readings_[name] = {
            "lower": lower, "sound_seeds": len(sound), "upper": upper,
            "by_kind": {k: [r[name] for r in rs]
                        for k, rs in by_kind.items()}}
    for kind, rs in by_kind.items():
        for r in rs:
            fails = any(limits[n] is not None and not r[n] <= limits[n]
                        for n in NUMBERS)
            if (kind == "sound") == fails:
                raise SystemExit(
                    f"{kind} seed {r['seed']} {'fails' if fails else 'passes'}"
                    f" the limits {limits}: no limit separates the readings")
    out = {"workload": workload, "limits": limits, "readings": readings_,
           "rule": "check.set_limit: lower^(1/3) * upper^(2/3); null where "
                   "no control or fault separates"}
    dest = os.path.join(bench_dir, "limits", f"{workload}.json")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    return out


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out")
    ap.add_argument("--write-limits", metavar="FILE", nargs="+")
    args = ap.parse_args(argv)
    if args.write_limits:
        print(json.dumps(write_limits(args.workload, args.write_limits)))
        return 0

    import gc
    import jax.numpy as jnp
    import harness
    from check import readings

    out = open(args.out, "a") if args.out else None

    def emit(kind, seed, values, t0, **extra):
        line = json.dumps({"workload": args.workload, "kind": kind,
                           "seed": seed, **values, **extra,
                           "seconds": time.perf_counter() - t0})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    quiet = dict(log=lambda *a: None, warm_shapes=False)
    for seed in dict.fromkeys(args.seeds + args.control_seeds):
        t0 = time.perf_counter()
        s = harness.session(args.workload, seed, args.seconds, False, **quiet)
        res = s.result
        emit("sound", seed, s.values, t0, correct=res["correct"],
             metrics={k: m["value"] for k, m in res["metrics"].items()},
             attempted=res["attempted"], reference_s=s.reference_s)
        emit("unchanged", seed,
             readings(s.start, unchanged(s.start, s.prog_rounds),
                      s.ref_rounds), t0)
        if seed in args.control_seeds:
            t1 = time.perf_counter()
            ctl = harness.reference_rounds(
                harness.load_cell(args.workload), s.built, s.start,
                len(s.ref_rounds), dtype=jnp.bfloat16)
            emit("control_bf16", seed, readings(s.start, ctl, s.ref_rounds),
                 t1)
        del s
        gc.collect()
    for seed in args.fault_seeds:
        t0 = time.perf_counter()
        s = harness.session(args.workload, seed, args.seconds, False,
                            plant=plant_half_batch, **quiet)
        emit("fault_half_batch", seed, s.values, t0,
             correct=s.result["correct"])
        del s
        gc.collect()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
