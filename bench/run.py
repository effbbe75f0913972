#!/usr/bin/env python3
"""FedSDD benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's federation from its files, runs the set-up's warm-up
rounds, times whole FedSDD rounds through the program's
``make_runner(...).run_round`` for ``--seconds``, then replays the first
rounds on the plain reference and compares.  The last line of standard
output is one JSON object: ``correct``, ``attempted`` (timed rounds),
``failed`` (timed rounds whose models or KD loss were not finite),
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones), ``device``, with ``--trace 1`` a ``breakdown``, and last the
compared numbers beside their limits (``checks``), which also end the
standard error.

Refuses to run (exit 3, no result) where JAX finds no TPU or fewer chips
than the cell asks for, and (exit 2) without the program's ``src/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(os.path.dirname(BENCH), "src")
sys.path[:0] = [BENCH, SRC]
# the persistent compile cache at a fixed path inside the checkout, given
# to the program before JAX reads its configuration
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(BENCH), ".jax_cache")


def finite_or_none(obj):
    """JSON has no inf or NaN: a number that is not finite prints null."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: finite_or_none(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite_or_none(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: the program is not at {SRC}", file=sys.stderr)
        return 2
    from harness import NoChip, run
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    result = finite_or_none(result)
    for name, row in result["checks"].items():
        print(f"check {name} {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
