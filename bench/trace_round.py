#!/usr/bin/env python3
"""Where a cell's round goes: its program spans, untraced and traced, and
the profiler trace reduced by program and by span.

    python3 bench/trace_round.py --workload <cell> --seed <n> [--rounds 2]
        [--out DIR]

On a TPU: builds the cell and runs its set-up as ``bench/run.py`` does
(warm-up rounds, then every local-training shape of the rounds to come),
then runs the same ``--rounds`` rounds three times from the same state:
untraced, traced, untraced.  Prints one JSON object: each span's host
seconds per round in the untraced and the traced runs; the cost of a
span with no profiler active, and the spans a round opens; and from the
trace the device time per program per round, the programs per round, the
share of in-round idle time that a program span covers, and the longest
idle gaps, named, with the names of the kernel (custom-call) operations.
With ``--out`` the trace is kept there (hundreds of MB for two ResNet-20
rounds).
"""
import argparse
import copy
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
# the compile cache of bench/run.py, which this command's programs share
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(BENCH), ".jax_cache")


def span_cost(n: int = 20000) -> float:
    """Seconds one span takes with no profiler active, inside a round."""
    from repro.analysis.spans import collect_round, span
    with collect_round(round=0):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("fedsdd.cost"):
                pass
        spent = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(n):
            pass
        spent -= time.perf_counter() - t0
    return spent / n


def _mean_spans(records) -> dict[str, float]:
    out: dict[str, float] = {}
    for rec in records:
        for name, s in rec.get("spans", {}).items():
            out[name] = out.get(name, 0.0) + s / len(records)
    return out


def run_rounds(b, state, rounds: int, trace_dir=None):
    """``rounds`` rounds from a copy of ``state`` (its own teacher bank and
    history), each ended in ``block_until_ready``, in the harness's
    window and round spans; traced where ``trace_dir`` is given."""
    import jax

    import device_trace
    state = dataclasses.replace(
        state, global_models=list(state.global_models),
        ensemble=copy.deepcopy(state.ensemble), history=[])
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    seconds = []
    with jax.profiler.TraceAnnotation(device_trace.WINDOW_SPAN):
        for _ in range(rounds):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(device_trace.ROUND_SPAN):
                state = b.runner.run_round(state)
                jax.block_until_ready(state.global_models)
            seconds.append(time.perf_counter() - t0)
    if trace_dir:
        jax.profiler.stop_trace()
    return state.history, seconds


def kernel_ops(path: str) -> list[str]:
    """The distinct device operations that are custom calls (the Pallas
    kernels), by name."""
    from jax.profiler import ProfileData
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    names |= {ev.name for ev in line.events
                              if "custom-call" in ev.name}
    return sorted(names)


def trace_rounds(cell: dict, seed: int, rounds: int, out: str | None = None,
                 require_chip: bool = True, log=print) -> dict:
    import jax

    import harness
    import program_trace as pt_lib
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_chip:
        harness.require_chips(cell["chips"])
    b = harness.build(cell, seed, log=log)
    state, *_ = harness.warm_up(cell, b, cell["mix"]["warmup_rounds"],
                                log=log)
    harness.warm_window_shapes(b, state, rounds, log=log)
    log(f"set-up done; rounds {state.round + 1}..{state.round + rounds}")
    # the same rounds three times: untraced (its cache misses warm the
    # client store for the others), traced, untraced
    _, first_s = run_rounds(b, state, rounds)
    tmp = tempfile.mkdtemp(prefix="trace-round-")
    with harness.CompileLog() as compiles:
        wall0 = time.time_ns()
        traced, traced_s = run_rounds(b, state, rounds, tmp)
    untraced, untraced_s = run_rounds(b, state, rounds)
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    result = {
        "device": jax.devices()[0].device_kind,
        "rounds": rounds,
        "round_s": {"first_untraced": first_s, "traced": traced_s,
                    "untraced": untraced_s},
        "spans_untraced": _mean_spans(untraced),
        "spans_traced": _mean_spans(traced),
        "counts": [rec.get("counts") for rec in untraced],
        "span_cost_s": span_cost(),
        "xplane_bytes": os.path.getsize(path),
    }
    pt = pt_lib.read(path, compiles.spans, wall0)
    if pt is not None:
        gaps = pt_lib.idle_gaps(pt)
        result.update(
            spans_per_round=len(pt.spans) / pt.n_rounds,
            busy_s=pt_lib.busy_seconds(pt),
            program_s=pt_lib.program_seconds(pt),
            named_share=pt_lib.named_share(pt),
            round_programs=pt_lib.round_programs(pt),
            idle_attributed_share=pt_lib.idle_attributed_share(pt),
            idle_gaps=[[what, ns / 1e9] for what, ns in gaps],
            kernel_ops=[n[:300] for n in kernel_ops(path)])
    if out:
        os.makedirs(out, exist_ok=True)
        shutil.copy(path, os.path.join(out, "rounds.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import harness
    log = (lambda *a: print(*a, file=sys.stderr, flush=True))
    try:
        result = trace_rounds(harness.load_cell(args.workload), args.seed,
                              args.rounds, args.out, log=log)
    except harness.NoChip as e:
        print(f"trace_round: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
