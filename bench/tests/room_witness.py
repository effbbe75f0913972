#!/usr/bin/env python3
"""How much device memory the round reference takes for a model that the
chip holds only a few times: the token family widened to a given size,
K=4 and R=2, run on the chip (not a benchmark cell).

    python3 bench/tests/room_witness.py --bench DIR --d-model D \
        --mode session|reference --seed S [--out FILE]

``DIR`` is the root of a checkout whose ``bench/`` and ``src/`` are
imported, so that one checkout's reference can be set against another's.
``session`` runs ``harness.session`` with the program pinned to its
sequential engine, one client a group and a bfloat16 teacher bank, so
that the program itself fits; ``reference`` runs the reference alone
from the same start, so that the process's peak is the reference's.
At every reference program call the device's ``bytes_in_use`` is read
before and after the call.  One JSON line: the model's bytes, those
readings in model copies, the process's peak, and where a
``RESOURCE_EXHAUSTED`` (or any other error) stopped the run.  It reads
the chip's own memory counters, so it runs only on a TPU: without one
it exits with an error and prints no line.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def widened(cells, d_model: int) -> dict:
    """The token cell at vocabulary 2,048 and width ``d_model``: 20
    clients of 100 sequences of 16 tokens, 4 sampled a round."""
    cell = cells.token_cell({"execution": "sequential",
                             "teacher_dtype": "bfloat16"})
    cell["config"].update(vocab=2048, d_model=d_model, topics=2,
                          num_train=2000, num_server=256, distill_steps=3)
    cell["mix"]["population"].update(partition="iid", num_clients=20)
    cell["mix"]["job"].update(participation=0.2, client_batch=16,
                              server_batch=64)
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bench", required=True)
    ap.add_argument("--d-model", type=int, required=True)
    ap.add_argument("--mode", choices=("session", "reference"),
                    required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 17)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.bench)
    sys.path[:0] = [os.path.join(root, "bench"), os.path.join(root, "src"),
                    HERE]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    import jax
    import cells
    import harness
    import reference

    try:
        dev = harness.require_chips(1)[0]
    except harness.NoChip as e:
        print(f"room_witness: {e}", file=sys.stderr)
        return 2
    calls = []
    line = {"bench": args.bench, "mode": args.mode, "d_model": args.d_model,
            "seed": args.seed, "platform": dev.platform,
            "device": dev.device_kind, "phase": "build"}

    def in_use():
        return int(dev.memory_stats()["bytes_in_use"])

    class Watched(reference.Reference):
        """The reference with ``bytes_in_use`` read around each program."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            line["phase"] = "reference"
            for name in ("_local_sum", "_teacher_share", "_kd"):
                setattr(self, name, self._watch(name, getattr(self, name)))

        @staticmethod
        def _watch(name, program):
            def call(*a):
                before = in_use()
                out = jax.block_until_ready(program(*a))
                calls.append((name, before, in_use()))
                return out
            return call

    reference.Reference = Watched
    t0 = time.perf_counter()
    with cells.token_family():
        cell = widened(cells, args.d_model)
        cfg = cell["config"]
        shapes = jax.eval_shape(harness.family_of(cfg).make_init(cfg, 0),
                                jax.random.PRNGKey(0))
    model = sum(a.size * 4 for a in jax.tree.leaves(shapes))
    line["model_bytes"] = model
    try:
        with cells.token_family():
            if args.mode == "session":
                line["phase"] = "program"
                s = harness.session(cell["name"], args.seed, 0.0, False,
                                    cell=cell, warm_shapes=False)
                line["correct"] = s.result["correct"]
                line["program_peak_bytes"] = \
                    s.result["device"]["memory_peak_bytes"]
            else:
                b = harness.build(cell, args.seed, log=lambda *a: None)
                start = harness.initial_models(cell, b)
                for _ in harness.reference_rounds(
                        cell, b, start, cell["mix"]["warmup_rounds"] + 1):
                    pass
        line["phase"] = "done"
    except Exception as e:          # the reading is where the run stopped
        line["error"] = f"{type(e).__name__}: {str(e)[:400]}"
    stats = dev.memory_stats()
    line["peak_bytes"] = int(stats["peak_bytes_in_use"])
    line["limit_bytes"] = int(stats["bytes_limit"])
    line["seconds"] = time.perf_counter() - t0
    line["calls"] = len(calls)
    line["peak_copies"] = line["peak_bytes"] / model
    if calls:
        line["max_in_use_bytes"] = max(max(b, a) for _, b, a in calls)
        line["max_in_use_copies"] = line["max_in_use_bytes"] / model
        by = {}
        for name, b, a in calls:
            by[name] = max(by.get(name, 0), b, a)
        line["max_in_use_copies_by_program"] = {
            k: v / model for k, v in by.items()}
    print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
