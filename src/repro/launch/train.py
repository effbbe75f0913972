"""End-to-end federated training driver (deliverable (b)).

Runs FedSDD (or any preset baseline) over either
  * the paper's image-classification setting (synthetic CIFAR stand-in,
    ResNet20/56, WRN16-2 or the fast CNN), or
  * any assigned architecture at reduced scale (``--arch``), proving the
    technique is model-agnostic.

Examples:
  PYTHONPATH=src python -m repro.launch.train --preset fedsdd --rounds 10
  PYTHONPATH=src python -m repro.launch.train --preset feddf --model resnet20
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-14b --rounds 3
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.faults import FaultPlan
from repro.core.fedsdd import PRESETS, make_runner
from repro.core.tasks import classification_task, lm_task
from repro.fedckpt.checkpointer import Checkpointer
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="fedsdd", choices=sorted(PRESETS))
    ap.add_argument("--model", default="cnn",
                    choices=["cnn", "resnet20", "resnet56", "wrn16-2"])
    ap.add_argument("--arch", default=None, choices=list(ASSIGNED_ARCHS),
                    help="run the LM task on a reduced assigned architecture "
                         "instead of image classification")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--K", type=int, default=4)
    ap.add_argument("--R", type=int, default=1)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--server-lr", type=float, default=0.05)
    ap.add_argument("--distill-steps", type=int, default=50)
    ap.add_argument("--execution", default="sequential",
                    choices=["sequential", "vectorized"],
                    help="client-execution engine (vectorized = fused "
                         "vmap/shard_map round loop)")
    ap.add_argument("--kd-pipeline", default="fused",
                    choices=["legacy", "fused"],
                    help="server KD phase: the fully-jitted fused pipeline "
                         "(default) or the legacy host-driven parity oracle")
    ap.add_argument("--kd-kernel", default="dense",
                    choices=["dense", "flash"],
                    help="KD kernel family: dense f32-prob cache (oracle) "
                         "or flash — vocab-tiled streaming KL over the "
                         "compressed mean-logit teacher cache")
    ap.add_argument("--kd-head-fusion", action="store_true",
                    help="flash only: stream the student LM-head matmul "
                         "through the vocab tiles too (tasks exposing a "
                         "features/head split — the --arch LM task), so "
                         "the (B, V) student logit row never "
                         "materializes; other tasks fall back to the "
                         "logits path")
    ap.add_argument("--teacher-cache-dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="flash teacher-cache storage precision (default "
                         "bfloat16 — half the dense cache bytes; compute "
                         "stays f32 inside the vocab tiles)")
    ap.add_argument("--overlap", default="off",
                    choices=["off", "async", "fused"],
                    help="overlapped round execution (paper Fig. 2): run "
                         "round t's server KD concurrently with round "
                         "t+1's k>0 local training — async = two device "
                         "dispatches, fused = one combined device program; "
                         "off = back-to-back oracle")
    ap.add_argument("--teacher-dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="teacher-bank storage precision (bfloat16 halves "
                         "bank memory; ensemble compute stays f32)")
    ap.add_argument("--client-store", default="memory",
                    choices=["memory", "spilling"],
                    help="per-client state/data store: memory keeps the "
                         "dense O(C) structures (parity oracle); spilling "
                         "keeps only touched clients resident and spills "
                         "SCAFFOLD controls/data shards through fedckpt, "
                         "so server memory is O(sampled)")
    ap.add_argument("--client-store-dir", default=None,
                    help="spill directory for --client-store spilling "
                         "(default: a fresh temp dir; reuse one to restore "
                         "spilled controls across restarts)")
    ap.add_argument("--client-cache-buckets", type=int, default=64,
                    help="LRU capacity of the store's device tier (rows + "
                         "bucket stacks + hot controls)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest loadable full-state "
                         "checkpoint in --ckpt-dir (crash-safe restart); "
                         "falls back to a fresh run when none exists")
    # deterministic fault injection (core/faults.py): any nonzero rate
    # builds a FaultPlan; --faults alone enables the harness at rate 0
    # (bit-identical to no faults — the chaos-off invariant)
    ap.add_argument("--faults", action="store_true",
                    help="enable the deterministic fault-injection "
                         "harness (seeded by --fault-seed)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round P(client drops out): zero Eq. 2 "
                         "weight, controls never committed")
    ap.add_argument("--straggler-rate", type=float, default=0.0,
                    help="per-round P(client misses the deadline): local "
                         "schedule cut to --straggler-frac of its steps")
    ap.add_argument("--straggler-frac", type=float, default=0.5)
    ap.add_argument("--corrupt-rate", type=float, default=0.0,
                    help="per-round P(client uploads non-finite): caught "
                         "by the isfinite guard, rejected pre-aggregation")
    ap.add_argument("--spill-fail-rate", type=float, default=0.0,
                    help="P(a spill/checkpoint path fails its first I/O "
                         "attempt): exercises fedckpt's bounded retry")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="fault-plan seed (default: --seed); replaying "
                         "the same seed replays the identical fault trace")
    ap.add_argument("--zero-fill", action="store_true",
                    help="ablation: aggregate dropouts as zero weight "
                         "WITHOUT survivor renormalization (the naive "
                         "baseline bench_faults gates against)")
    # Byzantine layer: finite adversarial uploads + robust Eq. 2 defense
    ap.add_argument("--attack", default="none",
                    choices=["none", "sign_flip", "scale", "gauss"],
                    help="Byzantine attack mode for adversarial clients "
                         "(finite uploads that PASS the isfinite guard; "
                         "defend with --aggregator / --clip-norm)")
    ap.add_argument("--attack-rate", type=float, default=0.0,
                    help="per-round P(surviving client is adversarial)")
    ap.add_argument("--attack-scale", type=float, default=10.0,
                    help="attack magnitude (update multiplier / noise std)")
    ap.add_argument("--aggregator", default="mean",
                    choices=["mean", "trimmed_mean", "median", "krum",
                             "multi_krum"],
                    help="group aggregation statistic (core/robust_agg): "
                         "mean = paper Eq. 2 (the oracle); the others are "
                         "Byzantine-robust order statistics")
    ap.add_argument("--trim-frac", type=float, default=0.2,
                    help="assumed per-group adversary fraction for "
                         "trimmed_mean/krum (trim depth / Krum's f)")
    ap.add_argument("--clip-norm", type=float, default=None,
                    help="clip client updates onto this multiple of the "
                         "group's median update norm before aggregating "
                         "(composes with any --aggregator)")
    ap.add_argument("--teacher-trust", action="store_true",
                    help="weight the KD teacher ensemble by cross-teacher "
                         "agreement + degraded-slot bookkeeping, zeroing "
                         "poisoned/stale teachers out of Eq. 3 (fused "
                         "pipeline only)")
    ap.add_argument("--out", default=None, help="write history JSON here")
    args = ap.parse_args()

    if args.arch:
        cfg = get_config(args.arch).reduced()
        task = lm_task(cfg, num_clients=args.clients, seed=args.seed)
        overrides = dict(client_lr=0.01, server_lr=0.01, client_batch=4)
    else:
        task = classification_task(model=args.model, num_clients=args.clients,
                                   alpha=args.alpha, seed=args.seed)
        overrides = dict(client_lr=args.client_lr, server_lr=args.server_lr)

    plan = None
    if args.faults or any(r > 0 for r in (
            args.dropout_rate, args.straggler_rate, args.corrupt_rate,
            args.spill_fail_rate, args.attack_rate)):
        plan = FaultPlan(
            seed=args.seed if args.fault_seed is None else args.fault_seed,
            dropout=args.dropout_rate, straggler=args.straggler_rate,
            straggler_frac=args.straggler_frac, corrupt=args.corrupt_rate,
            attack=args.attack, attack_rate=args.attack_rate,
            attack_scale=args.attack_scale,
            spill_fail=args.spill_fail_rate, zero_fill=args.zero_fill)

    runner = make_runner(
        args.preset, task, faults=plan,
        aggregator=args.aggregator, trim_frac=args.trim_frac,
        clip_norm=args.clip_norm, teacher_trust=args.teacher_trust,
        num_clients=args.clients, participation=args.participation,
        rounds=args.rounds, local_epochs=args.local_epochs,
        distill_steps=args.distill_steps, seed=args.seed,
        execution=args.execution, kd_pipeline=args.kd_pipeline,
        kd_kernel=args.kd_kernel,
        kd_head_fusion=args.kd_head_fusion,
        teacher_cache_dtype=args.teacher_cache_dtype,
        overlap=args.overlap, teacher_dtype=args.teacher_dtype,
        client_store=args.client_store,
        client_store_dir=args.client_store_dir,
        client_cache_buckets=args.client_cache_buckets,
        **({"K": args.K, "R": args.R}
           if PRESETS[args.preset].get("K", 1) > 1 else {}),
        **overrides)

    # two checkpoint families share --ckpt-dir: serving-format model
    # snapshots (ckpt_*, what serve/ loads) and crash-safe full-state
    # resume checkpoints (state_*, written/read by save_state/
    # restore_state — models + teacher bank + controls + history + any
    # in-flight deferred-KD job, all atomic with checksummed meta)
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    state_ckpt = (Checkpointer(args.ckpt_dir, prefix="state")
                  if args.ckpt_dir else None)
    t0 = time.time()
    state = (runner.restore_state(state_ckpt)
             if (args.resume and state_ckpt) else None)
    if state is not None:
        print(f"resumed from round {state.round}", flush=True)
    else:
        state = runner.init_state()
    for _ in range(state.round, args.rounds):
        state = runner.run_round(state)
        rec = state.history[-1]
        msg = f"[{args.preset}] round {state.round}/{args.rounds}"
        if "acc_main" in rec:
            msg += f" acc={rec['acc_main']:.4f}"
        if rec.get("kd_loss_last") is not None:
            msg += f" kd={rec['kd_loss_last']:.4f}"
        # fault/attack/degradation telemetry: every defense layer's
        # round-level ruling surfaces here, not only in history rows
        if rec.get("survivors") is not None:
            msg += f" survivors={len(rec['survivors'])}"
        if rec.get("dropped") or rec.get("rejected"):
            msg += (f" dropped={len(rec.get('dropped', []))}"
                    f" rejected={len(rec.get('rejected', []))}")
        if rec.get("attacked"):
            msg += f" attacked={len(rec['attacked'])}"
        if rec.get("degraded_groups"):
            msg += f" degraded_groups={rec['degraded_groups']}"
        if rec.get("teacher_trust") is not None:
            tw = rec["teacher_trust"]
            msg += (f" trust=[{', '.join(f'{w:.2f}' for w in tw)}]"
                    f" filtered={sum(1 for w in tw if w == 0.0)}")
        print(msg, flush=True)
        if ckpt:
            if state.pending_kd is None:
                ckpt.save(state.round, state.global_models[0],
                          meta={"round": state.round})
            elif state.last_distilled is not None:
                # overlap modes: round t's KD is in flight — checkpoint
                # the newest RESOLVED round (one behind, identical to the
                # off-mode checkpoint); the job itself is persisted by
                # save_state below
                r_done, model = state.last_distilled
                ckpt.save(r_done, model, meta={"round": r_done})
        if state_ckpt:
            runner.save_state(state_ckpt, state)
    # overlap modes defer the last round's KD — drain it so the final
    # model/checkpoint equals the overlap="off" result
    state = runner.finalize(state)
    if ckpt and args.overlap != "off":
        ckpt.save(state.round, state.global_models[0],
                  meta={"round": state.round, "drained": True})
    if state_ckpt:
        # drained state: save_state clears the now-stale pending spill
        runner.save_state(state_ckpt, state)
    print(f"done in {time.time() - t0:.1f}s")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(state.history, f, indent=1, default=str)


if __name__ == "__main__":
    main()
