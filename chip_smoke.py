#!/usr/bin/env python3
"""Smoke run of the FedSDD main path on a TPU.

Default (one chip): three FedSDD rounds of the paper's ResNet-20 with the
dense KD kernels, then three with the flash KD kernels, through the
library's own entry points (``classification_task`` + ``make_runner``).
Then each main-path Pallas kernel against its ``ref.py`` at the rounds'
shapes, and the first dense round against one round of the sequential
oracle on the same seed.

``--four-chips``: only the client-sharded path, FedSDD rounds with the
clients shard_mapped over a 4-device mesh and a FedDF round whose teacher
precompute is sharded with a psum, each against the same rounds on one
device.

Each engine comparison also runs once with a planted fault and fails
unless that run reads above the comparison's bound.

    python3 chip_smoke.py
    python3 chip_smoke.py --four-chips

It refuses to run without a TPU.  Every check that fails raises, so the
exit code is non-zero; on success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Round times printed here are smoke timings, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the paper's CIFAR-10 setting (FedSDD §4.1) on the synthetic stand-in
TASK = dict(model="resnet20", num_clients=20, alpha=0.1, num_train=8000,
            num_server=2048, server_batch=256)
RUNNER = dict(num_clients=20, participation=0.4, K=4, R=2, client_batch=64,
              temperature=4.0, execution="vectorized",
              client_lr=0.05, server_lr=0.05)     # launch/train.py defaults
# the only cuts: counts of steps (paper: 40 local epochs, 5000 KD steps)
CUTS = dict(local_epochs=1, distill_steps=20)
ROUNDS = 3
# --four-chips checks placement and agreement, not the model: the small
# CNN compiles in seconds where ResNet-20's programs take minutes, and
# eight near-IID clients, all sampled each round, give every round one
# bucket of one shape, so each program compiles once
FOUR_CHIP_TASK = dict(TASK, model="cnn", num_clients=8, alpha=100.0)
FOUR_CHIP_RUNNER = dict(RUNNER, num_clients=8, participation=1.0)

# kernel vs ref.py: f32 on both sides with no matmul, so only the order of
# the V-long reductions and the exp/log implementations differ
KERNEL_RTOL = 1e-4
# 4-chip shard_map vs one-device vmap, on the round's update: both sides
# run the same batched programs at the same precision, so they differ
# only where the sharded program sums across devices (v5e 2x2, CNN:
# 1.8e-5 FedSDD, 9.7e-7 FedDF).  A device's clients that skip training,
# or a psum that loses a device's teachers, reads far above it; each run
# plants both faults and fails unless they do.
SHARDED_RTOL = 1e-3
# vectorized engine vs the sequential oracle, one ResNet-20 round, on the
# round's update (global models after minus before).  At the chip's
# default matmul precision (bf16 MXU operands) the round amplifies any
# rounding difference to a few percent of the update.  On a v5e:
# starting either engine from models moved by one f32 ulp moves its
# round by 3.7e-2 to 3.8e-2, the scan and stepped lowerings of the same
# engine differ by 1.4e-2, and vectorized vs sequential reads 4.1e-2,
# at that floor (on the CPU in f32: 4.5e-5).  The bound sits 2.4x above
# the floor.  It sees a fault on a client that holds much of its group's
# data: each run plants one, the round's largest client left untrained,
# and fails unless it reads above the bound.  A fault on a small client
# stays under the floor; the CPU parity tests hold the engine logic to
# 1e-6.  "highest" precision would lower the floor, but compiling
# ResNet-20's round at it took ten minutes and more than the 40 GiB of
# host memory of a one-chip host.
ENGINE_RTOL = 1e-1

T0 = time.perf_counter()


def emit(tag: str, **fields) -> None:
    fields["elapsed_s"] = time.perf_counter() - T0
    print(f"{tag}: {json.dumps(fields, default=float)}", flush=True)


class CompileClock:
    """Seconds and count of XLA backend compiles while active, and the
    programs found in the persistent compilation cache instead."""
    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _hit(self, event, **_):
        if event == self.HIT:
            self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        jax.monitoring.register_event_listener(self._hit)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        jax.monitoring.unregister_event_listener(self._hit)


def require_tpu(count: int):
    """The devices to run on; exits when JAX has no TPU (never a CPU
    fallback) or fewer than ``count`` chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX found {devs[0].platform} "
                         "devices only, and this script never runs on them")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devs)}")
    return devs


def _max_abs_diff(a, b=None) -> float:
    """max |a − b| (or max |a|) over all leaves of two pytrees, in f64."""
    import jax
    import numpy as np
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b) if b is not None else [0.0] * len(la)
    return max((float(np.max(np.abs(np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64))))
                for x, y in zip(la, lb)), default=0.0)


def max_rel_err(got, want) -> float:
    """max |got − want| over max |want|."""
    return _max_abs_diff(got, want) / max(_max_abs_diff(want), 1e-30)


def update_rel_err(got, want, start) -> float:
    """max |got − want| over max |want − start|: how far two runs from the
    same ``start`` disagree, as a share of the update they made."""
    return _max_abs_diff(got, want) / max(_max_abs_diff(want, start), 1e-30)


def check(name: str, err: float, tol: float) -> None:
    if not err <= tol:          # also catches NaN
        raise AssertionError(f"{name}: error {err:.3e} exceeds {tol:.0e}")


def check_detects(name: str, err: float, tol: float) -> None:
    """Raise unless a planted fault reads above the bound meant to catch
    it: a bound a real fault can pass under checks nothing."""
    if not err > tol:
        raise AssertionError(f"{name}: the planted fault reads {err:.3e}, "
                             f"within the bound {tol:.0e}")


def make_task(seed: int, **overrides):
    from repro.core.tasks import classification_task
    return classification_task(**{**TASK, **overrides}, seed=seed)


def _host(models):
    import jax
    return jax.device_get(list(models))


def run_rounds(task, rounds: int, seed: int, preset: str = "fedsdd",
               spy=None, **cfg) -> dict:
    """Run ``rounds`` rounds through ``make_runner``; raises unless every
    KD loss and every global model is finite.  Returns host copies of the
    global models at the start and after each round, and timings (round 1
    includes its compiles).  ``spy(runner)`` runs before the first round."""
    import jax
    import numpy as np
    from repro.core.fedsdd import make_runner
    runner = make_runner(preset, task, rounds=rounds, seed=seed, **cfg)
    state = runner.init_state()
    if spy is not None:
        spy(runner)
    models = [_host(state.global_models)]
    seconds, kd_losses, round_1_compiles = [], [], None
    with CompileClock() as clock:
        for r in range(rounds):
            t0 = time.perf_counter()
            state = runner.run_round(state)
            jax.block_until_ready(state.global_models)
            seconds.append(time.perf_counter() - t0)
            emit("round", preset=preset, execution=runner.cfg.execution,
                 kd_kernel=runner.cfg.kd_kernel,
                 client_sharding=runner.cfg.client_sharding, round=r + 1,
                 seconds=seconds[-1])
            if not r:
                round_1_compiles = clock.count
            kd = state.history[-1].get("kd_loss_last")
            kd_losses.append(kd)
            if kd is not None and not math.isfinite(kd):
                raise AssertionError(f"round {r + 1}: KD loss is {kd}")
            models.append(_host(state.global_models))
            for k, model in enumerate(models[-1]):
                if not all(np.isfinite(x).all()
                           for x in jax.tree.leaves(model)):
                    raise AssertionError(
                        f"round {r + 1}: global model {k} is not finite")
    return dict(runner=runner, state=state, models=models, round_s=seconds,
                kd_loss=kd_losses, compile_s=clock.seconds,
                compiles=clock.count, cache_hits=clock.cache_hits,
                compiles_after_round_1=(clock.count - round_1_compiles
                                        if rounds > 1 else None))


def rounds_phase(task, kd_kernel: str, seed: int, rounds: int = ROUNDS,
                 **cfg) -> dict:
    """One-device FedSDD rounds with one KD kernel family."""
    emit("begin", phase=f"rounds[{kd_kernel}]")
    out = run_rounds(task, rounds, seed, kd_kernel=kd_kernel,
                     client_sharding="vmap", **cfg)
    steady = out["round_s"][1:]
    emit(f"rounds[{kd_kernel}]", compile_s=out["compile_s"],
         compiles=out["compiles"], cache_hits=out["cache_hits"],
         compiles_after_round_1=out["compiles_after_round_1"],
         round_s=out["round_s"],
         steady_round_s_smoke_timing_not_a_benchmark=(
             sum(steady) / len(steady) if steady else None),
         kd_loss=out["kd_loss"])
    return out


def _mosaic(fn, *args) -> None:
    """Raise unless ``fn`` lowers to a compiled Pallas (Mosaic) kernel."""
    import jax
    if "tpu_custom_call" not in jax.jit(fn).lower(*args).as_text():
        raise AssertionError("kernel did not lower to a TPU custom call")


def kernels_phase(task, seed: int, compiled: bool = True, K: int = 4,
                  R: int = 2, clients_per_group: int = 2,
                  temperature: float = 4.0) -> dict:
    """Each main-path kernel against its ``ref.py`` at the rounds' shapes:
    Eq. 2 over the model's leaves for K groups of sampled clients, the
    ensemble softmax over K·R teachers and the whole server set, and the
    dense and flash KD loss with their gradients on one server batch.
    ``compiled`` also requires that every kernel lowered to Mosaic."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.kd_loss import ops as kd_ops, ref as kd_ref
    from repro.kernels.weight_avg import ops as w_ops, ref as w_ref
    emit("begin", phase="kernels")
    if not kd_ops.pallas_active():
        raise AssertionError("the Pallas kernels are not active")
    tau = temperature
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = clients_per_group
    clients = jax.vmap(task.init_fn)(jax.random.split(ks[0], K * n))
    stacked = jax.tree.map(lambda x: x.reshape((K, n) + x.shape[1:]),
                           clients)
    w = jax.random.uniform(ks[1], (K, n), minval=15.0, maxval=1000.0)
    batches = task.server_batches
    B = batches[0]["x"].shape[0]
    V = task.logits_fn(task.init_fn(ks[0]), batches[0]).shape[-1]
    t = jax.random.normal(ks[2], (K * R, len(batches), B, V)) * 3.0
    s = jax.random.normal(ks[3], (B, V)) * 3.0
    zt = jnp.mean(t[:, 0], axis=0).astype(jnp.bfloat16)
    p_flash = jax.nn.softmax(zt.astype(jnp.float32) / tau, axis=-1)
    lse = kd_ops.teacher_cache_lse(zt, tau)
    flash = lambda x, l: kd_ops.flash_kd_loss(x, zt, tau, teacher_lse=l)

    if compiled:
        leaf = jax.tree.leaves(stacked)[0]
        _mosaic(w_ops.group_weighted_average,
                leaf.reshape(K, n, -1), w)
        _mosaic(lambda x: kd_ops.ensemble_softmax_many(x, tau), t)
        _mosaic(jax.value_and_grad(lambda x: kd_ops.kd_loss(x, p_flash,
                                                             tau)), s)
        _mosaic(jax.value_and_grad(lambda x: flash(x, lse)), s)
        _mosaic(jax.value_and_grad(lambda x: flash(x, None)), s)
    probs = kd_ops.ensemble_softmax_many(t, tau)
    p = probs[0]
    errs = {
        "weight_avg.group": max_rel_err(
            w_ops.group_weighted_average_pytree(stacked, w),
            jax.tree.map(lambda x: w_ref.group_weighted_average_ref(
                x.reshape(K, n, -1), w).reshape((K,) + x.shape[2:]),
                stacked)),
        "kd.ensemble_softmax": max_rel_err(
            probs, kd_ref.ensemble_softmax_ref(
                t.reshape(K * R, -1, V), tau).reshape(probs.shape)),
        "kd.kd_loss_fwd": max_rel_err(kd_ops.kd_loss(s, p, tau),
                                      kd_ref.kd_loss_ref(s, p, tau)),
        "kd.kd_loss_bwd": max_rel_err(
            jax.grad(kd_ops.kd_loss)(s, p, tau),
            kd_ref.kd_loss_grad_ref(s, p, tau)),
        "flash.fwd": max_rel_err(flash(s, None),
                                 kd_ref.kd_loss_ref(s, p_flash, tau)),
        "flash.fwd_lse": max_rel_err(flash(s, lse),
                                     kd_ref.kd_loss_ref(s, p_flash, tau)),
        "flash.bwd": max_rel_err(jax.grad(flash)(s, lse),
                                 kd_ref.kd_loss_grad_ref(s, p_flash, tau)),
    }
    emit("kernels", max_rel_err=errs, rtol=KERNEL_RTOL,
         shapes=dict(eq2=f"{K}x{n} clients x ResNet leaves",
                     ensemble=list(t.shape), kd=[B, V]))
    for name, err in errs.items():
        check(name, err, KERNEL_RTOL)
    return errs


def oracle_phase(task, seed: int, vectorized: dict, **cfg) -> float:
    """Round 1 of a vectorized ``rounds_phase`` (``vectorized``, dense KD)
    against one round of the sequential oracle on the same seed."""
    emit("begin", phase="oracle")
    cfg = {k: v for k, v in cfg.items() if k != "execution"}
    want = run_rounds(task, 1, seed, execution="sequential", kd_kernel="dense",
                      client_sharding="vmap", **cfg)["models"]
    err = update_rel_err(vectorized["models"][1], want[1], want[0])
    faulty = run_rounds(task, 1, seed, execution="vectorized",
                        kd_kernel="dense", client_sharding="vmap",
                        spy=_spy_skip_training(_largest_client),
                        **cfg)["models"]
    fault = update_rel_err(faulty[1], want[1], want[0])
    emit("oracle", vectorized_vs_sequential_update_rel_err=err,
         planted_fault_update_rel_err=fault, rtol=ENGINE_RTOL)
    check("vectorized vs sequential", err, ENGINE_RTOL)
    check_detects("vectorized, largest client untrained, vs sequential",
                  fault, ENGINE_RTOL)
    return err


def _spy_skip_training(pick):
    """Plant a fault: each round, the clients that ``pick(bucket plans)``
    names as ``{bucket: rows}`` skip local training (their step mask is
    zeroed), as if the engine had dropped them."""
    def spy(runner):
        import dataclasses
        eng = runner._make_engine()
        train_round = eng.train_round

        def faulty(rplan, *args, **kw):
            plans = list(rplan.plans)
            for i, rows in pick(plans).items():
                plans[i] = dataclasses.replace(
                    plans[i], step_mask=plans[i].step_mask.at[rows].set(False))
            return train_round(dataclasses.replace(rplan, plans=plans),
                               *args, **kw)

        eng.train_round = faulty
    return spy


def _largest_client(plans) -> dict:
    """The round's client with the largest shard: ``{bucket: row}``."""
    b = max(range(len(plans)), key=lambda i: plans[i].sizes.max())
    return {b: int(plans[b].sizes.argmax())}


def _spy_drop_teachers(n_dev: int):
    """Plant a fault: the last of ``n_dev`` devices' share of the teacher
    stack enters the sharded precompute as all-zero models (zero logits),
    as if the psum had lost that device's teachers."""
    def spy(runner):
        import jax
        pipe = runner._kd_pipeline()
        to_mesh = pipe._to_mesh

        def faulty(stack, batches):
            m = jax.tree.leaves(stack)[0].shape[0]
            keep = m - -(-m // n_dev)
            return to_mesh(jax.tree.map(lambda x: x.at[keep:].set(0), stack),
                           batches)

        pipe._to_mesh = faulty
    return spy


def _devices_of(tree) -> set:
    import jax
    return {d for x in jax.tree.leaves(tree) for d in x.sharding.device_set}


def _spy_engine(spans: set):
    """Record the devices that each sharded bucket's outputs span."""
    def spy(runner):
        eng = runner._make_engine()
        run = eng.run_prepared

        def recorded(args):
            out = run(args)
            spans.update(_devices_of(out))
            return out

        eng.run_prepared = recorded
    return spy


def _spy_precompute(spans: set):
    """Record the devices that the sharded teacher cache spans before it
    is brought to the server's device."""
    def spy(runner):
        pipe = runner._kd_pipeline()
        to_home = pipe._to_home

        def recorded(cache, batches):
            spans.update(_devices_of(cache))
            return to_home(cache, batches)

        pipe._to_home = recorded
    return spy


def four_chip_phase(task, seed: int, n_dev: int = 4, fedsdd_rounds: int = 2,
                    **cfg) -> dict:
    """Client-sharded rounds against the same rounds on one device:
    FedSDD with local training shard_mapped over the ('clients',) mesh,
    and FedDF whose client-teacher precompute is shard_mapped with a psum.
    Raises unless the results agree, the sharded programs' outputs span
    ``n_dev`` devices, and a planted fault of the sharded path (FedSDD:
    the last device's clients skip training; FedDF: the psum loses the
    last device's teachers) reads above the bound."""
    emit("begin", phase="four_chips")
    feddf = {k: v for k, v in cfg.items() if k not in ("K", "R")}
    # the last device's rows of every bucket (its shard, when the bucket's
    # client count divides over the devices, as it does here)
    skip_last_shard = _spy_skip_training(lambda plans: {
        i: slice(len(p.sizes) - len(p.sizes) // n_dev, None)
        for i, p in enumerate(plans)})
    out = {}
    for preset, kw, rounds, spy, fault_spy in (
            ("fedsdd", cfg, fedsdd_rounds, _spy_engine, skip_last_shard),
            ("feddf", feddf, 1, _spy_precompute, _spy_drop_teachers(n_dev))):
        spans: set = set()
        sharded = run_rounds(task, rounds, seed, preset, spy=spy(spans),
                             client_sharding="shard_map", **kw)
        single = run_rounds(task, rounds, seed, preset,
                            client_sharding="vmap", **kw)
        faulty = run_rounds(task, rounds, seed, preset, spy=fault_spy,
                            client_sharding="shard_map", **kw)
        want = single["models"]
        err = update_rel_err(sharded["models"][-1], want[-1], want[0])
        fault = update_rel_err(faulty["models"][-1], want[-1], want[0])
        out[preset] = dict(update_rel_err=err,
                           planted_fault_update_rel_err=fault, rounds=rounds,
                           output_devices=len(spans),
                           round_s=sharded["round_s"],
                           round_s_single=single["round_s"],
                           kd_loss=sharded["kd_loss"],
                           kd_loss_single=single["kd_loss"])
        emit(f"four_chips[{preset}]", rtol=SHARDED_RTOL, **out[preset])
        check(f"{preset} shard_map vs vmap", err, SHARDED_RTOL)
        check_detects(f"{preset} shard_map with a planted fault vs vmap",
                      fault, SHARDED_RTOL)
        if len(spans) != n_dev:
            raise AssertionError(
                f"{preset}: the sharded program's outputs span "
                f"{len(spans)} devices, not {n_dev}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded path on 4 chips and "
                         "compare it with the same rounds on one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)
    task_kw, runner_kw = ((FOUR_CHIP_TASK, FOUR_CHIP_RUNNER)
                          if args.four_chips else (TASK, RUNNER))
    emit("config", task=task_kw, runner=runner_kw, rounds=ROUNDS,
         cuts=dict(CUTS, paper=dict(local_epochs=40, distill_steps=5000)),
         seed=args.seed, compile_cache=cache_dir, device=devs[0].device_kind,
         devices=len(devs))
    t0 = time.perf_counter()
    task = make_task(args.seed, **task_kw)
    emit("task", seconds=time.perf_counter() - t0,
         client_sizes=sorted(len(d[1]) for d in task.client_data))
    cfg = {**runner_kw, **CUTS}
    if args.four_chips:
        four_chip_phase(task, args.seed, n_dev=n_chips, **cfg)
    else:
        dense = rounds_phase(task, "dense", args.seed, **cfg)
        rounds_phase(task, "flash", args.seed, **cfg)
        kernels_phase(task, args.seed, K=RUNNER["K"], R=RUNNER["R"],
                      temperature=RUNNER["temperature"])
        oracle_phase(task, args.seed, dense, **cfg)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
