"""Phase-graph round execution: overlap server KD with k>0 local training.

The paper's headline scalability claim (Fig. 2, §3.2) is that FedSDD's
server-side distillation adds ~zero wall-clock to a round: only the MAIN
global model (group 0) consumes the KD output, so groups k>0 can start
round t+1's local training while round t's KD is still running.
``core/scheduler.py`` *models* that overlap; this module *executes* it.

A round is an explicit phase plan::

    plan ─▶ kd_dispatch ─▶ train_rest ─▶ kd_resolve ─▶ train_main
                 │              │
                 └── overlap ───┘
        ─▶ finish_local ─▶ aggregate ─▶ push ─▶ kd_emit ─▶ record

The trick that makes the overlap an EXACT reordering of the sequential
oracle: round t's KD job (student = round t's raw group-0 aggregate,
teachers = the bank state right after round t's push) has exactly one
consumer — group 0's round-t+1 broadcast.  So the executor *defers* it:
the job is emitted as a ``PendingKD`` at the end of round t and runs
during round t+1's k>0 local training, which depends only on round t's
raw aggregates.  ``FederatedRunner.finalize`` (called by ``run``) drains
the last pending job, so the post-drain state is allclose to
``overlap="off"`` — the parity oracle — for every config.

Overlap modes (``FedConfig.overlap``):

  off    back-to-back phases, KD inline — bit-parity with the classic
         round loop; the oracle the parity suite pins the others to.
  async  the KD program (``KDPipeline.distill_async``) is dispatched from
         a dedicated worker thread at emit time, the k>0 training
         dispatches issue from the main thread, and the only host sync is
         the resolve at the point group 0 actually needs the distilled
         model.  On backends with async device dispatch the worker merely
         enqueues; on XLA:CPU — where jax dispatch is synchronous and
         executes ON the calling thread (``jax_cpu_enable_async_dispatch``
         defaults off) — the worker thread IS the concurrency, so the KD
         program and the training programs genuinely run on separate
         cores.
  fused  the KD scan and every k>0 bucket-training scan are emitted as
         subgraphs of ONE jitted device program (``FusedKDLocalProgram``)
         so XLA schedules the overlap itself — the TPU lowering, where
         both sides are single ``lax.scan`` programs.  Requires the
         vectorized engine with scan step mode on both sides; otherwise
         it falls back to the async dispatch strategy (the CPU default,
         where the engine's stepped escape hatch rules out a single
         program).

Deferral eligibility: ``distill_target == "main"`` and ``K > 1`` — with
one group (FedDF/FedBE) or all-model distillation (Table 6 "basic KD"),
every group consumes the KD output and the round structurally serializes
(exactly the paper's argument for why those baselines cannot hide KD);
such configs run their KD inline in every overlap mode and remain
parity-trivial.
"""
from __future__ import annotations

import concurrent.futures as cf
import json
import os
import time
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.analysis.spans import span
from repro.analysis.sync import allowed_sync

PyTree = Any

OVERLAP_MODES = ("off", "async", "fused")


@dataclass
class PendingKD:
    """A deferred round-t KD job: emitted at the end of round t, dispatched
    alongside round t+1's k>0 local training, resolved before group 0's
    round-t+1 broadcast (or at drain).  ``dispatched`` is either the
    ``(student_out, losses)`` device refs (fused path) or the worker
    thread's Future of them (async path)."""
    round_idx: int
    student: PyTree                 # round t's raw group-0 aggregate
    teachers: PyTree                # (M, ...) stacked snapshot (gathered —
    #                                 safe across later in-place bank pushes)
    record: dict                    # round t's history record, patched late
    dispatched: Optional[Any] = None
    teacher_weights: Optional[Any] = None   # (M,) trust weights or None

    def result(self) -> tuple:
        if isinstance(self.dispatched, cf.Future):
            return self.dispatched.result()
        return self.dispatched


# ---------------------------------------------------------------------
# pending-KD spill/restore: checkpoints taken mid-round with a deferred
# KD in flight persist the JOB (its inputs), not its output — KD is
# deterministic given (student, teachers), so re-running it at restore
# reproduces the drained result exactly.  The in-flight device
# computation (if any) is simply abandoned.
# ---------------------------------------------------------------------
def spill_pending_kd(directory: str, pending: PendingKD) -> str:
    """Serialize a deferred KD job through ``fedckpt``: one ``.npz`` with
    the student + the (M, ...) teacher snapshot, plus a ``.json`` sidecar
    (round_idx, the partially-filled history record, M).  Returns the npz
    path ``pending_kd_r{round:05d}.npz``."""
    from repro.fedckpt.checkpointer import save_json, save_pytree
    path = os.path.join(directory,
                        f"pending_kd_r{pending.round_idx:05d}.npz")
    tree = {"student": pending.student, "teachers": pending.teachers}
    if pending.teacher_weights is not None:
        tree["teacher_weights"] = jnp.asarray(pending.teacher_weights,
                                              jnp.float32)
    save_pytree(path, tree)
    meta = {
        "round_idx": pending.round_idx,
        "record": {k: v for k, v in pending.record.items()},
        "num_teachers": int(  # lint-ok: RA101 static shape read, no sync
            jax.tree.leaves(pending.teachers)[0].shape[0]),
        "has_teacher_weights": pending.teacher_weights is not None,
    }
    save_json(path.replace(".npz", ".json"), meta)
    return path


def restore_pending_kd(path: str, student_like: PyTree) -> PendingKD:
    """Rebuild a spilled ``PendingKD`` (``dispatched=None`` — the resolve
    re-dispatches it).  ``student_like`` supplies the model structure;
    the teacher snapshot restores as f32 (``fedckpt`` spills f32
    containers; a bf16-held bank round-trips losslessly and the KD
    pipeline casts teachers f32 at the forward boundary anyway)."""
    from repro.fedckpt.checkpointer import load_pytree
    with open(path.replace(".npz", ".json")) as f:
        meta = json.load(f)
    m = int(meta["num_teachers"])
    like = {
        "student": student_like,
        "teachers": jax.tree.map(
            lambda x: jnp.zeros((m,) + x.shape, jnp.float32), student_like),
    }
    # sidecars from before trust weighting have no flag — restore as None
    has_w = bool(meta.get("has_teacher_weights", False))
    if has_w:
        like["teacher_weights"] = jnp.zeros((m,), jnp.float32)
    tree = load_pytree(path, like)
    return PendingKD(round_idx=int(meta["round_idx"]),
                     student=tree["student"], teachers=tree["teachers"],
                     record=dict(meta["record"]),
                     teacher_weights=tree.get("teacher_weights"))


class FusedKDLocalProgram:
    """KD scan + k>0 bucket-training scans as ONE jitted device program.

    Tracing calls straight through the pipeline's and the engine's own
    jitted subprograms, so the fused program is by construction the same
    math as the two separate dispatches — XLA just sees both subgraphs at
    once and is free to interleave them.  Programs are cached per bucket
    count; shape changes (partial participation) retrace like any jit.
    """

    def __init__(self, pipe, engine):
        self.pipe = pipe
        self.engine = engine
        self._fns: dict[int, Any] = {}

    def __call__(self, student, teachers, batches, bucket_args,
                 weights=None):
        # trust-weighted and uniform cache builds are distinct compiled
        # programs (jnp.mean vs weighted einsum are not bit-identical) —
        # key the cache on both the bucket count and the weights' presence
        n = (len(bucket_args), weights is not None)
        if n not in self._fns:
            pipe, engine = self.pipe, self.engine

            if weights is None:
                def prog(student, teachers, batches, bargs):
                    cache = pipe.precompute_cache(teachers, batches)
                    st, losses = pipe._scan_fn(False)(student, batches,
                                                      cache)
                    outs = [engine.scan_fn()(*a) for a in bargs]
                    return st, losses, outs
            else:
                def prog(student, teachers, batches, bargs, w):
                    cache = pipe.precompute_cache(teachers, batches,
                                                  weights=w)
                    st, losses = pipe._scan_fn(False)(student, batches,
                                                      cache)
                    outs = [engine.scan_fn()(*a) for a in bargs]
                    return st, losses, outs

            self._fns[n] = jax.jit(prog)
        args = (student, teachers, batches, list(bucket_args))
        if weights is not None:
            args += (jnp.asarray(weights, jnp.float32),)
        return self._fns[n](*args)

    def jit_programs(self) -> dict:
        """Jitted fused programs by label (see ``analysis.TraceGuard``)."""
        return {f"fused/kd_local_b{n}{'_w' if w else ''}": fn
                for (n, w), fn in self._fns.items()}


class RoundExecutor:
    """Drives one federated round as the phase plan above.

    Engine-specific work (local training, aggregation, the engine-native
    inline-KD block) is delegated to a per-round ``ops`` adapter built by
    the runner (``fedsdd._SequentialRoundOps`` / ``_VectorizedRoundOps``);
    the executor owns the phase ordering, the PendingKD state machine and
    the per-phase wall-clock record the benches feed back into the
    scheduler model.
    """

    def __init__(self, runner):
        self.runner = runner
        self.cfg = runner.cfg
        self._fused: FusedKDLocalProgram | None = None
        self._worker: cf.ThreadPoolExecutor | None = None

    # ------------------------------------------------------- predicates
    def kd_active(self, t: int) -> bool:
        cfg = self.cfg
        return cfg.distill_target != "none" and t > cfg.distill_warmup_rounds

    def defer_eligible(self) -> bool:
        """True when KD's only consumer is next round's group-0 broadcast."""
        cfg = self.cfg
        return (cfg.overlap != "off" and cfg.distill_target == "main"
                and cfg.K > 1)

    # ------------------------------------------------------ KD plumbing
    def _pipe(self):
        return self.runner._kd_pipeline()

    def dispatch(self, pending: PendingKD) -> None:
        """Hand the deferred KD program to the dispatch worker (no host
        sync).  The single-thread worker keeps KD jobs ordered; on
        sync-dispatch backends (XLA:CPU) it also CARRIES the execution,
        which is what overlaps it with the main thread's training
        dispatches."""
        if pending.dispatched is None:
            if self._worker is None:
                self._worker = cf.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="kd-dispatch")
            pipe, batches = self._pipe(), self.runner.task.server_batches
            pending.dispatched = self._worker.submit(
                pipe.distill_async, pending.student, pending.teachers,
                batches, teacher_weights=pending.teacher_weights)

    def resolve_pending(self, state) -> None:
        """Block on the deferred KD and install its output as the main
        global model; completes the emitting round's history record."""
        pending = state.pending_kd
        if pending is None:
            return
        self.dispatch(pending)
        student, losses = pending.result()
        pending.record.update(self._pipe().losses_info(losses))
        if pending.teacher_weights is not None:
            import numpy as _np
            with allowed_sync("per-round teacher-trust weights into the "
                              "history record"):
                pending.record["teacher_trust"] = [
                    round(float(w), 4)
                    for w in _np.asarray(pending.teacher_weights)]
        state.global_models[0] = student
        state.last_distilled = (pending.round_idx, student)
        if self.runner.task.eval_fn is not None:
            with allowed_sync("per-round eval of the distilled main model"):
                pending.record["acc_main"] = \
                    self.runner.task.eval_fn(student)
        state.pending_kd = None

    def close(self) -> None:
        """Release the dispatch worker (recreated on the next dispatch).
        Called from ``FederatedRunner.finalize`` so drained runners leave
        no idle thread behind."""
        if self._worker is not None:
            self._worker.shutdown(wait=True)
            self._worker = None

    def _fused_capable(self, ops) -> bool:
        return (self.cfg.overlap == "fused" and ops.fused_capable()
                and self._pipe().scan_capable())

    def _fused_program(self) -> FusedKDLocalProgram:
        if self._fused is None:
            self._fused = FusedKDLocalProgram(self._pipe(),
                                              self.runner._make_engine())
        return self._fused

    # ------------------------------------------------------------ round
    def execute(self, state, t: int, active_count: int, ops):
        """Run round t's phases over the engine adapter ``ops``."""
        cfg, task = self.cfg, self.runner.task
        t_start = time.perf_counter()
        rec: dict[str, Any] = {"round": t, "active": active_count}

        if not self.defer_eligible():
            # ---- back-to-back phase order (the off-mode oracle) ----
            self.resolve_pending(state)     # only on an overlap->off edge
            ops.train("all")
            ops.finish_local()
            new_globals = ops.aggregate()
            rec.update(getattr(ops, "fault_info", {}))
            ops.push(t, state)
            with span("fedsdd.wait.local"):
                jax.block_until_ready(jax.tree.leaves(new_globals[0])[0])
            rec["t_local"] = time.perf_counter() - t_start
            if self.kd_active(t):
                t0 = time.perf_counter()
                rec.update(ops.inline_kd(new_globals))
                with span("fedsdd.wait.kd"):
                    jax.block_until_ready(jax.tree.leaves(new_globals[0])[0])
                rec["t_kd"] = time.perf_counter() - t0
            state.global_models = new_globals
            if task.eval_fn is not None:
                with allowed_sync("per-round eval of the main model"):
                    rec["acc_main"] = task.eval_fn(new_globals[0])
            state.history.append(rec)
            state.round = t
            return state

        # ---- overlapped phase order ----
        pending = state.pending_kd
        if pending is not None and self._fused_capable(ops):
            # ONE device program: pending KD scan + k>0 bucket scans
            pipe = self._pipe()
            batches = pipe.batches_for(task.server_batches)
            fused = self._fused_program()

            def run_buckets(bucket_args):
                st, losses, outs = fused(pending.student, pending.teachers,
                                         batches, bucket_args,
                                         weights=pending.teacher_weights)
                pending.dispatched = (st, losses)
                return outs

            ops.train("rest", run_buckets=run_buckets)
            self.dispatch(pending)   # no k>0 clients this round: plain path
        else:
            if pending is not None:
                self.dispatch(pending)   # re-assert: async emits eagerly
            ops.train("rest")

        self.resolve_pending(state)      # main model of round t-1 finalized
        ops.train("main")                # group 0 starts from KD output
        ops.finish_local()
        new_globals = ops.aggregate()
        rec.update(getattr(ops, "fault_info", {}))
        ops.push(t, state)
        state.global_models = new_globals
        state.round = t
        if self.kd_active(t):
            # emit round t's KD as a pending job; async dispatches NOW so
            # the program overlaps the host-side planning of round t+1 too
            with span("fedsdd.kd.teachers"):
                teachers = ops.kd_teachers(new_globals)
                weights = self.runner._teacher_trust_weights(state, teachers)
            state.pending_kd = PendingKD(
                round_idx=t, student=new_globals[0],
                teachers=teachers, record=rec, teacher_weights=weights)
            if cfg.overlap == "async":
                self.dispatch(state.pending_kd)
        elif task.eval_fn is not None:
            with allowed_sync("per-round eval of the main model"):
                rec["acc_main"] = task.eval_fn(new_globals[0])
        state.history.append(rec)
        return state
