"""FedSDD (Algorithm 1) and every baseline in the paper, as one runner.

A single ``FedConfig`` spans the paper's whole experimental matrix — each
baseline is a preset:

    FedAvg    = K=1, distill_target='none'
    FedProx   = FedAvg + local_algo='fedprox'
    SCAFFOLD  = FedAvg + local_algo='scaffold'
    FedDF     = K=1, distill_target='main', ensemble_source='clients'
    FedBE-ish = FedDF + ensemble_extra_sampled>0 (Gaussian posterior samples)
    Fed-ensemble = K>1, distill_target='none'
    FedSDD    = K>1, R≥1, distill_target='main', ensemble_source='aggregated'
    Table-6 "basic distillation"   = FedSDD + distill_target='all'
    Table-6 "codistillation warmup"= FedSDD + distill_warmup_rounds>0

The runner is generic over a task (init/loss/logits fns + per-client
datasets), so the same loop drives the paper's ResNets and the assigned
transformer architectures.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.spans import collect_round, count, span
from repro.core import distillation as dist
from repro.core import engine as vec_engine
from repro.core import faults as faults_lib
from repro.core import round_plan
from repro.core.aggregation import (
    fedavg_aggregate, fedavg_aggregate_grouped_masked, secure_aggregate,
)
from repro.core.client_store import ClientStore, make_client_store
from repro.core.faults import FaultPlan
from repro.core.grouping import assign_groups, sample_clients
from repro.core.robust_agg import AGGREGATORS, robust_aggregate_grouped
from repro.distill import KDPipeline, TeacherBank
from repro.optim.optimizers import (
    Optimizer, apply_updates, scaffold_new_control, sgd, with_fedprox,
    with_scaffold,
)
from repro.utils.pytree import (
    tree_all_finite, tree_concat, tree_stack, tree_zeros_like,
)

PyTree = Any


# =====================================================================
# configuration
# =====================================================================
@dataclass(frozen=True)
class FedConfig:
    # structure (paper defaults, §4.1)
    num_clients: int = 20
    participation: float = 0.4
    rounds: int = 100
    K: int = 4                      # number of global models
    R: int = 1                      # temporal-ensembling checkpoints
    # local training
    local_epochs: int = 40
    client_lr: float = 0.8
    client_batch: int = 64
    client_momentum: float = 0.0
    local_algo: str = "fedavg"      # fedavg | fedprox | scaffold
    fedprox_mu: float = 0.001
    # distillation
    distill_target: str = "main"    # main | all | none
    ensemble_source: str = "aggregated"   # aggregated | clients
    ensemble_extra_sampled: int = 0       # FedBE-style posterior samples
    distill_steps: int = 5000
    server_lr: float = 0.1
    server_batch: int = 256
    temperature: float = 4.0
    distill_warmup_rounds: int = 0  # codistillation-style KD skip
    # execution engine
    execution: str = "sequential"   # sequential (oracle) | vectorized
    client_sharding: str = "auto"   # auto | vmap | shard_map
    kd_pipeline: str = "fused"      # fused (one program) | legacy (oracle)
    # KD kernel family: "dense" consumes the f32 ensemble-PROB cache (the
    # parity oracle); "flash" stores the mean teacher LOGIT cache
    # (teacher_cache_dtype, bf16 default = half the bytes) and fuses
    # τ-softmax + log-softmax + KL into streaming vocab tiles
    kd_kernel: str = "dense"        # dense (oracle) | flash
    teacher_cache_dtype: Optional[str] = None  # None (auto) | float32 | bfloat16
    # head-fused flash KD: stream the student LM-head matmul through the
    # vocab tiles too (tasks exposing features_fn/head_fn — the LM task;
    # tasks without the split fall back to the logits path)
    kd_head_fusion: bool = False
    # overlapped round execution (paper Fig. 2): run round t's server KD
    # concurrently with round t+1's k>0 local training — an exact
    # reordering; ``off`` is the back-to-back oracle.  See core/round_plan.
    overlap: str = "off"            # off (oracle) | async | fused
    # teacher-bank storage precision: "bfloat16" stores the K·R ring bf16
    # on device (f32 ensemble compute), doubling R at the same memory
    teacher_dtype: Optional[str] = None   # None (keep) | float32 | bfloat16
    # client-state/data store (core/client_store.py): "memory" keeps the
    # dense O(C) structures (the parity oracle); "spilling" keeps only
    # touched clients resident — SCAFFOLD controls and data shards spill
    # through fedckpt, so server memory is O(sampled), not O(C)
    client_store: str = "memory"    # memory (oracle) | spilling
    client_store_dir: Optional[str] = None  # spill directory (spilling only)
    # LRU capacity of the store's device tier (rows + bucket stacks +
    # hot controls)
    client_cache_buckets: int = 64
    # deterministic fault injection (core/faults.py): None = the clean
    # world; a plan with all-zero rates is bit-identical to None on both
    # execution paths (the chaos-off invariant tests pin)
    faults: Optional[FaultPlan] = None
    # Byzantine-robust Eq. 2 (core/robust_agg.py): "mean" is the paper's
    # weighted mean and the bit-identical oracle; the order statistics
    # defend finite adversarial uploads that pass the isfinite guard.
    # clip_norm (optional) clips every survivor's update onto
    # clip_norm × the group's median update norm BEFORE the statistic —
    # it composes with any aggregator, including mean.
    aggregator: str = "mean"  # mean | trimmed_mean | median | krum | multi_krum
    trim_frac: float = 0.2          # assumed adversary fraction per group
    clip_norm: Optional[float] = None
    # trust-weighted teacher filtering (distill/pipeline.trust_weights):
    # weight the KD ensemble by cross-teacher agreement on the probe
    # batch + the bank's degraded-round bookkeeping, so a poisoned or
    # carried-forward teacher is down-weighted out of Eq. 3's mean logit
    teacher_trust: bool = False
    # misc
    secure_aggregation: bool = False
    seed: int = 0

    def validate(self) -> None:
        """Reject inconsistent configs with actionable ``ValueError``s.

        Deliberately not ``assert``: assertions vanish under ``python -O``
        and a silently-accepted bad config trains the wrong experiment.
        """
        def _require(ok: bool, msg: str) -> None:
            if not ok:
                raise ValueError(f"invalid FedConfig: {msg}")

        def _choice(name: str, allowed: tuple) -> None:
            _require(getattr(self, name) in allowed,
                     f"{name}={getattr(self, name)!r} not in {allowed}")

        _require(self.K >= 1, f"K={self.K} but need at least one global "
                 "model (K>=1)")
        _require(self.R >= 1, f"R={self.R} but the temporal ensemble "
                 "needs at least the current round (R>=1)")
        _choice("distill_target", ("main", "all", "none"))
        _choice("ensemble_source", ("aggregated", "clients"))
        _choice("local_algo", ("fedavg", "fedprox", "scaffold"))
        _choice("execution", ("sequential", "vectorized"))
        _choice("client_sharding", ("auto", "vmap", "shard_map"))
        _choice("kd_pipeline", ("legacy", "fused"))
        _choice("kd_kernel", ("dense", "flash"))
        if self.kd_head_fusion:
            _require(self.kd_kernel == "flash",
                     "kd_head_fusion streams the LM-head matmul through "
                     "the flash vocab tiles — the dense prob path "
                     "materializes full student rows by construction; set "
                     "kd_kernel='flash'")
        _choice("teacher_cache_dtype", (None, "float32", "bfloat16"))
        if self.teacher_cache_dtype is not None:
            _require(self.kd_kernel == "flash",
                     "teacher_cache_dtype selects the flash mean-logit "
                     "cache precision — the dense oracle's prob cache is "
                     "f32-only; set kd_kernel='flash' or drop the dtype")
            _require(self.kd_pipeline == "fused",
                     "the compressed teacher cache lives in the fused "
                     "KDPipeline; the legacy host loop keeps f32 rows, so "
                     "a cache dtype there would be silently inert")
        _choice("overlap", ("off", "async", "fused"))
        _choice("teacher_dtype", (None, "float32", "bfloat16"))
        if self.overlap != "off":
            _require(self.kd_pipeline == "fused",
                     "overlapped rounds dispatch KD as one device "
                     "program — the host-driven kd_pipeline='legacy' loop "
                     "cannot overlap; set kd_pipeline='fused' or "
                     "overlap='off'")
        if self.distill_target != "none" and self.ensemble_source == "clients":
            _require(not self.secure_aggregation,
                     "client-model ensembles (FedDF/FedBE) are "
                     "incompatible with secure aggregation — the FedSDD "
                     "privacy argument (§3.2); use "
                     "ensemble_source='aggregated'")
        _choice("client_store", ("memory", "spilling"))
        _require(self.client_cache_buckets >= 1,
                 f"client_cache_buckets={self.client_cache_buckets} but "
                 "the store needs at least one resident bucket")
        if self.client_store_dir is not None:
            _require(self.client_store == "spilling",
                     "client_store_dir names the spill directory, which "
                     "only the spilling store uses; set "
                     "client_store='spilling' or drop the directory")
        if self.faults is not None:
            self.faults.validate()
            _require(not (self.faults.active and self.secure_aggregation),
                     "client faults under secure aggregation need mask "
                     "recovery for the dropped clients' pairwise shares "
                     "(Bonawitz et al. §7) — not simulated here; disable "
                     "secure_aggregation or zero the client fault rates")
        _choice("aggregator", AGGREGATORS)
        _require(0.0 <= self.trim_frac < 0.5,
                 f"trim_frac={self.trim_frac} must be in [0, 0.5) — "
                 "trimming half or more from each end leaves no clients "
                 "(use aggregator='median' for the 50% limit)")
        if self.clip_norm is not None:
            _require(self.clip_norm > 0,
                     f"clip_norm={self.clip_norm} must be > 0 — it is the "
                     "clip radius as a multiple of the group's median "
                     "update norm (None disables clipping)")
        if self.aggregator != "mean" or self.clip_norm is not None:
            _require(not self.secure_aggregation,
                     "robust aggregation needs the individual client "
                     "updates, but secure aggregation makes every single "
                     "upload indistinguishable from noise by design "
                     "(Bonawitz et al.) — order statistics over masked "
                     "uploads are meaningless; use aggregator='mean' "
                     "without clip_norm, or disable secure_aggregation")
            _require(self.faults is None or not self.faults.zero_fill,
                     "zero_fill is an ablation of the WEIGHTED mean "
                     "(unrenormalized Eq. 2); robust order statistics "
                     "have no weight mass to zero-fill — drop zero_fill "
                     "or use aggregator='mean'")
        if self.teacher_trust:
            _require(self.kd_pipeline == "fused",
                     "teacher_trust computes agreement weights over the "
                     "stacked teacher bank inside the fused KD cache "
                     "build; the legacy host loop has no weighted cache — "
                     "set kd_pipeline='fused'")
            _require(self.distill_target != "none",
                     "teacher_trust weights the KD ensemble, but "
                     "distill_target='none' never distills — enable KD or "
                     "drop teacher_trust")


PRESETS: dict[str, dict] = {
    "fedavg":       dict(K=1, distill_target="none"),
    "fedprox":      dict(K=1, distill_target="none", local_algo="fedprox"),
    "scaffold":     dict(K=1, distill_target="none", local_algo="scaffold"),
    "feddf":        dict(K=1, distill_target="main", ensemble_source="clients"),
    "fedbe":        dict(K=1, distill_target="main", ensemble_source="clients",
                         ensemble_extra_sampled=10),
    "fed_ensemble": dict(K=4, distill_target="none"),
    "fedsdd":       dict(K=4, R=1, distill_target="main",
                         ensemble_source="aggregated"),
    "fedsdd_basic_kd": dict(K=4, R=1, distill_target="all",
                            ensemble_source="aggregated"),
}


def make_config(preset: str, **overrides) -> FedConfig:
    base = dict(PRESETS[preset])
    base.update(overrides)
    return FedConfig(**base)


# =====================================================================
# task plumbing
# =====================================================================
@dataclass
class FedTask:
    """What the runner needs to know about the learning problem."""
    init_fn: Callable[[jax.Array], PyTree]
    loss_fn: Callable[[PyTree, Any], tuple[jnp.ndarray, dict]]
    logits_fn: Callable[[PyTree, Any], jnp.ndarray]
    client_data: Sequence[Any]           # per-client (x, y) numpy pairs
    server_batches: Sequence[Any]        # unlabeled batches for KD
    make_batch: Callable[[Any, np.ndarray], Any]  # (client_ds, idx) -> batch
    eval_fn: Optional[Callable[[PyTree], float]] = None
    # optional features/head split of logits_fn (LM tasks): enables the
    # head-fused flash-KD path (FedConfig.kd_head_fusion) where the
    # student (B, V) logit row never materializes.  Contract:
    # logits_fn(p, b) == features_fn(p, b) @ W (+ b) for head_fn(p)=(W, b)
    features_fn: Optional[Callable[[PyTree, Any], jnp.ndarray]] = None
    head_fn: Optional[Callable[[PyTree], tuple]] = None


@dataclass
class FedState:
    round: int
    global_models: list[PyTree]          # index 0 = main global model
    ensemble: TeacherBank                # device-resident K·R teacher ring
    # per-client state/data tier (core/client_store.py) — ALL per-client
    # access (shards, padded device rows, SCAFFOLD controls) goes here
    store: Optional[ClientStore] = None
    scaffold_c_global: Optional[PyTree] = None
    history: list[dict] = field(default_factory=list)
    # overlap modes: the deferred round-t KD job (runs during round t+1's
    # k>0 local training; drained by FederatedRunner.finalize), and the
    # newest RESOLVED (round_idx, distilled main model) — what a mid-run
    # checkpoint should store, since global_models[0] is the raw aggregate
    # until its KD resolves
    pending_kd: Optional[round_plan.PendingKD] = None
    last_distilled: Optional[tuple] = None


# =====================================================================
# runner
# =====================================================================
class FederatedRunner:
    def __init__(self, cfg: FedConfig, task: FedTask):
        cfg.validate()
        self.cfg = cfg
        self.task = task
        self._train_step = None
        self._engine = None
        self._kd_pipe = None
        self._exec = None
        if cfg.faults is not None and cfg.faults.spill_fail > 0:
            # chaos I/O: route every fedckpt write/read through the
            # plan's deterministic first-attempt failure injector
            from repro.fedckpt import checkpointer as _fedckpt
            _fedckpt.set_io_fault_injector(cfg.faults.io_injector())

    # ---- init ----------------------------------------------------------
    def init_state(self) -> FedState:
        cfg = self.cfg
        key = jax.random.PRNGKey(cfg.seed)
        models = [self.task.init_fn(k) for k in jax.random.split(key, cfg.K)]
        state = FedState(
            round=0,
            global_models=models,
            ensemble=TeacherBank(cfg.K, cfg.R, dtype=cfg.teacher_dtype),
            store=make_client_store(cfg, self.task),
        )
        if cfg.local_algo == "scaffold":
            state.store.init_controls(models[0])
            state.scaffold_c_global = tree_zeros_like(models[0])
        return state

    # ---- local training --------------------------------------------------
    def _make_optimizer(self) -> Optimizer:
        cfg = self.cfg
        base = sgd(cfg.client_lr, momentum=cfg.client_momentum)
        if cfg.local_algo == "fedprox":
            return with_fedprox(base, cfg.fedprox_mu)
        if cfg.local_algo == "scaffold":
            return with_scaffold(base, cfg.client_lr)
        return base

    def _train_batch_step(self):
        if self._train_step is None:
            optimizer = self._make_optimizer()
            loss_fn = self.task.loss_fn

            @jax.jit
            def step(params, opt_state, batch):
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state, loss

            self._train_step = (optimizer, step)
        return self._train_step

    def _store(self, state: FedState) -> ClientStore:
        """The state's client store; states constructed by hand (tests,
        benches) get one lazily so every per-client access has a home."""
        if state.store is None:
            state.store = make_client_store(self.cfg, self.task)
            if self.cfg.local_algo == "scaffold":
                state.store.init_controls(state.global_models[0])
        return state.store

    def _local_train_scheduled(self, params: PyTree, client_id: int,
                               state: FedState, idx_rows,
                               control_out: Optional[dict] = None) -> PyTree:
        """One client's local training over a PRE-DRAWN minibatch schedule.

        The schedule (one index row per optimization step) comes from
        ``engine.build_round_entries``, which draws rng in the exact
        sequential-oracle order — pre-drawing is what lets the overlap
        executor train group 0 *after* groups k>0 without perturbing the
        rng stream.

        ``control_out``: when given, the SCAFFOLD control update is
        STASHED there instead of committed to the store — fault-injected
        rounds must hold commits back until the isfinite guard has ruled
        on the client's upload (a rejected client's control never lands).
        """
        cfg = self.cfg
        store = self._store(state)
        ds = store.client_shard(client_id)
        optimizer, step = self._train_batch_step()
        opt_state = optimizer.init(params)
        if cfg.local_algo == "fedprox":
            opt_state["anchor"] = params
        if cfg.local_algo == "scaffold":
            opt_state = opt_state._replace(
                c_local=store.get_control(client_id),
                c_global=state.scaffold_c_global)
        w_start = params
        for row in idx_rows:
            batch = self.task.make_batch(ds, row)
            params, opt_state, _ = step(params, opt_state, batch)
        if cfg.local_algo == "scaffold":
            new_c = scaffold_new_control(opt_state, w_start, params,
                                         cfg.client_lr)
            if control_out is None:
                store.put_control(client_id, new_c)
            else:
                control_out[int(client_id)] = new_c
        return params

    def local_train(self, params: PyTree, client_id: int, state: FedState,
                    rng: np.random.Generator) -> tuple[PyTree, int]:
        """One client's full local training (cfg.local_epochs over its shard)."""
        cfg = self.cfg
        n = self._store(state).num_examples(client_id)
        bs = min(cfg.client_batch, n)
        rows = []
        for _ in range(cfg.local_epochs):
            order = rng.permutation(n)
            rows += [order[i:i + bs] for i in range(0, n - bs + 1, bs)]
        return self._local_train_scheduled(params, client_id, state, rows), n

    # ---- distillation phase (Eq. 3-4), shared by both round paths --------
    def _kd_pipeline(self) -> KDPipeline:
        if self._kd_pipe is None:
            from repro.launch.mesh import make_client_mesh
            cfg = self.cfg
            self._kd_pipe = KDPipeline(
                self.task.logits_fn, steps=cfg.distill_steps,
                lr=cfg.server_lr, temperature=cfg.temperature,
                mesh=make_client_mesh(),
                teacher_sharding=cfg.client_sharding,
                kd_kernel=cfg.kd_kernel,
                cache_dtype=cfg.teacher_cache_dtype,
                features_fn=self.task.features_fn,
                head_fn=self.task.head_fn,
                head_fusion=cfg.kd_head_fusion)
        return self._kd_pipe

    def _executor(self) -> round_plan.RoundExecutor:
        if self._exec is None:
            self._exec = round_plan.RoundExecutor(self)
        return self._exec

    def _teacher_trust_weights(self, state, teacher_stack):
        """(M,) trust weights for this round's KD ensemble, or None when
        ``teacher_trust`` is off.  Cross-teacher agreement on the probe
        batch (``KDPipeline.trust_weights``) plus the bank's degraded-slot
        bookkeeping — a poisoned or carried-forward teacher is weighted
        (down to exactly) zero out of the Eq. 3 mean."""
        if not self.cfg.teacher_trust or teacher_stack is None:
            return None
        degraded = (state.ensemble.degraded_mask_stacked()
                    if self.cfg.ensemble_source == "aggregated" else None)
        return self._kd_pipeline().trust_weights(
            teacher_stack, self.task.server_batches, degraded_mask=degraded)

    def _distill_models(self, new_globals: list[PyTree], teachers,
                        *, stacked: bool,
                        stacked_students: PyTree | None = None,
                        teacher_weights=None) -> dict:
        """Distill the round's targets in place; returns the kd record.

        ``teachers``: a list of member pytrees (``stacked=False``) or one
        pytree whose leaves carry the leading (M, ...) member axis.  The
        fused pipeline always consumes the stacked form (the teacher bank
        hands it over without re-stacking); the legacy oracle takes either.
        ``stacked_students``: the (K, ...) stack of ``new_globals`` when
        the caller already has one (the vectorized engine) — skips a
        re-stack on the ``distill_target='all'`` path.
        ``teacher_weights``: optional (M,) trust weights (fused only —
        validate() pins teacher_trust to the fused pipeline).
        """
        cfg = self.cfg
        if cfg.kd_pipeline == "fused":
            pipe = self._kd_pipeline()
            tstack = teachers if stacked else tree_stack(list(teachers))
            if cfg.distill_target == "all":
                if stacked_students is None:
                    stacked_students = tree_stack(new_globals)
                out, kd_info = pipe.distill_all(
                    stacked_students, tstack, self.task.server_batches,
                    teacher_weights=teacher_weights)
                new_globals[:] = vec_engine.unstack_models(out)
            else:
                new_globals[0], kd_info = pipe.distill(
                    new_globals[0], tstack, self.task.server_batches,
                    teacher_weights=teacher_weights)
            if teacher_weights is not None:
                kd_info = dict(kd_info)
                from repro.analysis.sync import allowed_sync
                with allowed_sync("per-round teacher-trust weights into "
                                  "the history record"):
                    kd_info["teacher_trust"] = [
                        round(float(w), 4)
                        for w in np.asarray(teacher_weights)]
            return kd_info
        kd_info = {}
        targets = range(cfg.K) if cfg.distill_target == "all" else (0,)
        for k in targets:
            new_globals[k], kd_info = dist.distill(
                new_globals[k], teachers, self.task.server_batches,
                self.task.logits_fn,
                steps=cfg.distill_steps, lr=cfg.server_lr,
                temperature=cfg.temperature, stacked_teachers=stacked,
                kd_kernel=cfg.kd_kernel,
                features_fn=self.task.features_fn,
                head_fn=self.task.head_fn,
                head_fusion=cfg.kd_head_fusion)
        return kd_info

    # ---- one round (Algorithm 1) -----------------------------------------
    def run_round(self, state: FedState) -> FedState:
        """One round as an explicit phase plan (core/round_plan.py): the
        executor owns phase ordering + the deferred-KD state machine, the
        per-engine ops adapter below owns the engine-native phase bodies.

        The round's program spans and counters (``analysis/spans.py``)
        land in its history record as ``spans`` (host seconds by span),
        ``span_parents`` (the same seconds by enclosing span) and
        ``counts``.
        """
        cfg = self.cfg
        t = state.round + 1
        with collect_round(round=t) as col, span("fedsdd.round"):
            with span("fedsdd.sample"):
                rng = np.random.default_rng(cfg.seed * 100_000 + t)
                active = sample_clients(cfg.num_clients, cfg.participation,
                                        rng)
                groups = assign_groups(active, cfg.K, rng)
                ops_cls = (_VectorizedRoundOps
                           if cfg.execution == "vectorized"
                           else _SequentialRoundOps)
                ops = ops_cls(self, state, groups, rng, t)
            state = self._executor().execute(state, t, len(active), ops)
        state.history[-1].update(spans=col.seconds, span_parents=col.parents,
                                 counts=col.counts)
        return state

    def finalize(self, state: FedState) -> FedState:
        """Drain the deferred KD job (overlap modes).  After this the
        state is exactly what ``overlap='off'`` would have produced —
        ``run`` calls it automatically; manual ``run_round`` loops must
        call it once at the end."""
        self._executor().resolve_pending(state)
        self._executor().close()
        return state

    # ---- pending-KD spill/restore (checkpoints taken mid-round) ----------
    def spill_pending(self, state: FedState, directory: str) -> str | None:
        """Persist an in-flight deferred KD job next to a mid-round
        checkpoint (overlap modes) so it survives the process instead of
        being silently lost; returns the npz path, or None when no KD is
        pending."""
        if state.pending_kd is None:
            return None
        return round_plan.spill_pending_kd(directory, state.pending_kd)

    def restore_pending(self, state: FedState,
                        path: str) -> round_plan.PendingKD:
        """Reload a spilled deferred KD job into ``state``; the next
        ``resolve`` (or ``finalize``) re-runs it from its inputs — KD is
        deterministic, so the result equals the never-interrupted drain.
        The restored record is rebound to the live history record of the
        same round when present, so late KD/eval fields still land."""
        pending = round_plan.restore_pending_kd(path, state.global_models[0])
        if state.history and state.history[-1].get("round") == \
                pending.round_idx:
            state.history[-1].update(pending.record)
            pending.record = state.history[-1]
        else:
            state.history.append(pending.record)
        state.pending_kd = pending
        return pending

    # ---- crash-safe full-state checkpoints --------------------------------
    def save_state(self, ckpt, state: FedState) -> str:
        """One atomic full-state checkpoint at a round boundary.

        Captures everything round t+1 reads: the K global models, the
        teacher-bank ring (+ slot map/cursor/degraded log), SCAFFOLD's
        server control, the spilling store's running control sum
        (checkpointed verbatim — an incrementally-maintained fp sum
        differs in rounding from one rebuilt file-by-file), the history,
        and the in-flight deferred-KD job spilled as its INPUTS.  Hot
        store state is flushed to the spill directory in the same
        breath.  ``restore_state`` + continuing the round loop then
        reproduces the uninterrupted run bit-for-bit (with
        client_store='spilling' over a persistent directory when
        per-client SCAFFOLD controls are in play — the in-memory store
        has nowhere durable to keep them).
        """
        store = self._store(state)
        tree: dict = {"models": tree_stack(state.global_models)}
        bank_tree, bank_meta = state.ensemble.export_state()
        if bank_tree is not None:
            tree["bank"] = bank_tree
        if state.scaffold_c_global is not None:
            tree["c_global"] = state.scaffold_c_global
        if store.control_sum is not None:
            tree["ctrl_sum"] = store.control_sum
        store.flush()
        pend_path = self.spill_pending(state, ckpt.dir)
        # a resolved job's stale spill must not outlive it: a restore
        # would re-run KD over a model that already consumed it
        import glob
        for p in sorted(glob.glob(os.path.join(ckpt.dir,
                                               "pending_kd_r*.npz"))):
            if p != pend_path:
                for q in (p, p.replace(".npz", ".json")):
                    if os.path.exists(q):
                        os.remove(q)
        meta = {
            "round": int(state.round),
            "keys": sorted(tree),
            "bank": bank_meta,
            "history": state.history,
            "pending": (os.path.basename(pend_path) if pend_path else None),
        }
        return ckpt.save(state.round, tree, meta=meta)

    def _state_like(self, meta: dict) -> dict:
        """Shape/dtype template for one full-state checkpoint (which
        optional sections exist comes from the meta's ``keys``)."""
        cfg = self.cfg
        template = self.task.init_fn(jax.random.PRNGKey(cfg.seed))
        keys = set(meta.get("keys", ()))
        like: dict = {"models": jax.tree.map(
            lambda x: jnp.zeros((cfg.K,) + x.shape, x.dtype), template)}
        if "bank" in keys:
            like["bank"] = TeacherBank(
                cfg.K, cfg.R, dtype=cfg.teacher_dtype).bank_like(template)
        if "c_global" in keys:
            like["c_global"] = tree_zeros_like(template)
        if "ctrl_sum" in keys:
            like["ctrl_sum"] = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), template)
        return like

    def restore_state(self, ckpt) -> Optional[FedState]:
        """Rebuild a ``FedState`` from the newest LOADABLE full-state
        checkpoint in ``ckpt`` — corrupt/truncated steps are skipped
        backwards exactly like ``Checkpointer.restore_latest``.  Returns
        None when the directory holds no restorable state (callers fall
        back to ``init_state``)."""
        cfg = self.cfg
        for step in reversed(ckpt.steps()):
            meta = ckpt.load_meta(step)
            if meta is None or "keys" not in meta:
                continue
            try:
                if not ckpt.verify(step):
                    continue
                tree = ckpt.restore(step, self._state_like(meta))
            except Exception:
                continue
            state = FedState(
                round=int(meta["round"]),
                global_models=vec_engine.unstack_models(tree["models"]),
                ensemble=TeacherBank(cfg.K, cfg.R, dtype=cfg.teacher_dtype),
                store=make_client_store(cfg, self.task),
                history=[dict(r) for r in meta.get("history", [])])
            state.ensemble.import_state(tree.get("bank"), meta["bank"])
            if cfg.local_algo == "scaffold":
                # init_controls re-ingests the directory's spilled
                # controls; the checkpointed running sum then replaces
                # the rebuilt one so resumed fp state is exact
                state.store.init_controls(state.global_models[0])
                state.scaffold_c_global = tree.get(
                    "c_global", tree_zeros_like(state.global_models[0]))
            if "ctrl_sum" in tree:
                state.store.set_control_sum(tree["ctrl_sum"])
            if meta.get("pending"):
                p = os.path.join(ckpt.dir, meta["pending"])
                if os.path.exists(p):
                    self.restore_pending(state, p)
            return state
        return None

    # ---- vectorized engine ----------------------------------------------
    def _make_engine(self) -> vec_engine.VectorizedClientEngine:
        if self._engine is None:
            from repro.launch.mesh import make_client_mesh
            self._engine = vec_engine.VectorizedClientEngine(
                self.task.loss_fn, self._make_optimizer(),
                mesh=make_client_mesh(),
                client_sharding=self.cfg.client_sharding)
        return self._engine

    def _sample_posterior(self, models, sizes, n_samples, seed):
        """FedBE-style Gaussian posterior samples around the weighted mean."""
        mean = fedavg_aggregate(models, sizes)
        # elementwise variance around the mean
        var = jax.tree.map(lambda m, *xs: sum((x - m) ** 2 for x in xs) / max(1, len(xs) - 1),
                           mean, *models)
        out = []
        for i in range(n_samples):
            key = jax.random.PRNGKey(seed * 977 + i)
            keys = iter(jax.random.split(key, len(jax.tree.leaves(mean))))
            out.append(jax.tree.map(
                lambda m, v: m + jnp.sqrt(jnp.maximum(v, 0)).astype(m.dtype)
                * jax.random.normal(next(keys), m.shape, jnp.float32).astype(m.dtype),
                mean, var))
        return out

    # ---- full run -----------------------------------------------------------
    def run(self, rounds: int | None = None, log_every: int = 0,
            state: FedState | None = None) -> FedState:
        state = state or self.init_state()
        for _ in range(rounds or self.cfg.rounds):
            state = self.run_round(state)
            if log_every and state.round % log_every == 0:
                # overlap modes: the newest record's KD/eval fields land at
                # resolve time — log the newest COMPLETE record (one behind)
                rec = state.history[-1]
                if state.pending_kd is not None:
                    if len(state.history) < 2:
                        continue
                    rec = state.history[-2]
                print(f"[round {rec['round']:3d}] " +
                      " ".join(f"{k}={v}" for k, v in rec.items() if k != "round"))
        return self.finalize(state)

    # ---- evaluation helpers ----------------------------------------------
    def ensemble_eval_fn(self, state: FedState):
        """Accuracy of the K·R teacher ensemble (paper Table 5)."""
        teachers = state.ensemble.members() or state.global_models
        return lambda batch: dist.ensemble_predict(
            teachers, batch, self.task.logits_fn)


# =====================================================================
# per-engine phase bodies (consumed by round_plan.RoundExecutor)
# =====================================================================
class _SequentialRoundOps:
    """The oracle per-client Python loop, split into executor phases.

    ``subset`` selection ("all" | "rest" = groups k>0 | "main" = group 0)
    walks the pre-drawn entry list in group-major order, so the phase
    split changes WHEN clients train, never WHAT they compute.
    """

    def __init__(self, runner, state, groups, rng, t):
        self.runner, self.state = runner, state
        self.groups, self.t = groups, t
        self.entries = vec_engine.build_round_entries(
            runner.task, runner.cfg, groups, rng,
            store=runner._store(state))
        self.models: list = [None] * len(self.entries)   # by round position
        # fault injection: None (the exact legacy code paths run) or the
        # round's resolved trace folded into the entries' schedules
        self.faults = faults_lib.apply_round_faults(
            runner.cfg.faults, t, self.entries)
        self.fault_info: dict = {}
        self.degraded: list = []
        self._surv = None
        # scaffold + faults: stash control updates instead of committing —
        # finish_local commits survivors only, after the isfinite ruling
        self._ctrl_out = ({} if (self.faults is not None
                                 and runner.cfg.local_algo == "scaffold")
                          else None)

    def fused_capable(self) -> bool:
        return False    # a Python loop has no scan subgraph to fuse

    def _subset(self, which: str):
        if which == "all":
            return self.entries
        if which == "rest":
            return [e for e in self.entries if e.group != 0]
        return [e for e in self.entries if e.group == 0]

    def train(self, which: str, run_buckets=None) -> None:
        state, rf = self.state, self.faults
        for e in self._subset(which):
            if e.dropped:
                continue                 # a dropped client never reports
            model = self.runner._local_train_scheduled(
                state.global_models[e.group], e.cid, state, e.idx,
                control_out=self._ctrl_out)
            if rf is not None and e.cid in rf.attacked:
                # Byzantine upload: finite, guard-passing perturbation of
                # the honest update around the group's round-start model
                model = faults_lib.attack_model(
                    rf.plan, self.t, e.cid, model,
                    state.global_models[e.group])
            if rf is not None and e.cid in rf.corrupt:
                model = faults_lib.poison_model(model)
            self.models[e.pos] = model

    def _survivors(self) -> set:
        """Plan-dropped clients excluded a priori; every reported upload
        then passes the value-level isfinite guard or is rejected."""
        if self._surv is None:
            from repro.analysis.sync import allowed_sync
            surv, rejected = set(), []
            with allowed_sync("isfinite upload guard ruling — one bool "
                              "pull per client per degraded round "
                              "(sequential oracle)"):
                for e in self.entries:
                    if e.dropped:
                        continue
                    if bool(tree_all_finite(self.models[e.pos])):
                        surv.add(e.cid)
                    else:
                        rejected.append(e.cid)
            self._surv, self._rejected = surv, rejected
        return self._surv

    def finish_local(self) -> None:
        state, cfg = self.state, self.runner.cfg
        if cfg.local_algo == "scaffold":
            if self._ctrl_out is not None:
                surv = self._survivors()
                for e in self.entries:
                    if e.cid in surv and e.cid in self._ctrl_out:
                        state.store.put_control(e.cid, self._ctrl_out[e.cid])
            # server control: c += |S|/N * mean_i (c_i' − c_i)  (we use the
            # simpler running-average form: c = mean of client controls)
            state.scaffold_c_global = state.store.control_mean()

    def aggregate(self) -> list[PyTree]:
        """Per-group Eq. 1-2 over the trained client models."""
        cfg, rf = self.runner.cfg, self.faults
        if cfg.aggregator != "mean" or cfg.clip_norm is not None:
            return self._aggregate_robust()
        if rf is None:
            new_globals: list[PyTree] = []
            for k in range(len(self.groups)):
                ents = [e for e in self.entries if e.group == k]
                client_models = [self.models[e.pos] for e in ents]
                sizes = [e.n for e in ents]
                if cfg.secure_aggregation:
                    agg, _uploads = secure_aggregate(client_models, sizes,
                                                     seed=self.t)
                else:
                    agg = fedavg_aggregate(client_models, sizes)
                new_globals.append(agg)
            self.new_globals = new_globals
            return new_globals
        # degraded round: Eq. 2 over survivors only.  zero_fill keeps the
        # full-round denominator (the naive ablation); an emptied group
        # carries its previous global model forward.
        surv = self._survivors()
        new_globals, degraded = [], []
        for k in range(len(self.groups)):
            ents = [e for e in self.entries if e.group == k]
            live = [e for e in ents if e.cid in surv]
            if not live:
                new_globals.append(self.state.global_models[k])
                degraded.append(k)
                continue
            agg = fedavg_aggregate([self.models[e.pos] for e in live],
                                   [e.n for e in live])
            if rf.plan.zero_fill:
                frac = sum(e.n for e in live) / sum(e.n for e in ents)
                agg = jax.tree.map(
                    lambda x: (x * frac).astype(x.dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x, agg)
            new_globals.append(agg)
        self.degraded = degraded
        self.new_globals = new_globals
        self.fault_info = faults_lib.fault_record(
            rf, surv, self._rejected, degraded)
        return new_globals

    def _aggregate_robust(self) -> list[PyTree]:
        """Robust Eq. 2: stack the round's models client-major (dropped
        clients carry a placeholder row under a False mask) and call the
        SAME grouped entry point as the vectorized engine — one robust
        code path, exercised identically by both engines."""
        cfg, rf = self.runner.cfg, self.faults
        if rf is None:
            surv, mask = None, np.ones((len(self.entries),), bool)
        else:
            surv = self._survivors()
            mask = np.asarray([(not e.dropped) and e.cid in surv
                               for e in self.entries])
        stacked = tree_stack([
            self.models[e.pos] if self.models[e.pos] is not None
            else self.state.global_models[e.group] for e in self.entries])
        gids = np.asarray([e.group for e in self.entries])
        sizes = [e.n for e in self.entries]
        agg, degraded = robust_aggregate_grouped(
            stacked, sizes, gids, len(self.groups),
            aggregator=cfg.aggregator, trim_frac=cfg.trim_frac,
            clip_norm=cfg.clip_norm, survivor_mask=mask,
            fallback_stacked=tree_stack(self.state.global_models))
        self.new_globals = vec_engine.unstack_models(agg)
        self.degraded = degraded
        if rf is not None:
            self.fault_info = faults_lib.fault_record(
                rf, surv, self._rejected, degraded)
        return self.new_globals

    def push(self, t: int, state) -> None:
        state.ensemble.push(t, self.new_globals, degraded=self.degraded)

    def _client_teachers_list(self, new_globals) -> list[PyTree]:
        cfg, runner = self.runner.cfg, self.runner
        if self.faults is None:
            teachers = list(self.models)
            sizes = [e.n for e in self.entries]
        else:
            # FedDF/FedBE ensembles only ever see surviving uploads —
            # one poisoned teacher would NaN the whole ensemble mean
            surv = self._survivors()
            live = [e for e in self.entries if e.cid in surv]
            teachers = [self.models[e.pos] for e in live]
            sizes = [e.n for e in live]
            if not teachers:
                teachers = list(new_globals)    # carry-forwards still teach
                sizes = [1] * len(teachers)
        if cfg.ensemble_extra_sampled:
            teachers += runner._sample_posterior(
                list(teachers), sizes, cfg.ensemble_extra_sampled, self.t)
            teachers.append(new_globals[0])
        return teachers

    def inline_kd(self, new_globals) -> dict:
        """The engine-native back-to-back KD block (the off-mode oracle)."""
        cfg, runner, state = self.runner.cfg, self.runner, self.state
        if cfg.ensemble_source == "clients":
            teachers = self._client_teachers_list(new_globals)
            if cfg.teacher_trust:
                tstack = tree_stack(teachers)
                return runner._distill_models(
                    new_globals, tstack, stacked=True,
                    teacher_weights=runner._teacher_trust_weights(
                        state, tstack))
            return runner._distill_models(new_globals, teachers,
                                          stacked=False)
        if cfg.kd_pipeline == "fused":
            # fused path reads the (M, ...) stack straight off the bank
            tstack = state.ensemble.members_stacked()
            return runner._distill_models(
                new_globals, tstack, stacked=True,
                teacher_weights=runner._teacher_trust_weights(state, tstack))
        return runner._distill_models(
            new_globals, state.ensemble.members(), stacked=False)

    def kd_teachers(self, new_globals) -> PyTree:
        """(M, ...) stacked teacher snapshot for the deferred KD job."""
        if self.runner.cfg.ensemble_source == "clients":
            return tree_stack(self._client_teachers_list(new_globals))
        return self.state.ensemble.members_stacked()


class _VectorizedRoundOps:
    """Stacked-engine phase bodies.

    Secure aggregation needs no simulation here: pairwise masks cancel
    identically inside the fused Eq. 2 reduction, so the plain weighted
    mean IS the unmasked result.

    Phase-split training buckets each subset separately, but clients are
    reassembled into the full round's group-major order before the Eq. 2
    segment reduction, so the aggregation consumes bit-identical operand
    order whether the round ran split or whole.
    """

    def __init__(self, runner, state, groups, rng, t):
        self.runner, self.state = runner, state
        self.groups, self.t = groups, t
        self.eng = runner._make_engine()
        self.store = runner._store(state)
        self.entries = vec_engine.build_round_entries(
            runner.task, runner.cfg, groups, rng, store=self.store)
        # round-stable pad targets: subset buckets (the overlap phase
        # split) compile once instead of retracing per group shuffle.
        # Taken BEFORE fault truncation on purpose: degraded schedules
        # pad back up to the fault-free maxima, so a chaotic round reuses
        # the exact compiled programs of a clean one — faults never
        # retrace (truncated steps become masked no-ops).
        self.pad_hints = vec_engine.entry_pad_hints(self.entries)
        self.faults = faults_lib.apply_round_faults(
            runner.cfg.faults, t, self.entries)
        self.fault_info: dict = {}
        self.degraded: list = []
        self._surv = None
        cfg = runner.cfg
        # masked (faulted) and robust rounds keep their own Eq. 2 over the
        # reassembled client stack; the others end in one program
        self.eager_end = (self.faults is not None or cfg.aggregator != "mean"
                          or cfg.clip_norm is not None)
        self.stacked_clients = None
        self.results: list = []     # (stacked, gids, sizes, orders, cids)
        self.buckets: list = []     # every subset's, in training order

    def fused_capable(self) -> bool:
        return self.eng._resolved_step_mode() == "scan"

    def _subset(self, which: str):
        if which == "all":
            return self.entries
        if which == "rest":
            return [e for e in self.entries if e.group != 0]
        return [e for e in self.entries if e.group == 0]

    def train(self, which: str, run_buckets=None) -> None:
        ents = self._subset(which)
        if not ents:
            return
        runner, state, cfg = self.runner, self.state, self.runner.cfg
        store = self.store
        count("local_steps", sum(len(e.idx) for e in ents if not e.dropped))
        # pin this phase's clients resident while their bucket stacks are
        # assembled and consumed — the O(sampled) residency contract
        with store.sampled_view([e.cid for e in ents]) as view:
            with span("fedsdd.local.prep"):
                rplan = vec_engine.plan_from_entries(
                    runner.task, ents, self.groups, store=store,
                    pad_to=self.pad_hints)

            def start_for(plan):
                w0, s0 = self.eng.start_state(state.global_models,
                                              plan.group_of)
                if cfg.local_algo == "scaffold":
                    c_loc = tree_stack(view.controls(plan.cids))
                    nb = len(plan.cids)
                    c_glob = jax.tree.map(
                        lambda x: jnp.broadcast_to(x, (nb,) + x.shape),
                        state.scaffold_c_global)
                    s0 = s0._replace(c_local=c_loc, c_global=c_glob)
                return w0, s0

            buckets = self.eng.train_round(rplan, start_for,
                                           run_buckets=run_buckets)
        self.buckets.extend(buckets)
        if not self.eager_end:
            return      # aggregate() reorders and averages in one program
        with span("fedsdd.local.reassemble"):
            stacked, gids, sizes = vec_engine.reassemble(buckets)
        if self.faults is not None and self.faults.attacked:
            # Byzantine rows: same perturbation math as the sequential
            # engine's attack_model, scattered into this subset's stack
            # (rows are in `ents` order, post-reassembly)
            atk = [(i, int(e.cid), e.group) for i, e in enumerate(ents)
                   if e.cid in self.faults.attacked]
            if atk:
                stacked = faults_lib.attack_rows(
                    self.faults.plan, self.t, stacked, atk,
                    state.global_models)
        if self.faults is not None and self.faults.corrupt:
            # corruption strikes the upload, after training: poison the
            # stacked rows of this subset's corrupt clients (rows are in
            # ascending-pos order, i.e. `ents` order, post-reassembly)
            rows = [i for i, e in enumerate(ents)
                    if e.cid in self.faults.corrupt]
            stacked = faults_lib.poison_rows(stacked, rows)
        orders = np.sort(np.concatenate([p.order for p in rplan.plans]))
        cids = np.asarray([e.cid for e in ents])
        self.results.append((stacked, gids, sizes, orders, cids))
        self.buckets.extend(buckets)

    def _survivors(self) -> set:
        """Same contract as the sequential ops: plan-dropped excluded,
        then the stacked isfinite guard rules on every reported row."""
        if self._surv is None:
            rf = self.faults
            surv, rejected = set(), []
            for stacked, _, _, _, cids in self.results:
                fin = faults_lib.finite_rows(stacked)
                for c, ok in zip(cids, fin):
                    c = int(c)
                    if c in rf.dropped:
                        continue
                    if ok:
                        surv.add(c)
                    else:
                        rejected.append(c)
            self._surv, self._rejected = surv, sorted(rejected)
        return self._surv

    def finish_local(self) -> None:
        state, cfg = self.state, self.runner.cfg
        if cfg.local_algo == "scaffold":
            surv = (self._survivors() if self.faults is not None else None)
            for plan, p, s, w0 in self.buckets:
                new_c = jax.vmap(
                    lambda st, a, b: scaffold_new_control(
                        st, a, b, cfg.client_lr))(s, w0, p)
                for i, cid in enumerate(plan.cids):
                    if surv is not None and int(cid) not in surv:
                        continue    # dropped/rejected: control never lands
                    self.store.put_control(int(cid), jax.tree.map(
                        lambda x, i=i: x[i], new_c))
            state.scaffold_c_global = self.store.control_mean()

    def aggregate(self) -> list[PyTree]:
        """Eq. 2 for every group at once over the round-ordered client
        stack: one program (``finish_round``), or, for a masked or robust
        round, the reassembled stack and the masked or robust
        statistics."""
        count("local_eager_ends", int(self.eager_end))
        if not self.eager_end:
            with span("fedsdd.eq2"):
                (self.stacked_globals, new_globals, _, self.sizes,
                 self.cids_round) = vec_engine.finish_round(
                    self.buckets, self.runner.cfg.K)
                self.new_globals = list(new_globals)
            return self.new_globals
        if len(self.results) == 1:
            stacked, gids, sizes, _, cids = self.results[0]
        else:
            with span("fedsdd.local.reassemble"):
                orders = np.concatenate([r[3] for r in self.results])
                inv = np.argsort(orders)
                perm = jnp.asarray(inv)
                stacked = jax.tree.map(
                    lambda *xs: jnp.concatenate(xs)[perm],
                    *[r[0] for r in self.results])
                gids = np.concatenate([r[1] for r in self.results])[inv]
                sizes = np.concatenate([r[2] for r in self.results])[inv]
                cids = np.concatenate([r[4] for r in self.results])[inv]
        with span("fedsdd.eq2"):
            return self._eq2(stacked, gids, sizes, cids)

    def _eq2(self, stacked, gids, sizes, cids) -> list[PyTree]:
        """Masked or robust Eq. 2 over the round-ordered stack, unstacked
        into the K new global models."""
        self.stacked_clients, self.sizes = stacked, sizes
        self.cids_round = cids
        rf, cfg = self.faults, self.runner.cfg
        robust = cfg.aggregator != "mean" or cfg.clip_norm is not None
        if not robust:
            surv = self._survivors()
            mask = np.asarray([int(c) in surv for c in cids])
            self.stacked_globals, self.degraded = \
                fedavg_aggregate_grouped_masked(
                    stacked, sizes, gids, cfg.K, mask,
                    tree_stack(self.state.global_models),
                    zero_fill=rf.plan.zero_fill)
            self.fault_info = faults_lib.fault_record(
                rf, surv, self._rejected, self.degraded)
        else:
            if rf is None:
                surv, mask = None, np.ones((len(cids),), bool)
            else:
                surv = self._survivors()
                mask = np.asarray([int(c) in surv for c in cids])
            self.stacked_globals, self.degraded = robust_aggregate_grouped(
                stacked, sizes, gids, cfg.K, aggregator=cfg.aggregator,
                trim_frac=cfg.trim_frac, clip_norm=cfg.clip_norm,
                survivor_mask=mask,
                fallback_stacked=tree_stack(self.state.global_models))
            if rf is not None:
                self.fault_info = faults_lib.fault_record(
                    rf, surv, self._rejected, self.degraded)
        self.new_globals = vec_engine.unstack_models(self.stacked_globals)
        return self.new_globals

    def push(self, t: int, state) -> None:
        # the (K, ...) stack goes into the device bank as-is (Eq. 5)
        state.ensemble.push(t, self.stacked_globals, degraded=self.degraded)

    def _client_teacher_stack(self, new_globals) -> PyTree:
        cfg, runner = self.runner.cfg, self.runner
        if self.stacked_clients is None:    # the fold made none
            self.stacked_clients, _, _ = vec_engine.reassemble(self.buckets)
        teacher_stack, sizes = self.stacked_clients, list(self.sizes)
        if self.faults is not None:
            surv = self._survivors()
            keep = [i for i, c in enumerate(self.cids_round)
                    if int(c) in surv]
            if keep:
                ki = jnp.asarray(keep, jnp.int32)
                teacher_stack = jax.tree.map(lambda x: x[ki], teacher_stack)
                sizes = [sizes[i] for i in keep]
            else:
                teacher_stack = self.stacked_globals  # carry-forwards teach
                sizes = [1] * self.runner.cfg.K
        if cfg.ensemble_extra_sampled:
            extras = runner._sample_posterior(
                vec_engine.unstack_models(teacher_stack),
                sizes, cfg.ensemble_extra_sampled, self.t)
            extras.append(new_globals[0])
            teacher_stack = tree_concat([teacher_stack, tree_stack(extras)])
        return teacher_stack

    def inline_kd(self, new_globals) -> dict:
        runner, state = self.runner, self.state
        with span("fedsdd.kd.teachers"):
            teacher_stack = self.kd_teachers(new_globals)
            weights = runner._teacher_trust_weights(state, teacher_stack)
        return runner._distill_models(
            new_globals, teacher_stack, stacked=True,
            stacked_students=self.stacked_globals, teacher_weights=weights)

    def kd_teachers(self, new_globals) -> PyTree:
        if self.runner.cfg.ensemble_source == "clients":
            return self._client_teacher_stack(new_globals)
        return self.state.ensemble.members_stacked()


def make_runner(preset: str, task: FedTask, **overrides) -> FederatedRunner:
    return FederatedRunner(make_config(preset, **overrides), task)
