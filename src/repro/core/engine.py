"""Vectorized client-execution engine (server-side cost decoupled from C).

The sequential runner in ``fedsdd.py`` trains sampled clients one at a
time in a Python loop, so round wall-clock grows linearly with
participation — exactly the serialization FedSDD argues against.  This
module replaces that loop with a *stacked* representation: homogeneous
client pytrees are stacked along a leading client axis and every client's
full local-training schedule (SGD / FedProx / SCAFFOLD epochs) runs as ONE
jitted ``lax.scan`` under

  * ``jax.vmap``       — single device (CPU tests, one accelerator), or
  * ``shard_map``      — the client axis sharded over the ``clients`` mesh
                         from ``launch.mesh.make_client_mesh`` (multi-chip).

Exactness contract: the engine is an *oracle-equivalent* of the
sequential path.  ``build_round_plan`` draws the per-epoch permutations
in the identical order the sequential loop would (group-major, then
epoch), so both paths consume the same batches in the same order; clients
with fewer optimization steps than the bucket maximum are padded with
masked no-op steps (``tree_where`` keeps params AND optimizer state
frozen on padded steps), so padding changes nothing.  Clients whose local
batch size differs (tiny shards where |X_i| < client_batch) are bucketed
by batch size and each bucket is vectorized independently.

Aggregation consumes the stacked representation directly: Eq. 2 per group
is a segment reduction over the client axis (``tree_group_weighted_mean``
on CPU, the batched multi-model ``weight_avg`` Pallas kernel on TPU) —
no per-client Python iteration anywhere on the hot path.  Each end of
local training is one program, not an eager op per leaf: ``start_state``
(``fedsdd_local_start``) builds a bucket's start params and optimiser
state, ``finish_round`` (``fedsdd_eq2``) takes the buckets' trained params
in round order, averages each group and slices out the K models.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.spans import count, named_program, span
from repro.core.aggregation import eq2_grouped, eq2_operands
from repro.core.grouping import group_major_order
from repro.kernels.weight_avg.ops import EQ2
from repro.optim.optimizers import Optimizer, apply_updates
from repro.sharding.specs import CLIENT_AXIS
from repro.utils.pytree import tree_stack, tree_unstack, tree_where

PyTree = Any

# the stable names of the local-training programs in a profiler trace
BUCKET_SCAN = "fedsdd_bucket_scan"
LOCAL_START = "fedsdd_local_start"
ROUND_ORDER = "fedsdd_round_order"


# =====================================================================
# round plan: host-side schedule, stacked device-side batches
# =====================================================================
@dataclass
class ClientPlan:
    """One batch-size bucket of the round's clients, stacked for vmap.

    ``data`` holds the bucket's FULL client shards stacked on device
    (leaves (Cb, n_pad, ...)); per-round minibatches are formed by an
    on-device gather with the (Cb, S, bs) ``indices`` matrix inside the
    jitted step — the per-round host→device traffic is a few KB of
    int32 indices, not the epoch's worth of examples.  ``data`` is cached
    across rounds keyed on the bucket's client set (bucket rows are in
    sorted-cid order precisely so the key is round-stable while groups
    reshuffle).

    ``order`` gives each client's position in the round-global group-major
    ordering so bucket results can be scattered back without reordering
    surprises.
    """
    cids: np.ndarray        # (Cb,) client ids (sorted)
    group_of: np.ndarray    # (Cb,) group index per client
    sizes: np.ndarray       # (Cb,) dataset sizes |X_i|
    order: np.ndarray       # (Cb,) position in the group-major round order
    batch_size: int
    data: PyTree            # leaves (Cb, n_pad, ...) — cached shard stack
    indices: jnp.ndarray    # (Cb, S, bs) int32 rows into data
    step_mask: jnp.ndarray  # (Cb, S) bool — False rows are padded no-ops


@dataclass
class RoundPlan:
    groups: list[np.ndarray]
    plans: list[ClientPlan]
    num_clients: int        # total sampled this round (this plan's subset)


@dataclass
class ClientEntry:
    """One sampled client's fully-drawn local schedule (host side).

    The entry list is the rng-bearing half of round planning: it is drawn
    ONCE per round in the exact sequential-oracle order, then bucketed into
    ``ClientPlan``s — possibly as group subsets, which is how the overlap
    executor (core/round_plan.py) trains groups k>0 and group 0 at
    different phase positions without perturbing the rng stream.
    """
    pos: int                # position in the group-major round order
    cid: int
    group: int
    n: int                  # dataset size |X_i|
    bs: int                 # local batch size min(client_batch, n)
    idx: np.ndarray         # (S_c, bs) int32 minibatch index rows
    # fault injection (core/faults.py): a dropped client keeps a 1-step
    # schedule so bucket shapes stay fault-free (no retracing) but its
    # update carries zero aggregation weight and its controls never commit
    dropped: bool = False


# The per-client device-row / bucket-stack LRU now lives in
# ``core.client_store.ClientStore`` — the engine's old bolt-on cache
# promoted to an API with a first-class ``FedConfig(client_cache_buckets)``
# knob.  Plan building takes a store; ``None`` builds through an
# ephemeral in-memory store (no cross-call caching — the old
# ``data_cache=None`` semantics).
def _store_for(task, store):
    if store is None:
        from repro.core.client_store import InMemoryStore
        return InMemoryStore(task)
    return store


def build_round_entries(task, cfg, groups: Sequence[np.ndarray],
                        rng: np.random.Generator,
                        store=None) -> list[ClientEntry]:
    """Draw every sampled client's epoch schedule.

    CRITICAL: permutations are drawn in the exact order the sequential
    runner draws them (for k in groups: for cid in group: for epoch: ...),
    so sequential and vectorized execution see identical batches — and so
    the overlap executor can reorder *training* (groups k>0 before group
    0) without reordering the rng stream.
    """
    store = _store_for(task, store)
    entries: list[ClientEntry] = []
    cids, gids = group_major_order(groups)
    for pos, (cid, k) in enumerate(zip(cids, gids)):
        n = store.num_examples(int(cid))
        bs = min(cfg.client_batch, n)
        steps = []
        for _ in range(cfg.local_epochs):
            perm = rng.permutation(n)
            for i in range(0, n - bs + 1, bs):
                steps.append(perm[i:i + bs])
        entries.append(ClientEntry(
            pos=pos, cid=int(cid), group=int(k), n=n, bs=bs,
            idx=np.asarray(steps, np.int32)))  # lint-ok: RA101 host rng schedule
    return entries


def entry_pad_hints(entries: Sequence[ClientEntry]) -> dict[int, tuple]:
    """Per-batch-size (S, n_pad) maxima over a full round's entries.

    The overlap executor buckets group SUBSETS whose own maxima vary with
    the round's random group assignment; padding every subset bucket to
    the whole round's maxima keeps device-program shapes round-stable, so
    the jitted bucket programs compile once instead of retracing per
    group shuffle (padded steps/rows are exact masked no-ops either way).
    """
    hints: dict[int, tuple] = {}
    for e in entries:
        s, n = hints.get(e.bs, (0, 0))
        hints[e.bs] = (max(s, len(e.idx)), max(n, e.n))
    return hints


def plans_from_entries(task, entries: Sequence[ClientEntry],
                       store=None,
                       pad_to: Optional[dict] = None) -> list[ClientPlan]:
    """Bucket pre-drawn entries by batch size and stack them for vmap.

    All shard access goes through the ``ClientStore`` (``store=None``
    builds through an ephemeral in-memory one): rows/stacks come off its
    bounded device tier, so plan building is O(sampled) in memory no
    matter how many clients the task holds.
    """
    store = _store_for(task, store)
    plans: list[ClientPlan] = []
    for bs in sorted({e.bs for e in entries}):
        # sorted-cid bucket order -> round-stable data-cache key
        sub = sorted((e for e in entries if e.bs == bs), key=lambda e: e.cid)
        S = max(len(e.idx) for e in sub)
        n_pad = max(e.n for e in sub)
        if pad_to and bs in pad_to:
            S, n_pad = max(S, pad_to[bs][0]), max(n_pad, pad_to[bs][1])
        idxs, masks = [], []
        for e in sub:
            idx, s_c = e.idx, len(e.idx)
            if s_c < S:  # pad with replays of step 0; masked out below
                idx = np.concatenate([idx, np.tile(idx[:1], (S - s_c, 1))])
            idxs.append(idx)
            masks.append(np.arange(S) < s_c)
        plans.append(ClientPlan(
            cids=np.asarray([e.cid for e in sub]),
            group_of=np.asarray([e.group for e in sub]),
            sizes=np.asarray([e.n for e in sub]),
            order=np.asarray([e.pos for e in sub]),
            batch_size=bs,
            data=store.get_bucket([e.cid for e in sub], n_pad),
            indices=jnp.asarray(np.stack(idxs)),
            step_mask=jnp.asarray(np.stack(masks)),
        ))
    return plans


def plan_from_entries(task, entries: Sequence[ClientEntry],
                      groups: Sequence[np.ndarray],
                      store=None,
                      pad_to: Optional[dict] = None) -> RoundPlan:
    """RoundPlan over an entry subset (the overlap executor's phase split)."""
    return RoundPlan(groups=list(groups),
                     plans=plans_from_entries(task, entries, store,
                                              pad_to),
                     num_clients=len(entries))


def build_round_plan(task, cfg, groups: Sequence[np.ndarray],
                     rng: np.random.Generator,
                     store=None) -> RoundPlan:
    """Materialize every sampled client's epoch schedule, stacked."""
    entries = build_round_entries(task, cfg, groups, rng, store)
    return plan_from_entries(task, entries, groups, store)


# =====================================================================
# engine
# =====================================================================
def resolve_step_mode(mode: str = "auto", cpu_default: str = "stepped") -> str:
    """Shared scan-vs-stepped policy for every fused loop in the repo.

    scan: the whole schedule is ONE ``lax.scan`` program — the TPU
    lowering (no per-step dispatch, pipelines with the mesh).  stepped:
    one jitted dispatch per step, driven from Python.  Which wins on
    XLA:CPU depends on the loop body: the engine's client-vmapped bodies
    execute ~10x slower under scan (measured: 4.8s vs 0.5s for S=4, C=16
    CNN steps) so it passes ``cpu_default="stepped"``; the KD pipeline's
    single-student bodies are dispatch-bound and scan is ~10x FASTER
    (measured: 22ms vs 201ms for 200 MLP KD steps) so it passes
    ``cpu_default="scan"``.  ``REPRO_ENGINE_STEP_MODE`` overrides both
    the caller's mode and the backend heuristic.
    """
    mode = os.environ.get("REPRO_ENGINE_STEP_MODE", mode)
    if mode != "auto":
        return mode
    return "scan" if jax.default_backend() == "tpu" else cpu_default


class VectorizedClientEngine:
    """Runs a whole round of local training as one stacked program.

    ``loss_fn``/``optimizer`` are the same objects the sequential oracle
    uses, so the per-step math is identical — only the execution strategy
    (one fused scan per bucket instead of C Python loops) differs.
    """

    def __init__(self, loss_fn: Callable, optimizer: Optimizer,
                 mesh=None, client_sharding: str = "auto",
                 step_mode: str = "auto"):
        if client_sharding not in ("auto", "vmap", "shard_map"):
            raise ValueError(f"client_sharding={client_sharding!r} not in "
                             "('auto', 'vmap', 'shard_map')")
        if step_mode not in ("auto", "scan", "stepped"):
            raise ValueError(f"step_mode={step_mode!r} not in "
                             "('auto', 'scan', 'stepped')")
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self.client_sharding = client_sharding
        self.step_mode = step_mode
        self._vec_fn = None
        self._step_fn = None
        self._start_fn = None

    def _resolved_step_mode(self) -> str:
        """See ``resolve_step_mode``: the engine's vmapped loop bodies run
        ~10x slower under XLA:CPU scan, so its CPU default is stepped."""
        return resolve_step_mode(self.step_mode, cpu_default="stepped")

    # ---- shared per-client step --------------------------------------
    def _masked_step(self):
        optimizer, loss_fn = self.optimizer, self.loss_fn

        def step(p, s, batch, m):
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(p, batch)
            updates, s2 = optimizer.update(grads, s, p)
            p2 = apply_updates(p, updates)
            # padded step: keep params AND optimizer state frozen
            return tree_where(m, p2, p), tree_where(m, s2, s), loss

        return step

    # ---- the per-client scan (TPU path), built once -------------------
    def _one_client(self):
        step = self._masked_step()

        def run(params, opt_state, data, indices, mask):
            def body(carry, xs):
                p, s = carry
                idx, m = xs
                b = jax.tree.map(lambda x: x[idx], data)  # on-device gather
                p2, s2, loss = step(p, s, b, m)
                return (p2, s2), loss

            (p, s), losses = jax.lax.scan(
                body, (params, opt_state), (indices, mask))
            return p, s, losses

        return run

    # ---- one vmapped step (CPU path), built once ----------------------
    def _one_client_step(self):
        step = self._masked_step()

        def run(params, opt_state, data, indices, mask, si):
            idx = jax.lax.dynamic_index_in_dim(indices, si, 0,
                                               keepdims=False)
            b = jax.tree.map(lambda x: x[idx], data)      # on-device gather
            return step(params, opt_state, b, mask[si])

        return run

    def _use_shard_map(self) -> bool:
        from repro.launch.mesh import use_shard_map
        return use_shard_map(self.mesh, self.client_sharding)

    def _vectorized_fn(self):
        if self._vec_fn is None:
            vf = jax.vmap(self._one_client())
            if self._use_shard_map():
                spec = P(CLIENT_AXIS)
                vf = jax.shard_map(vf, mesh=self.mesh,
                                   in_specs=(spec,) * 5,
                                   out_specs=(spec, spec, spec),
                                   check_vma=False)
            self._vec_fn = named_program(BUCKET_SCAN, vf)
        return self._vec_fn

    def _stepped_fn(self):
        if self._step_fn is None:
            vf = jax.vmap(self._one_client_step(),
                          in_axes=(0, 0, 0, 0, 0, None))
            if self._use_shard_map():
                spec = P(CLIENT_AXIS)
                vf = jax.shard_map(vf, mesh=self.mesh,
                                   in_specs=(spec,) * 5 + (P(),),
                                   out_specs=(spec, spec, spec),
                                   check_vma=False)
            self._step_fn = named_program(BUCKET_SCAN, vf)
        return self._step_fn

    def jit_programs(self) -> dict:
        """Built jitted programs by label — ``analysis.TraceGuard`` watches
        these to attribute a steady-state compile to its owner."""
        out = {"engine/end": _round_end,
               "engine/round_order": _clients_in_round_order}
        if self._start_fn is not None:
            out["engine/start"] = self._start_fn
        if self._vec_fn is not None:
            out["engine/scan"] = self._vec_fn
        if self._step_fn is not None:
            out["engine/stepped"] = self._step_fn
        return out

    # ---- bucket execution, decomposed so the overlap executor can weave
    # ---- the same programs into a combined KD+training device program ---
    def prepare_bucket(self, plan: ClientPlan, stacked_params: PyTree,
                       stacked_opt_state: PyTree):
        """Pad a bucket's stacked args for the (possibly sharded) program.

        Returns ``(args, C)`` where ``args`` is the positional tuple the
        per-bucket program consumes and ``C`` the true (unpadded) client
        count ``finish_bucket`` trims back to.
        """
        n_shards = 1
        if self._use_shard_map():
            from repro.launch.mesh import mesh_size
            n_shards = mesh_size(self.mesh)
        C = plan.cids.shape[0]
        pad = (-C) % n_shards
        data, indices, mask = plan.data, plan.indices, plan.step_mask
        if pad:  # replicate row 0 with an all-False mask: exact no-ops
            def padrow(x):
                return jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            stacked_params = jax.tree.map(padrow, stacked_params)
            stacked_opt_state = jax.tree.map(padrow, stacked_opt_state)
            data = jax.tree.map(padrow, data)
            indices = padrow(indices)
            mask = jnp.concatenate(
                [mask, jnp.zeros((pad,) + mask.shape[1:], bool)])
        args = (stacked_params, stacked_opt_state, data, indices, mask)
        if self._use_shard_map():
            # lay each client row on its mesh device up front: the program
            # refuses operands committed to a device outside its mesh
            args = jax.device_put(args, NamedSharding(self.mesh,
                                                      P(CLIENT_AXIS)))
        return args, C

    def run_prepared(self, args):
        """Dispatch one padded bucket (scan or stepped); padded outputs."""
        if self._resolved_step_mode() == "scan":
            return self._vectorized_fn()(*args)
        fn = self._stepped_fn()
        p, s, (data, indices, mask) = args[0], args[1], args[2:]
        losses = []
        for si in range(mask.shape[1]):
            p, s, loss = fn(p, s, data, indices, mask, jnp.int32(si))
            losses.append(loss)
        return p, s, jnp.stack(losses, axis=1)  # (C, S) like the scan's

    def finish_bucket(self, out, C: int, home):
        """Trim shard padding; a sharded bucket's results go back to
        ``home``, the sharding of its start params.  Everything after
        local training (Eq. 2, the teacher bank, KD) runs where the server
        state lives, on one device: the chip's compiler cannot partition a
        Pallas kernel over several devices."""
        p, s, losses = out
        if jax.tree.leaves(p)[0].shape[0] != C:  # trim shard padding
            p = jax.tree.map(lambda x: x[:C], p)
            s = jax.tree.map(lambda x: x[:C], s)
            losses = losses[:C]
        if self._use_shard_map():
            p, s, losses = jax.device_put((p, s, losses), home)
        return p, s, losses

    def scan_fn(self):
        """The jitted per-bucket scan program — the subgraph the overlap
        executor composes with the KD scan into ONE device program."""
        return self._vectorized_fn()

    # ---- public: train every client of a plan bucket ------------------
    def train_bucket(self, plan: ClientPlan, stacked_params: PyTree,
                     stacked_opt_state: PyTree):
        """(Cb,...)-stacked params/opt state -> trained (Cb,...) stacks."""
        args, C = self.prepare_bucket(plan, stacked_params, stacked_opt_state)
        return self.finish_bucket(self.run_prepared(args), C,
                                  _home(stacked_params))

    def train_round(self, rplan: RoundPlan, start_for: Callable,
                    run_buckets=None) -> list:
        """Train every bucket of the round plan.

        ``start_for(plan) -> (w0, s0)``: the bucket's (Cb, ...) stacked
        start params and optimiser state (``start_state``, plus what the
        optimiser keeps per client).

        ``run_buckets``, when given, replaces the per-bucket dispatch: it
        receives the list of padded arg tuples (see ``prepare_bucket``)
        and must return the corresponding padded outputs — the overlap
        executor passes a closure that runs every bucket's scan AND the
        pending KD scan as one jitted program.

        Returns ``buckets``, a list of (plan, trained_params,
        final_opt_state, start_params) per batch-size bucket, rows in the
        bucket's sorted-cid order: ``finish_round`` (or ``reassemble``)
        puts them in round order.  SCAFFOLD's control update needs the
        bucket view, since opt-state trees are stacked per bucket.
        """
        prepared = []
        with span("fedsdd.local.prep"):
            for plan in rplan.plans:
                w0, s0 = start_for(plan)
                args, C = self.prepare_bucket(plan, w0, s0)
                prepared.append((plan, w0, args, C))
        # rows x steps of the step masks: shard padding and padded steps
        count("scan_steps", sum(args[4].size for _, _, args, _ in prepared))
        with span("fedsdd.local.dispatch"):
            if run_buckets is None:
                outs = [self.run_prepared(args)
                        for _, _, args, _ in prepared]
            else:
                outs = run_buckets([args for _, _, args, _ in prepared])
        with span("fedsdd.local.reassemble"):
            buckets = []
            for (plan, w0, _, C), out in zip(prepared, outs):
                p, s, _ = self.finish_bucket(out, C, _home(w0))
                buckets.append((plan, p, s, w0))
        return buckets

    # ---- the start of local training, one program --------------------
    def start_state(self, global_models: Sequence[PyTree], group_of):
        """A bucket's (Cb, ...) start params, each row its group's global
        model, and their fresh optimiser state: one program, whose shapes
        depend on Cb and the model alone."""
        if self._start_fn is None:
            init = self.optimizer.init

            def start(models, gid):
                w0 = jax.tree.map(lambda x: x[gid], tree_stack(models))
                return w0, jax.vmap(init)(w0)

            self._start_fn = named_program(LOCAL_START, start)
        return self._start_fn(list(global_models),
                              jnp.asarray(group_of, jnp.int32))


def _round_order(buckets):
    """The inverse of the buckets' concatenated round positions, and the
    group ids, sizes and client ids in round order.  Bucket rows are in
    sorted-cid order (the data-cache key), NOT round order: the
    permutation is required even for a single bucket."""
    plans = [b[0] for b in buckets]
    inv = np.argsort(np.concatenate([p.order for p in plans]))
    return inv, *(np.concatenate([getattr(p, f) for p in plans])[inv]
                  for f in ("group_of", "sizes", "cids"))


def _in_round_order(trained: Sequence[PyTree], perm) -> PyTree:
    return jax.tree.map(
        lambda *xs: (jnp.concatenate(xs) if len(xs) > 1 else xs[0])[perm],
        *trained)


_clients_in_round_order = named_program(ROUND_ORDER, _in_round_order)


@functools.partial(named_program, EQ2, static_argnames=("num_groups",))
def _round_end(trained, perm, w, gid, *, num_groups):
    # the round-ordered client stack is a temporary, not an output: the
    # outputs are allocated when the program is enqueued, while the
    # bucket scans still hold their memory
    agg = eq2_grouped(_in_round_order(trained, perm), w, gid, num_groups)
    return agg, [jax.tree.map(lambda x, k=k: x[k], agg)
                 for k in range(num_groups)]


def finish_round(buckets, num_groups: int):
    """Average each group of the round (Eq. 2) over every bucket's
    trained params in one program.

    Returns ``(stacked_globals, new_globals, group_ids, sizes, cids)``:
    the (K, ...) group averages, the same as K pytrees, and the round's
    host arrays in group-major order.  Eq. 2's route (the batched kernel
    or the segment reduction) is chosen on the host, as
    ``fedavg_aggregate_grouped`` chooses it, and its operands are the
    clients in round order, as ``reassemble`` gives them."""
    inv, gids, sizes, cids = _round_order(buckets)
    w, gid = eq2_operands(sizes, gids, num_groups)
    agg, models = _round_end([b[1] for b in buckets], jnp.asarray(inv), w,
                             gid, num_groups=num_groups)
    return agg, models, gids, sizes, cids


def reassemble(buckets):
    """The (C, ...) client stack in round order, in one program, with its
    group ids and sizes: for the rounds whose Eq. 2 is masked or robust,
    and for ensembles of the clients themselves."""
    inv, gids, sizes, _ = _round_order(buckets)
    return (_clients_in_round_order([b[1] for b in buckets],
                                    jnp.asarray(inv)), gids, sizes)


def _home(tree):
    return jax.tree.leaves(tree)[0].sharding


def unstack_models(stacked: PyTree) -> list[PyTree]:
    return tree_unstack(stacked)
