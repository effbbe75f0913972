"""Device-resident teacher bank (paper §3.1.3, Eq. 5).

The teacher ensemble is the checkpoints of all K global models over the
last R rounds.  Host-side pytree lists would be re-stacked and
re-uploaded every round;
here the whole bank is ONE stacked pytree held on device (leaves
``(R, K, ...)``) and ``push`` is an in-place ``dynamic_update_index_in_dim``
with the old buffer donated — no host round-trips, no re-stacking, and the
fused KD pipeline reads its ``(M, ...)`` teacher stack straight out of the
bank (``members_stacked``).

Spill-to-disk is retained for huge models: when ``spill_dir`` is set, a
round evicted from the ring is persisted through ``fedckpt`` (one ``.npz``
per member, ``r{round:05d}_g{k}.npz``) before its slot is overwritten —
the only device→host transfer the bank ever does.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.spans import span
from repro.fedckpt.checkpointer import spill_members
from repro.utils.pytree import tree_bytes, tree_stack, tree_unstack

PyTree = Any

_RING_WRITE = None
_GATHER = None


def _ring_write_fn():
    """Jitted slot write, built lazily so backend choice is settled.

    The bank buffer is donated on accelerators (true in-place update);
    XLA:CPU cannot reuse donated buffers, so donation is skipped there to
    avoid per-call warnings.
    """
    global _RING_WRITE
    if _RING_WRITE is None:
        def write(bank, member_stack, slot):
            return jax.tree.map(
                lambda b, m: jax.lax.dynamic_update_index_in_dim(
                    b, m.astype(b.dtype), slot, 0),
                bank, member_stack)
        donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
        _RING_WRITE = jax.jit(write, donate_argnums=donate)
    return _RING_WRITE


def _gather_fn():
    global _GATHER
    if _GATHER is None:
        def gather(bank, order):
            # (R, K, ...) -> rounds in `order`, flattened to (m·K, ...)
            def leaf(b):
                g = jnp.take(b, order, axis=0)
                return g.reshape((-1,) + b.shape[2:])
            return jax.tree.map(leaf, bank)
        _GATHER = jax.jit(gather)
    return _GATHER


class TeacherBank:
    """Ring buffer of the last R rounds' K aggregated checkpoints.

    API-compatible with the old host-list ``TemporalEnsemble`` (``push`` /
    ``members`` / ``num_members`` / ``rounds_held``), plus
    ``members_stacked()`` — the ``(M, ...)`` stacked teacher pytree the
    vectorized engine and the fused KD pipeline consume directly, M = K ×
    rounds-held, newest round first (fewer than K·R during the first R−1
    rounds).

    ``dtype`` is the on-device storage precision knob: with
    ``dtype=jnp.bfloat16`` floating-point leaves are held (and pushed)
    bf16, halving bank HBM so R can double at the same memory; the KD
    pipeline and the legacy oracle both cast teacher *logits* to f32
    before the ensemble reduction, so ``ensemble_softmax`` compute stays
    f32 and only the stored weights are rounded.  Integer/bool leaves
    keep their dtype.  Spill files are f32 containers either way
    (``fedckpt`` upcasts bf16 losslessly).
    """

    def __init__(self, K: int, R: int, spill_dir: str | None = None,
                 dtype=None):
        if K < 1 or R < 1:
            raise ValueError(f"K and R must be >= 1, got K={K}, R={R}")
        self.K, self.R = K, R
        self.spill_dir = spill_dir
        self.dtype = jnp.dtype(dtype) if dtype is not None else None
        self._bank: PyTree | None = None           # leaves (R, K, ...)
        self._slot_rounds: list[int | None] = [None] * R
        self._cursor = 0
        # fault bookkeeping: round -> tuple of group indices whose slot-k
        # snapshot is a carry-forward (group emptied by dropouts/rejects),
        # kept for the run's lifetime so degraded teachers are auditable
        self._degraded: dict[int, tuple] = {}

    def _store_dtype(self, leaf):
        if self.dtype is not None and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            return self.dtype
        return leaf.dtype

    # ------------------------------------------------------------- write
    def push(self, round_idx: int, global_models: Sequence[PyTree] | PyTree,
             degraded: Sequence[int] = ()) -> None:
        """Insert one round's K models, evicting (and spilling) the oldest.

        ``global_models``: list of K pytrees, or one pytree whose leaves
        already carry the leading (K, ...) model axis (the vectorized
        engine's representation — no re-stacking).  ``degraded`` names the
        groups whose model is a carry-forward this round (emptied by
        faults) — recorded so the ensemble's provenance stays auditable.
        """
        with span("fedsdd.bank_push"):
            if degraded:
                self._degraded[int(round_idx)] = tuple(
                    sorted(int(k) for k in degraded))
            if isinstance(global_models, (list, tuple)):
                if len(global_models) != self.K:
                    raise ValueError(f"expected {self.K} group models, "
                                     f"got {len(global_models)}")
                member_stack = tree_stack(list(global_models))
            else:
                member_stack = global_models
                lead = jax.tree.leaves(member_stack)[0].shape[0]
                if lead != self.K:
                    raise ValueError(
                        f"stacked model axis {lead} != K={self.K}")
            if self._bank is None:
                self._bank = jax.tree.map(
                    lambda m: jnp.zeros((self.R,) + m.shape,
                                        self._store_dtype(m)),
                    member_stack)
            slot = self._cursor
            evicted = self._slot_rounds[slot]
            if evicted is not None and self.spill_dir:
                spill_members(self.spill_dir, evicted, self.round_stack(slot))
            self._bank = _ring_write_fn()(self._bank, member_stack,
                                          jnp.int32(slot))
            self._slot_rounds[slot] = round_idx
            self._cursor = (slot + 1) % self.R

    # ------------------------------------------------------------- read
    def round_stack(self, slot: int) -> PyTree:
        """(K, ...) stack of one ring slot."""
        return jax.tree.map(lambda b: b[slot], self._bank)

    def _slots_newest_first(self) -> list[int]:
        held = [(r, s) for s, r in enumerate(self._slot_rounds)
                if r is not None]
        held.sort(reverse=True)
        return [s for _, s in held]

    def members_stacked(self) -> PyTree | None:
        """(M, ...) stacked teachers, newest round first; None if empty."""
        order = self._slots_newest_first()
        if not order:
            return None
        return _gather_fn()(self._bank, jnp.asarray(order, jnp.int32))

    def members(self) -> list[PyTree]:
        """Flat teacher list {w_{t-r,k}}, newest round first — the legacy
        host-list view (each member is a fresh gather, not a bank alias,
        so holding members across a later ``push`` is safe even with
        donation)."""
        stacked = self.members_stacked()
        return [] if stacked is None else tree_unstack(stacked)

    @property
    def num_members(self) -> int:
        return self.K * sum(r is not None for r in self._slot_rounds)

    def nbytes(self) -> int:
        """Device bytes held by the ring — the quantity the bf16 storage
        knob halves (see ``benchmarks/bench_distill.teacher_bank_precision``)."""
        if self._bank is None:
            return 0
        return tree_bytes(self._bank)

    def rounds_held(self) -> list[int]:
        return sorted(r for r in self._slot_rounds if r is not None)

    def degraded_rounds(self) -> dict[int, tuple]:
        """round -> groups that carried forward that round (see ``push``)."""
        return dict(self._degraded)

    def degraded_mask_stacked(self) -> np.ndarray | None:
        """(M,) bool aligned with ``members_stacked`` rows: True where
        member m is a group model that carried forward (degraded) in its
        slot's round — the bank-side input to KD trust weighting (a
        carried-forward teacher restates a STALE global; agreement alone
        cannot always tell it from a fresh one).  Row order mirrors the
        gather: slots newest-first, K group models contiguous per slot."""
        order = self._slots_newest_first()
        if not order:
            return None
        mask = []
        for s in order:
            bad = set(self._degraded.get(int(self._slot_rounds[s]), ()))
            mask.extend(k in bad for k in range(self.K))
        return np.asarray(mask, bool)  # lint-ok: RA101 host list

    # -------------------------------------------- crash-safe resume hooks
    def bank_like(self, member_like: PyTree) -> PyTree:
        """A zeros pytree with the bank's (R, K, ...) leaf shapes and
        STORAGE dtypes — the ``like`` a checkpoint restore loads into."""
        return jax.tree.map(
            lambda m: jnp.zeros((self.R, self.K) + m.shape,
                                self._store_dtype(m)), member_like)

    def export_state(self) -> tuple[PyTree | None, dict]:
        """(device ring, JSON-able meta) — everything a fresh bank needs
        to resume this one exactly (slot->round map, cursor, degraded
        log).  Empty slots encode as round −1 in the meta."""
        meta = {
            "slot_rounds": [-1 if r is None else int(r)
                            for r in self._slot_rounds],
            "cursor": int(self._cursor),
            "degraded": {str(r): list(v) for r, v in self._degraded.items()},
        }
        return self._bank, meta

    def import_state(self, bank: PyTree | None, meta: dict) -> None:
        """Adopt a checkpointed ring + meta (inverse of ``export_state``)."""
        self._bank = bank
        self._slot_rounds = [None if int(r) < 0 else int(r)
                             for r in meta["slot_rounds"]]
        self._cursor = int(meta["cursor"])
        self._degraded = {int(r): tuple(int(k) for k in v)
                          for r, v in meta.get("degraded", {}).items()}
