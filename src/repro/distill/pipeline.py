"""Fully-jitted server KD pipeline (paper Eqs. 3-4) over stacked teachers.

The legacy oracle (``core.distillation.distill``) is host-driven: one jit
dispatch per KD step, teacher probs in a host dict cache, losses pulled to
the host.  This pipeline makes the whole distillation phase one (or, in
the stepped escape hatch, ``distill_steps``) device program:

  1. **Teacher precompute** — ensemble probs for the WHOLE distillation
     set are computed once per round as a single ``(n_batches, B, V)``
     tensor: one batched ``(M, n_batches·B, V)`` teacher forward into the
     fused ``ensemble_softmax`` kernel (``ensemble_softmax_many``).
  2. **KD schedule** — the complete ``distill_steps`` schedule runs as one
     ``lax.scan`` program cycling the stacked batches on device; zero host
     syncs inside the loop, losses come back as one device array.
  3. **Multi-student** — ``distill_target='all'`` (paper Table 6) distills
     all K global models as ONE vmapped program sharing the same teacher
     tensor, instead of K sequential ``distill()`` calls.

Step mode mirrors ``core.engine``: ``REPRO_ENGINE_STEP_MODE=stepped``
forces one jitted dispatch per step (the XLA:CPU escape hatch).  Unlike
the client engine — whose vmapped loop bodies run ~10x slower under
XLA:CPU scan — the KD bodies are dispatch-bound, so scan is the default
on every backend (measured ~10x faster than stepped on CPU).

**Sharded teacher precompute.**  FedDF-style ensembles
(``ensemble_source='clients'``) carry an ``(C, ...)`` teacher stack that
grows with participation; with ``mesh=make_client_mesh()`` the teacher
pass shard_maps the member axis over the ``('clients',)`` mesh exactly
like the client engine shards local training: every device forwards its
teacher shard, one ``psum`` reduces the logit sum, and the fused
``ensemble_softmax`` kernel normalizes — so the precompute stops scaling
serially with C.  ``teacher_sharding`` takes the engine's
``auto|vmap|shard_map`` policy (``REPRO_FORCE_SHARD_MAP=1`` forces it on
a 1-device mesh for parity tests).

**Overlap support.**  ``distill_async`` dispatches the whole KD phase and
returns device arrays WITHOUT the end-of-phase host sync; the overlap
executor (``core/round_plan.py``) uses it to run the KD program
concurrently with groups k>0's local training and converts the losses
with ``losses_info`` only at resolve time.

**Flash-KD + compressed teacher cache.**  ``kd_kernel="dense"`` (the
parity oracle) precomputes the f32 ensemble-*probability* tensor and each
step consumes full ``(B, V)`` prob rows; ``kd_kernel="flash"`` stores the
mean teacher *logit* tensor instead — in ``cache_dtype`` (bf16 by
default: half the cache bytes, and exactly the logit-sum form the
sharded FedDF precompute psums) — and each step runs the vocab-tiled
``flash_kd_loss`` kernel, which fuses the teacher τ-softmax, student
log-softmax and KL into streaming ``tile_v``-wide passes with O(B·tile)
live memory (f32 tile compute either way; see ``kernels/kd_loss/flash``).
The dense prob cache is lane-padded ONCE at build on the Pallas path;
the flash cache is never padded anywhere — ragged vocabularies mask in
kernel, so the per-step bodies perform zero host-side copies.

**Head fusion.**  On the flash path a task may additionally supply
``features_fn(params, batch) -> (B, D)`` (the pre-head activations) and
``head_fn(params) -> (W, b|None)`` (the LM-head accessor); with
``head_fusion=True`` the step bodies then run ``flash_kd_head_loss``,
which computes ``h @ W[:, tile]`` INSIDE each streaming tile — the
``(B, V)`` student logit row never materializes either, closing the last
full-vocab tensor out of the per-step KD hot path (gradients reach the
backbone through ``∂h`` and the head through the per-tile ``∂W``/``∂b``
slices).  Tasks without a features/head split (CNN/ResNet heads fused
into ``logits_fn``) fall back to the plain ``flash_kd_loss`` path.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.spans import named_program, span
from repro.kernels.kd_loss import ops as kd_ops
from repro.optim.optimizers import apply_updates, sgd
from repro.sharding.specs import CLIENT_AXIS
from repro.utils.pytree import tree_cast, tree_stack

PyTree = Any
LogitsFn = Callable[[PyTree, Any], jnp.ndarray]

# trust-weight policy knobs (``KDPipeline.trust_weights``): a teacher
# whose normalized agreement weight falls below TRUST_FLOOR × uniform is
# cut to exactly zero (a Byzantine teacher must contribute NOTHING, not
# merely little); bank slots flagged degraded (carried-forward groups)
# are discounted before normalization.
TRUST_FLOOR = 0.1
TRUST_DEGRADED_DISCOUNT = 0.5

# stable names of the KD programs in a profiler trace
PRECOMPUTE = "fedsdd_kd_precompute"
KD_SCAN = "fedsdd_kd_scan"


def stack_server_batches(batches: Sequence[Any]) -> PyTree:
    """Server batch list -> one device pytree with leaves (n_batches, B, ...).

    The fused pipeline indexes batches on device (``dynamic_index_in_dim``
    inside the scan), which needs congruent shapes; task builders emit
    full-size server batches only, so a ragged tail means a misbuilt task.
    """
    try:
        return tree_stack(list(batches))
    except (ValueError, TypeError) as e:
        shapes = sorted({tuple(np.shape(x)) for b in batches
                         for x in jax.tree.leaves(b)})
        raise ValueError(
            f"fused KD pipeline needs same-shape server batches (saw leaf "
            f"shapes {shapes}); drop the ragged tail batch or use "
            f"kd_pipeline='legacy'") from e


class KDPipeline:
    """One round's distillation phase as a fused device program.

    Built once per runner (jitted programs cached across rounds); the
    stacked server-batch tensor is cached keyed on the batch list's
    identity, so the per-round host→device traffic is zero once warm.
    """

    def __init__(self, logits_fn: LogitsFn, *, steps: int, lr: float,
                 temperature: float = 4.0, momentum: float = 0.9,
                 step_mode: str = "auto", mesh=None,
                 teacher_sharding: str = "auto", kd_kernel: str = "dense",
                 cache_dtype=None, tile_v: int | None = None,
                 features_fn: Callable | None = None,
                 head_fn: Callable | None = None,
                 head_fusion: bool = False):
        if step_mode not in ("auto", "scan", "stepped"):
            raise ValueError(f"step_mode={step_mode!r} not in "
                             "('auto', 'scan', 'stepped')")
        if teacher_sharding not in ("auto", "vmap", "shard_map"):
            raise ValueError(f"teacher_sharding={teacher_sharding!r} not in "
                             "('auto', 'vmap', 'shard_map')")
        if kd_kernel not in ("dense", "flash"):
            raise ValueError(f"kd_kernel={kd_kernel!r} not in "
                             "('dense', 'flash')")
        if head_fusion and kd_kernel != "flash":
            raise ValueError(
                "head fusion streams the LM-head matmul through the "
                "flash vocab tiles — the dense prob path has no tiles "
                "to fuse it into")
        self.logits_fn = logits_fn
        self.features_fn = features_fn
        self.head_fn = head_fn
        # head fusion engages only when the task actually exposes the
        # features/head split; CNN/ResNet-style tasks (head fused into
        # logits_fn) silently keep the plain flash path
        self.head_fused = bool(head_fusion and features_fn is not None
                               and head_fn is not None)
        self.steps = int(steps)
        self.temperature = float(temperature)
        self.optimizer = sgd(lr, momentum=momentum)
        self.step_mode = step_mode
        self.mesh = mesh
        self.teacher_sharding = teacher_sharding
        self.kd_kernel = kd_kernel
        # compressed-cache storage dtype: flash defaults to bf16 mean
        # logits (half the f32-prob cache bytes); dense stores f32 probs
        if kd_kernel == "flash":
            self.cache_dtype = jnp.dtype(cache_dtype or jnp.bfloat16)
        else:
            if cache_dtype is not None and jnp.dtype(cache_dtype) != \
                    jnp.float32:
                raise ValueError("the dense prob cache is f32-only")
            self.cache_dtype = jnp.float32
        self.tile_v = tile_v
        self._probs_fn = None
        self._cache_fn = None
        self._cache_fn_w = None     # trust-weighted cache build
        self._trust_fn = None       # cross-teacher agreement weights
        self._scan_fns: dict[bool, Callable] = {}
        self._step_fns: dict[bool, Callable] = {}
        self._batches: PyTree | None = None
        self._batches_src: Sequence[Any] | None = None

    # ------------------------------------------------- server batch cache
    def batches_for(self, server_batches: Sequence[Any]) -> PyTree:
        # identity check against a retained reference: holding the keyed
        # list alive means a same-id reallocation can never alias the cache
        if self._batches_src is not server_batches:
            self._batches = stack_server_batches(server_batches)
            self._batches_src = server_batches
        return self._batches

    def nbytes(self) -> int:
        """Resident bytes of the pipeline's retained server-batch stack —
        the distill-side entry in the server residency audit alongside
        ``ClientStore.nbytes()`` and ``TeacherBank.nbytes()``.  O(server
        set), independent of C by construction; zero before the first
        round touches the pipeline."""
        if self._batches is None:
            return 0
        return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(self._batches))

    # --------------------------------------------------- teacher precompute
    def _shard_teachers(self) -> bool:
        """Shard decision for the teacher pass — the same shared policy
        the client engine resolves (``launch.mesh.use_shard_map``)."""
        from repro.launch.mesh import use_shard_map
        return use_shard_map(self.mesh, self.teacher_sharding)

    def _build_precompute(self, kind: str, weighted: bool = False):
        """Jitted per-round teacher pass.  ``kind="probs"`` is the dense
        oracle view (unpadded f32 ensemble probs); ``kind="cache"`` is the
        tensor the step bodies consume — identical for dense (plus the
        build-time lane pad on the Pallas path), the compressed
        ``cache_dtype`` mean-logit tensor for flash.

        ``weighted=True`` compiles the trust-weighted variant: Eq. 3's
        uniform mean logit becomes a convex combination Σ_m w_m·z_m
        (weights normalized inside the program), so a zero-weight teacher
        drops out of the KD target exactly.  A SEPARATE compiled program
        on purpose: ``jnp.mean`` and a uniform-weight einsum are not
        bit-identical, and trust-off must stay byte-equal to PR 8."""
        if kind not in ("probs", "cache"):
            raise ValueError(f"precompute kind={kind!r} not in "
                             "('probs', 'cache')")
        logits_fn, tau = self.logits_fn, self.temperature
        as_logits = kind == "cache" and self.kd_kernel == "flash"
        # dense-cache lane padding happens HERE, once per round, so the
        # jitted KD step bodies never re-pad the prob row; the flash
        # mean-logit cache needs no padding at all (in-kernel iota mask)
        keep_pad = kind == "cache" and kd_ops.pallas_active()
        cache_dtype = self.cache_dtype
        if not self._shard_teachers():
            @partial(named_program, PRECOMPUTE)
            def pre(ts, bs, w=None):
                # f32 compute regardless of bank storage dtype: bf16-held
                # members upcast at the forward boundary (XLA fuses the
                # cast; only the ring stays half-width)
                ts = tree_cast(ts, jnp.float32)
                lg = jax.vmap(lambda p: jax.vmap(
                    lambda b: logits_fn(p, b))(bs))(ts)        # (M, nB, B, V)
                lg = lg.astype(jnp.float32)
                if w is not None:
                    wn = w.astype(jnp.float32)
                    wn = wn / jnp.maximum(wn.sum(), 1e-12)
                    mean = jnp.einsum("m,mnbv->nbv", wn, lg)
                    if as_logits:
                        data = mean.astype(cache_dtype)
                        return data, kd_ops.teacher_cache_lse(data, tau)
                    return kd_ops.ensemble_softmax_many(mean[None], tau,
                                                        keep_pad=keep_pad)
                if as_logits:
                    data = jnp.mean(lg, axis=0).astype(cache_dtype)
                    # the f32 normalizer residual rides with the cache:
                    # τ-fixed and student-independent, computed ONCE here
                    # so the per-step kernel skips the teacher reduction
                    return data, kd_ops.teacher_cache_lse(data, tau)
                return kd_ops.ensemble_softmax_many(lg, tau,
                                                    keep_pad=keep_pad)

            if weighted:
                return named_program(PRECOMPUTE,
                                     lambda ts, bs, w: pre(ts, bs, w))
            return pre

        from repro.launch.mesh import mesh_size
        mesh = self.mesh
        n_dev = mesh_size(mesh)

        def local_cache(ts, mask, bs, *, M):
            # per-shard teacher forwards in ONE vmapped pass, f32 compute
            # and f32 sum (bf16-held members upcast at the boundary)
            ts = tree_cast(ts, jnp.float32)
            lg = jax.vmap(lambda p: jax.vmap(
                lambda b: logits_fn(p, b))(bs))(ts)            # (Ml, nB, B, V)
            lg = lg.astype(jnp.float32) * mask.reshape(
                (-1,) + (1,) * (lg.ndim - 1))
            mean = jax.lax.psum(lg.sum(0), CLIENT_AXIS)        # (nB, B, V)
            if M is not None:
                mean = mean / M
            # every device finishes the cache from the replicated mean:
            # inside the shard_map body the fused kernel sees one device's
            # arrays, the only form the chip's compiler accepts
            if as_logits:
                # the psum'd logit-sum/M IS the flash cache representation
                data = mean.astype(cache_dtype)
                return data, kd_ops.teacher_cache_lse(data, tau)
            # softmax(mean/τ) through the same fused kernel (M=1 stack)
            return kd_ops.ensemble_softmax_many(mean[None], tau,
                                                keep_pad=keep_pad)

        @partial(named_program, PRECOMPUTE)
        def pre(ts, bs, w=None):
            M = jax.tree.leaves(ts)[0].shape[0]
            pad = (-M) % n_dev
            if w is None:
                mask = (jnp.arange(M + pad) < M).astype(jnp.float32)
            else:
                # normalized trust weights ride the per-member mask lane:
                # the psum'd weighted sum IS the weighted mean (Σw = 1),
                # so the /M renormalization is skipped
                wn = w.astype(jnp.float32)
                wn = wn / jnp.maximum(wn.sum(), 1e-12)
                mask = jnp.concatenate([wn, jnp.zeros((pad,), jnp.float32)])
            if pad:  # replicate row 0, zero-masked: exact no-op members
                ts = jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])]),
                    ts)
            sharded = jax.shard_map(
                partial(local_cache, M=M if w is None else None), mesh=mesh,
                in_specs=(P(CLIENT_AXIS), P(CLIENT_AXIS), P()),
                out_specs=P(), check_vma=False)
            return sharded(ts, mask, bs)

        if weighted:
            return named_program(PRECOMPUTE, lambda ts, bs, w: pre(ts, bs, w))
        return pre

    def precompute_teacher_probs(self, teacher_stack: PyTree,
                                 batches: PyTree) -> jnp.ndarray:
        """(M, ...) teachers × (n_batches, B, ...) batches -> (n_batches, B, V)
        f32 ensemble probabilities — the dense oracle view, kept as the
        parity/bench API regardless of ``kd_kernel``.

        With an active ``('clients',)`` mesh the member axis is sharded
        (one logit-sum ``psum`` instead of a device-serial M-loop) — the
        FedDF ``(C, ...)`` client-teacher stack stops costing O(C) on one
        device.
        """
        if self._probs_fn is None:
            self._probs_fn = self._build_precompute("probs")
        return self._to_home(self._probs_fn(*self._to_mesh(teacher_stack,
                                                           batches)),
                             batches)

    def precompute_cache(self, teacher_stack: PyTree, batches: PyTree,
                         weights=None) -> PyTree:
        """The per-round teacher tensor the KD step bodies consume:
        the ``(n_batches, B, Vc)`` f32 prob tensor for
        ``kd_kernel="dense"`` (lane-padded on the Pallas path); for
        ``"flash"`` the compressed pair ``(mean_logits, lse)`` — the
        ``cache_dtype`` mean-logit tensor (bf16 default, ≤ half the
        dense cache bytes) plus its tiny ``(n_batches, B)`` f32
        normalizer residual — at the TRUE vocab width on every path
        (ragged tails are masked inside the flash kernels, never
        padded).

        ``weights`` (optional, (M,) per-teacher trust weights) swaps
        Eq. 3's uniform mean logit for the weighted combination — the
        trust-filtered ensemble target.  ``weights=None`` keeps the
        bit-identical uniform program."""
        args = self._to_mesh(teacher_stack, batches)
        if weights is None:
            cache = self._ensure_cache_fn()(*args)
        else:
            if self._cache_fn_w is None:
                self._cache_fn_w = self._build_precompute("cache",
                                                          weighted=True)
            cache = self._cache_fn_w(*args, jnp.asarray(weights, jnp.float32))
        return self._to_home(cache, batches)

    def _to_mesh(self, teacher_stack, batches):
        """The sharded precompute runs on the whole mesh and refuses
        operands committed to one device: replicate them onto the mesh
        (the program then slices each device's teacher shard locally)."""
        if not self._shard_teachers():
            return teacher_stack, batches
        return jax.device_put((teacher_stack, batches),
                              NamedSharding(self.mesh, P()))

    def _to_home(self, cache, batches):
        """A sharded precompute leaves its cache replicated on every mesh
        device; the KD scan runs on the server batches' one device, so
        take that device's copy."""
        if not self._shard_teachers():
            return cache
        return jax.device_put(cache, jax.tree.leaves(batches)[0].sharding)

    def _ensure_cache_fn(self):
        if self._cache_fn is None:
            if self.kd_kernel == "dense" and not kd_ops.pallas_active():
                # unpadded dense probs — byte-identical to the "probs"
                # program; alias it instead of compiling a duplicate
                if self._probs_fn is None:
                    self._probs_fn = self._build_precompute("probs")
                self._cache_fn = self._probs_fn
            else:
                self._cache_fn = self._build_precompute("cache")
        return self._cache_fn

    # ------------------------------------------------- teacher trust weights
    def trust_weights(self, teacher_stack: PyTree,
                      server_batches: Sequence[Any],
                      degraded_mask=None) -> jnp.ndarray:
        """(M,) per-teacher trust weights from cross-teacher agreement.

        Each teacher's τ-softmax on the probe batch (the first server
        batch — unlabeled, already resident) is compared to the ensemble
        CONSENSUS, the coordinate-wise median over teachers: a poisoned
        or stale member disagrees with the majority everywhere, an honest
        member tracks it.  Disagreement d_m = mean KL(p_m ‖ consensus) is
        self-normalized by the median disagreement (honest heterogeneity
        sets the scale, so clean rounds keep near-uniform weights), mapped
        through w = min(exp(1 − d/median(d)), 1), discounted ×
        ``TRUST_DEGRADED_DISCOUNT`` for bank slots flagged degraded
        (``degraded_mask``), normalized, and hard-floored: anything below
        ``TRUST_FLOOR``× uniform is cut to exactly 0 so a Byzantine
        teacher contributes NOTHING to Eq. 3, not merely little.

        Majority logic: the median consensus needs M ≥ 3 to identify a
        minority liar; at M ≤ 2 agreement is symmetric and only the
        degraded discount can break the tie.
        """
        batches = self.batches_for(server_batches)
        if self._trust_fn is None:
            logits_fn, tau = self.logits_fn, self.temperature

            @jax.jit
            def tw(ts, bs, discount):
                ts = tree_cast(ts, jnp.float32)
                probe = jax.tree.map(lambda x: x[0], bs)
                lg = jax.vmap(lambda p: logits_fn(p, probe))(ts)  # (M, B, V)
                p = jax.nn.softmax(lg.astype(jnp.float32) / tau, axis=-1)
                cons = jnp.median(p, axis=0)
                cons = cons / jnp.maximum(
                    cons.sum(-1, keepdims=True), 1e-12)
                eps = 1e-12
                kl = jnp.sum(p * (jnp.log(p + eps) - jnp.log(cons + eps)),
                             axis=-1)                             # (M, B)
                d = kl.mean(axis=-1)                              # (M,)
                scale = jnp.median(d) + 1e-12
                w = jnp.minimum(jnp.exp(1.0 - d / scale), 1.0) * discount
                m = w.shape[0]
                s = w.sum()
                w = jnp.where(s > 0, w / jnp.maximum(s, 1e-12),
                              jnp.full_like(w, 1.0 / m))
                w = jnp.where(w < TRUST_FLOOR / m, 0.0, w)
                s2 = w.sum()
                return jnp.where(s2 > 0, w / jnp.maximum(s2, 1e-12),
                                 jnp.full_like(w, 1.0 / m))

            self._trust_fn = tw
        m = jax.tree.leaves(teacher_stack)[0].shape[0]
        discount = np.ones((m,), np.float32)
        if degraded_mask is not None:
            discount = np.where(
                np.asarray(degraded_mask, bool),  # lint-ok: RA101 host bank mask
                TRUST_DEGRADED_DISCOUNT, 1.0).astype(np.float32)
        return self._trust_fn(teacher_stack, batches,
                              jnp.asarray(discount))

    def cache_nbytes(self, teacher_stack: PyTree, batches: PyTree) -> int:
        """Device bytes of the round's teacher cache (the quantity the
        compressed flash cache at least halves — see
        ``benchmarks/bench_distill.kd_memory``).  Shape-only: traced via
        ``eval_shape``, so probing a V≈256k cache costs no FLOPs and no
        allocation."""
        shapes = jax.eval_shape(self._ensure_cache_fn(), teacher_stack,
                                batches)
        return sum(int(np.prod(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves(shapes))

    # ------------------------------------------------------- KD step body
    def _kd_body(self):
        logits_fn, optimizer, tau = self.logits_fn, self.optimizer, \
            self.temperature

        if self.head_fused:
            tile_v = self.tile_v
            features_fn, head_fn = self.features_fn, self.head_fn

            def loss_fn(student, batch, cache_row):
                # head-fused flash: the student LM-head matmul runs
                # inside the streaming vocab tiles — neither the teacher
                # row nor the student row exists at (B, V) width; grads
                # reach the backbone via ∂h and the head via ∂W/∂b
                zt, lse = cache_row
                w, b = head_fn(student)
                return kd_ops.flash_kd_head_loss(
                    features_fn(student, batch), w, b, zt, tau, tile_v,
                    teacher_lse=lse)
        elif self.kd_kernel == "flash":
            tile_v = self.tile_v

            def loss_fn(student, batch, cache_row):
                # cache_row = (mean teacher logits [maybe bf16], f32 lse):
                # τ-softmax + KL fuse inside the vocab-tiled kernel, f32
                # tiles, and the precomputed normalizer skips the
                # per-step teacher reduction chain
                zt, lse = cache_row
                return kd_ops.flash_kd_loss(logits_fn(student, batch),
                                            zt, tau, tile_v,
                                            teacher_lse=lse)
        else:
            def loss_fn(student, batch, cache_row):
                return kd_ops.kd_loss(logits_fn(student, batch), cache_row,
                                      temperature=tau)

        def body(student, opt_state, batch, cache_row):
            loss, grads = jax.value_and_grad(loss_fn)(
                student, batch, cache_row)
            updates, opt_state = optimizer.update(grads, opt_state, student)
            return apply_updates(student, updates), opt_state, loss

        return body

    @staticmethod
    def _index_batch(batches: PyTree, cache: PyTree, bi):
        def idx(x):
            return jax.lax.dynamic_index_in_dim(x, bi, 0, keepdims=False)

        # cache is a bare prob tensor (dense) or the (logits, lse) pair
        # (flash) — every leaf carries the leading n_batches axis
        return jax.tree.map(idx, batches), jax.tree.map(idx, cache)

    # -------------------------------------------------------- scan program
    def _scan_fn(self, multi: bool):
        if multi not in self._scan_fns:
            body = self._kd_body()
            optimizer, steps = self.optimizer, self.steps

            def run(student, batches, probs):
                n = jax.tree.leaves(batches)[0].shape[0]
                opt_state = optimizer.init(student)

                def scan_body(carry, s):
                    st, os_ = carry
                    batch, tp = self._index_batch(batches, probs,
                                                  jax.lax.rem(s, n))
                    st2, os2, loss = body(st, os_, batch, tp)
                    return (st2, os2), loss

                (st, _), losses = jax.lax.scan(
                    scan_body, (student, opt_state), jnp.arange(steps))
                return st, losses

            fn = jax.vmap(run, in_axes=(0, None, None)) if multi else run
            self._scan_fns[multi] = named_program(KD_SCAN, fn)
        return self._scan_fns[multi]

    # ------------------------------------------------ stepped escape hatch
    def _step_fn(self, multi: bool):
        if multi not in self._step_fns:
            body = self._kd_body()

            def one(student, opt_state, batches, probs, s):
                n = jax.tree.leaves(batches)[0].shape[0]
                batch, tp = self._index_batch(batches, probs,
                                              jax.lax.rem(s, n))
                return body(student, opt_state, batch, tp)

            fn = jax.vmap(one, in_axes=(0, 0, None, None, None)) \
                if multi else one
            self._step_fns[multi] = named_program(KD_SCAN, fn)
        return self._step_fns[multi]

    def _run_stepped(self, student, batches, probs, multi: bool):
        fn = self._step_fn(multi)
        opt_state = (jax.vmap(self.optimizer.init) if multi
                     else self.optimizer.init)(student)
        losses = []
        for s in range(self.steps):
            student, opt_state, loss = fn(student, opt_state, batches,
                                          probs, jnp.int32(s))
            losses.append(loss)      # device scalars — no float() sync here
        if not losses:
            shape = (jax.tree.leaves(student)[0].shape[0], 0) if multi \
                else (0,)
            return student, jnp.zeros(shape, jnp.float32)
        axis = 1 if multi else 0
        return student, jnp.stack(losses, axis=axis)

    # ------------------------------------------------------------- public
    def scan_capable(self) -> bool:
        """True when the KD phase lowers to the single-scan program — the
        form the overlap executor can fuse with the engine's bucket scans."""
        from repro.core.engine import resolve_step_mode
        return resolve_step_mode(self.step_mode, cpu_default="scan") == "scan"

    def distill_async(self, student: PyTree, teacher_stack: PyTree,
                      server_batches: Sequence[Any],
                      multi: bool = False,
                      teacher_weights=None) -> tuple[PyTree, jnp.ndarray]:
        """Dispatch the whole KD phase; NO host sync — returns device
        ``(student, losses)``.  Convert losses with ``losses_info`` when
        the result is actually needed (the overlap executor's resolve
        phase).  The device program starts immediately, so local training
        dispatched afterwards runs concurrently with it.
        ``teacher_weights`` (optional (M,)) builds the trust-weighted
        teacher cache instead of the uniform Eq. 3 mean.
        """
        batches = self.batches_for(server_batches)
        with span("fedsdd.kd.precompute"):
            cache = self.precompute_cache(teacher_stack, batches,
                                          weights=teacher_weights)
        with span("fedsdd.kd.scan"):
            if self.scan_capable():
                return self._scan_fn(multi)(student, batches, cache)
            return self._run_stepped(student, batches, cache, multi)

    def losses_info(self, losses) -> dict:
        """The per-round kd record (ONE host sync) for async losses."""
        return self._info(losses)

    def _dispatch(self, student, teacher_stack, server_batches, multi: bool,
                  teacher_weights=None):
        student, losses = self.distill_async(student, teacher_stack,
                                             server_batches, multi,
                                             teacher_weights=teacher_weights)
        return student, self._info(losses)

    def distill(self, student: PyTree, teacher_stack: PyTree,
                server_batches: Sequence[Any],
                teacher_weights=None) -> tuple[PyTree, dict]:
        """Single-student fused KD; the drop-in for ``distill_target='main'``."""
        return self._dispatch(student, teacher_stack, server_batches,
                              multi=False, teacher_weights=teacher_weights)

    def distill_all(self, students_stacked: PyTree, teacher_stack: PyTree,
                    server_batches: Sequence[Any],
                    teacher_weights=None) -> tuple[PyTree, dict]:
        """All K students as one vmapped program (``distill_target='all'``);
        reported losses are the main model's (row 0)."""
        return self._dispatch(students_stacked, teacher_stack,
                              server_batches, multi=True,
                              teacher_weights=teacher_weights)

    def _info(self, losses) -> dict:
        from repro.analysis.sync import allowed_sync
        with allowed_sync("one-per-round KD loss pull into the history "
                          "record"):
            losses = np.asarray(losses)
        if losses.ndim == 2:                    # multi-student: main model
            losses = losses[0]
        return {"kd_loss_first": float(losses[0]) if losses.size else None,
                "kd_loss_last": float(losses[-1]) if losses.size else None,
                "kd_steps": self.steps}

    def jit_programs(self) -> dict:
        """Built jitted programs by label (see ``analysis.TraceGuard``)."""
        out = {}
        for multi, fn in self._scan_fns.items():
            out[f"kd/scan{'_multi' if multi else ''}"] = fn
        for multi, fn in self._step_fns.items():
            out[f"kd/step{'_multi' if multi else ''}"] = fn
        for name in ("_probs_fn", "_cache_fn", "_cache_fn_w", "_trust_fn"):
            fn = getattr(self, name)
            if fn is not None:
                out[f"kd/{name.strip('_')}"] = fn
        return out
