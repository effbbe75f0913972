"""Public jit'd KD ops with custom_vjp and backend dispatch.

On a TPU backend the Pallas kernels run compiled (Mosaic).  On any other
backend the ops run the pure-jnp implementations — ``ref.py`` for the
dense family, the tiled jnp sweeps of ``flash.py`` for the flash family
— unless ``REPRO_FORCE_PALLAS=1``, which runs the same Pallas kernels in
interpret mode (the CPU tests use it to hold the kernels to ``ref.py``).

Two KD kernel families live here:

  * **dense** (``kd_loss`` + ``ensemble_softmax``) — consumes a full
    ``(B, V)`` f32 teacher-*probability* row per step; the parity oracle.
  * **flash** (``flash_kd_loss`` / ``flash_kd_head_loss``) — consumes the
    mean teacher *logit* row (bf16-storable: the compressed teacher
    cache) and fuses the teacher τ-softmax, student log-softmax and KL
    into streaming ``V``-tile passes with online logsumexp (``flash.py``);
    the forward saves only per-row normalizers so the backward is a
    second streaming pass with no recompute.  The **head-fused** variant
    additionally takes pre-head features + the LM-head matrix and runs
    the ``h @ W[:, tile]`` matmul inside each tile, so the ``(B, V)``
    student logit row is never materialized either — gradients flow to
    the features, the head matrix and the optional bias through per-tile
    accumulators.

Vocab padding: the dense Pallas path pads to a multiple of 128 lanes with
-1e30 student logits / 0 teacher probs (exact for softmax + KL); the
flash paths pad NOTHING anywhere — tile-unaligned vocabularies are
handled in kernel (``flash._mask_tail``'s ``broadcasted_iota`` column
mask on the Pallas grid; a statically-shaped ragged epilogue tile on the
jnp sweep), so the per-step bodies perform zero host-side copies.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.kd_loss import flash, kernel, ref
from repro.kernels.kd_loss.flash import DEFAULT_TILE_V


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def pallas_active() -> bool:
    """Public probe: will the KD ops dispatch to the Pallas kernels?
    Cache builders use it to decide whether to pre-pad the DENSE prob
    tensor (the lane-padded Pallas layout) — the flash cache is never
    padded on any path."""
    return _use_pallas()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_v(x, fill, multiple: int = 128):
    V = x.shape[-1]
    pad = (-V) % multiple
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)], constant_values=fill)


# ---------------------------------------------------------------- kd_loss
@partial(jax.custom_vjp, nondiff_argnums=(2,))
def kd_loss(student_logits, teacher_probs, temperature: float = 1.0):
    """mean_b KL(teacher ‖ softmax(student/τ)) · τ².  Differentiable wrt
    student logits; teachers are constants (paper Eq. 4).

    ``teacher_probs`` may arrive pre-padded to the 128-lane multiple (the
    cache-resident layout) — zero-prob lanes are exact, and the student
    row is padded to match (a no-op for lane-aligned vocabularies).
    """
    if _use_pallas():
        s = _pad_v(student_logits, -1e30)
        t = _pad_v(teacher_probs, 0.0)
        return kernel.kd_loss_fwd(s, t, temperature, interpret=_interpret())
    return ref.kd_loss_ref(student_logits, teacher_probs, temperature)


def _kd_fwd(student_logits, teacher_probs, temperature):
    return kd_loss(student_logits, teacher_probs, temperature), \
        (student_logits, teacher_probs)


def _kd_bwd(temperature, saved, g):
    s, t = saved
    if _use_pallas():
        sp = _pad_v(s, -1e30)
        tp = _pad_v(t, 0.0)
        gs = kernel.kd_loss_bwd(sp, tp, g, temperature, interpret=_interpret())
        gs = gs[..., :s.shape[-1]]
    else:
        gs = (ref.kd_loss_grad_ref(s, t, temperature) * g).astype(s.dtype)
    return gs, None


kd_loss.defvjp(_kd_fwd, _kd_bwd)


# ------------------------------------------------------------ flash_kd_loss
def _flash_fwd_impl(s, zt, teacher_lse, temperature, tile_v):
    if _use_pallas():
        # no operand padding — ragged vocabularies mask in kernel
        return flash.flash_kd_fwd(s, zt, temperature,
                                  block_v=int(tile_v or DEFAULT_TILE_V),
                                  interpret=_interpret(),
                                  teacher_lse=teacher_lse)
    return flash.flash_kd_fwd_tiled(
        s, zt, temperature, int(tile_v or flash.DEFAULT_TILE_V_HOST),
        teacher_lse=teacher_lse)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_kd_loss(student_logits, teacher_mean_logits, teacher_lse,
                   temperature, tile_v):
    loss, _, _ = _flash_fwd_impl(student_logits, teacher_mean_logits,
                                 teacher_lse, temperature, tile_v)
    return loss


def _flash_fwd(student_logits, teacher_mean_logits, teacher_lse,
               temperature, tile_v):
    loss, lse_s, lse_t = _flash_fwd_impl(student_logits, teacher_mean_logits,
                                         teacher_lse, temperature, tile_v)
    return loss, (student_logits, teacher_mean_logits, lse_s, lse_t)


def _flash_bwd(temperature, tile_v, saved, g):
    s, zt, lse_s, lse_t = saved
    if _use_pallas():
        gs = flash.flash_kd_bwd(s, zt, lse_s, lse_t, g, temperature,
                                block_v=int(tile_v or DEFAULT_TILE_V),
                                interpret=_interpret())
    else:
        gs = flash.flash_kd_bwd_ref(s, zt, lse_s, lse_t, g, temperature)
    return gs, None, None


_flash_kd_loss.defvjp(_flash_fwd, _flash_bwd)


def flash_kd_loss(student_logits, teacher_mean_logits,
                  temperature: float = 1.0, tile_v: int | None = None,
                  teacher_lse=None):
    """Fused vocab-tiled KD loss from the COMPRESSED teacher cache.

    ``teacher_mean_logits`` is the ensemble-mean logit row z̄ (any float
    dtype — the bf16 cache upcasts to f32 inside the tile compute); the
    teacher τ-softmax, student log-softmax and KL reduce in one streaming
    pass over ``tile_v``-wide vocab tiles with O(B·tile) live memory.
    Equals ``kd_loss(s, softmax(z̄/τ), τ)`` up to f32 reduction order.
    Differentiable wrt student logits only (teachers frozen, Eq. 4).

    ``teacher_lse`` — the per-row normalizer logsumexp(z̄/τ), optional:
    it is τ-fixed and student-independent, so the KD pipeline computes it
    ONCE at cache build (``teacher_cache_lse``) and every step then skips
    the teacher's online max/sum chain; omitted, the kernel runs the full
    two-distribution online accumulator.
    """
    return _flash_kd_loss(student_logits, teacher_mean_logits, teacher_lse,
                          temperature, tile_v)


# ------------------------------------------------------ flash_kd_head_loss
def _flash_head_fwd_impl(h, w, b, zt, teacher_lse, temperature, tile_v):
    if _use_pallas():
        return flash.flash_kd_head_fwd(h, w, b, zt, temperature,
                                       block_v=int(tile_v or DEFAULT_TILE_V),
                                       interpret=_interpret(),
                                       teacher_lse=teacher_lse)
    return flash.flash_kd_head_fwd_tiled(
        h, w, b, zt, temperature, int(tile_v or flash.DEFAULT_TILE_V_HOST),
        teacher_lse=teacher_lse)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_kd_head_loss(features, head_w, head_b, teacher_mean_logits,
                        teacher_lse, temperature, tile_v):
    loss, _, _ = _flash_head_fwd_impl(features, head_w, head_b,
                                      teacher_mean_logits, teacher_lse,
                                      temperature, tile_v)
    return loss


def _flash_head_fwd(features, head_w, head_b, teacher_mean_logits,
                    teacher_lse, temperature, tile_v):
    loss, lse_s, lse_t = _flash_head_fwd_impl(features, head_w, head_b,
                                              teacher_mean_logits,
                                              teacher_lse, temperature,
                                              tile_v)
    return loss, (features, head_w, head_b, teacher_mean_logits,
                  lse_s, lse_t)


def _flash_head_bwd(temperature, tile_v, saved, g):
    h, w, b, zt, lse_s, lse_t = saved
    if _use_pallas():
        gh, gw, gb = flash.flash_kd_head_bwd(
            h, w, b, zt, lse_s, lse_t, g, temperature,
            block_v=int(tile_v or DEFAULT_TILE_V), interpret=_interpret())
    else:
        gh, gw, gb = flash.flash_kd_head_bwd_tiled(
            h, w, b, zt, lse_s, lse_t, g, temperature,
            int(tile_v or flash.DEFAULT_TILE_V_HOST))
    return gh, gw, gb, None, None


_flash_kd_head_loss.defvjp(_flash_head_fwd, _flash_head_bwd)


def flash_kd_head_loss(features, head_w, head_b=None,
                       teacher_mean_logits=None, temperature: float = 1.0,
                       tile_v: int | None = None, teacher_lse=None):
    """Head-fused vocab-tiled KD loss: the student LM-head matmul runs
    INSIDE the streaming V sweep.

    ``features`` is the pre-head activation ``(B, D)`` (post final-norm),
    ``head_w`` the ``(D, V)`` head matrix (any float dtype — bf16 heads
    upcast to f32 per tile), ``head_b`` an optional ``(V,)`` bias.  Each
    tile computes ``h @ W[:, tile] (+ b[tile])`` and feeds it straight
    into the online-logsumexp KL accumulator, so live student-logit
    memory is O(B·tile) — the full ``(B, V)`` row never exists, which is
    what lets server-side KD run at V≈256k × large B.

    Differentiable wrt ``features``, ``head_w`` and ``head_b`` (teachers
    frozen): the backward streams the same tiles once more, accumulating
    ``∂h`` across tiles and writing the disjoint ``∂W``/``∂b`` slices —
    the logit gradient only ever exists at ``(B, tile)`` width.  Equals
    ``flash_kd_loss(h @ W + b, z̄, τ)`` up to f32 accumulation order
    (bounded by the tile count; see ``flash.py``).
    """
    if teacher_mean_logits is None:
        # the bias slot precedes the teacher operand (so no-bias callers
        # read naturally) — catch the classic off-by-one-argument misuse
        # here instead of deep inside the kernel
        raise TypeError(
            "flash_kd_head_loss needs teacher_mean_logits; got None — "
            "did you skip the head_b slot? Pass head_b=None explicitly: "
            "flash_kd_head_loss(h, W, None, teacher_mean_logits, ...)")
    return _flash_kd_head_loss(features, head_w, head_b,
                               teacher_mean_logits, teacher_lse,
                               temperature, tile_v)


def teacher_cache_lse(mean_logits, temperature: float = 1.0):
    """Per-row logsumexp(z̄/τ) of a (…, V) mean-logit cache — the f32
    normalizer residual stored beside the compressed cache at build time.
    Computed from the STORED (possibly bf16-rounded) values so it is
    exact for what the per-step kernel consumes."""
    return jax.nn.logsumexp(mean_logits.astype(jnp.float32) / temperature,
                            axis=-1)


# ------------------------------------------------------- ensemble_softmax
def ensemble_softmax(teacher_logits, temperature: float = 1.0,
                     keep_pad: bool = False):
    """(K, B, V) -> (B, V) τ-softmax of the mean teacher logit (Eq. 3/5).
    Non-differentiable by design (teachers are frozen).

    ``keep_pad=True`` (Pallas path only) returns the lane-padded ``(B,
    Vp)`` tensor instead of slicing back — the cache-resident layout that
    lets per-step ``kd_loss`` calls skip the teacher re-pad (padded lanes
    hold exactly-zero probability).
    """
    teacher_logits = jax.lax.stop_gradient(teacher_logits)
    if _use_pallas():
        t = _pad_v(teacher_logits, -1e30)
        # padding note: -1e30/K per member keeps padded lanes at prob 0
        out = kernel.ensemble_softmax(t, temperature, interpret=_interpret())
        return out if keep_pad else out[..., :teacher_logits.shape[-1]]
    return ref.ensemble_softmax_ref(teacher_logits, temperature)


def ensemble_softmax_many(teacher_logits, temperature: float = 1.0,
                          keep_pad: bool = False):
    """(M, n_batches, B, V) -> (n_batches, B, V'): ensemble probs for the
    WHOLE distillation set in one pass (V' = padded V under ``keep_pad``).

    The KD pipeline precomputes every server batch's teacher probs once
    per round; merging the (n_batches, B) row dims lets the same
    ``ensemble_softmax`` kernel invocation (one grid, one HBM sweep of the
    teacher stack) serve any n_batches instead of dispatching per batch.
    """
    M, nB, B, V = teacher_logits.shape
    out = ensemble_softmax(teacher_logits.reshape(M, nB * B, V), temperature,
                           keep_pad=keep_pad)
    return out.reshape(nB, B, out.shape[-1])


def ensemble_kd_loss(student_logits, teacher_logits, temperature: float = 1.0):
    """Fully fused path: teacher stack (K, B, V) + student (B, V) -> loss."""
    return kd_loss(student_logits,
                   ensemble_softmax(teacher_logits, temperature), temperature)
