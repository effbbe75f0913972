"""Pallas TPU kernels for fused ensemble knowledge distillation.

Hot spot: the FedSDD server evaluates K·R teacher logit stacks and a
student over the task's vocabulary (10 classes for CIFAR, up to 256 K
for the model zoo's LMs).  Unfused, the teacher mean, its τ-softmax, the
student log-softmax and the KL reduction each round-trip (B, V) f32
tensors through HBM.  These kernels keep a (Bb, V) row tile resident in
VMEM per grid step:

  ensemble_softmax: grid (B/Bb, K) — accumulates teacher k's tile into the
    output tile (revisited across the K axis: TPU grids run sequentially so
    the output block acts as an accumulator), then finalizes max/exp/sum in
    VMEM on the last K step.  HBM traffic = read K tiles + write 1, the
    streaming minimum.

  kd_loss fwd/bwd: grid (B/Bb,) — one pass computes the student row
    logsumexp and the per-row KL (fwd, written lane-dense as a (Bb, 128)
    broadcast that the caller sums), or the analytic gradient
    τ·(softmax − t)/B (bwd, upstream cotangent read from SMEM).

Tiling: the chip's compiler accepts a block only when its last two dims
are multiples of (8, 128) or equal the array's own dims.  Every block
here spans the whole (lane-padded) V, and ``row_block`` picks the row
count.  Row padding: ops.py pads V to a lane multiple with -1e30 logits /
0 probs, which is exact for softmax and KL.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
# one f32 (rows, width) operand block; with double buffering, two or three
# operands and the kernels' temporaries this stays inside the 16 MiB of
# scoped VMEM a v5e kernel gets by default
BLOCK_BYTES = 1 << 20


def row_block(B: int, width: int) -> int:
    """Rows per block for a ``(B, width)`` operand: the largest multiple
    of 8 that divides B and keeps one f32 block (lane-padded width) within
    ``BLOCK_BYTES`` — at least 8 rows.  B itself when no multiple of 8
    divides it: a block equal to the array's dimension is always legal."""
    lanes = pl.cdiv(width, LANES) * LANES
    cap = max(SUBLANES, BLOCK_BYTES // (lanes * 4))
    for bb in range(min(cap, B) // SUBLANES * SUBLANES, 0, -SUBLANES):
        if B % bb == 0:
            return bb
    return B


# ---------------------------------------------------------------------
# ensemble softmax: (K, B, V) -> (B, V)
# ---------------------------------------------------------------------
def _ensemble_softmax_kernel(t_ref, o_ref, *, K: int, inv_temp: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = t_ref[0].astype(jnp.float32) * (1.0 / K)

    @pl.when(k > 0)
    def _acc():
        o_ref[...] += t_ref[0].astype(jnp.float32) * (1.0 / K)

    @pl.when(k == K - 1)
    def _finalize():
        z = o_ref[...] * inv_temp
        m = jnp.max(z, axis=-1, keepdims=True)
        e = jnp.exp(z - m)
        o_ref[...] = e / jnp.sum(e, axis=-1, keepdims=True)


def ensemble_softmax(teacher_logits: jnp.ndarray, temperature: float = 1.0,
                     interpret: bool = True):
    """teacher_logits (K, B, V) -> probs (B, V) f32."""
    K, B, V = teacher_logits.shape
    bb = row_block(B, V)
    return pl.pallas_call(
        functools.partial(_ensemble_softmax_kernel, K=K,
                          inv_temp=1.0 / temperature),
        grid=(B // bb, K),
        in_specs=[pl.BlockSpec((1, bb, V), lambda b, k: (k, b, 0))],
        out_specs=pl.BlockSpec((bb, V), lambda b, k: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, V), jnp.float32),
        interpret=interpret,
    )(teacher_logits)


# ---------------------------------------------------------------------
# KD loss forward: per-row KL, lane-dense
# ---------------------------------------------------------------------
def _kd_loss_fwd_kernel(s_ref, t_ref, o_ref, *, inv_temp: float):
    s = s_ref[...].astype(jnp.float32) * inv_temp            # (bb, V)
    t = t_ref[...].astype(jnp.float32)
    m = jnp.max(s, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(s - m), axis=-1, keepdims=True)) + m
    log_s = s - lse
    log_t = jnp.log(jnp.clip(t, 1e-20, None))
    kl = jnp.sum(t * (log_t - log_s), axis=-1, keepdims=True)  # (bb, 1)
    o_ref[...] = jnp.broadcast_to(kl, o_ref.shape)


def kd_loss_fwd(student_logits, teacher_probs, temperature: float = 1.0,
                interpret: bool = True):
    """Returns the scalar loss mean_b KL·τ²."""
    B, V = student_logits.shape
    bb = row_block(B, V)
    row_kl = pl.pallas_call(
        functools.partial(_kd_loss_fwd_kernel, inv_temp=1.0 / temperature),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, V), lambda b: (b, 0)),
                  pl.BlockSpec((bb, V), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((bb, LANES), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, LANES), jnp.float32),
        interpret=interpret,
    )(student_logits, teacher_probs)
    return jnp.sum(row_kl[:, 0]) / B * temperature ** 2


# ---------------------------------------------------------------------
# KD loss backward: grad_s = τ (softmax(s/τ) − t) / B  (× upstream g)
# ---------------------------------------------------------------------
def _kd_loss_bwd_kernel(s_ref, t_ref, g_ref, o_ref, *, inv_temp: float,
                        inv_b_tau: float):
    s = s_ref[...].astype(jnp.float32) * inv_temp
    t = t_ref[...].astype(jnp.float32)
    g = g_ref[0, 0]
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    o_ref[...] = ((p - t) * (g * inv_b_tau)).astype(o_ref.dtype)


def smem_scalar(g):
    """A scalar operand as the (1, 1) f32 array a kernel reads from SMEM."""
    return jnp.reshape(g, (1, 1)).astype(jnp.float32)


SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def kd_loss_bwd(student_logits, teacher_probs, g, temperature: float = 1.0,
                interpret: bool = True):
    B, V = student_logits.shape
    bb = row_block(B, V)
    return pl.pallas_call(
        functools.partial(_kd_loss_bwd_kernel, inv_temp=1.0 / temperature,
                          inv_b_tau=temperature / B),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, V), lambda b: (b, 0)),
                  pl.BlockSpec((bb, V), lambda b: (b, 0)),
                  SMEM_SPEC],
        out_specs=pl.BlockSpec((bb, V), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((B, V), student_logits.dtype),
        interpret=interpret,
    )(student_logits, teacher_probs, smem_scalar(g))
