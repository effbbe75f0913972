"""Flash-KD: vocab-tiled fused distillation kernels (online logsumexp).

The dense KD path (``kernel.py``) holds full ``(B, V)`` rows live three
times per step — the f32 teacher-*prob* cache row, the student logits and
the student softmax/log-softmax intermediates — which for the model-zoo
vocabularies (V ≈ 256 K) makes the KD phase memory-bound: every forward
and backward re-reads full-``V`` rows from HBM.  Flash-KD restructures
Eq. 4 the way flash attention restructures softmax(QKᵀ)V:

  * the teacher is consumed as its **mean logit** tensor z̄ (exactly the
    logit-sum form the sharded FedDF precompute psums, storable in bf16 —
    half the cache bytes of f32 probs), and
  * the τ-softmax of the teacher, the student log-softmax and the KL
    reduction are fused into ONE streaming pass over ``V``-tiles with
    O(B·tile) live memory, carrying per-row online-renormalized
    accumulators (m, Σe) for both distributions plus the cross term.

With s = z_s/τ and t = z̄/τ (scaled logits), per row:

    KL(p‖q) = Σ_v p_v (t_v − s_v) − lse(t) + lse(s)
            = A / l_t − (m_t + log l_t) + (m_s + log l_s)

where (m_x, l_x) are the running max / rescaled sum-of-exp of x and
A = Σ_v e^{t_v − m_t}(t_v − s_v) is rescaled by e^{m_t−m_t'} whenever the
teacher max advances — the flash-attention identity applied to the KL
cross term.  The forward saves only the per-row normalizers (lse_s,
lse_t): the backward

    ∂loss/∂z_s = g·(τ/B)·(e^{s − lse_s} − e^{t − lse_t})

is then a single second streaming pass with NO reductions and no
recompute of either softmax.

**Head fusion** (``flash_kd_head_*``): at LM scale the student row
``z_s = h @ W (+ b)`` is itself the memory wall — ``logits_fn`` has to
materialize the full ``(B, V)`` product before the loss even starts.  The
head-fused variants take the pre-head features ``h`` ``(B, D)`` plus the
LM-head matrix ``W`` ``(D, V)`` and compute ``h @ W[:, tile]`` INSIDE each
streaming tile, so the student logit row never exists at any width beyond
one tile.  The backward is still reduction-free per tile — with
d = g·(τ/B)·(q_tile − p_tile):

    ∂h += d @ W[:, tile]ᵀ        (accumulated across tiles)
    ∂W[:, tile] = hᵀ @ d         (written once per tile)
    ∂b[tile]    = Σ_batch d

i.e. the ``(B, V)`` gradient exists only as the transient ``(B, tile)``
block ``d``; the per-tile ∂h accumulator merely REASSOCIATES the same
V-term sum the dense contraction computes, so its deviation from the
dense grouping random-walks over the tile count — ≈1e-7·√(V/tile)
relative, far inside the 2e-4 end-to-end budget (the ∂W/∂b slices are
single f32 contractions, bit-comparable to the dense grad).

Two implementations share the algorithm:

  * ``flash_kd_fwd_tiled`` / ``flash_kd_bwd_ref`` and the head-fused
    ``flash_kd_head_fwd_tiled`` / ``flash_kd_head_bwd_tiled`` —
    pure-jnp streaming loops (``lax.fori_loop`` over full tiles + a
    static ragged-tail epilogue, so no padding copies anywhere).  The
    default off-TPU path and the target of the hypothesis property
    suites (``tests/test_flash_kd.py``, ``tests/test_head_fusion.py``).
  * ``flash_kd_fwd`` / ``flash_kd_bwd`` / ``flash_kd_head_fwd`` /
    ``flash_kd_head_bwd`` — Pallas TPU kernels; the per-row accumulators
    ride in revisited f32 output blocks (TPU grids run sequentially, so
    a block mapped to the same slot acts as carry — the same trick
    ``kernel.ensemble_softmax`` uses).

VMEM budget: ``kernel.row_block`` sizes the (Bb, Vt) tiles to ≤ 1 MiB of
f32 each (Bb = 64 rows at Vt=4096) — live memory is set by the TILE, not
by V; the 256 K-vocab rows never exist on
chip at once.  Ragged vocabularies (V not a tile multiple) need NO
padding on any path: the Pallas grid runs ``ceil(V/Vt)`` tiles and the
kernels mask the tail lanes in place with a ``broadcasted_iota`` column
check (masked lanes read as ``FLASH_PAD`` — exp underflows to exactly 0
under the running max, the cross term sees (t−s) = 0, and masked
backward lanes are zeroed), while the jnp path streams the tail as one
statically-shaped epilogue tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kd_loss.kernel import LANES, SMEM_SPEC, row_block, smem_scalar

DEFAULT_TILE_V = 4096
# the jnp (host) path has no VMEM budget — a wider default tile keeps the
# XLA:CPU sweep at full vector width; explicit tile_v always wins (tests
# pin small tiles to exercise the accumulator)
DEFAULT_TILE_V_HOST = 32768
# masked-lane fill for BOTH student logits and the mean-logit cache:
# representable in bf16, exp()→0 exactly, and (t − s) = 0 on masked lanes
FLASH_PAD = -1e30


# =====================================================================
# pure-jnp tiled streaming implementation (CPU default + property oracle)
# =====================================================================
def _acc_tile(carry, s_c, t_c, inv_temp: float):
    """One online-accumulator update over a (B, tile) pair of tiles."""
    m_s, l_s, m_t, l_t, acc = carry
    s = s_c.astype(jnp.float32) * inv_temp
    t = t_c.astype(jnp.float32) * inv_temp
    m_s2 = jnp.maximum(m_s, jnp.max(s, axis=-1))
    l_s = l_s * jnp.exp(m_s - m_s2) + jnp.sum(
        jnp.exp(s - m_s2[:, None]), axis=-1)
    m_t2 = jnp.maximum(m_t, jnp.max(t, axis=-1))
    e_t = jnp.exp(t - m_t2[:, None])
    scale = jnp.exp(m_t - m_t2)
    l_t = l_t * scale + jnp.sum(e_t, axis=-1)
    acc = acc * scale + jnp.sum(e_t * (t - s), axis=-1)
    return m_s2, l_s, m_t2, l_t, acc


def _acc_tile_lse(carry, s_c, t_c, lse_t, inv_temp: float):
    """Accumulator update when the teacher normalizer is ALREADY KNOWN
    (precomputed once at cache build): p = e^{t − lse_t} needs no running
    max/rescale chain, so only the student stays online."""
    m_s, l_s, cross = carry
    s = s_c.astype(jnp.float32) * inv_temp
    t = t_c.astype(jnp.float32) * inv_temp
    m_s2 = jnp.maximum(m_s, jnp.max(s, axis=-1))
    l_s = l_s * jnp.exp(m_s - m_s2) + jnp.sum(
        jnp.exp(s - m_s2[:, None]), axis=-1)
    p = jnp.exp(t - lse_t[:, None])
    cross = cross + jnp.sum(p * (t - s), axis=-1)
    return m_s2, l_s, cross


def _tiled_sweep(student_logits, teacher_mean_logits, carry, update,
                 tile: int):
    """Drive ``update(carry, s_tile, t_tile)`` over the vocab tiles: few
    tiles unroll with static slices so XLA fuses the whole sweep (a
    1-iteration ``fori_loop`` walls off fusion and measurably slows the
    small-V CPU path); many tiles run rolled to keep the program small.
    The ragged tail (V % tile) is one statically-shaped epilogue update —
    no padding copies anywhere."""
    V = student_logits.shape[1]
    n_full = V // tile
    if n_full <= 16:
        for i in range(n_full):
            carry = update(carry,
                           student_logits[:, i * tile:(i + 1) * tile],
                           teacher_mean_logits[:, i * tile:(i + 1) * tile])
    else:
        def body(i, c):
            s_c = jax.lax.dynamic_slice_in_dim(student_logits, i * tile,
                                               tile, axis=1)
            t_c = jax.lax.dynamic_slice_in_dim(teacher_mean_logits, i * tile,
                                               tile, axis=1)
            return update(c, s_c, t_c)

        carry = jax.lax.fori_loop(0, n_full, body, carry)
    if V % tile:
        carry = update(carry, student_logits[:, n_full * tile:],
                       teacher_mean_logits[:, n_full * tile:])
    return carry


def flash_kd_fwd_tiled(student_logits, teacher_mean_logits,
                       temperature: float = 1.0,
                       tile_v: int = DEFAULT_TILE_V, teacher_lse=None):
    """Streaming fused KD forward; returns ``(loss, lse_s, lse_t)``.

    ``lse_s``/``lse_t`` are the per-row normalizers of the SCALED logits
    (z/τ) — the residuals that make the backward a single pad-free
    streaming pass.  When ``teacher_lse`` is supplied (the KD pipeline
    precomputes it ONCE at cache build — it is τ-fixed and
    student-independent), the per-step teacher max/sum reduction chain
    disappears entirely and only the student lse stays online.
    """
    B, V = student_logits.shape
    inv_temp = 1.0 / float(temperature)
    tile = max(1, min(int(tile_v), V))

    neg_inf = jnp.full((B,), -jnp.inf, jnp.float32)
    zero = jnp.zeros((B,), jnp.float32)
    if teacher_lse is not None:
        lse_t = teacher_lse.astype(jnp.float32)
        m_s, l_s, cross = _tiled_sweep(
            student_logits, teacher_mean_logits, (neg_inf, zero, zero),
            lambda c, s_c, t_c: _acc_tile_lse(c, s_c, t_c, lse_t, inv_temp),
            tile)
        lse_s = m_s + jnp.log(l_s)
        kl = cross - lse_t + lse_s
    else:
        m_s, l_s, m_t, l_t, acc = _tiled_sweep(
            student_logits, teacher_mean_logits,
            (neg_inf, zero, neg_inf, zero, zero),
            lambda c, s_c, t_c: _acc_tile(c, s_c, t_c, inv_temp), tile)
        lse_s = m_s + jnp.log(l_s)
        lse_t = m_t + jnp.log(l_t)
        kl = acc / l_t - lse_t + lse_s
    loss = jnp.mean(kl) * float(temperature) ** 2
    return loss, lse_s, lse_t


def flash_kd_bwd_ref(student_logits, teacher_mean_logits, lse_s, lse_t, g,
                     temperature: float = 1.0):
    """Residual-fed backward: one elementwise pass, zero reductions.

    ``exp(s − lse_s)`` IS the student softmax and ``exp(t − lse_t)`` the
    teacher probs — no max/sum recompute (the dense path's backward
    re-reduces both over the full V).
    """
    B = student_logits.shape[0]
    inv_temp = 1.0 / float(temperature)
    q = jnp.exp(student_logits.astype(jnp.float32) * inv_temp
                - lse_s[:, None])
    p = jnp.exp(teacher_mean_logits.astype(jnp.float32) * inv_temp
                - lse_t[:, None])
    coef = g * (float(temperature) / B)
    return ((q - p) * coef).astype(student_logits.dtype)


# =====================================================================
# pure-jnp head-fused streaming implementation
# =====================================================================
def _head_sweep(h32, head_w, head_b, teacher_mean_logits, carry, update,
                tile: int):
    """Like ``_tiled_sweep`` but the student tile is COMPUTED on the fly:
    ``h @ W[:, tile] (+ b[tile])`` — the ``(B, V)`` student row never
    exists.  Same unroll-vs-fori policy and static ragged-tail epilogue.

    ``update(carry, s_tile, t_tile, w_tile, i0)`` additionally receives
    the head slab and the tile's start column so the backward can reuse
    this exact scaffolding (∂h needs ``w_tile``, the disjoint ∂W/∂b
    writes need ``i0``); forward updates ignore the extras.
    """
    V = teacher_mean_logits.shape[1]
    n_full = V // tile

    def s_of(w_c, b_c):
        s = h32 @ w_c.astype(jnp.float32)
        if b_c is not None:
            s = s + b_c.astype(jnp.float32)[None, :]
        return s

    def at(c, i0, w_c, b_c, t_c):
        return update(c, s_of(w_c, b_c), t_c, w_c, i0)

    if n_full <= 16:
        for i in range(n_full):
            sl = slice(i * tile, (i + 1) * tile)
            carry = at(carry, i * tile, head_w[:, sl],
                       None if head_b is None else head_b[sl],
                       teacher_mean_logits[:, sl])
    else:
        def body(i, c):
            w_c = jax.lax.dynamic_slice_in_dim(head_w, i * tile, tile, axis=1)
            t_c = jax.lax.dynamic_slice_in_dim(teacher_mean_logits, i * tile,
                                               tile, axis=1)
            b_c = (None if head_b is None else
                   jax.lax.dynamic_slice_in_dim(head_b, i * tile, tile, 0))
            return at(c, i * tile, w_c, b_c, t_c)

        carry = jax.lax.fori_loop(0, n_full, body, carry)
    if V % tile:
        sl = slice(n_full * tile, V)
        carry = at(carry, n_full * tile, head_w[:, sl],
                   None if head_b is None else head_b[sl],
                   teacher_mean_logits[:, sl])
    return carry


def flash_kd_head_fwd_tiled(features, head_w, head_b, teacher_mean_logits,
                            temperature: float = 1.0,
                            tile_v: int = DEFAULT_TILE_V_HOST,
                            teacher_lse=None):
    """Head-fused streaming KD forward: ``(loss, lse_s, lse_t)`` from
    pre-head features ``(B, D)`` + head ``(D, V)`` (+ optional ``(V,)``
    bias) — ``z_s = h @ W + b`` is produced one ``(B, tile)`` block at a
    time inside the online-logsumexp sweep and discarded."""
    B = features.shape[0]
    V = teacher_mean_logits.shape[-1]
    inv_temp = 1.0 / float(temperature)
    tile = max(1, min(int(tile_v), V))
    h32 = features.astype(jnp.float32)

    neg_inf = jnp.full((B,), -jnp.inf, jnp.float32)
    zero = jnp.zeros((B,), jnp.float32)
    if teacher_lse is not None:
        lse_t = teacher_lse.astype(jnp.float32)
        m_s, l_s, cross = _head_sweep(
            h32, head_w, head_b, teacher_mean_logits, (neg_inf, zero, zero),
            lambda c, s_c, t_c, *_: _acc_tile_lse(c, s_c, t_c, lse_t,
                                                  inv_temp),
            tile)
        lse_s = m_s + jnp.log(l_s)
        kl = cross - lse_t + lse_s
    else:
        m_s, l_s, m_t, l_t, acc = _head_sweep(
            h32, head_w, head_b, teacher_mean_logits,
            (neg_inf, zero, neg_inf, zero, zero),
            lambda c, s_c, t_c, *_: _acc_tile(c, s_c, t_c, inv_temp), tile)
        lse_s = m_s + jnp.log(l_s)
        lse_t = m_t + jnp.log(l_t)
        kl = acc / l_t - lse_t + lse_s
    loss = jnp.mean(kl) * float(temperature) ** 2
    return loss, lse_s, lse_t


def flash_kd_head_bwd_tiled(features, head_w, head_b, teacher_mean_logits,
                            lse_s, lse_t, g, temperature: float = 1.0,
                            tile_v: int = DEFAULT_TILE_V_HOST):
    """Head-fused residual backward: ``(∂h, ∂W, ∂b)`` in one streaming
    pass, zero re-reductions.  The per-tile logit gradient
    d = g·(τ/B)·(q − p) exists only at ``(B, tile)`` width; ``∂h``
    accumulates ``d @ W_tileᵀ`` across tiles (f32 accumulator — error
    grows with the tile count only, see module docstring) while
    ``∂W[:, tile] = hᵀ @ d`` / ``∂b[tile] = Σ_b d`` are disjoint
    write-once slices."""
    B, D = features.shape
    V = teacher_mean_logits.shape[-1]
    inv_temp = 1.0 / float(temperature)
    tile = max(1, min(int(tile_v), V))
    h32 = features.astype(jnp.float32)
    coef = jnp.asarray(g, jnp.float32) * (float(temperature) / B)
    lse_s = lse_s.astype(jnp.float32)
    lse_t = lse_t.astype(jnp.float32)

    def bwd_tile(c, s_c, t_c, w_c, i0):
        gh, gw, gb = c
        q = jnp.exp(s_c * inv_temp - lse_s[:, None])
        p = jnp.exp(t_c.astype(jnp.float32) * inv_temp - lse_t[:, None])
        d = (q - p) * coef                  # (B, width) — the only width
        #                                     the logit grad ever has
        gh = gh + d @ w_c.astype(jnp.float32).T
        gw = jax.lax.dynamic_update_slice_in_dim(gw, h32.T @ d, i0, axis=1)
        if gb is not None:
            gb = jax.lax.dynamic_update_slice_in_dim(gb, jnp.sum(d, axis=0),
                                                     i0, 0)
        return gh, gw, gb

    gh, gw, gb = _head_sweep(
        h32, head_w, head_b, teacher_mean_logits,
        (jnp.zeros((B, D), jnp.float32), jnp.zeros((D, V), jnp.float32),
         None if head_b is None else jnp.zeros((V,), jnp.float32)),
        bwd_tile, tile)
    return (gh.astype(features.dtype), gw.astype(head_w.dtype),
            None if gb is None else gb.astype(head_b.dtype))


# =====================================================================
# Pallas kernels: grid (B/Bb, ceil(V/Vt)), V innermost (sequential carry)
# =====================================================================
def _mask_tail(x, v_idx, v_total: int, fill):
    """Replace the ragged-tail lanes (global column ≥ v_total) with
    ``fill`` — the in-kernel ``broadcasted_iota`` mask that removes any
    need for host-side padding.  Static no-op when the tile divides V."""
    vt = x.shape[-1]
    if v_total % vt == 0:
        return x
    col = v_idx * vt + jax.lax.broadcasted_iota(jnp.int32, x.shape,
                                                x.ndim - 1)
    return jnp.where(col < v_total, x, fill)


def _flash_fwd_kernel(s_ref, t_ref, m_s_ref, l_s_ref, m_t_ref, l_t_ref,
                      acc_ref, *, inv_temp: float, v_total: int):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_s_ref[...] = jnp.full(m_s_ref.shape, -jnp.inf, jnp.float32)
        l_s_ref[...] = jnp.zeros(l_s_ref.shape, jnp.float32)
        m_t_ref[...] = jnp.full(m_t_ref.shape, -jnp.inf, jnp.float32)
        l_t_ref[...] = jnp.zeros(l_t_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # ragged tail: FLASH_PAD lanes are exact no-ops (exp→0, (t−s)=0)
    s = _mask_tail(s_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    s = s * inv_temp                                       # (bb, vt)
    t = t * inv_temp

    # accumulator blocks are (bb, LANES) with the value broadcast across
    # lanes — revisited across the v axis they carry the online state
    m_s_old = m_s_ref[...]
    m_s_new = jnp.maximum(m_s_old, jnp.max(s, axis=-1, keepdims=True))
    l_s_ref[...] = (l_s_ref[...] * jnp.exp(m_s_old - m_s_new)
                    + jnp.sum(jnp.exp(s - m_s_new[:, :1]), axis=-1,
                              keepdims=True))
    m_s_ref[...] = m_s_new

    m_t_old = m_t_ref[...]
    m_t_new = jnp.maximum(m_t_old, jnp.max(t, axis=-1, keepdims=True))
    e_t = jnp.exp(t - m_t_new[:, :1])
    scale = jnp.exp(m_t_old - m_t_new)
    l_t_ref[...] = (l_t_ref[...] * scale
                    + jnp.sum(e_t, axis=-1, keepdims=True))
    acc_ref[...] = (acc_ref[...] * scale
                    + jnp.sum(e_t * (t - s), axis=-1, keepdims=True))
    m_t_ref[...] = m_t_new


def _flash_fwd_lse_kernel(s_ref, t_ref, lse_t_ref, m_s_ref, l_s_ref,
                          cross_ref, *, inv_temp: float, v_total: int):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_s_ref[...] = jnp.full(m_s_ref.shape, -jnp.inf, jnp.float32)
        l_s_ref[...] = jnp.zeros(l_s_ref.shape, jnp.float32)
        cross_ref[...] = jnp.zeros(cross_ref.shape, jnp.float32)

    s = _mask_tail(s_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    s = s * inv_temp
    t = t * inv_temp

    m_s_old = m_s_ref[...]
    m_s_new = jnp.maximum(m_s_old, jnp.max(s, axis=-1, keepdims=True))
    l_s_ref[...] = (l_s_ref[...] * jnp.exp(m_s_old - m_s_new)
                    + jnp.sum(jnp.exp(s - m_s_new[:, :1]), axis=-1,
                              keepdims=True))
    m_s_ref[...] = m_s_new

    # teacher normalizer precomputed at cache build: p needs no max chain
    p = jnp.exp(t - lse_t_ref[...])
    cross_ref[...] += jnp.sum(p * (t - s), axis=-1, keepdims=True)


def _lane_block(V: int, block_v: int) -> int:
    """Vocab tile: all of V when it fits in ``block_v``, else ``block_v``
    rounded down to the 128-lane tile (the chip refuses any other)."""
    if V <= block_v:
        return V
    return max(LANES, block_v // LANES * LANES)


def _col(x):
    """(B,) per-row residual -> the (B, 1) column its blocks are cut from
    (a 1-D block must be a multiple of 128 long; a (Bb, 1) one is legal)."""
    return x.astype(jnp.float32).reshape(-1, 1)


def flash_kd_fwd(student_logits, teacher_mean_logits,
                 temperature: float = 1.0,
                 block_v: int = DEFAULT_TILE_V, interpret: bool = True,
                 teacher_lse=None):
    """Fused streaming KD forward; any V works — a tile-unaligned vocab
    runs ``ceil(V/Vt)`` grid steps with the tail lanes masked IN KERNEL
    (``_mask_tail``), so neither operand is ever padded host-side.
    Returns ``(loss, lse_s, lse_t)`` — the residuals feed the backward.
    With ``teacher_lse`` (cache-build precompute) the kernel drops the
    teacher's online max/rescale chain: 3 accumulators instead of 5.
    """
    B, V = student_logits.shape
    vt = _lane_block(V, block_v)
    bb = row_block(B, vt)
    grid = (B // bb, pl.cdiv(V, vt))
    tile = pl.BlockSpec((bb, vt), lambda b, v: (b, v))
    stat = pl.BlockSpec((bb, LANES), lambda b, v: (b, 0))
    n_stats = 5 if teacher_lse is None else 3
    call = functools.partial(
        pl.pallas_call, grid=grid,
        out_specs=[stat] * n_stats,
        out_shape=[jax.ShapeDtypeStruct((B, LANES), jnp.float32)] * n_stats,
        interpret=interpret, name="flash_kd_fwd")
    if teacher_lse is not None:
        lse_t = teacher_lse.astype(jnp.float32)
        outs = call(
            functools.partial(_flash_fwd_lse_kernel,
                              inv_temp=1.0 / temperature, v_total=V),
            in_specs=[tile, tile,
                      pl.BlockSpec((bb, 1), lambda b, v: (b, 0))],
        )(student_logits, teacher_mean_logits, _col(lse_t))
        m_s, l_s, cross = (o[:, 0] for o in outs)
        lse_s = m_s + jnp.log(l_s)
        kl = cross - lse_t + lse_s
        return jnp.mean(kl) * temperature ** 2, lse_s, lse_t
    outs = call(
        functools.partial(_flash_fwd_kernel, inv_temp=1.0 / temperature,
                          v_total=V),
        in_specs=[tile, tile],
    )(student_logits, teacher_mean_logits)
    m_s, l_s, m_t, l_t, acc = (o[:, 0] for o in outs)
    lse_s = m_s + jnp.log(l_s)
    lse_t = m_t + jnp.log(l_t)
    kl = acc / l_t - lse_t + lse_s
    return jnp.mean(kl) * temperature ** 2, lse_s, lse_t


def _flash_bwd_kernel(s_ref, t_ref, lse_s_ref, lse_t_ref, g_ref, o_ref, *,
                      inv_temp: float, tau_over_b: float, v_total: int):
    v = pl.program_id(1)
    s = _mask_tail(s_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    q = jnp.exp(s * inv_temp - lse_s_ref[...])
    p = jnp.exp(t * inv_temp - lse_t_ref[...])
    o_ref[...] = ((q - p) * (g_ref[0, 0] * tau_over_b)).astype(o_ref.dtype)


def flash_kd_bwd(student_logits, teacher_mean_logits, lse_s, lse_t, g,
                 temperature: float = 1.0,
                 block_v: int = DEFAULT_TILE_V, interpret: bool = True):
    """Second streaming pass: ∂loss/∂student_logits from saved residuals.
    Ragged-tail stores past V land in masked lanes (q = p = 0 there)."""
    B, V = student_logits.shape
    vt = _lane_block(V, block_v)
    bb = row_block(B, vt)
    tile = pl.BlockSpec((bb, vt), lambda b, v: (b, v))
    col = pl.BlockSpec((bb, 1), lambda b, v: (b, 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, inv_temp=1.0 / temperature,
                          tau_over_b=temperature / B, v_total=V),
        grid=(B // bb, pl.cdiv(V, vt)),
        in_specs=[tile, tile, col, col, SMEM_SPEC],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, V), student_logits.dtype),
        interpret=interpret, name="flash_kd_bwd",
    )(student_logits, teacher_mean_logits, _col(lse_s), _col(lse_t),
      smem_scalar(g))


# =====================================================================
# Pallas head-fused kernels: grid (ceil(V/Vt),), full feature rows live
# =====================================================================
# The head-fused grid streams the V axis only: the (B, D) feature block
# and the (B, LANES) accumulators stay resident while each step loads one
# (D, Vt) head slab + one (B, Vt) cache tile and runs the MXU matmul
# in-kernel.  That keeps every output revisit CONSECUTIVE (a TPU
# requirement for carry blocks): ∂h accumulates across the whole grid,
# ∂W/∂b blocks are written exactly once at their own v step.  The bias
# and its gradient travel as (1, V) rows: a 2-D (1, Vt) block is legal
# where a 1-D one would need the iota mask in one dimension.
#
# VMEM: the resident (B, D) blocks do not shrink with the tile, so the
# vocab tile is cut to keep the f32 view of one (D, Vt) head slab within
# HEAD_SLAB_BYTES (Vt = 256 at D = 2048, 128 at D ≥ 4096), and the scoped
# VMEM limit is raised from the 16 MiB default (a v5e core has 128 MiB).
# B·D must still fit: at D = 5120 that holds to about 1K feature rows.
HEAD_SLAB_BYTES = 2 << 20
HEAD_VMEM_LIMIT = 96 << 20


def _head_call(kernel, **kw):
    return pl.pallas_call(
        kernel, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=HEAD_VMEM_LIMIT), **kw)


def _head_block_v(V: int, D: int, block_v: int) -> int:
    cap = max(LANES, HEAD_SLAB_BYTES // (4 * D) // LANES * LANES)
    return _lane_block(V, min(block_v, cap))


def _head_tile(h, w_ref, b_ref, v, v_total: int):
    """(B, vt) student tile ``h @ W_tile (+ b_tile)`` with masked-lane
    head columns zeroed first (OOB slab lanes must not poison the MXU)."""
    w = _mask_tail(w_ref[...].astype(jnp.float32), v, v_total, 0.0)
    s = jnp.dot(h, w, preferred_element_type=jnp.float32)
    if b_ref is not None:
        s = s + _mask_tail(b_ref[...].astype(jnp.float32), v, v_total, 0.0)
    return _mask_tail(s, v, v_total, FLASH_PAD)


def _flash_head_fwd_kernel(h_ref, w_ref, b_ref, t_ref, m_s_ref, l_s_ref,
                           m_t_ref, l_t_ref, acc_ref, *, inv_temp: float,
                           v_total: int):
    v = pl.program_id(0)

    @pl.when(v == 0)
    def _init():
        m_s_ref[...] = jnp.full(m_s_ref.shape, -jnp.inf, jnp.float32)
        l_s_ref[...] = jnp.zeros(l_s_ref.shape, jnp.float32)
        m_t_ref[...] = jnp.full(m_t_ref.shape, -jnp.inf, jnp.float32)
        l_t_ref[...] = jnp.zeros(l_t_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    h = h_ref[...].astype(jnp.float32)
    s = _head_tile(h, w_ref, b_ref, v, v_total) * inv_temp
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total,
                   FLASH_PAD) * inv_temp

    m_s_old = m_s_ref[...]
    m_s_new = jnp.maximum(m_s_old, jnp.max(s, axis=-1, keepdims=True))
    l_s_ref[...] = (l_s_ref[...] * jnp.exp(m_s_old - m_s_new)
                    + jnp.sum(jnp.exp(s - m_s_new[:, :1]), axis=-1,
                              keepdims=True))
    m_s_ref[...] = m_s_new

    m_t_old = m_t_ref[...]
    m_t_new = jnp.maximum(m_t_old, jnp.max(t, axis=-1, keepdims=True))
    e_t = jnp.exp(t - m_t_new[:, :1])
    scale = jnp.exp(m_t_old - m_t_new)
    l_t_ref[...] = (l_t_ref[...] * scale
                    + jnp.sum(e_t, axis=-1, keepdims=True))
    acc_ref[...] = (acc_ref[...] * scale
                    + jnp.sum(e_t * (t - s), axis=-1, keepdims=True))
    m_t_ref[...] = m_t_new


def _flash_head_fwd_lse_kernel(h_ref, w_ref, b_ref, t_ref, lse_t_ref,
                               m_s_ref, l_s_ref, cross_ref, *,
                               inv_temp: float, v_total: int):
    v = pl.program_id(0)

    @pl.when(v == 0)
    def _init():
        m_s_ref[...] = jnp.full(m_s_ref.shape, -jnp.inf, jnp.float32)
        l_s_ref[...] = jnp.zeros(l_s_ref.shape, jnp.float32)
        cross_ref[...] = jnp.zeros(cross_ref.shape, jnp.float32)

    h = h_ref[...].astype(jnp.float32)
    s = _head_tile(h, w_ref, b_ref, v, v_total) * inv_temp
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total,
                   FLASH_PAD) * inv_temp

    m_s_old = m_s_ref[...]
    m_s_new = jnp.maximum(m_s_old, jnp.max(s, axis=-1, keepdims=True))
    l_s_ref[...] = (l_s_ref[...] * jnp.exp(m_s_old - m_s_new)
                    + jnp.sum(jnp.exp(s - m_s_new[:, :1]), axis=-1,
                              keepdims=True))
    m_s_ref[...] = m_s_new

    p = jnp.exp(t - lse_t_ref[...])
    cross_ref[...] += jnp.sum(p * (t - s), axis=-1, keepdims=True)


def _without_bias(kern, trailing=()):
    """Kernel adapter for the no-bias call: ``None`` for ``b_ref`` (the
    third input) and for the ``trailing`` refs the kernel expects after
    the real ones (the backward's bias-gradient output)."""
    return lambda h_ref, w_ref, *rest, **kw: kern(h_ref, w_ref, None, *rest,
                                                  *trailing, **kw)


def flash_kd_head_fwd(features, head_w, head_b, teacher_mean_logits,
                      temperature: float = 1.0,
                      block_v: int = DEFAULT_TILE_V, interpret: bool = True,
                      teacher_lse=None):
    """Pallas head-fused forward: ``(loss, lse_s, lse_t)``.  The student
    logit row exists only as the in-kernel ``(B, vt)`` MXU product."""
    B, D = features.shape
    V = teacher_mean_logits.shape[-1]
    vt = _head_block_v(V, D, block_v)
    stat = pl.BlockSpec((B, LANES), lambda v: (0, 0))
    in_specs = [pl.BlockSpec((B, D), lambda v: (0, 0)),
                pl.BlockSpec((D, vt), lambda v: (0, v))]
    operands = [features, head_w]
    if head_b is not None:
        in_specs.append(pl.BlockSpec((1, vt), lambda v: (0, v)))
        operands.append(head_b.reshape(1, V))
    in_specs.append(pl.BlockSpec((B, vt), lambda v: (0, v)))
    operands.append(teacher_mean_logits)
    if teacher_lse is not None:
        lse_t = teacher_lse.astype(jnp.float32)
        in_specs.append(pl.BlockSpec((B, 1), lambda v: (0, 0)))
        operands.append(_col(lse_t))
    kern = (_flash_head_fwd_kernel if teacher_lse is None
            else _flash_head_fwd_lse_kernel)
    n_stats = 5 if teacher_lse is None else 3
    if head_b is None:
        kern = _without_bias(kern)
    outs = _head_call(
        functools.partial(kern, inv_temp=1.0 / temperature, v_total=V),
        grid=(pl.cdiv(V, vt),), in_specs=in_specs,
        out_specs=[stat] * n_stats,
        out_shape=[jax.ShapeDtypeStruct((B, LANES), jnp.float32)] * n_stats,
        interpret=interpret,
    )(*operands)
    if teacher_lse is not None:
        m_s, l_s, cross = (o[:, 0] for o in outs)
        lse_s = m_s + jnp.log(l_s)
        kl = cross - lse_t + lse_s
        return jnp.mean(kl) * temperature ** 2, lse_s, lse_t
    m_s, l_s, m_t, l_t, acc = (o[:, 0] for o in outs)
    lse_s = m_s + jnp.log(l_s)
    lse_t = m_t + jnp.log(l_t)
    kl = acc / l_t - lse_t + lse_s
    return jnp.mean(kl) * temperature ** 2, lse_s, lse_t


def _flash_head_bwd_kernel(h_ref, w_ref, b_ref, t_ref, lse_s_ref, lse_t_ref,
                           g_ref, gh_ref, gw_ref, gb_ref, *, inv_temp: float,
                           tau_over_b: float, v_total: int):
    v = pl.program_id(0)

    @pl.when(v == 0)
    def _init():
        gh_ref[...] = jnp.zeros(gh_ref.shape, jnp.float32)

    h = h_ref[...].astype(jnp.float32)
    w = _mask_tail(w_ref[...].astype(jnp.float32), v, v_total, 0.0)
    s = jnp.dot(h, w, preferred_element_type=jnp.float32)
    if b_ref is not None:
        s = s + _mask_tail(b_ref[...].astype(jnp.float32), v, v_total, 0.0)
    s = _mask_tail(s, v, v_total, FLASH_PAD)
    t = _mask_tail(t_ref[...].astype(jnp.float32), v, v_total, FLASH_PAD)
    q = jnp.exp(s * inv_temp - lse_s_ref[...])
    p = jnp.exp(t * inv_temp - lse_t_ref[...])
    d = (q - p) * (g_ref[0, 0] * tau_over_b)    # (B, vt) — THE only width
    #                                             the logit grad ever has
    # ∂h accumulates across the v sweep (masked lanes: d = 0, w = 0)
    gh_ref[...] += jax.lax.dot_general(
        d, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    gw_ref[...] = jax.lax.dot_general(
        h, d, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(gw_ref.dtype)
    if gb_ref is not None:
        gb_ref[...] = jnp.sum(d, axis=0, keepdims=True).astype(gb_ref.dtype)


def flash_kd_head_bwd(features, head_w, head_b, teacher_mean_logits,
                      lse_s, lse_t, g, temperature: float = 1.0,
                      block_v: int = DEFAULT_TILE_V, interpret: bool = True):
    """Pallas head-fused backward: ``(∂h, ∂W, ∂b)`` from saved residuals —
    one streaming V sweep, ∂h carried in a revisited f32 block."""
    B, D = features.shape
    V = teacher_mean_logits.shape[-1]
    vt = _head_block_v(V, D, block_v)
    col = pl.BlockSpec((B, 1), lambda v: (0, 0))
    in_specs = [pl.BlockSpec((B, D), lambda v: (0, 0)),
                pl.BlockSpec((D, vt), lambda v: (0, v))]
    operands = [features, head_w]
    if head_b is not None:
        in_specs.append(pl.BlockSpec((1, vt), lambda v: (0, v)))
        operands.append(head_b.reshape(1, V))
    in_specs += [pl.BlockSpec((B, vt), lambda v: (0, v)), col, col,
                 SMEM_SPEC]
    operands += [teacher_mean_logits, _col(lse_s), _col(lse_t),
                 smem_scalar(g)]
    out_specs = [pl.BlockSpec((B, D), lambda v: (0, 0)),
                 pl.BlockSpec((D, vt), lambda v: (0, v))]
    out_shape = [jax.ShapeDtypeStruct((B, D), jnp.float32),
                 jax.ShapeDtypeStruct((D, V), head_w.dtype)]
    if head_b is not None:
        out_specs.append(pl.BlockSpec((1, vt), lambda v: (0, v)))
        out_shape.append(jax.ShapeDtypeStruct((1, V), head_b.dtype))
    kern = _flash_head_bwd_kernel
    if head_b is None:
        kern = _without_bias(kern, trailing=(None,))
    outs = _head_call(
        functools.partial(kern, inv_temp=1.0 / temperature,
                          tau_over_b=temperature / B, v_total=V),
        grid=(pl.cdiv(V, vt),), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*operands)
    gh = outs[0].astype(features.dtype)
    gw = outs[1]
    gb = outs[2].reshape(V) if head_b is not None else None
    return gh, gw, gb
