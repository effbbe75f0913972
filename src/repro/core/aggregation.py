"""Model aggregation (paper Eq. 2) — weight averaging within a group.

Includes the secure-aggregation simulation (Bonawitz et al. [2]) the paper
cites as FedSDD's privacy advantage: because the distillation stage only
ever consumes *aggregated* group models, clients can pairwise-mask their
updates so the server learns nothing but the sum — impossible for FedDF,
which needs each client model for its ensemble.
"""
from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.pytree import (
    group_weighted_mean, tree_group_weighted_mean, tree_stacked_weighted_mean,
    tree_weighted_mean, tree_zeros_like,
)

PyTree = Any


def fedavg_aggregate(models: Sequence[PyTree], num_samples: Sequence[int]) -> PyTree:
    """w = Σ_i (|X_i| / Σ_j |X_j|) · w_i   (Eq. 2)."""
    return tree_weighted_mean(
        list(models),
        np.asarray(num_samples, np.float64))  # lint-ok: RA101 host counts


def fedavg_aggregate_stacked(stacked: PyTree, num_samples) -> PyTree:
    """Same, over leaves with a leading client axis (the pjit'd path —
    this is what the weight_avg Pallas kernel implements on TPU)."""
    return tree_stacked_weighted_mean(stacked, num_samples)


def fedavg_aggregate_grouped(stacked: PyTree, num_samples, group_ids,
                             num_groups: int) -> PyTree:
    """Eq. 2 for ALL K groups in one pass over a client-stacked pytree.

    ``stacked`` leaves are (C, ...) in group-major client order,
    ``group_ids`` (C,) maps each row to its group.  When the groups are
    uniform (|S|/K clients each — the production shape) the reduction
    routes through the batched multi-model ``weight_avg`` Pallas kernel;
    ragged groups (C % K != 0) fall back to a fused segment reduction.
    Either way there is no per-group Python loop.
    """
    w, gid = eq2_operands(num_samples, group_ids, num_groups)
    return eq2_grouped(stacked, w, gid, num_groups)


def eq2_operands(num_samples, group_ids, num_groups: int) -> tuple:
    """Eq. 2's device operands, with its route chosen on the host.

    Uniform group-major groups on the Pallas backend: ``((K, n) float32
    weights, None)``, the batched multi-model kernel's route.  Otherwise
    ``((C,) float32 weights, (C,) int32 group ids)``, the segment
    reduction's.  ``eq2_grouped`` takes either pair."""
    from repro.kernels.weight_avg import ops as wops
    gid = np.asarray(group_ids)            # lint-ok: RA101 host group map
    counts = np.bincount(gid, minlength=num_groups)
    uniform = (counts == counts[0]).all() and counts[0] > 0
    group_major = bool((np.diff(gid) >= 0).all())
    if uniform and group_major and wops._use_pallas():
        w = jnp.asarray(
            np.asarray(num_samples, np.float64)  # lint-ok: RA101 host counts
            .reshape(num_groups, int(counts[0])), jnp.float32)
        return w, None
    sizes = np.asarray(num_samples)        # lint-ok: RA101 host counts
    return jnp.asarray(sizes, jnp.float32), jnp.asarray(gid, jnp.int32)


def eq2_grouped(stacked: PyTree, w, gid, num_groups: int) -> PyTree:
    """(K, ...) group averages of the (C, ...) client stack, over the
    operands ``eq2_operands`` chose; runs eagerly or inside a program."""
    if gid is None:
        from repro.kernels.weight_avg import ops as wops
        n = w.shape[1]
        regrouped = jax.tree.map(
            lambda x: x.reshape((num_groups, n) + x.shape[1:]), stacked)
        return wops.group_weighted_average_pytree(regrouped, w)
    return group_weighted_mean(stacked, w, gid, num_groups=num_groups)


def survivor_group_weights(num_samples, group_ids, num_groups: int,
                           survivor_mask) -> tuple:
    """(masked per-client weights, per-group live weight, empty groups).

    The shared bookkeeping between masked Eq. 2 (here) and the robust
    statistics (``core/robust_agg``): non-survivors get weight zero, and
    a group whose surviving weight mass is zero is ``empty`` — its
    aggregate must come from the carry-forward fallback.
    """
    mask = np.asarray(survivor_mask, bool)  # lint-ok: RA101 host fault mask
    gid = np.asarray(group_ids)             # lint-ok: RA101 host group map
    w_full = np.asarray(num_samples, np.float64)  # lint-ok: RA101 host counts
    w = np.where(mask, w_full, 0.0)
    live_w = np.bincount(gid, weights=w, minlength=num_groups)
    empty = [k for k in range(num_groups) if live_w[k] == 0.0]
    return w, live_w, empty


def fedavg_aggregate_grouped_masked(
        stacked: PyTree, num_samples, group_ids, num_groups: int,
        survivor_mask, fallback_stacked: PyTree,
        zero_fill: bool = False) -> tuple[PyTree, list[int]]:
    """Eq. 2 under partial participation: non-survivors get zero weight.

    Default (``zero_fill=False``) renormalizes within each group over the
    surviving weight mass — the paper's Eq. 2 restricted to the clients
    that actually reported.  ``zero_fill=True`` is the naive ablation:
    dead clients still contribute zero VECTORS to the unrenormalized
    group mean (the aggregate shrinks toward zero by the lost weight
    fraction) — the baseline ``bench_faults`` gates against.

    A group with no surviving weight cannot aggregate at all; its row is
    substituted from ``fallback_stacked`` (the (K, ...)-stacked previous
    global models — the carry-forward contract) and its index reported in
    the returned ``degraded`` list.  An all-True mask without zero_fill
    short-circuits to ``fedavg_aggregate_grouped`` verbatim, keeping the
    zero-fault path bit-identical to the no-faults engine.
    """
    mask = np.asarray(survivor_mask, bool)  # lint-ok: RA101 host fault mask
    gid = np.asarray(group_ids)             # lint-ok: RA101 host group map
    if mask.all() and not zero_fill:
        return fedavg_aggregate_grouped(stacked, num_samples, gid,
                                        num_groups), []
    w_full = np.asarray(num_samples, np.float64)  # lint-ok: RA101 host counts
    w, live_w, empty = survivor_group_weights(num_samples, gid, num_groups,
                                              mask)
    # zero weight alone cannot silence a poisoned row (0·NaN = NaN, and
    # NaN sums into its group's segment) — dead rows are zeroed outright
    maskj = jnp.asarray(mask)
    stacked = jax.tree.map(
        lambda x: jnp.where(maskj.reshape((-1,) + (1,) * (x.ndim - 1)),
                            x, jnp.zeros((), x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else x, stacked)
    # empty groups: the segment mean divides 0/0 into NaN rows, which are
    # overwritten by the fallback below — NaN never escapes group k's row
    agg = tree_group_weighted_mean(stacked, w, gid, num_groups)
    if zero_fill:
        total_w = np.bincount(gid, weights=w_full, minlength=num_groups)
        frac = jnp.asarray((live_w / np.maximum(total_w, 1e-300)
                            ).astype(np.float32))
        agg = jax.tree.map(
            lambda x: (x * frac.reshape((num_groups,) + (1,) * (x.ndim - 1)
                                        ).astype(x.dtype))
            if jnp.issubdtype(x.dtype, jnp.floating) else x, agg)
    if empty:
        idx = jnp.asarray(empty, jnp.int32)
        agg = jax.tree.map(
            lambda a, f: a.at[idx].set(f[idx].astype(a.dtype)),
            agg, fallback_stacked)
    return agg, empty


# ---------------------------------------------------------------- secure agg
def pairwise_masks(models: Sequence[PyTree], seed: int) -> list[PyTree]:
    """Antisymmetric pairwise masks: client i adds Σ_{j>i} r_ij − Σ_{j<i} r_ji.
    Masks cancel exactly in the (weighted) sum."""
    n = len(models)
    like = models[0]
    masks = [tree_zeros_like(like) for _ in range(n)]
    for i in range(n):
        for j in range(i + 1, n):
            key = jax.random.PRNGKey(seed * 1_000_003 + i * 1009 + j)
            keys = jax.random.split(key, len(jax.tree.leaves(like)))
            it = iter(keys)
            r = jax.tree.map(lambda x: jax.random.normal(next(it), x.shape, jnp.float32)
                             .astype(x.dtype), like)
            masks[i] = jax.tree.map(jnp.add, masks[i], r)
            masks[j] = jax.tree.map(jnp.subtract, masks[j], r)
    return masks


def secure_aggregate(models: Sequence[PyTree], num_samples: Sequence[int],
                     seed: int = 0) -> tuple[PyTree, list[PyTree]]:
    """Simulated Bonawitz-style secure aggregation.

    Each client uploads w_i + m_i / ŵ_i where the masks are antisymmetric
    *after* weighting, so the weighted mean of the uploads equals Eq. 2 while
    every individual upload is noise to the server.  Returns
    (aggregate, uploaded_masked_models) so tests can assert both properties.
    """
    w = np.asarray(num_samples, np.float64)  # lint-ok: RA101 host counts
    w = w / w.sum()
    masks = pairwise_masks(models, seed)
    uploads = []
    for i, (m, msk) in enumerate(zip(models, masks)):
        # divide the mask by this client's weight so weighting cancels it
        uploads.append(jax.tree.map(
            lambda x, r: x + (r / w[i]).astype(x.dtype), m, msk))
    agg = tree_weighted_mean(uploads, w)
    return agg, uploads
