"""Pytree utilities shared across the framework.

All federated-learning state in this codebase is a pytree of jnp arrays
(nested dicts).  These helpers implement the handful of whole-tree algebra
operations the FedSDD core needs (weighted sums, linear combinations,
distances) plus flatten/unflatten used by the checkpointer and the
weight-averaging Pallas kernel.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.spans import named_program

PyTree = Any


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_weighted_sum(trees: Sequence[PyTree], weights) -> PyTree:
    """sum_i weights[i] * trees[i].  Weights may be a python/np/jnp vector."""
    weights = jnp.asarray(weights)

    def leaf(*leaves):
        stacked = jnp.stack(leaves)
        w = weights.astype(stacked.dtype).reshape((-1,) + (1,) * (stacked.ndim - 1))
        return jnp.sum(stacked * w, axis=0)

    return jax.tree.map(leaf, *trees)


def tree_weighted_mean(trees: Sequence[PyTree], weights) -> PyTree:
    weights = jnp.asarray(weights, dtype=jnp.float32)
    weights = weights / jnp.sum(weights)
    return tree_weighted_sum(trees, weights)


def tree_stacked_weighted_mean(stacked: PyTree, weights) -> PyTree:
    """Weighted mean over leading (client) axis of every leaf.

    ``stacked`` leaves have shape (N, ...); returns leaves of shape (...).
    This is Eq. (2) of the paper when ``weights`` are |X_i| dataset sizes.
    """
    weights = jnp.asarray(weights, dtype=jnp.float32)
    norm = weights / jnp.sum(weights)

    def leaf(x):
        w = norm.astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.sum(x * w, axis=0)

    return jax.tree.map(leaf, stacked)


def tree_stack(trees: Sequence[PyTree]) -> PyTree:
    """List of congruent pytrees -> one pytree with a new leading axis.

    The stacked form is the vectorized-engine representation: leaf i of
    client c lives at ``stacked_leaf[c]``.
    """
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def tree_unstack(stacked: PyTree) -> list[PyTree]:
    """Inverse of ``tree_stack``: split the leading axis back into a list."""
    n = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(n)]


def tree_concat(trees: Sequence[PyTree], axis: int = 0) -> PyTree:
    """Concatenate congruent pytrees along an existing (leading) axis."""
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=axis), *trees)


def tree_where(pred, on_true: PyTree, on_false: PyTree) -> PyTree:
    """Leafwise ``jnp.where`` with a scalar/broadcastable predicate — the
    masked-step combinator the vectorized engine uses for padded steps."""
    return jax.tree.map(lambda a, b: jnp.where(pred, a, b), on_true, on_false)


@functools.partial(named_program, "fedsdd_eq2",  # weight_avg/ops.py's EQ2
                   static_argnames=("num_groups",))
def group_weighted_mean(stacked, w, gid, *, num_groups):
    """``tree_group_weighted_mean`` over device operands: (C,) float32
    weights and (C,) int32 group ids."""
    # jitted: eager scatter_add dispatch is ~100x slower on CPU
    totals = jax.ops.segment_sum(w, gid, num_segments=num_groups)
    norm = w / totals[gid]

    def leaf(x):
        wx = norm.astype(x.dtype).reshape((-1,) + (1,) * (x.ndim - 1))
        return jax.ops.segment_sum(x * wx, gid, num_segments=num_groups)

    return jax.tree.map(leaf, stacked)


def tree_group_weighted_mean(stacked: PyTree, weights, group_ids,
                             num_groups: int) -> PyTree:
    """Per-group Eq. 2 over a client-stacked pytree in one fused pass.

    ``stacked`` leaves have shape (C, ...); ``group_ids`` (C,) maps each
    client row to one of ``num_groups`` segments; returns leaves of shape
    (num_groups, ...) where row g is the |X_i|-weighted mean of g's
    clients.  Ragged groups need no padding — this is a segment reduction.
    """
    w = jnp.asarray(np.asarray(weights), dtype=jnp.float32)
    gid = jnp.asarray(np.asarray(group_ids), dtype=jnp.int32)
    return group_weighted_mean(stacked, w, gid, num_groups=num_groups)


def tree_dot(a: PyTree, b: PyTree):
    parts = jax.tree.leaves(jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b))
    return sum(parts)


def tree_sq_dist(a: PyTree, b: PyTree):
    d = tree_sub(a, b)
    return tree_dot(d, d)


def tree_cast(tree: PyTree, dtype) -> PyTree:
    """Cast floating leaves to ``dtype``; leaves already there pass
    through untouched (no copy, no convert op — callers re-casting an
    already-f32 tree per batch must not pay a pytree copy per call)."""
    dtype = jnp.dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != dtype else x,
        tree)


def tree_size(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_flatten_to_vector(tree: PyTree) -> jnp.ndarray:
    """Concatenate every leaf (raveled) into one flat f32 vector."""
    leaves = jax.tree.leaves(tree)
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])


def tree_unflatten_from_vector(vec: jnp.ndarray, like: PyTree) -> PyTree:
    leaves, treedef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(l.shape))
        out.append(jnp.reshape(vec[off:off + n], l.shape).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


def tree_paths(tree: PyTree) -> list[str]:
    """Stable '/'-joined path for every leaf (checkpointer key space)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def tree_map_with_path(fn: Callable, tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map_with_path(lambda p, x: fn(jax.tree_util.keystr(p), x), tree)


def tree_all_finite(tree: PyTree):
    flags = [jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(tree)
             if jnp.issubdtype(x.dtype, jnp.floating)]
    if not flags:
        return jnp.asarray(True)
    return jnp.all(jnp.stack(flags))
