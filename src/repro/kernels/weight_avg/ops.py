"""Public weighted-average ops: 2-D entry points + whole-pytree wrappers
used by ``core.aggregation`` on TPU.

The pytree wrappers are one jitted program per tree structure and leaf
shapes: a ``pallas_call`` made outside ``jit`` builds a new callable on
every call and so compiles again on every call, which for a ResNet's
~60 leaves was ~60 kernel compiles per round.  The backend dispatch is
decided outside the trace and passed in as static arguments.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.spans import named_program
from repro.kernels.weight_avg import kernel, ref

# the stable name of the Eq. 2 program in a profiler trace
EQ2 = "fedsdd_eq2"


def _use_pallas() -> bool:
    if os.environ.get("REPRO_FORCE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _weighted_average(stacked, weights, pallas: bool, interpret: bool,
                      block_d: int | None = None):
    if not pallas:
        return ref.weighted_average_ref(stacked, weights)
    N, D = stacked.shape
    db = block_d or min(kernel.DEFAULT_DB, max(128, D))
    pad = (-D) % db
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    out = kernel.weighted_average(stacked, weights, block_d=db,
                                  interpret=interpret)
    return out[:D]


def weighted_average(stacked, weights, block_d: int | None = None):
    """stacked (N, D), weights (N,) -> (D,)."""
    return _weighted_average(stacked, weights, _use_pallas(), _interpret(),
                             block_d)


@partial(named_program, EQ2, static_argnums=(2, 3))
def _weighted_average_tree(stacked_tree, weights, pallas, interpret):
    def leaf(x):
        N = x.shape[0]
        return _weighted_average(x.reshape(N, -1), weights, pallas,
                                 interpret).reshape(x.shape[1:])

    return jax.tree.map(leaf, stacked_tree)


def weighted_average_pytree(stacked_tree, weights):
    """Leaves with leading client axis (N, ...) -> averaged leaves (...)."""
    return _weighted_average_tree(stacked_tree, weights, _use_pallas(),
                                  _interpret())


def _group_weighted_average(stacked, weights, pallas: bool, interpret: bool,
                            block_d: int | None = None):
    if not pallas:
        return ref.group_weighted_average_ref(stacked, weights)
    _, _, D = stacked.shape
    db = block_d or min(kernel.DEFAULT_DB, max(128, D))
    pad = (-D) % db
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, 0), (0, pad)))
    out = kernel.multi_weighted_average(stacked, weights, block_d=db,
                                        interpret=interpret)
    return out[:, :D]


def group_weighted_average(stacked, weights, block_d: int | None = None):
    """Batched multi-model path: stacked (G, N, D), weights (G, N) ->
    (G, D) — all G group averages in one fused pass."""
    return _group_weighted_average(stacked, weights, _use_pallas(),
                                   _interpret(), block_d)


@partial(named_program, EQ2, static_argnums=(2, 3))
def _group_weighted_average_tree(stacked_tree, weights, pallas, interpret):
    def leaf(x):
        G, N = x.shape[:2]
        return _group_weighted_average(
            x.reshape(G, N, -1), weights, pallas,
            interpret).reshape((G,) + x.shape[2:])

    return jax.tree.map(leaf, stacked_tree)


def group_weighted_average_pytree(stacked_tree, weights):
    """Leaves with leading (G, N, ...) axes -> averaged leaves (G, ...)."""
    return _group_weighted_average_tree(stacked_tree, weights, _use_pallas(),
                                        _interpret())
