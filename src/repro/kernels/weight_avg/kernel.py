"""Streaming weighted model average (FedAvg Eq. 2) as a Pallas kernel.

Aggregation is purely memory-bound: read N client parameter shards once,
write the average once.  The kernel tiles the flattened parameter axis into
(N, Db) VMEM blocks — the N client rows of one column tile are resident
together, multiplied by the normalized weight vector (prefetched whole, it
is tiny) and reduced on the VPU.  HBM traffic is exactly N·D reads + D
writes with no intermediate (N, D) temporaries, which is what XLA's
unfused ``sum(stack * w)`` would materialize at this size.

Grid: (D / Db,). Block: (N, Db) f32 — Db=16384 at N≤32 keeps the block
≤ 2 MB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_DB = 16384


def _wavg_kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)              # (N, Db)
    w = w_ref[...].astype(jnp.float32)              # (N, 1)
    o_ref[...] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)[0]


def weighted_average(stacked: jnp.ndarray, weights: jnp.ndarray,
                     block_d: int = DEFAULT_DB, interpret: bool = True):
    """stacked (N, D), weights (N,) -> (D,).  D padded to block_d by ops.py."""
    N, D = stacked.shape
    db = min(block_d, D)
    assert D % db == 0, (D, db)
    w = (weights.astype(jnp.float32) / jnp.sum(weights.astype(jnp.float32)))
    return pl.pallas_call(
        _wavg_kernel,
        grid=(D // db,),
        in_specs=[pl.BlockSpec((N, 1), lambda d: (0, 0)),
                  pl.BlockSpec((N, db), lambda d: (0, d))],
        out_specs=pl.BlockSpec((db,), lambda d: (d,)),
        out_shape=jax.ShapeDtypeStruct((D,), stacked.dtype),
        interpret=interpret, name="weight_avg",
    )(w[:, None], stacked)


def _multi_wavg_kernel(w_ref, x_ref, o_ref):
    x = x_ref[0].astype(jnp.float32)                # (N, Db)
    w = w_ref[0].astype(jnp.float32)                # (N, 1)
    o_ref[0] = jnp.sum(x * w, axis=0, keepdims=True).astype(o_ref.dtype)


def multi_weighted_average(stacked: jnp.ndarray, weights: jnp.ndarray,
                           block_d: int = DEFAULT_DB, interpret: bool = True):
    """Batched multi-model variant for the vectorized engine: reduce the
    client axis of ALL G groups in one launch.

    stacked (G, N, D), weights (G, N) -> (G, D).  Grid (G, D/Db); each
    program reads one group's (N, Db) column tile plus its (N, 1) weight
    column (normalized per group on the host side of the call — tiny) and
    reduces on the VPU.  HBM traffic stays at the streaming optimum
    G·N·D reads + G·D writes with no (G, N, D) temporaries.

    The output is produced as (G, 1, D) and reshaped: a (1, Db) block of
    a (G, D) array has a second-minor dim (1) that is neither a multiple
    of 8 nor G, which the chip's compiler refuses; over (G, 1, D) the
    same block's last two dims are (1 = the array's, Db).
    """
    G, N, D = stacked.shape
    db = min(block_d, D)
    assert D % db == 0, (D, db)
    w = weights.astype(jnp.float32)
    w = w / jnp.sum(w, axis=1, keepdims=True)
    out = pl.pallas_call(
        _multi_wavg_kernel,
        grid=(G, D // db),
        in_specs=[pl.BlockSpec((1, N, 1), lambda g, d: (g, 0, 0)),
                  pl.BlockSpec((1, N, db), lambda g, d: (g, 0, d))],
        out_specs=pl.BlockSpec((1, 1, db), lambda g, d: (g, 0, d)),
        out_shape=jax.ShapeDtypeStruct((G, 1, D), stacked.dtype),
        interpret=interpret, name="weight_avg_multi",
    )(w[:, :, None], stacked)
    return out.reshape(G, D)
