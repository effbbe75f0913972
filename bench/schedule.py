"""Which clients each FedSDD round trains, on which minibatches.

The algorithm fixes this from the job's seed and the round number
(paper §3.1.1: sample the participating clients, deal them randomly and
evenly into K groups; each client then runs its local epochs over
shuffled minibatches, dropping the ragged tail).  The random stream is
the one the program documents for both of its engines: one
``numpy.random.default_rng(seed * 100_000 + t)`` per round, drawn as
sample, shuffle, then each client's epoch permutations in group-major
order.  The reference replays the same rounds from it, and the harness
reads from it which bucket shapes a window will use.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class ClientRun:
    cid: int
    group: int
    n: int                  # shard size |X_i|, the client's Eq. 2 weight
    rows: np.ndarray        # (steps, batch) indices into the shard


def round_schedule(sizes, participation: float, K: int, client_batch: int,
                   local_epochs: int, seed: int, t: int) -> list[ClientRun]:
    """Round ``t``'s clients in group-major order."""
    rng = np.random.default_rng(seed * 100_000 + t)
    C = len(sizes)
    m = min(max(1, int(round(C * participation))), C)
    active = rng.choice(C, size=m, replace=False)
    dealt = np.array(active, copy=True)
    rng.shuffle(dealt)
    runs = []
    for k in range(K):
        for cid in dealt[k::K]:
            n = int(sizes[cid])
            bs = min(client_batch, n)
            rows = []
            for _ in range(local_epochs):
                perm = rng.permutation(n)
                rows += [perm[i:i + bs] for i in range(0, n - bs + 1, bs)]
            runs.append(ClientRun(int(cid), k, n, np.asarray(rows)))
    return runs


def bucket_shapes(runs: list[ClientRun]) -> tuple:
    """The round's local-training program shapes: for each minibatch size,
    (batch, clients, steps, padded shard) — clients with fewer steps or a
    smaller shard are padded to the bucket's largest."""
    out = []
    for bs in sorted({r.rows.shape[1] for r in runs}):
        sub = [r for r in runs if r.rows.shape[1] == bs]
        out.append((bs, len(sub), max(len(r.rows) for r in sub),
                    max(r.n for r in sub)))
    return tuple(out)
