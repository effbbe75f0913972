"""A token-sequence family for the benchmark's tests only: it shows that
a family of another kind of model plugs into the harness and the round
reference as files of its own, with no edit to either.

Data: each training sequence belongs to one of ``topics`` topics, each a
Markov chain over the vocabulary (its transition rows drawn from the
run's seed); the clients split the sequences by topic as the mix's
partition says.  Inputs are ``(n, seq)`` int32 tokens, targets the next
tokens; the server holds unlabeled sequences of random topics.

Model: a token embedding, a causal mixer (each position adds
``tanh(W @ mean of the positions up to it)``), RMS norm, and a linear
head over the vocabulary.  Its logits are rows of ``batch * seq``; its
features (the normed activations) and head give the split that the
program's head-fused KD takes.  The program's side is built from the
program's own layers (``repro.models.layers``: the norm and the token
cross-entropy) and computes the causal mean by a running sum; the plain
model computes it as a masked matrix product.
"""
from __future__ import annotations

import copy
import types

import jax
import jax.numpy as jnp
import numpy as np

RMS_EPS = 1e-6
# the round reference in blocks, as a family too large for one program
# would run it: three clients, and three teachers, at once
REFERENCE_BLOCK = (3, 3)


# ------------------------------------------------------------- the data
def _markov(rng, cdf, topics, length):
    """``(n, length)`` token chains, each under its topic's transitions."""
    n, V = len(topics), cdf.shape[-1]
    out = np.empty((n, length), np.int32)
    out[:, 0] = rng.integers(0, V, n)
    for t in range(1, length):
        u = rng.random(n)[:, None]
        out[:, t] = np.minimum((u > cdf[topics, out[:, t - 1]]).sum(-1), V - 1)
    return out


def federation(cfg: dict, mix: dict, data_seed: int):
    from data import dirichlet_partition, iid_partition
    pop = mix["population"]
    rng = np.random.default_rng(pop["partition_seed"])
    topics = rng.integers(0, cfg["topics"], cfg["num_train"])
    if pop["partition"] == "dirichlet":
        parts = dirichlet_partition(topics, pop["num_clients"], pop["alpha"],
                                    seed=pop["partition_seed"] + 1,
                                    min_size=pop["min_shard"])
    else:
        parts = iid_partition(cfg["num_train"], pop["num_clients"],
                              seed=pop["partition_seed"] + 1)
    rng = np.random.default_rng(data_seed)
    V, S = cfg["vocab"], cfg["seq"]
    logits = 3.0 * rng.standard_normal((cfg["topics"], V, V))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    cdf = np.cumsum(p / p.sum(-1, keepdims=True), axis=-1)
    seqs = _markov(rng, cdf, topics, S + 1)
    x, y = seqs[:, :-1], seqs[:, 1:]
    server = _markov(rng, cdf, rng.integers(0, cfg["topics"],
                                            cfg["num_server"]), S)
    return [(x[ix], y[ix]) for ix in parts], server


# ---------------------------------------------------------- the program
def program(cfg: dict, server_x, server_batch: int) -> dict:
    from repro.models.layers import apply_norm, cross_entropy
    norm_cfg = types.SimpleNamespace(norm_variant="rmsnorm", norm_eps=RMS_EPS)
    D, V, S = cfg["d_model"], cfg["vocab"], cfg["seq"]
    B = server_batch

    def features_fn(p, b):
        h = p["embed"][b["tokens"]]
        mean = jnp.cumsum(h, axis=1) / jnp.arange(1, S + 1)[:, None]
        h = h + jnp.tanh(mean @ p["mix"])
        return apply_norm(p["norm"], h, norm_cfg).reshape(-1, D)

    def head_fn(p):
        return p["head"]["w"], p["head"]["b"]

    def logits_fn(p, b):
        return features_fn(p, b) @ p["head"]["w"] + p["head"]["b"]

    def loss_fn(p, b):
        return cross_entropy(logits_fn(p, b), b["targets"].reshape(-1)), {}

    def make_batch(ds, idx):
        x, y = ds
        return {"tokens": jnp.asarray(x[idx]), "targets": jnp.asarray(y[idx])}

    return {"loss_fn": loss_fn, "logits_fn": logits_fn,
            "make_batch": make_batch,
            "server_batches": [{"tokens": jnp.asarray(server_x[i:i + B])}
                               for i in range(0, len(server_x) - B + 1, B)],
            "features_fn": features_fn, "head_fn": head_fn}


# --------------------------------------------------------------- weights
def make_init(cfg: dict, weight_seed: int):
    D, V = cfg["d_model"], cfg["vocab"]

    def init(key):
        k = jax.random.split(jax.random.fold_in(key, weight_seed), 3)
        return {"embed": jax.random.normal(k[0], (V, D), jnp.float32),
                "mix": jax.random.normal(k[1], (D, D), jnp.float32)
                / np.sqrt(D),
                "norm": {"scale": jnp.ones((D,), jnp.float32)},
                "head": {"w": jax.random.normal(k[2], (D, V), jnp.float32)
                         / np.sqrt(D),
                         "b": jnp.zeros((V,), jnp.float32)}}

    return jax.jit(init)


# ------------------------------------------------------------- the model
def plain_model(cfg: dict):
    S = cfg["seq"]
    causal = np.tril(np.ones((S, S), np.float32))
    causal /= causal.sum(-1, keepdims=True)

    def fwd(params, x):
        h = params["embed"][x]
        mean = jnp.einsum("st,btd->bsd", causal.astype(h.dtype), h)
        h = h + jnp.tanh(mean @ params["mix"])
        hf = h.astype(jnp.float32)
        hf = hf / jnp.sqrt(jnp.mean(hf * hf, axis=-1, keepdims=True) + RMS_EPS)
        h = (hf * params["norm"]["scale"].astype(jnp.float32)).astype(h.dtype)
        z = h @ params["head"]["w"] + params["head"]["b"]
        return z.reshape(-1, z.shape[-1])

    def loss(params, x, y, rows):
        z = fwd(params, x[rows]).astype(jnp.float32)
        t = y[rows].reshape(-1)
        logp = jax.nn.log_softmax(z)
        return -jnp.mean(jnp.take_along_axis(logp, t[:, None], axis=-1))

    return fwd, loss


# ------------------------------------------------------------ the counts
def _forward_flops(cfg: dict, sequences: int) -> int:
    """Matrix products per forward pass: the causal mean (as a masked
    product), the mixer and the head, two FLOPs a multiply-add."""
    D, V, S = cfg["d_model"], cfg["vocab"], cfg["seq"]
    return sequences * S * 2 * (S * D + D * D + D * V)


def round_flops(cfg: dict, job: dict, runs, teachers: int) -> int:
    local = sum(3 * _forward_flops(cfg, r.rows.size) for r in runs)
    pre = teachers * _forward_flops(cfg, cfg["num_server"])
    kd = job["distill_steps"] * 3 * _forward_flops(cfg, job["server_batch"])
    return local + pre + kd


# ------------------------------------------------------------ the tests
def shrink(cell: dict) -> dict:
    """Ten clients of a few dozen sequences, minibatches of 8, KD batches
    of 16 sequences (256 rows)."""
    cell = copy.deepcopy(cell)
    cell["config"].update(num_train=512, num_server=64, distill_steps=3)
    pop = cell["mix"]["population"]
    if pop["partition"] == "dirichlet":
        pop.update(num_clients=10, alpha=1.0, min_shard=16)
    else:
        pop.update(num_clients=8)
    cell["mix"]["job"].update(server_batch=16, client_batch=8)
    return cell
