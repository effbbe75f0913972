"""The plain reference: a CIFAR ResNet and FedSDD rounds in straightforward
``jax.numpy``, independent of the program.

The model is He et al. 2016 (arXiv:1512.03385 §4.2): a 3x3 stem of 16
channels, three stages of n basic blocks of widths 16/32/64 (depth
6n+2), stride 2 at the first block of stages 2 and 3 with a 1x1
projection shortcut where the shape changes, global average pooling and
a linear head.  One departure from the paper, which the configuration
states: GroupNorm with gcd(8, C) groups in place of BatchNorm, whose
running statistics do not average across clients (Hsieh et al. 2020).
Parameters are named as the configuration's layout gives them
(``stem``, ``stem_n``, ``s{stage}b{block}`` with ``conv1``/``n1``/
``conv2``/``n2``/``proj``, ``head``), so that one set of initial weights
serves the program and the reference.

A round (FedSDD, arXiv:2312.17029, Algorithm 1): each sampled client runs
SGD from its group's global model over its schedule of minibatches
(``schedule.round_schedule``); each group's model becomes its clients'
size-weighted mean (Eq. 2); the K new models join the teacher bank,
which keeps the last R rounds; the main model (group 0) is distilled by
SGD with momentum 0.9 on the unlabeled server batches, cycled, against
the mean teacher logit, with the loss tau^2 * KL(softmax(t/tau) ||
softmax(s/tau)) (Eqs. 3-5).

The configuration states float32 parameters, updates and accumulation,
with convolution and matrix-product operands at the chip's default
precision (one bfloat16 pass on a TPU), as the program runs them;
``dtype=float32`` computes so.  ``dtype=bfloat16`` is the control: the
same arithmetic with parameters, activations and updates in bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from schedule import round_schedule

WIDTHS = (16, 32, 64)
GN_EPS = 1e-5
KD_MOMENTUM = 0.9


# --------------------------------------------------------------- weights
def init_params(key, depth: int, num_classes: int) -> dict:
    """He-normal convolutions (std sqrt(2 / fan_in)), a normal head over
    sqrt(fan_in), GroupNorm scale 1 and bias 0, all float32."""
    n = (depth - 2) // 6

    def conv(k, kh, cin, cout):
        std = math.sqrt(2.0 / (kh * kh * cin))
        return jax.random.normal(k, (kh, kh, cin, cout), jnp.float32) * std

    def norm(c):
        return {"scale": jnp.ones((c,), jnp.float32),
                "bias": jnp.zeros((c,), jnp.float32)}

    keys = iter(jax.random.split(key, 3 * 3 * n + 2))
    params = {"stem": conv(next(keys), 3, 3, 16), "stem_n": norm(16)}
    cin = 16
    for s, w in enumerate(WIDTHS):
        for b in range(n):
            block = {"conv1": conv(next(keys), 3, cin, w), "n1": norm(w),
                     "conv2": conv(next(keys), 3, w, w), "n2": norm(w)}
            proj_key = next(keys)
            if cin != w:
                block["proj"] = conv(proj_key, 1, cin, w)
            params[f"s{s}b{b}"] = block
            cin = w
    params["head"] = {
        "w": jax.random.normal(next(keys), (cin, num_classes), jnp.float32)
        / math.sqrt(cin),
        "b": jnp.zeros((num_classes,), jnp.float32)}
    return params


def make_init(depth: int, num_classes: int, weight_seed: int):
    """``init(key) -> params`` as one jitted call, the run's weight seed
    folded into every key the caller passes."""
    fn = jax.jit(lambda key: init_params(
        jax.random.fold_in(key, weight_seed), depth, num_classes))
    return fn


# ------------------------------------------------------------- the model
def _conv(x, w, stride, precision):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _group_norm(p, x):
    c = x.shape[-1]
    g = math.gcd(8, c)
    xg = x.reshape(x.shape[:-1] + (g, c // g))
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) / jnp.sqrt(var + GN_EPS)).reshape(x.shape)
    return xn * p["scale"] + p["bias"]


def logits(params, x, depth: int, precision):
    n = (depth - 2) // 6
    h = jax.nn.relu(_group_norm(params["stem_n"],
                                _conv(x, params["stem"], 1, precision)))
    for s in range(3):
        for b in range(n):
            p = params[f"s{s}b{b}"]
            stride = 2 if (s > 0 and b == 0) else 1
            y = jax.nn.relu(_group_norm(p["n1"],
                                        _conv(h, p["conv1"], stride, precision)))
            y = _group_norm(p["n2"], _conv(y, p["conv2"], 1, precision))
            short = _conv(h, p["proj"], stride, precision) if "proj" in p else h
            h = jax.nn.relu(y + short)
    h = jnp.mean(h, axis=(1, 2))
    return jnp.dot(h, params["head"]["w"], precision=precision) \
        + params["head"]["b"]


def cross_entropy(z, y):
    logp = jax.nn.log_softmax(z.astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def kd_loss(z_student, z_teacher, tau: float):
    t = jax.nn.softmax(z_teacher.astype(jnp.float32) / tau, axis=-1)
    log_t = jax.nn.log_softmax(z_teacher.astype(jnp.float32) / tau, axis=-1)
    log_s = jax.nn.log_softmax(z_student.astype(jnp.float32) / tau, axis=-1)
    return jnp.mean(jnp.sum(t * (log_t - log_s), axis=-1)) * tau ** 2


# -------------------------------------------------------------- a round
class Reference:
    """FedSDD rounds of one configuration and job, in ``dtype``.

    A round is three programs: every sampled client's SGD at once (each
    client's minibatches padded to the population's longest shard, the
    padded steps leaving the model as it was) with Eq. 2's weighted means;
    the teacher ensemble's mean logits over the server batches; and the
    KD steps.  Convolutions and matrix products run at the precision the
    configuration states (``Precision.DEFAULT``)."""

    def __init__(self, depth: int, job: dict, dtype=jnp.float32):
        self.depth, self.job = depth, job
        self.dtype = jnp.dtype(dtype)
        fwd = partial(logits, depth=depth,
                      precision=jax.lax.Precision.DEFAULT)
        lr_c, lr_s = job["client_lr"], job["server_lr"]
        tau = job["temperature"]
        dt = self.dtype

        def local(models, x_all, y_all, rows, live, weights):
            """``models``: the K group models; ``rows`` (C, S, B) indices
            into ``x_all``, ``live`` (C, S) which steps are real,
            ``weights`` (K, C) each group's Eq. 2 weights of the clients."""
            group = jnp.argmax(weights, axis=0)

            def client(k, rows_c, live_c):
                p0 = jax.tree.map(lambda m: m[k], models)

                def step(p, inp):
                    r, ok = inp
                    g = jax.grad(lambda q: cross_entropy(
                        fwd(q, x_all[r]), y_all[r]))(p)
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, (a - lr_c * b).astype(dt),
                                               a), p, g), None

                return jax.lax.scan(step, p0, (rows_c, live_c))[0]

            trained = jax.vmap(client)(group, rows, live)
            return jax.tree.map(
                lambda t: jnp.sum(
                    weights.reshape(weights.shape + (1,) * (t.ndim - 1))
                    * t.astype(jnp.float32)[None], axis=1).astype(dt),
                trained)

        def teacher_mean(members, server):
            z = jax.vmap(lambda m: jax.vmap(lambda xb: fwd(m, xb))(server))(
                members)
            return jnp.mean(z.astype(jnp.float32), axis=0).astype(dt)

        def kd(p, server, zt, steps):
            nb = server.shape[0]

            def step(carry, s):
                p, mu = carry
                loss, g = jax.value_and_grad(lambda q: kd_loss(
                    fwd(q, server[s % nb]), zt[s % nb], tau))(p)
                mu = jax.tree.map(lambda m, b: (KD_MOMENTUM * m + b).astype(dt),
                                  mu, g)
                p = jax.tree.map(lambda a, m: (a - lr_s * m).astype(dt), p, mu)
                return (p, mu), loss

            mu = jax.tree.map(jnp.zeros_like, p)
            (p, _), losses = jax.lax.scan(step, (p, mu), jnp.arange(steps))
            return p, losses

        self._local = jax.jit(local)
        self._teacher_mean = jax.jit(teacher_mean)
        self._kd = jax.jit(kd, static_argnums=3)

    def run(self, models: list, client_data, server_x: np.ndarray,
            sizes, seed: int, rounds: int) -> list[dict]:
        """``rounds`` FedSDD rounds from the K initial ``models``; for each
        round the K models after it (host float32) and the KD loss of its
        first and last step."""
        job = self.job
        K, R, B = job["K"], job["R"], job["server_batch"]
        dt = self.dtype
        offsets = np.cumsum([0] + [len(y) for _, y in client_data])
        x_all = jnp.asarray(np.concatenate([x for x, _ in client_data]), dt)
        y_all = jnp.asarray(np.concatenate([y for _, y in client_data]))
        nb = len(server_x) // B
        server = jnp.asarray(server_x[:nb * B].reshape(
            (nb, B) + server_x.shape[1:]), dt)
        steps = job["local_epochs"] * (max(sizes) // job["client_batch"])
        models = jax.tree.map(lambda *a: jnp.asarray(np.stack(a), dt),
                              *models)
        bank: list = []
        out = []
        for t in range(1, rounds + 1):
            runs = round_schedule(sizes, job["participation"], K,
                                  job["client_batch"], job["local_epochs"],
                                  seed, t)
            C = len(runs)
            bs = runs[0].rows.shape[1]
            if any(r.rows.shape[1] != bs for r in runs):
                raise ValueError("clients of one round with different "
                                 "minibatch sizes")
            rows = np.zeros((C, steps, bs), np.int32)
            live = np.zeros((C, steps), bool)
            weights = np.zeros((K, C), np.float32)
            for c, r in enumerate(runs):
                rows[c, :len(r.rows)] = r.rows + offsets[r.cid]
                live[c, :len(r.rows)] = True
                weights[r.group, c] = r.n
            weights /= weights.sum(axis=1, keepdims=True)
            new = self._local(models, x_all, y_all, jnp.asarray(rows),
                              jnp.asarray(live), jnp.asarray(weights))
            bank = [new] + bank[:R - 1]
            members = jax.tree.map(lambda *a: jnp.concatenate(a), *bank)
            zt = self._teacher_mean(members, server)
            student, losses = self._kd(jax.tree.map(lambda a: a[0], new),
                                       server, zt, job["distill_steps"])
            models = jax.tree.map(lambda m, s: m.at[0].set(s), new, student)
            host = jax.tree.map(lambda a: np.asarray(a, np.float32), models)
            losses = np.asarray(losses, np.float32)
            out.append({
                "models": [jax.tree.map(lambda a, k=k: a[k], host)
                           for k in range(K)],
                "kd_loss_first": float(losses[0]),
                "kd_loss_last": float(losses[-1])})
        return out
