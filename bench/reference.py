"""The plain reference of a FedSDD round, in straightforward ``jax.numpy``,
independent of the program.

The model is the configuration's family's own (``plain_model`` in
``families/<family>.py``): logits over rows, and the local training loss
over a minibatch of ``(inputs, targets)``.  This module holds the round
(FedSDD, arXiv:2312.17029, Algorithm 1): each sampled client runs SGD
from its group's global model over its schedule of minibatches
(``schedule.round_schedule``); each group's model becomes its clients'
size-weighted mean (Eq. 2); the K new models join the teacher bank,
which keeps the last R rounds; the main model (group 0) is distilled by
SGD with momentum 0.9 on the unlabeled server batches, cycled, against
the mean teacher logit, with the loss tau^2 * KL(softmax(t/tau) ||
softmax(s/tau)) (Eqs. 3-5).

The configuration states float32 parameters, updates and accumulation;
``dtype=float32`` computes so.  ``dtype=bfloat16`` is the control: the
same arithmetic with parameters, floating inputs, activations and
updates in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from schedule import round_schedule

KD_MOMENTUM = 0.9


def kd_loss(z_student, z_teacher, tau: float):
    t = jax.nn.softmax(z_teacher.astype(jnp.float32) / tau, axis=-1)
    log_t = jax.nn.log_softmax(z_teacher.astype(jnp.float32) / tau, axis=-1)
    log_s = jax.nn.log_softmax(z_student.astype(jnp.float32) / tau, axis=-1)
    return jnp.mean(jnp.sum(t * (log_t - log_s), axis=-1)) * tau ** 2


def _inputs(a: np.ndarray, dt):
    """Floating inputs in the reference's dtype, token ids as they are."""
    if np.issubdtype(a.dtype, np.floating):
        return jnp.asarray(a, dt)
    return jnp.asarray(a)


def _blocks(n: int, block: int | None) -> list[slice]:
    """``n`` rows in slices of ``block``, the last one shorter where
    ``block`` does not divide ``n``; one slice where ``block`` is None or
    covers them all."""
    if block is None or block >= n:
        return [slice(0, n)]
    return [slice(i, min(i + block, n)) for i in range(0, n, block)]


class Reference:
    """FedSDD rounds of one model and job, in ``dtype``.

    ``model`` is ``(logits, loss)``: ``logits(params, inputs) -> (rows, V)``
    and ``loss(params, inputs, targets, rows)``, the local training loss
    over the minibatch ``rows`` of ``(inputs, targets)``.  A round is
    three programs: the sampled clients' SGD (each client's minibatches
    padded to the population's longest shard, the padded steps leaving
    the model as it was) with Eq. 2's weighted means; the teacher
    ensemble's mean logits over the server batches; and the KD steps.

    ``clients`` and ``teachers`` say how many clients, and how many
    teachers, run at once; None runs all of them in one program.  In
    blocks, each block is one ``vmap``ped program, and the blocks add up
    Eq. 2's weighted sum and the teachers' shares of the mean logit in
    float32, so that a model too large for the whole round at once still
    fits."""

    def __init__(self, model, job: dict, dtype=jnp.float32,
                 clients: int | None = None, teachers: int | None = None):
        self.job = job
        self.dtype = jnp.dtype(dtype)
        self.clients, self.teachers = clients, teachers
        fwd, loss_of = model
        lr_c, lr_s = job["client_lr"], job["server_lr"]
        tau = job["temperature"]
        dt = self.dtype

        def trained(models, x_all, y_all, rows, live, weights):
            """``models``: the K group models; ``rows`` (C, S, B) indices
            into ``x_all``, ``live`` (C, S) which steps are real,
            ``weights`` (K, C) each group's Eq. 2 weights of the clients."""
            group = jnp.argmax(weights, axis=0)

            def client(k, rows_c, live_c):
                p0 = jax.tree.map(lambda m: m[k], models)

                def step(p, inp):
                    r, ok = inp
                    g = jax.grad(lambda q: loss_of(q, x_all, y_all, r))(p)
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, (a - lr_c * b).astype(dt),
                                               a), p, g), None

                return jax.lax.scan(step, p0, (rows_c, live_c))[0]

            return jax.vmap(client)(group, rows, live)

        def weighted(t, weights, out):
            return jnp.sum(
                weights.reshape(weights.shape + (1,) * (t.ndim - 1))
                * t.astype(jnp.float32)[None], axis=1).astype(out)

        def local_sum(models, x_all, y_all, rows, live, weights):
            return jax.tree.map(
                lambda t: weighted(t, weights, jnp.float32),
                trained(models, x_all, y_all, rows, live, weights))

        def logits_of(members, server):
            return jax.vmap(lambda m: jax.vmap(lambda xb: fwd(m, xb))(server))(
                members).astype(jnp.float32)

        def teacher_share(members, server, n):
            """The members' share of the mean logit of ``n`` teachers."""
            return jnp.sum(logits_of(members, server), axis=0) / n

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

        self._local_sum = jax.jit(local_sum)
        self._teacher_share = jax.jit(teacher_share, static_argnums=2)
        self._kd = jax.jit(kd, static_argnums=3)

    def _eq2(self, models, x_all, y_all, rows, live, weights):
        """Eq. 2's K models from the round's clients, in blocks of
        ``self.clients``; a short last block is padded with clients of
        no steps and no weight, so that every block has one shape."""
        C, b = rows.shape[0], self.clients
        pad = 0 if b is None or b >= C else -C % b
        rows = np.concatenate([rows, np.zeros((pad,) + rows.shape[1:],
                                              rows.dtype)])
        live = np.concatenate([live, np.zeros((pad,) + live.shape[1:], bool)])
        weights = np.concatenate([weights, np.zeros((len(weights), pad),
                                                    weights.dtype)], axis=1)
        acc = None
        for part in _blocks(C + pad, b):
            s = self._local_sum(models, x_all, y_all, jnp.asarray(rows[part]),
                                jnp.asarray(live[part]),
                                jnp.asarray(weights[:, part]))
            acc = s if acc is None else jax.tree.map(jnp.add, acc, s)
        return jax.tree.map(lambda a: a.astype(self.dtype), acc)

    def _teacher_logits(self, bank: list, server):
        """The mean teacher logit over the bank's K*R models, in blocks of
        ``self.teachers``."""
        members = jax.tree.map(lambda *a: jnp.concatenate(a), *bank)
        n = len(jax.tree.leaves(members)[0])
        acc = None
        for part in _blocks(n, self.teachers):
            s = self._teacher_share(jax.tree.map(lambda a: a[part], members),
                                    server, n)
            acc = s if acc is None else acc + s
        return acc.astype(self.dtype)

    def run(self, models: list, client_data, server_x: np.ndarray,
            sizes, seed: int, rounds: int) -> list[dict]:
        """``rounds`` FedSDD rounds from the K initial ``models``; for each
        round the K models after it (host float32) and the KD loss of its
        first and last step."""
        job = self.job
        K, R, B = job["K"], job["R"], job["server_batch"]
        dt = self.dtype
        offsets = np.cumsum([0] + [len(y) for _, y in client_data])
        x_all = _inputs(np.concatenate([x for x, _ in client_data]), dt)
        y_all = jnp.asarray(np.concatenate([y for _, y in client_data]))
        nb = len(server_x) // B
        server = _inputs(server_x[:nb * B].reshape(
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
            new = self._eq2(models, x_all, y_all, rows, live, weights)
            bank = [new] + bank[:R - 1]
            zt = self._teacher_logits(bank, server)
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
