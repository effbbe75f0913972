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

The configuration states the reference's dtype (``reference_dtype``,
float32 for parameters, updates and accumulation) and the control's
(``control_dtype``: the same arithmetic with parameters, floating
inputs, activations and updates in that dtype).

The round streams through the host, so that a model the chip holds only
a few times still fits.  The K models and the R*K teacher bank are host
arrays; a model goes up to the device only while a program needs it:

- Eq. 2, group by group: the group's model goes up once, its clients
  train in blocks of ``clients``, and one float32 accumulator adds each
  block's weighted sum; the new model comes back.  A family that fits
  whole says None: the round's clients then train in one program from
  the K models stacked, which returns the K groups' sums.
- The mean teacher logit: ``teachers`` bank members go up at a time
  (None: the whole bank), each block's share adding into one float32
  logit accumulator.
- KD: the student (group 0's new model) goes up, and comes back.

Device budget, in copies of one model in float32, beyond the data (every
client's inputs and targets, the server batches) and the logit buffers
(the teacher logit, its accumulator and a block's share: one float32
logit per server row each): between the programs at most max(3, T),
T the teachers a block (``teachers``, R*K where None): the group model,
the accumulator and a block's weighted sum (3), or a block of teachers,
or the student before and after KD (2); with ``clients`` None, max(2K,
T): the K models and their K sums.  Inside a program add its working
set: a block of clients' training state (each client's parameters and
gradient, about two copies a client, and the block's weighted sum), or
the student's momentum and gradient (two).  The host holds the K models and the R*K
bank, of which the newest K share K-1 models with the K: (R+1)*K copies
in the reference's dtype at most.
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
    three programs: a block of clients' SGD (each client's minibatches
    padded to the population's longest shard, the padded steps leaving
    the model as it was) with their share of Eq. 2's weighted means; a
    block of teachers' share of the mean logit over the server batches;
    and the KD steps.

    ``clients`` and ``teachers`` say how many clients of one group, and
    how many teachers, run at once; None runs the whole round's clients,
    or the whole bank.  Each block is one ``vmap``ped program; every
    client block of a round has one shape, a short one padded with
    clients of no steps and no weight."""

    def __init__(self, model, job: dict, dtype=jnp.float32,
                 clients: int | None = None, teachers: int | None = None):
        self.job = job
        self.dtype = jnp.dtype(dtype)
        self.clients, self.teachers = clients, teachers
        fwd, loss_of = model
        lr_c, lr_s = job["client_lr"], job["server_lr"]
        tau = job["temperature"]
        dt = self.dtype

        def local_sum(models, x_all, y_all, group, rows, live, weights):
            """The block's clients trained from their group's model, and
            each group's weighted sum of them in float32: ``models`` the
            G group models stacked, ``group`` (C,) each client's index
            into them, ``rows`` (C, S, B) indices into ``x_all``, ``live``
            (C, S) which steps are real, ``weights`` (G, C) each group's
            Eq. 2 weights of the clients."""
            def client(k, rows_c, live_c):
                p0 = jax.tree.map(lambda m: m[k], models)

                def step(p, inp):
                    r, ok = inp
                    g = jax.grad(lambda q: loss_of(q, x_all, y_all, r))(p)
                    return jax.tree.map(
                        lambda a, b: jnp.where(ok, (a - lr_c * b).astype(dt),
                                               a), p, g), None

                return jax.lax.scan(step, p0, (rows_c, live_c))[0]

            trained = jax.vmap(client)(group, rows, live)
            return jax.tree.map(lambda t: jnp.sum(
                weights.reshape(weights.shape + (1,) * (t.ndim - 1))
                * t.astype(jnp.float32)[None], axis=1), trained)

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

    def _up(self, model):
        """A host model on the device, in the reference's dtype."""
        return jax.tree.map(lambda a: jnp.asarray(a, self.dtype), model)

    def _eq2(self, models: list, x_all, y_all, group, rows, live,
             weights) -> list:
        """Eq. 2's K new models, on the host, from the round's clients
        (``group`` (C,) each one's group, ``weights`` (C,) their shard
        sizes, Eq. 2's weights before normalising): with ``self.clients``
        None in one program over the K models; else group by group, each
        group's clients in blocks of ``self.clients``, a short block
        padded with clients of no steps and no weight."""
        K = len(models)
        if self.clients is None:
            work = [(list(range(K)), list(range(len(group))))]
            b = len(group)
        else:
            work = [([k], [c for c in range(len(group)) if group[c] == k])
                    for k in range(K)]
            b = self.clients
        new = []
        for ks, clients in work:
            pad = -len(clients) % b
            n = len(clients) + pad
            w = np.zeros((len(ks), n), np.float32)
            for i, c in enumerate(clients):
                w[ks.index(group[c]), i] = weights[c]
            w /= w.sum(axis=1, keepdims=True)
            own = np.zeros(n, np.int32)
            own[:len(clients)] = [ks.index(group[c]) for c in clients]
            b_rows = np.concatenate([rows[clients], np.zeros(
                (pad,) + rows.shape[1:], rows.dtype)])
            b_live = np.concatenate([live[clients],
                                     np.zeros((pad,) + live.shape[1:], bool)])
            up = jax.tree.map(lambda *a: jnp.asarray(np.stack(a), self.dtype),
                              *[models[k] for k in ks])
            acc = None
            for part in _blocks(n, b):
                s = self._local_sum(up, x_all, y_all, jnp.asarray(own[part]),
                                    jnp.asarray(b_rows[part]),
                                    jnp.asarray(b_live[part]),
                                    jnp.asarray(w[:, part]))
                acc = s if acc is None else jax.tree.map(jnp.add, acc, s)
                del s
            del up
            host = jax.device_get(jax.tree.map(
                lambda a: a.astype(self.dtype), acc))
            del acc
            new += [jax.tree.map(lambda a, i=i: a[i], host)
                    for i in range(len(ks))]
        return new

    def _teacher_logits(self, bank: list, server):
        """The mean teacher logit over the bank (R rounds, newest first, of
        K host models each), ``self.teachers`` members at a time."""
        members = [m for models in bank for m in models]
        n, acc = len(members), None
        for part in _blocks(n, self.teachers):
            block = jax.tree.map(lambda *a: jnp.asarray(np.stack(a),
                                                        self.dtype),
                                 *members[part])
            s = self._teacher_share(block, server, n)
            del block
            acc = s if acc is None else acc + s
            del s
        return acc.astype(self.dtype)

    def run(self, models: list, client_data, server_x: np.ndarray,
            sizes, seed: int, rounds: int):
        """``rounds`` FedSDD rounds from the K initial host ``models``:
        yields, as each round ends, the K models after it (host arrays in
        the reference's dtype) and the KD loss of its first and last
        step."""
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
        bank: list = []
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
            for c, r in enumerate(runs):
                rows[c, :len(r.rows)] = r.rows + offsets[r.cid]
                live[c, :len(r.rows)] = True
            group = [r.group for r in runs]
            weights = [r.n for r in runs]
            new = self._eq2(models, x_all, y_all, group, rows, live, weights)
            bank = [new] + bank[:R - 1]
            zt = self._teacher_logits(bank, server)
            student, losses = self._kd(self._up(new[0]), server, zt,
                                       job["distill_steps"])
            del zt
            models = [jax.device_get(student)] + new[1:]
            del student
            losses = np.asarray(losses, np.float32)
            yield {"models": models,
                   "kd_loss_first": float(losses[0]),
                   "kd_loss_last": float(losses[-1])}
