"""The image family: CIFAR ResNets (He et al. 2016, arXiv:1512.03385
§4.2) on the synthetic CIFAR-shaped images of ``bench/data.py``.

The names the harness and the round reference take from a family are
listed in ``bench/harness.py``'s docstring.

The model: a 3x3 stem of 16 channels, three stages of n basic blocks of
widths 16/32/64 (depth 6n+2), stride 2 at the first block of stages 2
and 3 with a 1x1 projection shortcut where the shape changes, global
average pooling and a linear head.  One departure from the paper, which
the configuration states: GroupNorm with gcd(8, C) groups in place of
BatchNorm, whose running statistics do not average across clients
(Hsieh et al. 2020).  Parameters are named as the program's layout gives
them (``stem``, ``stem_n``, ``s{stage}b{block}`` with ``conv1``/``n1``/
``conv2``/``n2``/``proj``, ``head``), so that one set of initial weights
serves the program and the reference.  Convolutions and matrix products
run at the precision the configuration states (``Precision.DEFAULT``:
one bfloat16 pass on a TPU), as the program runs them.
"""
from __future__ import annotations

import copy
import dataclasses
import math

import jax
import jax.numpy as jnp

WIDTHS = (16, 32, 64)
GN_EPS = 1e-5
REFERENCE_BLOCK = (None, None)


# ------------------------------------------------------------- the data
def federation(cfg: dict, mix: dict, data_seed: int):
    from data import make_federation
    return make_federation(cfg, mix, data_seed)


# ---------------------------------------------------------- the program
def program(cfg: dict, server_x, server_batch: int) -> dict:
    from repro.configs.resnet_cifar import get_resnet_config
    from repro.models.resnet import resnet_logits, resnet_loss

    rcfg = dataclasses.replace(
        get_resnet_config(cfg["model"], cfg["num_classes"]),
        depth=cfg["depth"])
    B = server_batch

    def make_batch(ds, idx):
        x, y = ds
        return {"x": jnp.asarray(x[idx]), "y": jnp.asarray(y[idx])}

    return {
        "loss_fn": lambda p, b: resnet_loss(p, b, rcfg),
        "logits_fn": lambda p, b: resnet_logits(p, b["x"], rcfg),
        "make_batch": make_batch,
        "server_batches": [{"x": jnp.asarray(server_x[i:i + B])}
                           for i in range(0, len(server_x) - B + 1, B)],
        "features_fn": None, "head_fn": None}


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


def make_init(cfg: dict, weight_seed: int):
    """``init(key) -> params`` as one jitted call, the run's weight seed
    folded into every key the caller passes."""
    depth, num_classes = cfg["depth"], cfg["num_classes"]
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


def plain_model(cfg: dict):
    depth = cfg["depth"]

    def fwd(params, x):
        return logits(params, x, depth, jax.lax.Precision.DEFAULT)

    def loss(params, x, y, rows):
        return cross_entropy(fwd(params, x[rows]), y[rows])

    return fwd, loss


# ------------------------------------------------------------ the counts
def round_flops(cfg: dict, job: dict, runs, teachers: int) -> int:
    """Model FLOPs a round requires: each sampled client's local steps,
    the teacher forwards over the server set, and the KD steps."""
    from flops.resnet import forward_flops, train_step_flops
    d, V = cfg["depth"], cfg["num_classes"]
    local = sum(len(r.rows) * train_step_flops(d, V, r.rows.shape[1])
                for r in runs)
    pre = teachers * forward_flops(d, V, cfg["num_server"])
    kd = job["distill_steps"] * train_step_flops(d, V, job["server_batch"])
    return local + pre + kd


# ------------------------------------------------------------ the tests
def shrink(cell: dict) -> dict:
    """A cell cut to a size the CPU runs in seconds: ResNet-8, ten
    clients of a few dozen images, three KD steps."""
    cell = copy.deepcopy(cell)
    cell["config"].update(depth=8, num_train=512, num_server=64,
                          distill_steps=3)
    pop = cell["mix"]["population"]
    if pop["partition"] == "dirichlet":
        pop.update(num_clients=10, alpha=1.0, min_shard=16)
    else:
        pop.update(num_clients=8)
    cell["mix"]["job"].update(server_batch=32, client_batch=16)
    return cell
