"""CIFAR-style ResNets (ResNet-20/56, WRN16-2) — the paper's own models.

Functional JAX implementation used by the faithful FedSDD reproduction.
Normalization is GroupNorm by default: BatchNorm's running statistics are
known to interact badly with FedAvg weight averaging under Non-IID data
(Hsieh et al. 2020), and the paper's claims are about the aggregation
scheme, not the norm layer.  ``norm="batch"`` gives training-mode batch
statistics (stats averaged like any other state) for completeness.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.resnet_cifar import ResNetConfig


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * np.sqrt(2.0 / fan_in)


def conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _norm_params(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def apply_norm(p, x, cfg: ResNetConfig):
    if cfg.norm == "batch":
        mu = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
        var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
        xn = (x - mu) * jax.lax.rsqrt(var + 1e-5)
    else:  # groupnorm with 8 groups (or fewer for narrow layers)
        C = x.shape[-1]
        g = math.gcd(8, C)
        xg = x.reshape(*x.shape[:-1], g, C // g)
        mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
        var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
        xn = ((xg - mu) * jax.lax.rsqrt(var + 1e-5)).reshape(x.shape)
    return xn * p["scale"] + p["bias"]


def _init_block(key, cin, cout, stride):
    ks = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(ks[0], 3, 3, cin, cout),
        "n1": _norm_params(cout),
        "conv2": _conv_init(ks[1], 3, 3, cout, cout),
        "n2": _norm_params(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(ks[2], 1, 1, cin, cout)
    return p


def _apply_block(p, x, cfg, stride):
    h = jax.nn.relu(apply_norm(p["n1"], conv(x, p["conv1"], stride), cfg))
    h = apply_norm(p["n2"], conv(h, p["conv2"]), cfg)
    sc = conv(x, p["proj"], stride) if "proj" in p else x
    return jax.nn.relu(h + sc)


def init_resnet(key, cfg: ResNetConfig):
    n = cfg.num_blocks_per_stage
    widths = [16 * cfg.width_mult, 32 * cfg.width_mult, 64 * cfg.width_mult]
    ks = jax.random.split(key, 3 * n + 2)
    params = {"stem": _conv_init(ks[0], 3, 3, 3, 16), "stem_n": _norm_params(16)}
    cin = 16
    ki = 1
    for s, w in enumerate(widths):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            params[f"s{s}b{b}"] = _init_block(ks[ki], cin, w, stride)
            cin = w
            ki += 1
    params["head"] = {
        "w": jax.random.normal(ks[-1], (cin, cfg.num_classes), jnp.float32) / np.sqrt(cin),
        "b": jnp.zeros((cfg.num_classes,)),
    }
    return params


def resnet_logits(params, x, cfg: ResNetConfig):
    """x: (B, 32, 32, 3) f32 -> logits (B, num_classes)."""
    n = cfg.num_blocks_per_stage
    h = jax.nn.relu(apply_norm(params["stem_n"], conv(x, params["stem"]), cfg))
    for s in range(3):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            h = _apply_block(params[f"s{s}b{b}"], h, cfg, stride)
    h = jnp.mean(h, axis=(1, 2))
    return h @ params["head"]["w"] + params["head"]["b"]


def resnet_loss(params, batch, cfg: ResNetConfig):
    logits = resnet_logits(params, batch["x"], cfg)
    labels = batch["y"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"acc": acc}


# one program per (cfg, batch shape) for the whole process: a jit built
# inside resnet_accuracy would compile again on every evaluation
_logits_jit = jax.jit(resnet_logits, static_argnames="cfg")


def resnet_accuracy(params, x, y, cfg: ResNetConfig, batch: int = 500):
    """Full-set accuracy evaluated in minibatches."""
    hits = 0
    for i in range(0, len(x), batch):
        logits = _logits_jit(params, jnp.asarray(x[i:i + batch]), cfg)
        hits += int(jnp.sum(jnp.argmax(logits, -1) == jnp.asarray(y[i:i + batch])))
    return hits / len(x)
