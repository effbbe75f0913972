"""The image family's data (``families/resnet.py``): synthetic
CIFAR-shaped images and their split over clients.

A copy, kept with the benchmark so that a change to the program's own
data module cannot move the yardstick, of the synthetic stand-in
(each class a low-frequency template image, a sample its template plus
Gaussian noise; the server's unlabeled set slightly domain-shifted) and
of the Dirichlet label partition of Hsu, Qi & Brown (arXiv:1909.06335).

What is fixed by the traffic mix and what by ``--seed``:

- the population (every client's labels, hence its shard size and label
  mix) comes from the mix's ``partition_seed``, so a federation keeps its
  clients across seeds and every seed does the same amount of work;
- the pixels (class templates, noise, the server set) come from the run's
  seed.
"""
from __future__ import annotations

import numpy as np


def class_templates(rng: np.random.Generator, num_classes: int,
                    image_shape) -> np.ndarray:
    """(num_classes, H, W, C) templates: random 4x4 patterns upsampled by
    repetition, so that convolution and pooling keep the class signal."""
    h, w, c = image_shape
    coarse = rng.normal(0, 1, (num_classes, 4, 4, c)).astype(np.float32)
    return np.kron(coarse, np.ones((1, h // 4, w // 4, 1), np.float32))


def noisy_images(templates: np.ndarray, labels: np.ndarray, noise: float,
                 rng: np.random.Generator, shift: float = 0.0) -> np.ndarray:
    """templates[labels] + noise, plus one shared shift image when given."""
    x = templates[labels]
    x += noise * rng.standard_normal(x.shape, dtype=np.float32)
    if shift:
        x += shift * rng.standard_normal((1,) + x.shape[1:], dtype=np.float32)
    return x


def dirichlet_partition(labels: np.ndarray, num_clients: int, alpha: float,
                        seed: int, min_size: int = 2) -> list[np.ndarray]:
    """Per-client index arrays covering ``labels`` once: for every class,
    its share of each client is drawn from Dir(alpha); redrawn until every
    client holds at least ``min_size`` examples."""
    rng = np.random.default_rng(seed)
    labels = np.asarray(labels)
    while True:
        parts: list[list[int]] = [[] for _ in range(num_clients)]
        for c in np.unique(labels):
            idx_c = np.flatnonzero(labels == c)
            rng.shuffle(idx_c)
            props = rng.dirichlet([alpha] * num_clients)
            cuts = (np.cumsum(props) * len(idx_c)).astype(int)[:-1]
            for cid, part in enumerate(np.split(idx_c, cuts)):
                parts[cid].extend(part.tolist())
        if min(len(p) for p in parts) >= min_size:
            break
    out = []
    for p in parts:
        arr = np.asarray(p, dtype=np.int64)
        rng.shuffle(arr)
        out.append(arr)
    return out


def iid_partition(n: int, num_clients: int, seed: int) -> list[np.ndarray]:
    """Equal IID shards: one permutation cut into ``num_clients`` pieces."""
    if n % num_clients:
        raise ValueError(f"{n} examples do not split into {num_clients} "
                         "equal shards")
    perm = np.random.default_rng(seed).permutation(n)
    return list(perm.reshape(num_clients, -1))


def population(cfg: dict, mix: dict):
    """``(labels, parts)``: the training labels and each client's index
    array into them.  Depends on the mix's
    ``partition_seed`` alone, never on the run's seed."""
    pop = mix["population"]
    rng = np.random.default_rng(pop["partition_seed"])
    labels = rng.integers(0, cfg["num_classes"], cfg["num_train"]).astype(
        np.int32)
    if pop["partition"] == "dirichlet":
        parts = dirichlet_partition(labels, pop["num_clients"], pop["alpha"],
                                    seed=pop["partition_seed"] + 1,
                                    min_size=pop["min_shard"])
    elif pop["partition"] == "iid":
        parts = iid_partition(cfg["num_train"], pop["num_clients"],
                              seed=pop["partition_seed"] + 1)
    else:
        raise ValueError(f"unknown partition {pop['partition']!r}")
    return labels, parts


def make_federation(cfg: dict, mix: dict, data_seed: int):
    """``(client_data, server_x)``: each client's (x, y) numpy shard and
    the (num_server, H, W, C) unlabeled server images.

    Every image is drawn in one bulk pass, then sliced per client."""
    labels, parts = population(cfg, mix)
    shape = tuple(cfg["image_shape"])
    rng = np.random.default_rng(data_seed)
    templates = class_templates(rng, cfg["num_classes"], shape)
    noise = cfg["pixel_noise"]
    x = noisy_images(templates, labels, noise, rng)
    server_labels = rng.integers(0, cfg["num_classes"], cfg["num_server"])
    server_x = noisy_images(templates, server_labels, noise, rng,
                            shift=cfg["server_shift"])
    client_data = [(x[ix], labels[ix]) for ix in parts]
    return client_data, server_x
