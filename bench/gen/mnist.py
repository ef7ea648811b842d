"""The §V data of arXiv:2201.11247, made from a seed.

A copy of the program's synthetic-MNIST generator and group partition
(``repro.data.synthetic_mnist.generate``, ``repro.data.partition.partition``)
kept with the benchmark, so that a change to the program cannot move the
inputs it is measured on. Classes are smooth prototype images with jitter
and pixel noise; the training set is sorted by label into groups of 50 and
each UE gets 1-30 groups, drawn without replacement (non-IID, unbalanced).
Malicious UEs have every label of the source class flipped to the target.

The deployment's devices are fixed: each UE's profile (number of groups,
CPU clock, distance to the base station, malicious or not) is drawn once
from the configuration's ``profile_seed``. The run's seed deals the
profiles to UE ids in another order and draws everything else: the
images, which groups each UE holds, the channels and the initial model.
So every seed gives the same set of dataset sizes, clocks and distances,
and the same shapes to compile.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

N_CLASSES = 10
IMG = 28


@dataclasses.dataclass
class Images:
    x: np.ndarray    # (n, 784) float32 in [0, 1]
    y: np.ndarray    # (n,) int32

    def __len__(self):
        return self.x.shape[0]


def _prototypes(rng: np.random.Generator, n_proto: int = 4) -> np.ndarray:
    protos = np.zeros((N_CLASSES, n_proto, IMG, IMG), np.float32)
    yy, xx = np.mgrid[0:IMG, 0:IMG] / (IMG - 1)
    for c in range(N_CLASSES):
        for p in range(n_proto):
            img = np.zeros((IMG, IMG), np.float32)
            for b in range(3 + (c % 3)):
                cx = 0.2 + 0.6 * ((c * 7 + b * 3 + p) % 10) / 9.0
                cy = 0.2 + 0.6 * ((c * 3 + b * 5) % 10) / 9.0
                sx = 0.05 + 0.08 * rng.uniform()
                sy = 0.05 + 0.08 * rng.uniform()
                img += np.exp(-((xx - cx) ** 2 / (2 * sx ** 2)
                                + (yy - cy) ** 2 / (2 * sy ** 2)))
            protos[c, p] = img / max(img.max(), 1e-6)
    return protos


def images(n_train: int, n_test: int, rng: np.random.Generator,
           noise: float = 0.15) -> Tuple[Images, Images]:
    protos = _prototypes(rng)

    def make(n):
        y = rng.integers(0, N_CLASSES, size=n).astype(np.int32)
        p = rng.integers(0, protos.shape[1], size=n)
        base = protos[y, p]
        shift = rng.integers(-2, 3, size=(n, 2))
        imgs = np.empty_like(base)
        for i in range(n):
            imgs[i] = np.roll(np.roll(base[i], shift[i, 0], 0),
                              shift[i, 1], 1)
        imgs = imgs + noise * rng.standard_normal(imgs.shape).astype(
            np.float32)
        return Images(np.clip(imgs, 0.0, 1.0).reshape(n, IMG * IMG)
                      .astype(np.float32), y)

    return make(n_train), make(n_test)


def partition(train: Images, counts: np.ndarray, rng: np.random.Generator,
              group_size: int) -> List[np.ndarray]:
    """Row indices of each UE's groups: ``counts[k]`` label-sorted groups
    of ``group_size`` for UE k, drawn without replacement."""
    order = np.argsort(train.y, kind="stable")
    n_groups = len(train) // group_size
    groups = order[: n_groups * group_size].reshape(n_groups, group_size)
    perm = rng.permutation(n_groups)
    if counts.sum() > n_groups:
        raise ValueError(f"{counts.sum()} groups drawn from {n_groups}")
    out, cursor = [], 0
    for k in range(len(counts)):
        out.append(groups[perm[cursor: cursor + counts[k]]].reshape(-1))
        cursor += counts[k]
    return out


@dataclasses.dataclass
class FeelData:
    """One §V deployment's inputs: per-UE datasets (after the attack),
    their clean twins, the test set, the malicious set, and each UE's CPU
    clock and distance to the base station."""
    clients: List[Images]
    clean: List[Images]
    test: Images
    malicious: np.ndarray      # sorted UE ids
    cpu_hz: np.ndarray
    distances: np.ndarray


def profiles(cfg: dict):
    """The deployment's fixed devices: (groups, CPU clock, distance,
    malicious) per profile, from the configuration's ``profile_seed``."""
    d, feel = cfg["data"], cfg["feel"]
    k = feel["n_ues"]
    rng = np.random.default_rng(cfg["profile_seed"])
    counts = rng.integers(d["min_groups"], d["max_groups"] + 1, size=k)
    cpu = rng.uniform(feel["cpu_hz_min"], feel["cpu_hz_max"], size=k)
    half = feel["cell_side_m"] / 2.0
    dist = np.maximum(np.linalg.norm(rng.uniform(-half, half, (k, 2)),
                                     axis=1), 1.0)
    mal = np.zeros(k, bool)
    mal[rng.choice(k, size=feel["n_malicious"], replace=False)] = True
    return counts, cpu, dist, mal


def feel_data(cfg: dict, rng: np.random.Generator) -> FeelData:
    d = cfg["data"]
    counts, cpu, dist, mal = profiles(cfg)
    order = rng.permutation(cfg["feel"]["n_ues"])
    counts, cpu, dist, mal = counts[order], cpu[order], dist[order], \
        mal[order]
    train, test = images(d["n_train"], d["n_test"], rng)
    src, dst = cfg["attack"]["flip"]
    clients, clean = [], []
    for u, idx in enumerate(partition(train, counts, rng, d["group_size"])):
        ds = Images(train.x[idx], train.y[idx])
        clean.append(ds)
        if mal[u]:
            ds = Images(ds.x, np.where(ds.y == src, dst, ds.y)
                        .astype(np.int32))
        clients.append(ds)
    return FeelData(clients, clean, test, np.flatnonzero(mal), cpu, dist)
