"""A candidate pool of N UEs for R stacked scheduling runs, from a seed.

A copy of the program's ``repro.core.population.synthetic_population``
generator kept with the benchmark, drawn from the run's seed instead of
fixed ones: per run and UE a dataset size of 1-30 groups of 50 samples,
a CPU clock uniform in the configured range, an element diversity
uniform in [0, 0.9] and a starting reputation uniform in [0, 1]; one UE
position per candidate, uniform over the square cell. Each round draws a
Rayleigh channel per UE and run, a visiting permutation for each run of
the ``random`` policy (the identity for the others, as the program's own
runs pass), and the cohort's (local, test) accuracies that the round's
Eq. 1 update consumes: pairs drawn, with replacement, from the
per-UE readings of the §V experiment that the traffic file names
(bench/record_accuracies.py recorded them from the program on the chip).
"""
from __future__ import annotations

import dataclasses
import os
from typing import List

import numpy as np

from reference import control as ctl


@dataclasses.dataclass
class Pool:
    policies: List[str]
    sizes: np.ndarray        # (R, N) float64
    divs: np.ndarray         # (R, N)
    cpu_hz: np.ndarray       # (R, N)
    r_min: np.ndarray        # (R, N) Eq. 5-7, round-invariant
    reputations: np.ndarray  # (R, N) at round 0
    distances: np.ndarray    # (N,)
    gains: List[np.ndarray]      # per pooled round, (R, N)
    rand_rank: List[np.ndarray]  # per pooled round, (R, N) int64
    acc_local: List[np.ndarray]  # per pooled round, (R, K)
    acc_test: List[np.ndarray]   # per pooled round, (R, K)


def pool(cfg: dict, traffic: dict, rng: np.random.Generator) -> Pool:
    feel = cfg["feel"]
    n, k = feel["population"], feel["n_ues"]
    policies = list(cfg["policies"])
    r = len(policies)
    sizes = (rng.integers(1, 31, (r, n)) * 50).astype(float)
    cpu = rng.uniform(feel["cpu_hz_min"], feel["cpu_hz_max"], (r, n))
    divs = rng.uniform(0.0, 0.9, (r, n))
    rep = rng.uniform(0.0, 1.0, (r, n))
    half = feel["cell_side_m"] / 2.0
    xy = rng.uniform(-half, half, size=(n, 2))
    dist = np.maximum(np.linalg.norm(xy, axis=1), 1.0)
    pl = dist ** (-feel["pathloss_exp"])
    rounds = traffic["pool_rounds"]
    pairs = cohort_accuracies(traffic["cohort_accuracies"])
    acc = [pairs[rng.integers(0, len(pairs), (r, k))] for _ in range(rounds)]
    return Pool(
        policies=policies, sizes=sizes, divs=divs, cpu_hz=cpu,
        r_min=ctl.min_rate(feel, sizes, cpu), reputations=rep,
        distances=dist,
        gains=[pl * rng.exponential(1.0, (r, n)) for _ in range(rounds)],
        rand_rank=[np.stack([np.argsort(rng.permutation(n)) if p == "random"
                             else np.arange(n) for p in policies])
                   for _ in range(rounds)],
        acc_local=[a[..., 0] for a in acc],
        acc_test=[a[..., 1] for a in acc])


def cohort_accuracies(name: str) -> np.ndarray:
    """(M, 2) float64 (acc_local, acc_test) pairs from a CSV under
    bench/traffic with columns seed,round,malicious,acc_local,acc_test."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "traffic", name)
    rows = np.loadtxt(path, delimiter=",", skiprows=1, ndmin=2)
    return np.ascontiguousarray(rows[:, 3:5], np.float64)
