"""The paper's control plane, plainly (arXiv:2201.11247 §III-IV).

Eq. 2 diversity index, Eq. 3 data-quality value, Eq. 4/9 bandwidth cost,
Algorithm 2 greedy packing with the modified-greedy fallback, the §V
baselines' visit orders, and the Eq. 1 reputation update. Every function
takes a dtype so that the same code, run in float32, is the control that
has to fail the comparison.
"""
from __future__ import annotations

import numpy as np


def dbm_to_watt(dbm: float) -> float:
    return 10.0 ** (dbm / 10.0) / 1000.0


def _minmax(v):
    lo = v.min(-1, keepdims=True)
    span = v.max(-1, keepdims=True) - lo
    one = np.ones_like(span)
    return np.where(span < 1e-12, one, (v - lo) / np.where(span < 1e-12,
                                                           one, span))


def values(rep, divs, sizes, ages, gamma, w_rep, w_div, dtype=np.float64):
    """Eq. 3 over the last (UE) axis: w_rep * R + w_div * I, with Eq. 2's
    I = sum_i gamma_i * minmax(metric_i) over (diversity, size, age)."""
    f = lambda a: np.asarray(a, dtype)
    g = [dtype(x) for x in gamma]
    div_index = (g[0] * _minmax(f(divs)) + g[1] * _minmax(f(sizes))
                 + g[2] * _minmax(f(ages)))
    return dtype(w_rep) * f(rep) + dtype(w_div) * div_index


def min_rate(feel: dict, sizes, cpu_hz, dtype=np.float64):
    """Eq. 5-7: r_min = s / (T - t_train), t_train = eps |D| zeta bits / f;
    infinite where the deadline is blown."""
    t_train = (dtype(feel["local_epochs"]) * np.asarray(sizes, dtype)
               * dtype(feel["sample_bits"]) * dtype(feel["cycles_per_bit"])
               / np.asarray(cpu_hz, dtype))
    slack = dtype(feel["deadline_s"]) - t_train
    with np.errstate(divide="ignore"):
        return np.where(slack > 0, dtype(feel["model_size_bits"]) / slack,
                        dtype(np.inf))


def costs(gains, r_min, feel: dict, dtype=np.float64):
    """Eq. 9: the least c in 1..K with r(c/K) >= r_min (Eq. 4), K+1 where
    the whole band is not enough. r(c/K) rises with c, so bisection."""
    k = int(feel["n_ues"])
    g = np.asarray(gains, dtype)
    r_min = np.asarray(r_min, dtype)
    bw = dtype(feel["bandwidth_hz"])
    p = dtype(dbm_to_watt(feel["tx_power_dbm"]))
    n0 = dtype(dbm_to_watt(feel["noise_dbm_hz"]))

    def ok(c):
        a = c.astype(dtype) / dtype(k)
        return a * bw * np.log2(dtype(1) + g * p / (a * bw * n0)) >= r_min

    lo = np.ones(g.shape, np.int64)
    hi = np.full(g.shape, k, np.int64)
    while np.any(lo < hi):
        mid = (lo + hi) // 2
        hit = ok(mid)
        lo = np.where(hit, lo, mid + 1)
        hi = np.where(hit, mid, hi)
    return np.where(ok(np.full(g.shape, k, np.int64)), lo, k + 1)


def _pack(order, c_sorted, k):
    """Alg. 2's walk: visit ``order``, take a UE whenever its cost fits the
    remaining budget, skip it otherwise. Returns the taken UE ids."""
    taken, pos, budget = [], 0, k
    n = c_sorted.size
    while budget > 0 and pos < n:
        fits = np.flatnonzero(c_sorted[pos:] <= budget)
        if fits.size == 0:
            break
        pos += int(fits[0])
        taken.append(int(order[pos]))
        budget -= int(c_sorted[pos])
        pos += 1
    return np.asarray(taken, np.int64)


def schedule(policy, vals, cost, k, n_sel, gains=None, rand_rank=None):
    """One run's round: (selected ids sorted, forced).

    Visit orders: dqs by V/c falling, random by the given permutation,
    best_channel by c then gain, max_count by c; ties to the lower id.
    dqs then keeps the single best feasible UE alone when it is worth more
    than the whole pack; top_value takes the n_sel highest values. With
    nothing selected the highest-value UE is forced."""
    n = vals.size
    dt = vals.dtype.type
    cf = cost.astype(vals.dtype)
    if policy == "top_value":
        sel = np.argsort(-vals, kind="stable")[:n_sel]
    else:
        if policy == "dqs":
            key = -(vals / cf)
        elif policy == "random":
            key = np.asarray(rand_rank).astype(vals.dtype)
        elif policy == "best_channel":
            g = np.asarray(gains, vals.dtype)
            key = cf * dt(k) - g / (g.max() + dt(1e-12))
        elif policy == "max_count":
            key = cf
        else:
            raise KeyError(policy)
        order = np.argsort(key, kind="stable")
        sel = _pack(order, cost[order], k)
        if policy == "dqs":
            feas = np.flatnonzero(cost <= k)
            if feas.size:
                best = int(feas[np.argmax(vals[feas])])
                if vals[best] > vals[np.sort(sel)].sum():
                    sel = np.array([best])
    forced = sel.size == 0
    if forced:
        sel = np.array([int(np.argmax(vals))])
    return np.sort(sel), forced


def reputation_update(rep, sel, acc_local, acc_test, feel: dict):
    """Eq. 1 on the cohort, clipped to [0, 1]; returns a new array."""
    a = np.asarray(acc_local, float)
    delta = feel["eta"] * (feel["beta1"] * (a - np.mean(a))
                           + feel["beta2"] * (a - np.asarray(acc_test,
                                                             float)))
    out = np.array(rep, float)
    out[sel] = np.clip(out[sel] - delta, 0.0, 1.0)
    return out
