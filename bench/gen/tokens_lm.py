"""The token data of a federated LM deployment, made from a seed.

A copy of the program's synthetic token streams and window cut
(``repro.data.tokens.make_stream`` / ``make_windows``, stream version 2)
kept with the benchmark, so that a change to the program cannot move the
inputs it is measured on. Each of ``n_domains`` domains is a Markov
stream over the vocabulary (Zipf unigrams, a domain-shifted bigram map);
windows are cut from the domain streams round-robin, sorted by domain into
groups of ``group_size`` windows, and each UE gets its profile's number of
groups without replacement (non-IID, unbalanced). Malicious UEs have
every occurrence of the source token replaced by the target token.

As in ``gen/mnist.py``, the devices are fixed by the configuration's
``profile_seed`` (windows per UE, CPU clock, distance, malicious or not)
and the run's seed deals them to UE ids and draws the streams, the groups
and everything else.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def _zipf_cdf(vocab: int, s: float = 1.1) -> np.ndarray:
    p = 1.0 / np.arange(1, vocab + 1) ** s
    return np.cumsum(p / p.sum())


def _affine_tables(n: int, vocab: int, domain: int):
    pows, seen = [], {}
    v = 1
    while v not in seen:
        seen[v] = len(pows)
        pows.append(v)
        v = (v * 31) % vocab
    start = seen[v]
    period = len(pows) - start
    idx = np.arange(n)
    cyc = np.where(idx < len(pows), idx, start + (idx - start) % period)
    A = np.asarray(pows, np.int64)[np.minimum(cyc, len(pows) - 1)]
    S = np.concatenate([[0], np.cumsum(A[:-1]) % vocab])
    return A, ((7 + domain) % vocab) * S % vocab


def stream(n_tokens: int, vocab: int, seed: int, domain: int) -> np.ndarray:
    rng = np.random.default_rng(seed + 7919 * domain)
    cdf = _zipf_cdf(vocab)
    t0 = int(rng.integers(vocab))
    u_branch = rng.random(n_tokens)
    z = np.searchsorted(cdf, rng.random(n_tokens)).astype(np.int64)
    is_start = np.empty(n_tokens, bool)
    is_start[0] = True
    is_start[1:] = u_branch[:-1] >= 0.6
    start_val = np.empty(n_tokens, np.int64)
    start_val[0] = t0
    start_val[1:] = z[:-1]
    pos = np.arange(n_tokens)
    seg = np.maximum.accumulate(np.where(is_start, pos, -1))
    A, C = _affine_tables(n_tokens, vocab, domain)
    return ((A[pos - seg] * start_val[seg] + C[pos - seg]) % vocab).astype(
        np.int32)


def windows(n: int, vocab: int, seq: int, n_domains: int, seed: int):
    """(tokens (n, seq) int32, domain (n,) int32), round-robin by domain."""
    per = -(-n // n_domains)
    toks = np.stack([stream(per * seq, vocab, seed, d).reshape(per, seq)
                     for d in range(n_domains)], axis=1)
    dom = np.broadcast_to(np.arange(n_domains, dtype=np.int32),
                          (per, n_domains))
    return toks.reshape(per * n_domains, seq)[:n], dom.reshape(-1)[:n].copy()


@dataclasses.dataclass
class LmData:
    """One deployment's inputs: per-UE windows (after the attack) with
    their domains, their clean twins, the held-out test windows, the
    malicious set, and each UE's CPU clock and distance."""
    clients: List[np.ndarray]    # (n_k, seq) int32
    domains: List[np.ndarray]    # (n_k,) int32
    clean: List[np.ndarray]
    test: np.ndarray             # (n_test, seq) int32
    malicious: np.ndarray        # sorted UE ids
    cpu_hz: np.ndarray
    distances: np.ndarray


def profiles(cfg: dict):
    """(windows, CPU clock, distance, malicious) per device profile."""
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


def lm_data(cfg: dict, rng: np.random.Generator) -> LmData:
    d = cfg["data"]
    counts, cpu, dist, mal = profiles(cfg)
    order = rng.permutation(cfg["feel"]["n_ues"])
    counts, cpu, dist, mal = counts[order], cpu[order], dist[order], \
        mal[order]
    seed = int(rng.integers(2**31))
    toks, dom = windows(d["n_train"] + d["n_test"], cfg["vocab_size"],
                        d["seq"], d["n_domains"], seed)
    train, train_dom = toks[:d["n_train"]], dom[:d["n_train"]]
    g = d["group_size"]
    sort = np.argsort(train_dom, kind="stable")
    n_groups = d["n_train"] // g
    groups = sort[: n_groups * g].reshape(n_groups, g)
    perm = rng.permutation(n_groups)
    if counts.sum() > n_groups:
        raise ValueError(f"{counts.sum()} groups drawn from {n_groups}")
    src, dst = cfg["attack"]["flip"]
    clients, domains, clean, cursor = [], [], [], 0
    for u in range(len(counts)):
        idx = groups[perm[cursor: cursor + counts[u]]].reshape(-1)
        cursor += counts[u]
        w = train[idx]
        clean.append(w)
        domains.append(train_dom[idx])
        clients.append(np.where(w == src, dst, w).astype(np.int32)
                       if mal[u] else w)
    return LmData(clients, domains, clean, toks[d["n_train"]:],
                  np.flatnonzero(mal), cpu, dist)
