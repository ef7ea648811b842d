"""The §V round of arXiv:2201.11247 (Alg. 1), plainly, in float64.

Per round: Eq. 2/3 values from reputations, diversities, sizes and ages;
one Rayleigh channel draw per UE; Eq. 9 costs; Alg. 2 DQS selection; each
selected UE trains the global MLP (784-64-10, ReLU, softmax cross-entropy)
by mini-batch SGD on its own rows in order; the server scores each upload
on the test samples of the classes the UE claims, averages the uploads
weighted by dataset size (FedAvg), scores the global model, and applies
Eq. 1. ``mm`` and ``dtype`` default to exact float64.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import numpy as np

from reference import control as ctl


def gini_simpson(y: np.ndarray, n_classes: int) -> float:
    p = np.bincount(y, minlength=n_classes) / max(y.size, 1)
    return float(1.0 - np.sum(p * p))


def f32_ratio(correct: float, valid: float) -> float:
    """Accuracy as the program reports it: a float32 quotient of counts."""
    return float(np.float32(correct) / np.float32(max(valid, 1.0)))


def forward(p, x, mm):
    pre = mm(x, p["w1"]) + p["b1"]
    h = np.maximum(pre, 0)
    return pre, h, mm(h, p["w2"]) + p["b2"]


def correct(p, x, y, mm) -> int:
    return int(np.sum(np.argmax(forward(p, x, mm)[2], -1) == y))


def local_sgd(p, x, y, epochs: int, lr: float, batch: int, mm):
    p = {k: v.copy() for k, v in p.items()}
    dt = p["w1"].dtype.type
    for _ in range(epochs):
        for i in range(0, x.shape[0] - batch + 1, batch):
            xb, yb = x[i:i + batch], y[i:i + batch]
            pre, h, logits = forward(p, xb, mm)
            z = np.exp(logits - logits.max(-1, keepdims=True))
            d = z / z.sum(-1, keepdims=True)
            d[np.arange(batch), yb] -= 1
            d /= dt(batch)
            dh = mm(d, p["w2"].T) * (pre > 0)
            grads = {"w2": mm(h.T, d), "b2": d.sum(0),
                     "w1": mm(xb.T, dh), "b1": dh.sum(0)}
            for k in p:
                p[k] = p[k] - dt(lr) * grads[k]
    return p


def schedule_round(feel: dict, rep, divs, sizes, ages, cpu_hz, distances,
                   channel: np.random.Generator, dtype=np.float64) -> dict:
    """One round's control plane: Eq. 3 values, one channel draw per UE,
    Eq. 9 costs, Alg. 2 DQS; values and costs computed in ``dtype``."""
    k = feel["n_ues"]
    vals = ctl.values(rep, divs, sizes, ages, feel["gamma"],
                      feel["omega_rep"], feel["omega_div"], dtype=dtype)
    gains = distances ** (-feel["pathloss_exp"]) * channel.exponential(
        1.0, size=k)
    cost = ctl.costs(gains, ctl.min_rate(feel, sizes, cpu_hz, dtype=dtype),
                     feel, dtype=dtype)
    sel, forced = ctl.schedule("dqs", vals, cost, k, feel["min_selected"])
    objective = 0.0 if forced else float(vals[sel].sum())
    return {"sel": sel, "forced": forced, "objective": objective,
            "values": vals.astype(float), "costs": cost}


def initial_state(cfg: dict, data):
    """Round 0's (reputations, ages, diversities, sizes) of the deployment."""
    k, n_cls = cfg["feel"]["n_ues"], cfg["task"]["n_classes"]
    sizes = np.array([len(c) for c in data.clients], float)
    divs = np.array([gini_simpson(c.y, n_cls) for c in data.clients])
    return np.ones(k), np.ones(k), divs, sizes


def follow(cfg: dict, data, w0: Dict[str, np.ndarray], distances, cpu_hz,
           channel: np.random.Generator, rounds: int,
           mm: Callable = np.matmul, dtype=np.float64) -> List[dict]:
    """The first ``rounds`` rounds from the initial state. Returns, per
    round, the selection, forced flag, objective, values, Eq. 9 costs,
    each selected UE's upload (in selection order), and the
    reputations, global parameters and global accuracy after it."""
    feel, task = cfg["feel"], cfg["task"]
    n_cls = task["n_classes"]
    rep, ages, divs, sizes = initial_state(cfg, data)
    tx = data.test.x.astype(dtype)
    ty = data.test.y
    masks = [np.isin(ty, np.flatnonzero(np.bincount(c.y, minlength=n_cls)))
             for c in data.clients]
    params = {n: np.asarray(v, dtype) for n, v in w0.items()}
    out = []
    for _ in range(rounds):
        plan = schedule_round(feel, rep, divs, sizes, ages, cpu_hz,
                              distances, channel)
        sel, forced = plan["sel"], plan["forced"]
        uploads, acc_local, acc_test = [], [], []
        for u in sel:
            c = data.clients[u]
            q = local_sgd(params, c.x.astype(dtype), c.y,
                          feel["local_epochs"], task["lr"],
                          task["batch_size"], mm)
            uploads.append(q)
            acc_local.append(f32_ratio(correct(q, c.x.astype(dtype), c.y,
                                               mm), len(c)))
            m = masks[u]
            acc_test.append(f32_ratio(correct(q, tx[m], ty[m], mm),
                                      m.sum()))
        w = sizes[sel] / sizes[sel].sum()
        params = {n: sum(wi * q[n] for wi, q in zip(w, uploads))
                  .astype(dtype) for n in params}
        acc = f32_ratio(correct(params, tx, ty, mm), ty.size)
        rep = ctl.reputation_update(rep, sel, acc_local, acc_test, feel)
        ages = ages + 1.0
        ages[sel] = 1.0
        out.append(dict(plan, rep=rep.copy(),
                        params={n: v.astype(float) for n, v in
                                params.items()},
                        acc=acc, uploads=uploads))
    return out
