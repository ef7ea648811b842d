"""Operations and bytes of the benchmark's work, computed from shapes.

Only what the algorithm needs is counted: real samples (never padding),
the matrix products of the forward pass, and in the backward pass the
weight gradients of every layer and the input gradients of every layer
but the first (nothing needs the gradient of the images). Elementwise
work is left out; it is under 1% of the products here. Bytes are what
must cross HBM at least once: each sample read once per epoch, each
client's model read and written once, each evaluated sample read once.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

F32 = 4


def mlp_forward_flops(layers: Sequence[int]) -> int:
    return sum(2 * a * b for a, b in zip(layers[:-1], layers[1:]))


def mlp_train_flops(layers: Sequence[int]) -> int:
    """Per sample-step: forward, weight gradients, input gradients of all
    layers after the first."""
    pairs = list(zip(layers[:-1], layers[1:]))
    return (mlp_forward_flops(layers) + sum(2 * a * b for a, b in pairs)
            + sum(2 * a * b for a, b in pairs[1:]))


def mlp_params(layers: Sequence[int]) -> int:
    return sum(a * b + b for a, b in zip(layers[:-1], layers[1:]))


def claimed_units(data) -> np.ndarray:
    """Per UE, the test samples of the classes it claims to hold (the
    units Eq. 1's server-side accuracy is taken over)."""
    ty = data.test.y
    n_cls = int(ty.max()) + 1
    return np.array([np.isin(ty, np.flatnonzero(
        np.bincount(c.y, minlength=n_cls))).sum() for c in data.clients])


def feel_round(cfg: dict, sizes_sel, units_sel, n_test: int,
               n_watched: int) -> Dict[str, float]:
    """One §V round's device work: local training of the cohort, each
    client's accuracy on its own samples, each upload scored on its
    claimed test samples, the global model on the whole test set and
    twice on the watched source class."""
    layers = cfg["task"]["layers"]
    epochs = cfg["feel"]["local_epochs"]
    fwd, trn, p = (mlp_forward_flops(layers), mlp_train_flops(layers),
                   mlp_params(layers))
    sample_bytes = (layers[0] + 1) * F32
    samples = float(np.sum(sizes_sel))
    units = float(np.sum(units_sel)) + n_test + 2 * n_watched
    return {
        "train_flops": trn * samples * epochs,
        "train_bytes": sample_bytes * samples * epochs
        + 2 * p * F32 * len(sizes_sel),
        "eval_flops": fwd * (samples + units),
        "eval_bytes": sample_bytes * (samples + units),
    }


def prefilter_round(runs: int, n: int, rounds: int) -> Dict[str, float]:
    """Bytes the prefilter kernel must move: seven (R, N) operands in
    (reputations, ages, diversities, sizes, minimum rates, gains as
    float64, the permutation as int64) and four out (selection as bool,
    bandwidth shares and values as float64, costs as int32)."""
    return {"prefilter_bytes": float(runs * n * (7 * 8 + 1 + 8 + 8 + 4)
                                     * rounds)}


def total(per_step: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for d in per_step:
        for k, v in d.items():
            out[k] = out.get(k, 0.0) + v
    return out
