"""Defense plane: robust aggregation + data-quality validation
(companion work arXiv:2102.09491 — validation-based detection of the
unreliable-data family; arXiv:2004.00490 — folding a trust signal back
into the scheduling objective).

PR 4's threat-model plane surfaced the hole this module closes: Eq. 1 as
written *rewards* feature-noise clients (their honestly-low self-reports
turn the beta1 term into a credit — DESIGN.md §8, the negative
`feature_noise_*` rep gaps in results/robustness.json). The paper has no
server-side defense beyond Eq. 1, so the defense is a first-class axis
mirroring ``core.attacks.AttackScenario``:

    DefensePolicy — a named bundle of two orthogonal components:
        aggregator  RobustAggregator  replaces/augments FedAvg over the
                                      stacked cohort: coordinate-wise
                                      trimmed mean, coordinate median,
                                      update-norm clipping, Krum /
                                      multi-Krum distance filtering
        detector    ValidationDetector  a held-out validation pass over
                                      the uploaded models whose anomaly
                                      score feeds a trust penalty into
                                      Eq. 1 (and therefore into the
                                      Eq. 3 value the scheduler ranks)

Every aggregator has a host numpy oracle — per-client / compressed
``(n, P)`` math, the ``engine="loop"`` path — AND a batched jnp twin
operating on the padded ``(K_pad, P)`` flattened-update layout of the
vectorized cohort engine (padding rows ride along under a validity
sentinel and weight 0). Parity contract (DESIGN.md §9,
tests/test_defenses.py): every *decision* (trim ranks, Krum selection,
clip counts) is bit-equal between the planes; float payloads are
bit-equal where the reduction order is pinned (trimmed mean / median use
an identical ascending sequential accumulation on both planes) and
documented-ulp otherwise (norm/distance reductions run in float64, where
XLA's reduce grouping may differ from numpy's in the last bit — a
selection flip needs a measure-zero tie, mirroring the control plane's
Eq. 9 log2 note).

The trimmed-mean / median reductions also exist as a Pallas TPU kernel
(``kernels/robust_aggregate.py`` — sort/select over the stacked-client
axis in a ``weighted_aggregate``-style block layout); the batched twin
routes through it under ``REPRO_USE_PALLAS=1``, and otherwise uses the
exact-parity jnp path below.

Randomness: defenses draw nothing — they are deterministic functions of
the uploaded cohort, so threading them through the sweep never perturbs
the RNG stream-of-record (DESIGN.md §2) and a defended run's schedule
diverges from its undefended twin only through the model/reputation
effects of the defense itself.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------- #
# Flattened-update layout helpers (the (K_pad, P) defense layout)
# ---------------------------------------------------------------------- #
def flatten_params_np(params) -> np.ndarray:
    """One parameter pytree -> (P,) float32 numpy vector (host layout)."""
    return np.concatenate([np.asarray(l, np.float32).ravel()
                           for l in jax.tree.leaves(params)])


def flatten_stacked(stacked) -> jnp.ndarray:
    """Stacked pytree (leaves (N, ...)) -> (N, P) float32 device matrix.

    Leaf order and the per-leaf reshape match ``fedavg_stacked``'s kernel
    route, so host and batched planes index identical columns.
    """
    leaves = jax.tree.leaves(stacked)
    n = leaves[0].shape[0]
    return jnp.concatenate(
        [l.reshape(n, -1).astype(jnp.float32) for l in leaves], axis=1)


def unflatten_vec(template, vec):
    """(P,) vector -> pytree shaped like ``template`` (dtype-preserving)."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for l in leaves:
        m = int(np.prod(l.shape, dtype=np.int64))
        out.append(jnp.asarray(vec[off:off + m]).reshape(l.shape)
                   .astype(l.dtype))
        off += m
    return jax.tree.unflatten(treedef, out)


def unflatten_stacked(stacked_template, flat):
    """(N, P) matrix -> stacked pytree shaped like ``stacked_template``."""
    leaves, treedef = jax.tree.flatten(stacked_template)
    n = leaves[0].shape[0]
    out, off = [], 0
    for l in leaves:
        m = int(np.prod(l.shape[1:], dtype=np.int64))
        out.append(jnp.asarray(flat[:, off:off + m]).reshape(l.shape)
                   .astype(l.dtype))
        off += m
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------- #
# Per-round defense statistics (RoundLog / SweepResult payload)
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class DefenseStats:
    """What the defense did this round (metrics only — ground truth never
    feeds back into the defense itself)."""
    n_clipped: int = 0        # norm-clip: rows whose update was shrunk
    n_rejected: int = 0       # trim/Krum: rows excluded from aggregation
    n_flagged: int = 0        # detector: rows with positive anomaly
    det_precision: float = float("nan")   # flagged ∩ malicious / flagged
    det_recall: float = float("nan")      # flagged ∩ malicious / malicious


# ---------------------------------------------------------------------- #
# Robust aggregators
# ---------------------------------------------------------------------- #
def _seq_mean(rows, count):
    """Ascending sequential sum / count — the ONE accumulation order both
    planes use, so trimmed-mean payloads are bit-equal host vs batched
    (elementwise IEEE f32 adds; numpy and XLA round identically)."""
    acc = rows[0]
    for r in rows[1:]:
        acc = acc + r
    return acc / count


@dataclasses.dataclass(frozen=True)
class TrimmedMean:
    """Coordinate-wise trimmed mean [Yin et al., 2018]: per parameter,
    sort the n uploaded values, drop ``n_trim(n)`` from each end, average
    the rest (unweighted — robust statistics replace the size-weighted
    FedAvg entirely)."""
    trim: float = 0.2      # fraction trimmed from EACH end

    def __post_init__(self):
        assert 0.0 < self.trim < 0.5, self.trim

    def n_trim(self, n: int) -> int:
        return min(int(np.floor(self.trim * n)), max((n - 1) // 2, 0))

    def aggregate_host(self, flat: np.ndarray
                       ) -> Tuple[np.ndarray, DefenseStats]:
        """(n, P) float32 compressed matrix -> (P,) aggregate."""
        n = flat.shape[0]
        b = self.n_trim(n)
        xs = np.sort(flat, axis=0)
        agg = _seq_mean([xs[i] for i in range(b, n - b)],
                        np.float32(n - 2 * b))
        return agg, DefenseStats(n_rejected=2 * b)

    def aggregate_batched(self, flat: jnp.ndarray, n: int, kernel=None
                          ) -> Tuple[jnp.ndarray, DefenseStats]:
        """(N_pad, P) padded matrix (real rows first) -> (P,) aggregate.

        Padding rows sort to the top under a +inf sentinel and the kept
        rank window [b, n-b) never reaches them. ``kernel=True`` routes
        through the Pallas ``robust_aggregate`` kernel (None defers to
        ``ops.use_pallas()``); the default is the exact-parity jnp path
        (same ascending sequential accumulation as the host oracle).
        """
        b = self.n_trim(n)
        stats = DefenseStats(n_rejected=2 * b)
        if _use_kernel(kernel):
            from repro.kernels import ops
            return ops.robust_aggregate(flat, n, trim=b,
                                        mode="trimmed_mean"), stats
        xs = _sorted_rows(flat, n)
        agg = _seq_mean([xs[i] for i in range(b, n - b)],
                        np.float32(n - 2 * b))
        return agg, stats


@dataclasses.dataclass(frozen=True)
class Median:
    """Coordinate-wise median: rank (n-1)//2 / n//2 midpoint — exact on
    both planes (one add and one halving; no reduction order at all)."""

    def aggregate_host(self, flat: np.ndarray
                       ) -> Tuple[np.ndarray, DefenseStats]:
        n = flat.shape[0]
        xs = np.sort(flat, axis=0)
        agg = (xs[(n - 1) // 2] + xs[n // 2]) * np.float32(0.5)
        return agg, DefenseStats(n_rejected=n - 2 + (n % 2))

    def aggregate_batched(self, flat: jnp.ndarray, n: int, kernel=None
                          ) -> Tuple[jnp.ndarray, DefenseStats]:
        stats = DefenseStats(n_rejected=n - 2 + (n % 2))
        if _use_kernel(kernel):
            from repro.kernels import ops
            return ops.robust_aggregate(flat, n, mode="median"), stats
        xs = _sorted_rows(flat, n)
        agg = (xs[(n - 1) // 2] + xs[n // 2]) * np.float32(0.5)
        return agg, stats


def _mask_rows(flat: jnp.ndarray, n: int) -> jnp.ndarray:
    """+inf-sentinel the padding rows so sorts push them past rank n-1."""
    if flat.shape[0] == n:
        return flat
    row = jnp.arange(flat.shape[0])[:, None]
    return jnp.where(row < n, flat, jnp.inf)


def _sorted_rows(flat: jnp.ndarray, n: int, via: Optional[str] = None):
    """Ascending per-coordinate sort of the padded stack (+inf sentinel
    rows last). ``via`` — "numpy" | "jax" | None (backend default):
    XLA CPU's wide-matrix sort loses ~10x to numpy's (the measurement
    behind the control plane's hybrid layout, DESIGN.md §6), so the cpu
    backend stages the sort through a host copy — the sorted VALUES are
    identical either way, so the parity contract is untouched; real
    accelerators keep the device sort (or the Pallas kernel route).
    """
    masked = _mask_rows(flat, n)
    if via is None:
        via = "numpy" if jax.default_backend() == "cpu" else "jax"
    if via == "numpy":
        return np.sort(np.asarray(masked), axis=0)
    return jnp.sort(masked, axis=0)


def _use_kernel(kernel: Optional[bool]) -> bool:
    if kernel is None:
        from repro.kernels import ops
        return ops.use_pallas()
    return bool(kernel)


@dataclasses.dataclass(frozen=True)
class NormClip:
    """Update-norm clipping: Delta_k = Omega_k − g is shrunk to L2 norm
    <= tau (norms in float64 on both planes, the scale quantized to
    float32 so the elementwise clip is bit-identical), then the clipped
    uploads go through the usual size-weighted FedAvg."""
    tau: float = 1.0

    def __post_init__(self):
        assert self.tau > 0, self.tau

    def scales_host(self, flat: np.ndarray, g: np.ndarray) -> np.ndarray:
        delta = flat - g[None]
        n2 = np.sum(delta.astype(np.float64) ** 2, axis=1)
        return np.minimum(
            1.0, self.tau / np.maximum(np.sqrt(n2), 1e-12)
        ).astype(np.float32)

    def clip_host(self, flat: np.ndarray, g: np.ndarray
                  ) -> Tuple[np.ndarray, DefenseStats]:
        s = self.scales_host(flat, g)
        clipped = g[None] + s[:, None] * (flat - g[None])
        return clipped, DefenseStats(n_clipped=int((s < 1.0).sum()))

    def clip_batched(self, flat: jnp.ndarray, g: jnp.ndarray, n: int
                     ) -> Tuple[jnp.ndarray, DefenseStats]:
        delta = flat - g[None]
        with jax.enable_x64(True):
            n2 = jnp.sum(delta.astype(jnp.float64) ** 2, axis=1)
            s64 = jnp.minimum(1.0,
                              self.tau / jnp.maximum(jnp.sqrt(n2), 1e-12))
        s = s64.astype(jnp.float32)
        clipped = g[None] + s[:, None] * delta
        n_clipped = int((np.asarray(s)[:n] < 1.0).sum())
        return clipped, DefenseStats(n_clipped=n_clipped)


@dataclasses.dataclass(frozen=True)
class Krum:
    """Krum / multi-Krum distance filter [Blanchard et al., 2017]: each
    upload is scored by the summed squared distance to its n−f−2 nearest
    neighbours; the ``n_select`` lowest-score uploads survive and go
    through the usual size-weighted FedAvg. Distances/scores run in
    float64 on both planes (documented-ulp residue; a selection flip
    needs a measure-zero score tie). Degrades to plain FedAvg (nothing
    rejected) when the cohort is too small for the bound (n < f + 3).
    """
    n_select: Optional[int] = None    # None -> n - f (multi-Krum)
    f: Optional[int] = None           # assumed Byzantine count;
    #                                   None -> the server's cfg.n_malicious

    def _resolve(self, n: int, n_byz: int) -> Tuple[int, int]:
        f = self.f if self.f is not None else n_byz
        m = self.n_select if self.n_select is not None else max(n - f, 1)
        return f, min(max(m, 1), n)

    def select_host(self, flat: np.ndarray, n_byz: int) -> np.ndarray:
        """(n, P) -> sorted indices of the selected uploads. Pairwise
        squared distances via the float64 gram matrix (one BLAS gemm
        instead of an O(n) loop of (n, P) temporaries)."""
        n = flat.shape[0]
        f, m = self._resolve(n, n_byz)
        if n - f - 2 < 1:
            return np.arange(n)
        X = flat.astype(np.float64)
        sq = np.einsum("ij,ij->i", X, X)
        d2 = np.maximum(sq[:, None] + sq[None, :] - 2.0 * (X @ X.T), 0.0)
        np.fill_diagonal(d2, 0.0)             # exact self term
        ds = np.sort(d2, axis=1)              # ds[:, 0] is the self term
        scores = ds[:, 1:n - f - 1].sum(axis=1)
        return np.sort(np.argsort(scores, kind="stable")[:m])

    def select_batched(self, flat: jnp.ndarray, n: int,
                       n_byz: int) -> np.ndarray:
        """Padded (N_pad, P) twin — scores only the n real rows; returns
        the same sorted index array as the host oracle (float64 gram
        formulation on both planes; gemm-implementation ulps could flip
        a selection only on a measure-zero score tie)."""
        f, m = self._resolve(n, n_byz)
        if n - f - 2 < 1:
            return np.arange(n)
        with jax.enable_x64(True):
            X = flat[:n].astype(jnp.float64)
            sq = jnp.einsum("ij,ij->i", X, X)
            d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (X @ X.T),
                             0.0)
            d2 = d2.at[jnp.arange(n), jnp.arange(n)].set(0.0)
            ds = jnp.sort(d2, axis=1)
            scores = np.asarray(ds[:, 1:n - f - 1].sum(axis=1))
        return np.sort(np.argsort(scores, kind="stable")[:m])


RobustAggregator = Union[TrimmedMean, Median, NormClip, Krum]


# ---------------------------------------------------------------------- #
# Aggregation entry points (the two engines route through these)
# ---------------------------------------------------------------------- #
def aggregate_host(agg: RobustAggregator, params_list: List,
                   weights: np.ndarray, global_params, n_byz: int):
    """Host oracle over a compressed list of uploaded pytrees — the
    ``engine="loop"`` defense path. Returns (new global params, stats).

    The final combine of the filtering/clipping aggregators reuses the
    stock ``fedavg`` (lazy import — federated imports core), so the
    defended combine inherits the float64-normalise / float32-accumulate
    contract the engines are already pinned on.
    """
    from repro.federated.aggregation import fedavg
    weights = np.asarray(weights, float)
    if isinstance(agg, (TrimmedMean, Median)):
        flat = np.stack([flatten_params_np(p) for p in params_list])
        vec, stats = agg.aggregate_host(flat)
        return unflatten_vec(global_params, vec), stats
    if isinstance(agg, NormClip):
        flat = np.stack([flatten_params_np(p) for p in params_list])
        clipped, stats = agg.clip_host(flat,
                                       flatten_params_np(global_params))
        rows = [unflatten_vec(global_params, clipped[i])
                for i in range(clipped.shape[0])]
        return fedavg(rows, weights), stats
    assert isinstance(agg, Krum), agg
    flat = np.stack([flatten_params_np(p) for p in params_list])
    sel = agg.select_host(flat, n_byz)
    stats = DefenseStats(n_rejected=len(params_list) - sel.size)
    return fedavg([params_list[i] for i in sel], weights[sel]), stats


def aggregate_stacked(agg: RobustAggregator, stacked, weights: np.ndarray,
                      global_params, n: int, n_byz: int, kernel=None):
    """Batched twin over the padded stacked cohort (leaves (N_pad, ...),
    real rows first, padding weight 0) — the vectorized engine's defense
    path. Returns (new global params, stats)."""
    from repro.federated.aggregation import fedavg_stacked
    weights = np.asarray(weights, float)
    if isinstance(agg, (TrimmedMean, Median)):
        vec, stats = agg.aggregate_batched(flatten_stacked(stacked), n,
                                           kernel=kernel)
        return unflatten_vec(global_params, vec), stats
    if isinstance(agg, NormClip):
        flat = flatten_stacked(stacked)
        clipped, stats = agg.clip_batched(
            flat, jnp.asarray(flatten_params_np(global_params)), n)
        return fedavg_stacked(unflatten_stacked(stacked, clipped),
                              weights), stats
    assert isinstance(agg, Krum), agg
    sel = agg.select_batched(flatten_stacked(stacked), n, n_byz)
    stats = DefenseStats(n_rejected=n - sel.size)
    w = np.zeros_like(weights)
    w[sel] = weights[sel]
    return fedavg_stacked(stacked, w), stats


# ---------------------------------------------------------------------- #
# Validation detector (the unreliable-data family, arXiv:2102.09491)
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ValidationDetector:
    """Server-side validation pass over the uploaded models: every
    scheduled UE's upload is scored on a held-out validation split — the
    first ``n_val`` rows of the server's public test set, clamped to the
    set size, restricted to the classes the UE claims to hold (the same
    masking argument as Eq. 1's ``acc_test``, DESIGN.md §2: unmasked, an
    honest non-IID UE is indistinguishable from a noise UE) — alongside
    the start-of-round GLOBAL model on the same per-UE masks, in one
    extra vmapped ``cohort_eval``. The anomaly score is the upload's
    degradation of its own claimed classes relative to the global model:

        a_k = max(0, v_global,k − v_k − tol)

    (the per-UE global baseline also cancels the class-count bias a raw
    accuracy level carries: a single-class UE scores ~1 on its own mask
    whatever it uploads). ``weight * a_k`` enters Eq. 1 as a trust
    penalty (an extra subtracted term inside the same clip), so it flows
    into the Eq. 3 value the scheduler ranks. This is what closes the
    feature-noise reward hole: Eq. 1 only ever compares a UE's *report*
    against measurements, and a noise UE's honestly-low report keeps
    those gaps small — the detector instead reads the measured quality of
    the upload itself: local training on clean data improves (or holds)
    the UE's own classes, while fitting noise-corrupted features drags
    them below the global baseline, no matter what the UE reports. Flags
    (a_k > 0) are metrics-only; ground truth never feeds back.
    """
    # defaults tuned on the §V-scale feature-noise matrix
    # (examples/robustness_extensions.py, DESIGN.md §9): tol=0.1 keeps
    # honest skewed UEs out of the flag set once the global model is
    # trained; weight=5.0 makes one confident detection decisive (a
    # flagged noise UE's anomaly ~0.2 wipes its reputation) so malicious
    # UEs that are only scheduled a few times still end below honest
    n_val: int = 1000
    tol: float = 0.1
    weight: float = 5.0

    def __post_init__(self):
        assert self.n_val >= 1 and self.tol >= 0 and self.weight >= 0

    def anomaly(self, acc_val: np.ndarray) -> np.ndarray:
        """acc_val (2, n): row 0 = per-upload masked validation accuracy,
        row 1 = the global model's accuracy on the same masks."""
        v, g = np.asarray(acc_val, float)
        return np.maximum(g - v - self.tol, 0.0)

    def penalties(self, acc_val: np.ndarray) -> np.ndarray:
        return self.weight * self.anomaly(acc_val)


def detection_stats(flags: np.ndarray, truth: np.ndarray) -> Tuple[float,
                                                                   float]:
    """(precision, recall) of the flagged set against the ground-truth
    malicious mask over the round's cohort (NaN when undefined)."""
    flags = np.asarray(flags, bool)
    truth = np.asarray(truth, bool)
    tp = float((flags & truth).sum())
    prec = tp / flags.sum() if flags.any() else float("nan")
    rec = tp / truth.sum() if truth.any() else float("nan")
    return prec, rec


# ---------------------------------------------------------------------- #
# DefensePolicy: the composite defense + registry
# ---------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class DefensePolicy:
    """A named defense: robust aggregator + validation detector. Either
    may be None; all-None is the undefended control (``"none"``)."""
    name: str
    aggregator: Optional[RobustAggregator] = None
    detector: Optional[ValidationDetector] = None

    @property
    def benign(self) -> bool:
        return self.aggregator is None and self.detector is None


DEFENSES: Dict[str, DefensePolicy] = {}


def register(defense: DefensePolicy) -> DefensePolicy:
    assert defense.name not in DEFENSES, \
        f"defense {defense.name!r} already registered"
    DEFENSES[defense.name] = defense
    return defense


def trimmed_mean(trim: float = 0.2,
                 name: Optional[str] = None) -> DefensePolicy:
    name = name or ("trimmed_mean" if trim == 0.2
                    else f"trimmed_mean_{int(round(trim * 100))}")
    return DefensePolicy(name, aggregator=TrimmedMean(trim))


def median(name: Optional[str] = None) -> DefensePolicy:
    return DefensePolicy(name or "median", aggregator=Median())


def norm_clip(tau: float = 1.0,
              name: Optional[str] = None) -> DefensePolicy:
    name = name or ("norm_clip" if tau == 1.0 else f"norm_clip_{tau:g}")
    return DefensePolicy(name, aggregator=NormClip(tau))


def krum(n_select: Optional[int] = None, f: Optional[int] = None,
         name: Optional[str] = None) -> DefensePolicy:
    return DefensePolicy(name or "krum", aggregator=Krum(n_select, f))


def validation(n_val: int = 1000, tol: float = 0.1, weight: float = 5.0,
               name: Optional[str] = None) -> DefensePolicy:
    return DefensePolicy(name or "validation",
                         detector=ValidationDetector(n_val, tol, weight))


def with_validation(base: DefensePolicy,
                    det: Optional[ValidationDetector] = None,
                    name: Optional[str] = None) -> DefensePolicy:
    """Compose a detector onto an aggregator-only defense."""
    return dataclasses.replace(
        base, name=name or f"{base.name}+validation",
        detector=det or ValidationDetector())


NO_DEFENSE = register(DefensePolicy("none"))
register(trimmed_mean(0.2))
register(median())
register(norm_clip(1.0))
register(krum())
register(validation())
register(with_validation(trimmed_mean(0.2)))


def as_defense(spec) -> DefensePolicy:
    """Coerce a defense spec: DefensePolicy passes through, str looks up
    the registry, None is the undefended control."""
    if spec is None:
        return NO_DEFENSE
    if isinstance(spec, DefensePolicy):
        return spec
    if isinstance(spec, str):
        return DEFENSES[spec]
    raise TypeError(f"not a defense policy spec: {spec!r}")
