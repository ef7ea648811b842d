"""Wireless edge model (paper §III-C, Eq. 4-7, 9).

OFDMA uplink from K UEs to one BS at the centre of a square cell. Channel
gain = large-scale pathloss x Rayleigh small-scale fading:
``|g_k|^2 = d_k^-alpha |h_k|^2``. Achievable rate with bandwidth fraction
``a_k`` (Eq. 4):

    r_k = a_k B log2(1 + g_k P_k / (a_k B N0))

Round deadline T bounds ``t_train + t_up`` (Eq. 5); training time follows the
cycles/bit model (Eq. 6); upload time ``t_up = s / r_k`` (Eq. 7). The DQS
bandwidth *cost* c_k (Eq. 9) is the minimum number of uniform 1/K fractions
that meets the UE's minimum rate.

Eq. 9 is solved by monotone bisection: r_k(c/K) is strictly increasing in c
(Eq. 4 is concave increasing in the bandwidth fraction), so the minimal
feasible c is found in O(log K) rate evaluations per UE instead of the
seed's dense (K, K) rate matrix — O(K log K) total, which is what lets the
control plane scale to thousands of UEs. ``cost_scan`` keeps the exhaustive
scan as the test oracle (tests/test_wireless.py pins exact equality,
including the infeasible c = K+1 and blown-deadline t_train >= T edges).

The module also exposes the pure-JAX twins (``rate_eq4``, ``cost_bisect``)
used by the batched control plane (core/control.py): same formulas over
arbitrary leading batch axes, jit/vmap-able, run in float64 (under
``jax.enable_x64(True)``) so they agree with the numpy oracle to
the last integer cost. The Eq. 9 right-hand side (min rates) is
round-invariant, so the control plane precomputes it once per run with
the numpy ``min_rate`` — there is deliberately no jnp twin for it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FeelConfig, dbm_to_watt  # noqa: F401
# dbm_to_watt is defined beside FeelConfig's p_watt/n0_watt_hz properties
# (one conversion shared by both control planes) and re-exported here for
# the historical import path.


@dataclasses.dataclass
class ChannelState:
    """Per-round channel realisation for K UEs."""
    gains: np.ndarray          # |g_k|^2, linear
    distances: np.ndarray      # d_k in metres

    @property
    def k(self) -> int:
        return self.gains.shape[0]


class WirelessModel:
    def __init__(self, cfg: FeelConfig, rng: np.random.Generator):
        self.cfg = cfg
        self.rng = rng
        half = cfg.cell_side_m / 2.0
        # one position per *candidate* (N == K when no population is set);
        # Eq. 9's budget/denominator stays cfg.n_ues in cost()/cost_scan()
        xy = rng.uniform(-half, half, size=(cfg.n_population, 2))
        self.distances = np.maximum(np.linalg.norm(xy, axis=1), 1.0)
        self.p_watt = cfg.p_watt
        self.n0 = cfg.n0_watt_hz     # W/Hz
        # AR(1)/Gauss-Markov fading state (DESIGN.md §13): complex h per
        # candidate, components N(0, 1/2) so |h|^2 is stationary Exp(1).
        # Only touched when cfg.channel_corr > 0 — the rho = 0 path keeps
        # the legacy memoryless exponential draw bit-for-bit.
        self._h: Optional[np.ndarray] = None       # (N, 2) re/im
        self.last_gains: Optional[np.ndarray] = None

    def draw_channels(self) -> ChannelState:
        """Rayleigh |h|^2 ~ Exp(1); gains = d^-alpha |h|^2.

        With ``cfg.channel_corr = rho > 0`` the small-scale component is a
        per-UE Gauss-Markov process ``h_t = rho h_{t-1} + sqrt(1-rho^2) w_t``
        (w complex, components N(0, 1/2)): stationary |h|^2 ~ Exp(1) as in
        the memoryless model, lag-1 correlation of |h|^2 equal to rho^2.
        rho = 0 (the default) draws the exact legacy exponential variate so
        existing goldens pin bit-for-bit.
        """
        rho = self.cfg.channel_corr
        if rho == 0.0:
            h2 = self.rng.exponential(1.0, size=self.distances.shape)
        else:
            w = self.rng.standard_normal(self.distances.shape + (2,)) \
                * np.sqrt(0.5)
            if self._h is None:
                self._h = w
            else:
                self._h = rho * self._h + np.sqrt(1.0 - rho * rho) * w
            h2 = (self._h ** 2).sum(axis=-1)
        gains = self.distances ** (-self.cfg.pathloss_exp) * h2
        self.last_gains = gains
        return ChannelState(gains=gains, distances=self.distances)

    # ------------------------------------------------------------------ #
    # Eq. 4 / 7 / 6
    # ------------------------------------------------------------------ #
    def rate(self, gains: np.ndarray, alpha: np.ndarray) -> np.ndarray:
        """Eq. 4 — vectorised; rate is 0 where alpha == 0."""
        cfg = self.cfg
        alpha = np.asarray(alpha, float)
        with np.errstate(divide="ignore", invalid="ignore"):
            snr = gains * self.p_watt / (alpha * cfg.bandwidth_hz * self.n0)
            r = alpha * cfg.bandwidth_hz * np.log2(1.0 + snr)
        return np.where(alpha > 0, r, 0.0)

    def upload_time(self, gains, alpha) -> np.ndarray:
        r = self.rate(gains, alpha)
        with np.errstate(divide="ignore"):
            return np.where(r > 0, self.cfg.model_size_bits / r, np.inf)

    def train_time(self, dataset_sizes: np.ndarray,
                   cpu_hz: np.ndarray) -> np.ndarray:
        """Eq. 6: t = eps * |D_k| * zeta / f."""
        cfg = self.cfg
        bits = dataset_sizes * cfg.sample_bits
        return cfg.local_epochs * bits * cfg.cycles_per_bit / cpu_hz

    # ------------------------------------------------------------------ #
    # Eq. 9 — bandwidth cost in uniform 1/K fractions
    # ------------------------------------------------------------------ #
    def min_rate(self, train_times: np.ndarray) -> np.ndarray:
        """r_min = s / (T - t_train); inf when the deadline is already blown."""
        slack = self.cfg.deadline_s - train_times
        with np.errstate(divide="ignore"):
            return np.where(slack > 0, self.cfg.model_size_bits / slack, np.inf)

    def cost(self, gains: np.ndarray, train_times: np.ndarray) -> np.ndarray:
        """c_k = min{c in [1,K] : r_k(c/K) >= r_min}; K+1 when infeasible.

        Monotone bisection (see module docstring): rate is strictly
        increasing in c, so binary search over the integers [1, K] finds
        the same minimum the exhaustive scan finds, in O(log K) rate
        evaluations. Infeasibility (including a blown deadline, r_min =
        inf) is decided up front by probing the whole band (c = K).
        """
        K = self.cfg.n_ues
        r_min = self.min_rate(train_times)                      # (K,)
        feasible = self.rate(gains, np.ones_like(gains)) >= r_min
        lo = np.ones(gains.shape, int)
        hi = np.full(gains.shape, K, int)
        while np.any(lo < hi):
            mid = (lo + hi) // 2
            ok = self.rate(gains, mid / K) >= r_min
            lo = np.where(ok, lo, mid + 1)
            hi = np.where(ok, mid, hi)
        return np.where(feasible, lo, K + 1).astype(int)

    def cost_scan(self, gains: np.ndarray,
                  train_times: np.ndarray) -> np.ndarray:
        """Exhaustive Eq. 9 (the seed's dense (K, K) rate matrix) — kept as
        the O(K^2) test oracle for ``cost``."""
        K = self.cfg.n_ues
        r_min = self.min_rate(train_times)                      # (K,)
        cs = np.arange(1, K + 1) / K                            # (K,) fractions
        rates = self.rate(gains[:, None], cs[None, :])          # (K, K)
        feasible = rates >= r_min[:, None]
        c = np.where(feasible.any(1), feasible.argmax(1) + 1, K + 1)
        return c.astype(int)


# ---------------------------------------------------------------------- #
# Pure-JAX twins (batched control plane) — arbitrary leading batch axes.
# ---------------------------------------------------------------------- #
def rate_eq4(gains, alpha, bandwidth_hz, p_watt, n0):
    """Eq. 4 in jnp; 0 where alpha == 0 (the inf/nan the division produces
    there is discarded by the where)."""
    snr = gains * p_watt / (alpha * bandwidth_hz * n0)
    return jnp.where(alpha > 0, alpha * bandwidth_hz * jnp.log2(1.0 + snr),
                     0.0)


def cost_bisect(gains, r_min, k: int, bandwidth_hz, p_watt, n0):
    """Eq. 9 by monotone bisection, jnp, batched: (..., K_ues) -> int32.

    ``k`` (static) is the fraction denominator (cfg.n_ues). The loop runs a
    fixed ceil(log2 k) + 1 iterations — once the bracket collapses the
    extra iterations are no-ops for feasible UEs, and infeasible UEs are
    overridden by the up-front whole-band probe.
    """
    gains = jnp.asarray(gains)

    def ok(c):
        # the fraction in the gains' precision: int32 / int is float32
        # even under enable_x64, which would round Eq. 4's denominator
        # to float32 and flip a cost whose rate lies within ~1e-8 of r_min
        a = c.astype(gains.dtype) / k
        return rate_eq4(gains, a, bandwidth_hz, p_watt, n0) >= r_min

    feasible = ok(jnp.full(gains.shape, k, jnp.int32))
    n_iter = max(1, math.ceil(math.log2(max(k, 2)))) + 1

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        hit = ok(mid)
        return jnp.where(hit, lo, mid + 1), jnp.where(hit, mid, hi)

    lo, hi = jax.lax.fori_loop(
        0, n_iter, body, (jnp.ones(gains.shape, jnp.int32),
                          jnp.full(gains.shape, k, jnp.int32)))
    return jnp.where(feasible, lo, k + 1).astype(jnp.int32)
