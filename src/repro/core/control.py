"""Batched control plane (paper §III-IV, all runs at once).

The paper's per-round control loop — channel draw -> Eq. 9 bandwidth costs
-> Eq. 2/3 data-quality values -> Algorithm 2 selection -> Eq. 1 reputation
update — was sequential per-server numpy: at sweep scale (policies x seeds x
attack pairs) the *scheduler*, not training, became the serial bottleneck,
and Eq. 9's dense (K, K) rate matrix capped the UE count. Here the control
state of all R runs lives in a ``ControlState`` struct-of-arrays with a
leading run axis, and round t of every run is scheduled together:

    schedule_runs — values (Eq. 2/3) -> costs (Eq. 9 monotone bisection,
        O(K log K)) -> per-policy priority key -> shared greedy packing
        -> dqs modified-greedy fallback / top-value override ->
        forced-round rewrite. One batched pass, no per-run Python.
    finalize_runs — Eq. 1 reputation update + staleness ages of every run
        in one call (reputation.reputation_update_eq1).

Two kernel layouts compute the identical schedule (tests/test_control.py
pins them equal):

    "jax"    — ONE jitted vmapped kernel (``_schedule_kernel``): the whole
        phase is a single XLA program. The right layout for accelerator
        backends, and the reference composition of the pure per-equation
        functions (wireless.cost_bisect, scheduler.greedy_pack_jnp, ...).
    "hybrid" — CPU default. XLA CPU's float64 sort is ~5x slower than
        numpy's and its elementwise math has per-op dispatch cost, while
        numpy cannot express the sequential budget-carrying pack at all
        and loses ~3x to XLA on the log2-heavy Eq. 9 probes. So the
        elementwise math and the stable argsort run as *batched numpy*
        (the same float64 ops as the host oracle, over the (R, K) block)
        and two small jitted kernels do what numpy cannot: the Eq. 9
        bisection and the lax.scan greedy pack. Still zero per-run Python.

Randomness stays on the host: each run draws its K channel gains (and, for
the ``random`` policy, its permutation) from its own numpy Generator — the
exact streams of the sequential oracle — and the kernels are deterministic
functions of those draws. Everything runs in float64 (``enable_x64``) with
the same operation order as the numpy oracle. Parity contract
(tests/test_control.py): the hybrid layout reproduces the host oracle
bit-for-bit on every output — values, keys, pack sums, Eq. 1 updates all
use the oracle's own float64 expressions and summation order; the one
theoretical residue is Eq. 9's jitted bisection, where XLA's log2 may
differ from libm's by an ulp and could flip an integer cost only on a
measure-zero comparison boundary (never observed; pinned exact on random
instances). The jax layout matches the integer outputs (selection, costs,
forced) bit-for-bit and the float outputs to ~1 ulp — XLA contracts
``a*b + c`` into FMAs and strength-reduces the divide-by-constant in
alpha, so its last bit can differ from numpy's. That holds for
``schedule_runs``; the population prefilter's jax layout
(``core.population``) builds alpha on the host with the hybrid path's
float64 division, so its alpha is exact except in rows it escalates
here.

The per-run path survives as ``FeelServer(..., control="host")`` — the
bit-parity oracle, mirroring the ``engine="loop"`` pattern of the data
plane.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FeelConfig
from repro.core.diversity import diversity_index_eq2, diversity_index_rows
from repro.core.quality import data_quality_value
from repro.core.reputation import reputation_update_eq1
from repro.core.scheduler import (POLICY_IDS, greedy_pack_jnp, pack_scan,
                                  priority_key)
from repro.core.wireless import cost_bisect
from repro.obs import trace


@dataclasses.dataclass
class ControlState:
    """Struct-of-arrays control-plane state for R runs over K UEs each.

    Static per-run fields (sizes, element diversities, Eq. 5-7 minimum
    rates, policy ids) are stacked once; the mutable fields (reputations,
    ages) are synced from/to the owning ``FeelServer`` objects around each
    round (``pull`` / ``push``) so the servers' logs and summaries keep
    reading their usual attributes.

    The trailing axis is the candidate width: K in the legacy regime,
    N = cfg.n_population under a population cut (DESIGN.md §12) — every
    kernel takes the width from the arrays and the bandwidth budget from
    ``cfg.n_ues``.
    """
    policy_id: np.ndarray     # (R,)  int32, scheduler.POLICY_IDS
    sizes: np.ndarray         # (R, K) float64 true dataset sizes
    divs: np.ndarray          # (R, K) element (Gini-Simpson) diversities
    r_min: np.ndarray         # (R, K) Eq. 9 min rates (round-invariant)
    reputations: np.ndarray   # (R, K) Eq. 1 state
    ages: np.ndarray          # (R, K) rounds since last selected
    cfg: FeelConfig           # shared scalars (asserted identical per run)

    @property
    def n_runs(self) -> int:
        return self.policy_id.shape[0]

    @classmethod
    def from_servers(cls, servers: Sequence) -> "ControlState":
        cfg = servers[0].cfg
        # the control plane never touches the data/model plane, so configs
        # differing ONLY in ``task`` are compatible (each FeelServer builds
        # its own one-run state; the pool drivers stack runs directly)
        assert all(dataclasses.replace(s.cfg, task=cfg.task) == cfg
                   for s in servers), \
            "batched control requires one shared FeelConfig across runs " \
            "(modulo the task field)"
        r_min = np.stack([
            s.wireless.min_rate(s.wireless.train_time(s.sizes, s.cpu_hz))
            for s in servers])
        return cls(
            policy_id=np.array([POLICY_IDS[s.policy] for s in servers],
                               np.int32),
            sizes=np.stack([s.sizes for s in servers]).astype(float),
            divs=np.stack([s.divs for s in servers]).astype(float),
            r_min=r_min,
            reputations=np.stack([s.reputation.values for s in servers]),
            ages=np.stack([s.ages for s in servers]),
            cfg=cfg)

    def pull(self, servers: Sequence) -> None:
        """Refresh the mutable rows from the servers (before a round)."""
        for i, s in enumerate(servers):
            self.reputations[i] = s.reputation.values
            self.ages[i] = s.ages

    def push(self, servers: Sequence) -> None:
        """Write the mutable rows back to the servers (after finalize)."""
        for i, s in enumerate(servers):
            s.reputation.values[:] = self.reputations[i]
            s.ages[:] = self.ages[i]


# ---------------------------------------------------------------------- #
# "jax" layout: the whole schedule phase as ONE jitted vmapped kernel
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("k", "n_sel"))
def _schedule_kernel(policy_id, rep, ages, divs, sizes, r_min, gains,
                     rand_rank, w_rep, w_div, gamma, bandwidth_hz, p_watt,
                     n0, *, k: int, n_sel: int):
    """One round of every run: (R, K) arrays in, (x, alpha, costs, values,
    forced) out. vmapped over the run axis; float64 under enable_x64."""

    def one(pid, rep, ages, divs, sizes, r_min, gains, rand_rank,
            w_rep, w_div):
        # Eq. 2/3 — data-quality values
        I = diversity_index_eq2(divs, sizes, ages, gamma)
        values = data_quality_value(rep, I, None, omega=(w_rep, w_div))
        # Eq. 9 — bandwidth costs by monotone bisection
        costs = cost_bisect(gains, r_min, k, bandwidth_hz, p_watt, n0)
        costs_f = costs.astype(values.dtype)
        # priority keys — the ONE definition in scheduler.priority_key;
        # the ascending stable argsort of each reproduces the host
        # policy's visit order
        key = jnp.where(
            pid == 0, priority_key("dqs", values, costs_f, k),
            jnp.where(pid == 1, rand_rank.astype(values.dtype),
                      jnp.where(pid == 2,
                                priority_key("best_channel", values,
                                             costs_f, k, gains=gains),
                                costs_f)))
        x, alpha = greedy_pack_jnp(key, costs, k)

        # dqs modified-greedy fallback: best single feasible UE vs the pack
        feas = costs <= k
        masked = jnp.where(feas, values, -jnp.inf)
        k_best = jnp.argmax(masked)
        use_fb = ((pid == 0) & feas.any()
                  & (masked[k_best] > (values * x).sum()))
        onehot_best = jnp.zeros_like(x).at[k_best].set(True)
        x = jnp.where(use_fb, onehot_best, x)
        alpha = jnp.where(use_fb,
                          jnp.where(onehot_best, costs_f / k, 0.0), alpha)

        # top_value override: top-n by value, no wireless constraint
        rank = jnp.argsort(jnp.argsort(-values, stable=True), stable=True)
        x = jnp.where(pid == 4, rank < n_sel, x)
        alpha = jnp.where(pid == 4,
                          jnp.where(rank < n_sel, 1.0 / max(n_sel, 1), 0.0),
                          alpha)

        # degenerate round: no UE met the deadline — force the single
        # highest-value UE (whole band); problem (8) was infeasible, the
        # caller logs objective 0.0 (DESIGN.md §2)
        forced = ~x.any()
        onehot_f = jnp.zeros_like(x).at[jnp.argmax(values)].set(True)
        x = jnp.where(forced, onehot_f, x)
        alpha = jnp.where(forced, jnp.where(onehot_f, 1.0, 0.0), alpha)
        return x, alpha, costs, values, forced

    return jax.vmap(one)(policy_id, rep, ages, divs, sizes, r_min, gains,
                         rand_rank, w_rep, w_div)


# ---------------------------------------------------------------------- #
# "hybrid" layout: batched numpy + the two kernels numpy cannot express
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("k",))
def _cost_kernel(gains, r_min, bandwidth_hz, p_watt, n0, *, k: int):
    return cost_bisect(gains, r_min, k, bandwidth_hz, p_watt, n0)


_pack_kernel = functools.partial(jax.jit, static_argnames=("k",))(pack_scan)


def _schedule_hybrid(state: ControlState, gains, rand_rank, w_rep, w_div):
    cfg = state.cfg
    K = cfg.n_ues                        # bandwidth budget (fractions)
    R = state.n_runs
    N = state.reputations.shape[1]       # candidate width (N == K legacy)
    pid = state.policy_id

    # Eq. 2/3 — batched numpy, same float64 ops as the host oracle
    I = diversity_index_rows(state.divs, state.sizes, state.ages,
                             cfg.gamma)
    values = data_quality_value(state.reputations, I, cfg,
                                omega=(w_rep[:, None], w_div[:, None]))

    # Eq. 9 — jitted bisection (XLA's f64 log2 beats numpy's ~3x here)
    with jax.enable_x64(True):
        costs = np.asarray(_cost_kernel(
            gains, state.r_min, cfg.bandwidth_hz, cfg.p_watt,
            cfg.n0_watt_hz, k=K)).astype(int)
    costs_f = costs.astype(float)

    # priority keys — the ONE definition in scheduler.priority_key
    keys = np.empty((R, N))
    m = pid == 0
    keys[m] = priority_key("dqs", values[m], costs_f[m], K)
    m = pid == 1
    keys[m] = rand_rank[m]
    m = pid == 2
    keys[m] = priority_key("best_channel", values[m], costs_f[m], K,
                           gains=gains[m])
    m = (pid == 3) | (pid == 4)          # top_value rows: key unused
    keys[m] = costs_f[m]

    # shared greedy pack: numpy stable sort + the scan kernel
    order = np.argsort(keys, axis=-1, kind="stable")
    c_sorted = np.take_along_axis(costs, order, -1).astype(np.int32)
    take = np.asarray(_pack_kernel(c_sorted, k=K))
    x = np.zeros((R, N), bool)
    np.put_along_axis(x, order, take, -1)
    alpha = np.where(x, costs_f / K, 0.0)

    # dqs modified-greedy fallback. The pack-value side of the comparison
    # sums the COMPRESSED selection exactly like the host oracle
    # (values[x].sum()) — a full-K masked sum groups numpy's pairwise
    # summation differently and could flip the '>' on a ~1-ulp tie,
    # silently breaking host parity.
    feas = costs <= K
    masked = np.where(feas, values, -np.inf)
    k_best = masked.argmax(-1)
    rows = np.arange(R)
    pack_val = np.array([values[i][x[i]].sum() if pid[i] == 0 else 0.0
                         for i in range(R)])
    use_fb = ((pid == 0) & feas.any(-1)
              & (masked[rows, k_best] > pack_val))
    fb = np.flatnonzero(use_fb)
    x[fb] = False
    x[fb, k_best[fb]] = True
    alpha[fb] = 0.0
    alpha[fb, k_best[fb]] = costs_f[fb, k_best[fb]] / K

    # top_value override
    tv = np.flatnonzero(pid == 4)
    if tv.size:
        n = cfg.min_selected
        top = np.argsort(-values[tv], axis=-1, kind="stable")[:, :n]
        xt = np.zeros((tv.size, N), bool)
        np.put_along_axis(xt, top, True, -1)
        x[tv] = xt
        alpha[tv] = np.where(xt, 1.0 / max(n, 1), 0.0)

    # degenerate rounds: force the single highest-value UE
    forced = ~x.any(-1)
    fr = np.flatnonzero(forced)
    kf = values[fr].argmax(-1)
    x[fr] = False
    x[fr, kf] = True
    alpha[fr] = 0.0
    alpha[fr, kf] = 1.0
    return x, alpha, costs, values, forced


# ---------------------------------------------------------------------- #
# Host entry points
# ---------------------------------------------------------------------- #
@functools.lru_cache(maxsize=1)
def default_kernel() -> str:
    """Backend default, resolved lazily on first use — probing
    jax.default_backend() at import time would eagerly initialize XLA for
    every ``import repro.core`` and lock the platform choice.

    Single-device CPU keeps "hybrid" (numpy's sort + elementwise beat
    XLA CPU there, module docstring); accelerators and *multi-device
    meshes* default to "jax" — the hybrid layout is host-numpy and
    cannot shard, while the jitted kernel GSPMD-partitions the UE axis
    across the mesh and wins from the first extra device (re-benched on
    the forced-multi-device host mesh in results/BENCH_population.json;
    crossover recorded in DESIGN.md §12)."""
    if jax.default_backend() != "cpu":
        return "jax"
    return "jax" if jax.local_device_count() > 1 else "hybrid"


def schedule_runs(state: ControlState, gains: np.ndarray,
                  rand_rank: np.ndarray, w_rep: np.ndarray,
                  w_div: np.ndarray, kernel: Optional[str] = None):
    """Schedule round t of all R runs in one batched pass.

    gains — (R, K) per-run channel draws (host RNG, oracle streams);
    rand_rank — (R, K) inverse permutations for ``random``-policy rows
    (ignored elsewhere); w_rep / w_div — (R,) Eq. 3 weights (annealed per
    round under adaptive omega); kernel — "jax" | "hybrid" (None = the
    backend default, see module docstring; both produce the identical
    schedule). Returns numpy (x bool, alpha, costs int, values, forced).
    """
    gains = np.asarray(gains, float)
    rand_rank = np.asarray(rand_rank)
    w_rep = np.asarray(w_rep, float)
    w_div = np.asarray(w_div, float)
    kern = kernel or default_kernel()
    with trace.span("schedule.pack") as sp:
        if trace.enabled():
            sp.set(kernel=kern, runs=int(state.n_runs),
                   width=int(state.reputations.shape[1]))
        if kern == "hybrid":
            return _schedule_hybrid(state, gains, rand_rank, w_rep, w_div)
        cfg = state.cfg
        with jax.enable_x64(True):
            x, alpha, costs, values, forced = _schedule_kernel(
                state.policy_id, state.reputations, state.ages, state.divs,
                state.sizes, state.r_min, gains, rand_rank, w_rep, w_div,
                np.asarray(cfg.gamma, float), cfg.bandwidth_hz, cfg.p_watt,
                cfg.n0_watt_hz, k=cfg.n_ues, n_sel=cfg.min_selected)
        return (np.asarray(x), np.asarray(alpha),
                np.asarray(costs).astype(int), np.asarray(values),
                np.asarray(forced))


@jax.jit
def _finalize_kernel(rep, ages, sel_mask, acc_local, acc_test, pen,
                     eta, beta1, beta2):
    """Eq. 1 (+ defense trust penalty) + staleness for every run."""
    rep = reputation_update_eq1(rep, sel_mask, acc_local, acc_test,
                                eta, beta1, beta2, penalty=pen)
    ages = jnp.where(sel_mask > 0, 1.0, ages + 1.0)
    return rep, ages


def finalize_runs(state: ControlState, sels: List[np.ndarray],
                  acc_locals: List[np.ndarray],
                  acc_tests: List[np.ndarray],
                  penalties: Optional[List] = None,
                  kernel: Optional[str] = None) -> None:
    """Eq. 1 reputation + staleness of all R runs in one call, written back
    into ``state`` (callers then ``push`` to the servers).

    ``penalties`` — optional per-run defense trust penalties (aligned with
    ``sels``; entries may be None): the validation detector's extra
    subtracted Eq. 1 term (core/defenses.py, DESIGN.md §9).

    The hybrid layout applies Eq. 1 as batched numpy with the cohort
    average computed exactly like the host tracker (np.mean over the
    compressed cohort) — bit-for-bit against ReputationTracker.update.
    The jax layout routes through the jitted kernel (accelerator path;
    ~1 ulp from FMA contraction).
    """
    cfg = state.cfg
    R, K = state.reputations.shape
    with trace.span("schedule.finalize") as sp:
        if trace.enabled():
            sp.set(runs=int(R), width=int(K))
        mask = np.zeros((R, K))
        al = np.zeros((R, K))
        at = np.zeros((R, K))
        pen = np.zeros((R, K))
        for i, (sel, a, t) in enumerate(zip(sels, acc_locals, acc_tests)):
            mask[i, sel] = 1.0
            al[i, sel] = a
            at[i, sel] = t
            if penalties is not None and penalties[i] is not None:
                pen[i, sel] = penalties[i]
        if (kernel or default_kernel()) == "hybrid":
            # cohort average computed exactly like the host tracker (np.mean
            # over the compressed cohort, not a full-K masked sum)
            avg = np.array([[np.mean(a) if len(a) else 0.0]
                            for a in acc_locals])
            delta = cfg.eta * (cfg.beta1 * (al - avg)
                               + cfg.beta2 * (al - at)) + pen
            new = np.clip(state.reputations - delta, 0.0, 1.0)
            state.reputations = np.where(mask > 0, new, state.reputations)
            state.ages = np.where(mask > 0, 1.0, state.ages + 1.0)
            return
        with jax.enable_x64(True):
            rep, ages = _finalize_kernel(
                state.reputations, state.ages, mask, al, at, pen,
                cfg.eta, cfg.beta1, cfg.beta2)
        # np.array (not asarray): device outputs give read-only numpy views,
        # and these buffers are written in-place by the next round's pull()
        state.reputations = np.array(rep)
        state.ages = np.array(ages)


def staleness_discount(ages: np.ndarray, decay: float) -> np.ndarray:
    """Staleness discount d(a) = decay**a for the async engine (DESIGN.md §13).

    ``ages`` — integer aggregation ages (aggregation_version minus the
    model version the update was computed on), all >= 0. ``decay`` in
    (0, 1] is ``cfg.async_staleness``. Host float64 like the rest of the
    control plane. d(0) == 1.0 *exactly* (any IEEE base to the 0th power),
    so an age-0 upload's weight ``w * d(0)`` is bit-identical to the
    FedAvg weight — the zero-latency parity contract rests on this.
    """
    ages = np.asarray(ages)
    assert 0.0 < decay <= 1.0, f"async_staleness must be in (0, 1]: {decay}"
    assert np.all(ages >= 0), "negative staleness age"
    return np.asarray(decay, np.float64) ** ages.astype(np.float64)
