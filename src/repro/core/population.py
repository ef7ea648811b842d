"""Population plane: million-UE candidate state + schedule-preserving
top-M prefilter (DESIGN.md §12).

Production FEEL schedules each round's cohort from a persistent
*population* of N candidate devices (10^6+), not from the K-sized
scheduling plane the paper's §V protocol materializes. This module keeps
that population as a struct-of-arrays ``PopulationState`` — O(N) memory,
one row per run — and feeds the existing batched control plane
(``core.control.schedule_runs`` / ``finalize_runs``) two ways:

  exact      — the (R, N) state is materialized as a ``ControlState``
      view and scheduled by the unchanged kernels: O(N log N) stable
      sort + an O(N)-sequential-step budget scan per round. The oracle.
  prefilter  — ``prefilter_schedule_runs``: the per-policy priority key
      (scheduler.priority_key — monotone in the per-UE value for every
      packing policy) is computed over all N candidates, but only the
      first M positions of the *visit order* (``lax.top_k`` of the
      negated key; ties resolve to the lower index, exactly the stable
      argsort prefix) enter the sort + budget walk. Alg. 2's greedy walk
      only ever admits K fractions' worth of UEs, so M ≳ K·headroom
      almost always contains the whole exact selection — and instead of
      trusting "almost always", every round carries a per-instance
      **preservation certificate**:

          B_rem < min{ c_u : u not kept }

      where B_rem is the budget remaining after packing the kept
      prefix. Dropped candidates all follow the kept prefix in visit
      order, the walk's remaining budget is non-increasing, and a UE is
      admitted iff its cost fits the remaining budget — so the
      certificate implies the exact N-wide walk admits no dropped
      candidate and the two selections are *identical* (infeasible
      dropped UEs cost K+1 > K >= B_rem, so the plain min works; an
      empty dropped set passes vacuously). Rows whose certificate fails
      are escalated to the exact path — the prefilter is exact by
      construction, the certificate only decides who pays the O(N log N)
      toll. The dqs modified-greedy fallback and the forced-round
      rewrite compare against *global* O(N) reductions (masked argmax /
      masked sum over all N), so they need no kept-set argument;
      ``top_value`` rows take ``lax.top_k(values, n_sel)`` directly
      (preserved whenever M >= n_sel).

``scatter_finalize`` closes the loop: each round's K-sized results
update the N-wide state sparsely (``reputations[i, sel]`` and a
``last_sel`` round stamp whose difference to t reproduces the dense
ages in exact integers) — bit-for-bit against the dense
``finalize_runs`` (tests/test_population.py).

The population axis shards over a device mesh (``population_mesh`` /
``shard_population``): the previously-dead ``launch.mesh`` +
``sharding.specs`` provide the mesh and the NamedSharding placement, and
the jitted prefilter kernel runs GSPMD-sharded over the ``data`` axes —
elementwise Eq. 2/3/9 math and the top-M cut are population-parallel,
only the M-sized tail is sequential. ``bench_round --population``
measures both paths at N ∈ {10^4, 10^5, 10^6}
(results/BENCH_population.json), asserting prefilter == exact per cell.

Channel state is per-UE and N-wide but lives in ``core.wireless``
(``WirelessModel`` already spans the full candidate population): with
``cfg.channel_corr`` > 0 each candidate carries a persistent AR(1)
block-fading state across rounds (DESIGN.md §13) instead of the legacy
memoryless per-round redraw — closing the PR 8 follow-up that channel
statistics had no temporal state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.configs.base import FeelConfig
from repro.core import control as ctl
from repro.core.diversity import diversity_index_eq2, diversity_index_rows
from repro.core.quality import data_quality_value
from repro.core.scheduler import POLICY_IDS, pack_scan, priority_key
from repro.core.wireless import WirelessModel, cost_bisect
from repro.launch.mesh import make_host_mesh
from repro.obs import trace
from repro.sharding.specs import data_axes, named

# Default M = PREFILTER_HEADROOM * K candidates survive the top-M cut.
# The greedy walk admits at most K UEs (every cost >= 1 fraction), so K
# of headroom covers the selection itself and the rest buys certificate
# slack: the walk usually drives B_rem to 0 (plenty of cost-1 feasible
# candidates near the top of the key order), making the certificate
# pass outright. Escalation keeps any choice of M exact.
PREFILTER_HEADROOM = 8


def default_m(cfg: FeelConfig) -> int:
    return min(cfg.n_population, PREFILTER_HEADROOM * cfg.n_ues)


@dataclasses.dataclass
class PopulationState:
    """Struct-of-arrays population-plane state: R runs x N candidates.

    The mutable per-candidate fields are ``reputations`` and
    ``last_sel`` (round of last selection, -1 = never): the dense ages
    the control kernels consume are the exact integer difference
    ``t - last_sel`` (init 1.0, +1 per round, reset to 1 on selection —
    same trajectory, no O(N) per-round age sweep). Everything else is
    round-invariant and shared with the ControlState view.
    """
    policy_id: np.ndarray     # (R,)  int32, scheduler.POLICY_IDS
    sizes: np.ndarray         # (R, N) float64 true dataset sizes
    divs: np.ndarray          # (R, N) element (Gini-Simpson) diversities
    r_min: np.ndarray         # (R, N) Eq. 9 min rates (round-invariant)
    reputations: np.ndarray   # (R, N) Eq. 1 state
    last_sel: np.ndarray      # (R, N) int64 round of last selection, -1
    cfg: FeelConfig

    @property
    def n_runs(self) -> int:
        return self.policy_id.shape[0]

    @property
    def n_population(self) -> int:
        return self.reputations.shape[1]

    def ages(self, t: int) -> np.ndarray:
        """Dense staleness ages at schedule time of round ``t``."""
        return (t - self.last_sel).astype(float)

    def nbytes(self) -> int:
        return (self.sizes.nbytes + self.divs.nbytes + self.r_min.nbytes
                + self.reputations.nbytes + self.last_sel.nbytes)

    @classmethod
    def from_control(cls, state: ctl.ControlState,
                     t: int = 0) -> "PopulationState":
        """Adopt a dense control state at round ``t`` (ages -> last_sel)."""
        last_sel = (t - np.asarray(state.ages)).astype(np.int64)
        return cls(policy_id=np.asarray(state.policy_id),
                   sizes=np.asarray(state.sizes, float),
                   divs=np.asarray(state.divs, float),
                   r_min=np.asarray(state.r_min, float),
                   reputations=np.array(state.reputations, float),
                   last_sel=last_sel, cfg=state.cfg)

    def control_view(self, t: int) -> ctl.ControlState:
        """ControlState over the SAME buffers (ages materialized for
        round ``t``) — feed it to ``schedule_runs`` / the exact path;
        finalize through ``scatter_finalize``, not ``finalize_runs``."""
        return ctl.ControlState(
            policy_id=self.policy_id, sizes=self.sizes, divs=self.divs,
            r_min=self.r_min, reputations=self.reputations,
            ages=self.ages(t), cfg=self.cfg)


def scatter_finalize(pop: PopulationState, t: int,
                     sels: List[np.ndarray],
                     acc_locals: List[np.ndarray],
                     acc_tests: List[np.ndarray],
                     penalties: Optional[List] = None) -> None:
    """Eq. 1 + staleness from K-sized round results, scattered into the
    N-wide state — O(R*K) writes, no O(N) sweep.

    Bit-for-bit against the dense ``finalize_runs`` hybrid path: the
    cohort average is ``np.mean`` over the compressed cohort and the
    delta/clip expressions are the same float64 ops in the same order;
    ages agree exactly because ``t - last_sel`` is integer arithmetic.
    """
    cfg = pop.cfg
    for i, (sel, a, te) in enumerate(zip(sels, acc_locals, acc_tests)):
        sel = np.asarray(sel, int)
        if sel.size == 0:
            continue
        a = np.asarray(a, float)
        te = np.asarray(te, float)
        delta = cfg.eta * (cfg.beta1 * (a - np.mean(a))
                           + cfg.beta2 * (a - te))
        if penalties is not None and penalties[i] is not None:
            delta = delta + penalties[i]
        pop.reputations[i, sel] = np.clip(
            pop.reputations[i, sel] - delta, 0.0, 1.0)
        pop.last_sel[i, sel] = t


# ---------------------------------------------------------------------- #
# Top-M visit-order prefix (host side)
# ---------------------------------------------------------------------- #
def _topm_prefix(keys: np.ndarray, m: int) -> np.ndarray:
    """First ``m`` positions of each row's visit order — the stable
    ascending argsort prefix (ties to the lower index) — in O(N + m log m)
    per row via argpartition + a pivot/tie fixup instead of a full
    O(N log N) sort."""
    R, _ = keys.shape
    out = np.empty((R, m), np.int64)
    for i in range(R):
        k = keys[i]
        part = np.argpartition(k, m - 1)[:m]
        pivot = k[part].max()
        strict = np.flatnonzero(k < pivot)
        ties = np.flatnonzero(k == pivot)[:m - strict.size]
        idx = np.concatenate([strict, ties])
        # stable argsort of the kept keys: equal keys keep their
        # ascending-index layout, reproducing the global visit order
        out[i] = idx[np.argsort(k[idx], kind="stable")]
    return out


# ---------------------------------------------------------------------- #
# "jax" layout: prefilter as ONE jitted vmapped (shardable) kernel
# ---------------------------------------------------------------------- #
def _prefilter_rows(policy_id, rep, ages, divs, sizes, r_min, gains,
                    rand_rank, w_rep, w_div, gamma, bandwidth_hz,
                    p_watt, n0, *, k: int, n_sel: int, m: int):
    """``_prefilter_kernel``'s (x, costs, values, forced, cert) with
    ``values`` one (R, N) array. Alpha is not computed on the chip:
    ``_alpha_from_schedule`` derives it on the host from x, costs and
    forced."""

    def one(pid, rep, ages, divs, sizes, r_min, gains, rand_rank,
            w_rep, w_div):
        I = diversity_index_eq2(divs, sizes, ages, gamma)
        values = data_quality_value(rep, I, None, omega=(w_rep, w_div))
        costs = cost_bisect(gains, r_min, k, bandwidth_hz, p_watt, n0)
        costs_f = costs.astype(values.dtype)
        key = jnp.where(
            pid == 0, priority_key("dqs", values, costs_f, k),
            jnp.where(pid == 1, rand_rank.astype(values.dtype),
                      jnp.where(pid == 2,
                                priority_key("best_channel", values,
                                             costs_f, k, gains=gains),
                                costs_f)))
        # top_value rows pre-filter by value so the kept prefix contains
        # the exact top-n_sel selection
        key = jnp.where(pid == 4, -values, key)

        # visit-order prefix: top_k of the negated key returns the m
        # smallest keys ascending, ties to the lower index — exactly the
        # stable argsort prefix the exact path walks first
        _, kept = jax.lax.top_k(-key, m)
        c_kept = jnp.take(costs, kept)
        take = pack_scan(c_kept, k)
        x = jnp.zeros(costs.shape, bool).at[kept].set(take)

        # preservation certificate: remaining budget cannot admit any
        # dropped candidate (see module docstring)
        b_rem = k - jnp.where(take, c_kept, 0).sum()
        dmin = jnp.min(costs.at[kept].set(k + 2))
        cert = (b_rem < dmin) | (pid == 4)

        # dqs modified-greedy fallback — global O(N) reductions
        feas = costs <= k
        masked = jnp.where(feas, values, -jnp.inf)
        k_best = jnp.argmax(masked)
        use_fb = ((pid == 0) & feas.any()
                  & (masked[k_best] > (values * x).sum()))
        onehot_best = jnp.zeros_like(x).at[k_best].set(True)
        x = jnp.where(use_fb, onehot_best, x)

        # top_value override: top-n_sel by value (ties to lower index ==
        # the exact path's stable rank)
        _, topn = jax.lax.top_k(values, n_sel)
        x4 = jnp.zeros_like(x).at[topn].set(True)
        x = jnp.where(pid == 4, x4, x)

        # degenerate round: force the single highest-value UE
        forced = ~x.any()
        onehot_f = jnp.zeros_like(x).at[jnp.argmax(values)].set(True)
        x = jnp.where(forced, onehot_f, x)
        return x, costs, values, forced, cert

    return jax.vmap(one)(policy_id, rep, ages, divs, sizes, r_min, gains,
                         rand_rank, w_rep, w_div)


@functools.partial(jax.jit, static_argnames=("k", "n_sel", "m"))
def _prefilter_kernel(policy_id, rep, ages, divs, sizes, r_min, gains,
                      rand_rank, w_rep, w_div, gamma, bandwidth_hz,
                      p_watt, n0, *, k: int, n_sel: int, m: int):
    """One prefiltered round of every run: (R, N) in, (x, costs, values,
    forced, cert) out, ``values`` as a tuple of R per-run (N,) buffers.
    The O(N) work (Eq. 2/3/9, top_k, the global fallback reductions) is
    population-parallel and shards over the mesh data axes; only the
    (R, M) sort + budget scan is serial.

    A TPU holds float64 as a pair of float32 words; the host converts
    each element to IEEE binary64 as it copies, which costs far more
    than the copy itself. R separate buffers are R transfers the
    runtime converts side by side, where one (R, N) array converts as
    one: on a TPU v5e host, (5, 10^6) float64 reads in 68-86 ms as five
    buffers against 187-196 ms as one array. The TPU compiler has no
    bitcast of float64, so the IEEE bits cannot be formed on the chip
    instead."""
    x, costs, values, forced, cert = _prefilter_rows(
        policy_id, rep, ages, divs, sizes, r_min, gains, rand_rank, w_rep,
        w_div, gamma, bandwidth_hz, p_watt, n0, k=k, n_sel=n_sel, m=m)
    return x, costs, tuple(values), forced, cert


def _alpha_from_schedule(policy_id, x, costs, forced, k: int, n_sel: int):
    """The kernel's bandwidth fractions, rebuilt on the host from the
    fetched schedule by its precedence: 1.0 at a forced row's UE,
    ``1 / max(n_sel, 1)`` at a ``top_value`` row's, ``costs / k`` at
    every other selected UE (the dqs fallback's included) — the same
    float64 division as ``control._schedule_hybrid``. O(R*K) writes,
    by flat index (``np.nonzero`` of a 2-D mask is ~10x slower)."""
    idx = np.flatnonzero(x)
    rows = idx // x.shape[1]
    a = np.take(costs, idx).astype(float) / k
    a = np.where(policy_id[rows] == POLICY_IDS["top_value"],
                 1.0 / max(n_sel, 1), a)
    alpha = np.zeros(x.shape)
    np.put(alpha, idx, np.where(forced[rows], 1.0, a))
    return alpha


# ---------------------------------------------------------------------- #
# Entry point
# ---------------------------------------------------------------------- #
def prefilter_schedule_runs(state: ctl.ControlState, gains, rand_rank,
                            w_rep, w_div, m: Optional[int] = None,
                            kernel: Optional[str] = None, mesh=None):
    """Schedule round t of all R runs through the top-M prefilter.

    Same inputs/outputs as ``control.schedule_runs`` plus an ``info``
    dict: ``(x, alpha, costs, values, forced, info)`` with
    ``info = {"m", "n_escalated"}``. The schedule is IDENTICAL to the
    exact path for every run — certificate-passing rows by the
    preservation argument (module docstring), failing rows by
    escalation to ``schedule_runs`` itself.

    ``mesh`` (jax layout only) places the (R, N) operands with the
    population axis sharded over the mesh's data axes before the kernel
    runs, so XLA partitions the O(N) stages across devices; without one
    they go to the default device. The jax layout times its three
    host<->chip stages as child spans of ``schedule.prefilter``
    (``.put``, ``.kernel``, ``.fetch``) and counts the bytes that cross
    (``population.h2d_bytes``, ``population.d2h_bytes``). Its fetch is
    one ``jax.device_get`` of x, costs (int32), values (R per-run float64
    buffers, stacked on the host), forced and the certificate; alpha
    does not cross but is rebuilt on the host (``_alpha_from_schedule``)
    before escalated rows overwrite theirs.
    """
    cfg = state.cfg
    K = cfg.n_ues
    gains = np.asarray(gains, float)
    rand_rank = np.asarray(rand_rank)
    w_rep = np.asarray(w_rep, float)
    w_div = np.asarray(w_div, float)
    R = state.n_runs
    N = state.reputations.shape[1]
    m_eff = int(min(m if m is not None else default_m(cfg), N))
    assert m_eff >= cfg.min_selected, (m_eff, cfg.min_selected)
    with trace.span("schedule.prefilter") as sp:
        if m_eff >= N:      # no cut: the exact path IS the prefilter path
            out = ctl.schedule_runs(state, gains, rand_rank, w_rep, w_div,
                                    kernel=kernel)
            if trace.enabled():
                sp.set(m=N, runs=int(R), width=int(N), n_escalated=0)
            return (*out, {"m": N, "n_escalated": 0})

        kern = kernel or ctl.default_kernel()
        if kern == "jax":
            ops = [np.asarray(a) for a in (
                state.reputations, state.ages, state.divs, state.sizes,
                state.r_min, gains, rand_rank)]
            sh = (None if mesh is None else
                  named(mesh, PartitionSpec(None, data_axes(mesh))))
            with jax.enable_x64(True):
                # placed INSIDE enable_x64: outside it device_put would
                # canonicalize the float64 control state down to float32
                # and silently break oracle bit-parity
                with trace.span("schedule.prefilter.put"):
                    ops = trace.ready(jax.device_put(ops, sh))
                with trace.span("schedule.prefilter.kernel"):
                    outs = trace.ready(_prefilter_kernel(
                        state.policy_id, *ops, w_rep, w_div,
                        np.asarray(cfg.gamma, float), cfg.bandwidth_hz,
                        cfg.p_watt, cfg.n0_watt_hz,
                        k=K, n_sel=cfg.min_selected, m=m_eff))
            with trace.span("schedule.prefilter.fetch"):
                # one device_get starts every copy before it reads any
                x, costs, values, forced, cert = jax.device_get(outs)
                x, forced = np.array(x), np.array(forced)
                costs, values = costs.astype(int), np.stack(values)
            alpha = _alpha_from_schedule(state.policy_id, x, costs, forced,
                                         K, cfg.min_selected)
            if trace.enabled():
                trace.counter_inc("population.h2d_bytes",
                                  sum(a.nbytes for a in ops))
                trace.counter_inc("population.d2h_bytes",
                                  sum(o.nbytes for o in
                                      jax.tree.leaves(outs)))
        else:
            x, alpha, costs, values, forced, cert = _prefilter_hybrid(
                state, gains, rand_rank, w_rep, w_div, m_eff)

        # escalate certificate failures to the exact path (still one batched
        # call over just the failing rows)
        bad = np.flatnonzero(~cert)
        if bad.size:
            sub = ctl.ControlState(
                policy_id=state.policy_id[bad], sizes=state.sizes[bad],
                divs=state.divs[bad], r_min=state.r_min[bad],
                reputations=state.reputations[bad], ages=state.ages[bad],
                cfg=cfg)
            xs, als, cs, vs, fs = ctl.schedule_runs(
                sub, gains[bad], rand_rank[bad], w_rep[bad], w_div[bad],
                kernel=kern)
            x[bad], alpha[bad], forced[bad] = xs, als, fs
            costs[bad], values[bad] = cs, vs
        if trace.enabled():
            sp.set(m=m_eff, runs=int(R), width=int(N),
                   n_escalated=int(bad.size))
            trace.counter_inc("population.escalations", int(bad.size))
        return (x, alpha, costs, values, forced,
                {"m": m_eff, "n_escalated": int(bad.size)})


def _prefilter_hybrid(state: ctl.ControlState, gains, rand_rank,
                      w_rep, w_div, m: int):
    """Hybrid (CPU) layout of the prefilter: batched-numpy elementwise
    math + argpartition prefix, the jitted Eq. 9 bisection and (R, M)
    budget scan — mirroring ``control._schedule_hybrid`` stage for
    stage so certificate-passing rows match it bit-for-bit."""
    cfg = state.cfg
    K = cfg.n_ues
    R = state.n_runs
    N = state.reputations.shape[1]
    pid = state.policy_id

    I = diversity_index_rows(state.divs, state.sizes, state.ages,
                             cfg.gamma)
    values = data_quality_value(state.reputations, I, cfg,
                                omega=(w_rep[:, None], w_div[:, None]))
    with jax.enable_x64(True):
        costs = np.asarray(ctl._cost_kernel(
            gains, state.r_min, cfg.bandwidth_hz, cfg.p_watt,
            cfg.n0_watt_hz, k=K)).astype(int)
    costs_f = costs.astype(float)

    keys = np.empty((R, N))
    msk = pid == 0
    keys[msk] = priority_key("dqs", values[msk], costs_f[msk], K)
    msk = pid == 1
    keys[msk] = rand_rank[msk]
    msk = pid == 2
    keys[msk] = priority_key("best_channel", values[msk], costs_f[msk], K,
                             gains=gains[msk])
    msk = pid == 3
    keys[msk] = costs_f[msk]
    msk = pid == 4
    keys[msk] = -values[msk]

    kept = _topm_prefix(keys, m)                       # (R, m) visit order
    rows = np.arange(R)[:, None]
    c_kept = costs[rows, kept].astype(np.int32)
    take = np.asarray(ctl._pack_kernel(c_kept, k=K))
    x = np.zeros((R, N), bool)
    x[rows, kept] = take
    alpha = np.where(x, costs_f / K, 0.0)

    # preservation certificate
    b_rem = K - np.where(take, c_kept, 0).sum(-1)
    dropped = np.ones((R, N), bool)
    dropped[rows, kept] = False
    dmin = np.where(dropped, costs, K + 2).min(-1)
    cert = (b_rem < dmin) | (pid == 4)

    # dqs modified-greedy fallback — compressed pack sum, like the
    # hybrid exact path (bit parity on the '>' comparison)
    feas = costs <= K
    masked = np.where(feas, values, -np.inf)
    k_best = masked.argmax(-1)
    ridx = np.arange(R)
    pack_val = np.array([values[i][x[i]].sum() if pid[i] == 0 else 0.0
                         for i in range(R)])
    use_fb = ((pid == 0) & feas.any(-1)
              & (masked[ridx, k_best] > pack_val))
    fb = np.flatnonzero(use_fb)
    x[fb] = False
    x[fb, k_best[fb]] = True
    alpha[fb] = 0.0
    alpha[fb, k_best[fb]] = costs_f[fb, k_best[fb]] / K

    # top_value: first n_sel of the (-values)-ordered kept prefix ==
    # the exact stable argsort(-values)[:n] selection (m >= n_sel)
    tv = np.flatnonzero(pid == 4)
    if tv.size:
        n = cfg.min_selected
        xt = np.zeros((tv.size, N), bool)
        xt[np.arange(tv.size)[:, None], kept[tv, :n]] = True
        x[tv] = xt
        alpha[tv] = np.where(xt, 1.0 / max(n, 1), 0.0)

    # degenerate rounds
    forced = ~x.any(-1)
    fr = np.flatnonzero(forced)
    kf = values[fr].argmax(-1)
    x[fr] = False
    x[fr, kf] = True
    alpha[fr] = 0.0
    alpha[fr, kf] = 1.0
    return x, alpha, costs, values, forced, cert


# ---------------------------------------------------------------------- #
# Mesh plumbing: shard the population axis over the local devices
# ---------------------------------------------------------------------- #
def population_mesh(model_parallel: int = 1):
    """The host mesh (launch.mesh) the population axis shards over —
    axes ("data", "model") spanning every local device."""
    return make_host_mesh(model_parallel=model_parallel)


def shard_population(mesh, *arrays):
    """Place (R, N) control arrays with the population (trailing) axis
    sharded over the mesh's data axes (sharding.specs.named)."""
    sh = named(mesh, PartitionSpec(None, data_axes(mesh)))
    out = tuple(jax.device_put(np.asarray(a), sh) for a in arrays)
    return out if len(out) != 1 else out[0]


def bytes_per_device(pop: PopulationState, n_devices: int) -> int:
    """Resident population-state bytes per device when the N axis is
    sharded over ``n_devices`` (policy_id and cfg scalars replicate)."""
    return pop.nbytes() // max(n_devices, 1) + pop.policy_id.nbytes


# ---------------------------------------------------------------------- #
# Synthetic population cell (benchmarks/bench_round.py, chip_smoke.py)
# ---------------------------------------------------------------------- #
def synthetic_population(n: int, k: int, n_runs: int):
    """Seeded N-wide control state of ``n_runs`` stacked runs (policies
    cycled over POLICY_IDS, K = ``k`` budget) and ``draw(t)`` -> per-run
    (gains, rand_rank) of round t. Returns (state, omega, draw)."""
    cfg = FeelConfig(n_ues=k, n_malicious=max(k // 10, 1), population=n)
    rng = np.random.default_rng(0)
    policies = [list(POLICY_IDS)[i % len(POLICY_IDS)] for i in range(n_runs)]
    wm = WirelessModel(cfg, np.random.default_rng(1))
    sizes = (rng.integers(1, 31, (n_runs, n)) * 50).astype(float)
    cpu = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max, (n_runs, n))
    state = ctl.ControlState(
        policy_id=np.array([POLICY_IDS[p] for p in policies], np.int32),
        sizes=sizes, divs=rng.uniform(0.0, 0.9, (n_runs, n)),
        r_min=np.stack([wm.min_rate(wm.train_time(sizes[i], cpu[i]))
                        for i in range(n_runs)]),
        reputations=rng.uniform(0.0, 1.0, (n_runs, n)),
        ages=np.ones((n_runs, n)), cfg=cfg)
    omega = np.full(n_runs, cfg.omega_rep), np.full(n_runs, cfg.omega_div)

    def draw(t):
        g = np.stack([wm.rng.exponential(1.0, n) * wm.distances
                      ** (-cfg.pathloss_exp) for _ in range(n_runs)])
        rr = np.stack([np.argsort(np.random.default_rng((t, i))
                                  .permutation(n)) for i in range(n_runs)])
        return g, rr

    return state, omega, draw
