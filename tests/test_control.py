"""Batched control plane (core/control.py) vs the host numpy oracle.

Parity contract: the hybrid kernel layout is bit-for-bit against the host
path on every output; the pure-jax layout matches the integer outputs
(selection, costs, forced) bit-for-bit and floats to ~1 ulp (XLA FMA
contraction). Pinned per policy on random instances, plus full-run and
full-sweep parity through FeelServer / run_sweep.
"""
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.base import FeelConfig
from repro.core import control as ctl
from repro.core.diversity import diversity_index
from repro.core.poisoning import EASY_PAIR, LabelFlipAttack, pick_malicious
from repro.core.quality import data_quality_value
from repro.core.reputation import ReputationTracker
from repro.core.scheduler import (POLICIES, POLICY_IDS, top_value_schedule)
from repro.core.wireless import WirelessModel

ALL_POLICIES = list(POLICY_IDS)


class _Replay:
    """numpy-Generator stand-in replaying one pre-drawn permutation."""

    def __init__(self, perm):
        self.perm = perm

    def permutation(self, n):
        assert n == len(self.perm)
        return self.perm


def _random_instance(seed, k, r=10, deadline=None):
    """R runs x K UEs of random control state + one round of draws."""
    rng = np.random.default_rng(seed)
    cfg = FeelConfig(n_ues=k, **({} if deadline is None
                                 else {"deadline_s": deadline}))
    wms = [WirelessModel(cfg, np.random.default_rng(seed * 100 + i))
           for i in range(r)]
    sizes = (rng.integers(1, 31, (r, k)) * 50).astype(float)
    cpu = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max, (r, k))
    t_train = np.stack([wms[i].train_time(sizes[i], cpu[i])
                        for i in range(r)])
    policies = [ALL_POLICIES[i % len(ALL_POLICIES)] for i in range(r)]
    state = ctl.ControlState(
        policy_id=np.array([POLICY_IDS[p] for p in policies], np.int32),
        sizes=sizes, divs=rng.uniform(0, 0.9, (r, k)),
        r_min=np.stack([wms[i].min_rate(t_train[i]) for i in range(r)]),
        reputations=rng.uniform(0, 1, (r, k)), ages=np.ones((r, k)),
        cfg=cfg)
    gains = np.stack([wms[i].draw_channels().gains for i in range(r)])
    perms = [rng.permutation(k) for _ in range(r)]
    rand_rank = np.stack([np.argsort(p) for p in perms])
    omega = np.full(r, cfg.omega_rep), np.full(r, cfg.omega_div)
    return cfg, wms, t_train, policies, state, gains, perms, rand_rank, omega


def _host_schedule(cfg, wm, t_train, policy, state, i, gains, perm):
    """The sequential oracle: FeelServer._schedule_round's host path,
    recomposed from the per-equation numpy functions."""
    I = diversity_index(state.divs[i], state.sizes[i], state.ages[i],
                        cfg.gamma)
    values = data_quality_value(state.reputations[i], I, cfg)
    costs = wm.cost(gains, t_train)
    if policy == "top_value":
        s = top_value_schedule(values, costs, cfg, cfg.min_selected)
    elif policy == "random":
        s = POLICIES[policy](values, costs, cfg, _Replay(perm))
    elif policy == "best_channel":
        s = POLICIES[policy](values, costs, cfg, gains)
    else:
        s = POLICIES[policy](values, costs, cfg)
    x, alpha, forced = s.x.copy(), s.alpha.copy(), False
    if not x.any():
        x[np.argmax(values)] = True
        alpha[:] = 0.0
        alpha[np.argmax(values)] = 1.0
        forced = True
    return x, alpha, costs, values, forced


@given(st.integers(0, 2**31 - 1), st.integers(8, 60))
@settings(max_examples=15, deadline=None)
def test_batched_schedule_matches_host_per_policy(seed, k):
    """schedule_runs (hybrid layout) == host oracle, bit-for-bit, for a
    random mix of all five policies stacked in one call."""
    (cfg, wms, t_train, policies, state, gains, perms, rand_rank,
     omega) = _random_instance(seed, k)
    x, alpha, costs, values, forced = ctl.schedule_runs(
        state, gains, rand_rank, *omega, kernel="hybrid")
    for i, p in enumerate(policies):
        hx, halpha, hcosts, hvalues, hforced = _host_schedule(
            cfg, wms[i], t_train[i], p, state, i, gains[i], perms[i])
        np.testing.assert_array_equal(x[i], hx, err_msg=p)
        np.testing.assert_array_equal(costs[i], hcosts, err_msg=p)
        np.testing.assert_array_equal(alpha[i], halpha, err_msg=p)
        np.testing.assert_array_equal(values[i], hvalues, err_msg=p)
        assert bool(forced[i]) == hforced, p


@given(st.integers(0, 2**31 - 1), st.integers(8, 40))
@settings(max_examples=8, deadline=None)
def test_jax_kernel_matches_hybrid(seed, k):
    """The pure-jax layout (accelerator path) picks the same UEs/costs as
    the hybrid layout; floats agree to ~1 ulp (XLA FMA contraction)."""
    _, _, _, _, state, gains, _, rand_rank, omega = _random_instance(
        seed, k)
    h = ctl.schedule_runs(state, gains, rand_rank, *omega, kernel="hybrid")
    j = ctl.schedule_runs(state, gains, rand_rank, *omega, kernel="jax")
    np.testing.assert_array_equal(h[0], j[0])        # x
    np.testing.assert_array_equal(h[2], j[2])        # costs
    np.testing.assert_array_equal(h[4], j[4])        # forced
    np.testing.assert_allclose(h[1], j[1], rtol=1e-14, atol=0)   # alpha
    np.testing.assert_allclose(h[3], j[3], rtol=1e-14, atol=0)   # values


def test_all_policies_forced_when_deadline_blown():
    """t_train >= T for every UE -> every cost is K+1, problem (8) is
    infeasible: every policy (except top_value, which ignores wireless)
    reports forced=True with exactly one whole-band UE."""
    _, _, _, policies, state, gains, _, rand_rank, omega = \
        _random_instance(3, 12, deadline=1e-6)
    x, alpha, costs, values, forced = ctl.schedule_runs(
        state, gains, rand_rank, *omega)
    assert np.all(costs == state.cfg.n_ues + 1)
    for i, p in enumerate(policies):
        if p == "top_value":
            assert not forced[i]
            continue
        assert forced[i], p
        assert x[i].sum() == 1
        k = int(np.flatnonzero(x[i])[0])
        assert k == int(np.argmax(values[i]))
        assert alpha[i, k] == 1.0


@given(st.integers(0, 2**31 - 1), st.integers(2, 8))
@settings(max_examples=15, deadline=None)
def test_finalize_runs_matches_reputation_tracker(seed, n_sel):
    """finalize_runs == per-run ReputationTracker.update + age rules."""
    rng = np.random.default_rng(seed)
    R, K = 6, 12
    cfg = FeelConfig(n_ues=K)
    rep = rng.uniform(0, 1, (R, K))
    ages = rng.integers(1, 10, (R, K)).astype(float)
    state = ctl.ControlState(
        policy_id=np.zeros(R, np.int32), sizes=np.ones((R, K)),
        divs=np.ones((R, K)), r_min=np.ones((R, K)),
        reputations=rep.copy(), ages=ages.copy(), cfg=cfg)
    sels = [rng.choice(K, size=n_sel, replace=False) for _ in range(R)]
    accs_l = [rng.uniform(0, 1, n_sel) for _ in range(R)]
    accs_t = [rng.uniform(0, 1, n_sel) for _ in range(R)]
    ctl.finalize_runs(state, sels, accs_l, accs_t)
    for i in range(R):
        rt = ReputationTracker(cfg)
        rt.values = rep[i].copy()
        rt.update(sels[i], accs_l[i], accs_t[i])
        np.testing.assert_allclose(state.reputations[i], rt.values,
                                   rtol=0, atol=1e-12)
        expect_ages = ages[i] + 1.0
        expect_ages[sels[i]] = 1.0
        np.testing.assert_array_equal(state.ages[i], expect_ages)


# ---------------------------------------------------------------------- #
# End-to-end parity through the server / sweep
# ---------------------------------------------------------------------- #
KW = dict(n_train=2500, n_test=300, rounds=3)


@pytest.mark.slow
def test_run_experiment_control_parity():
    from repro.federated.simulation import run_experiment
    for policy in ("dqs", "random", "top_value"):
        a = run_experiment(policy, EASY_PAIR, seed=0, control="batched",
                           **KW)
        b = run_experiment(policy, EASY_PAIR, seed=0, control="host", **KW)
        np.testing.assert_allclose(a["acc"], b["acc"], atol=1e-7)
        assert a["malicious_selected"] == b["malicious_selected"]
        np.testing.assert_allclose(a["objective"], b["objective"],
                                   atol=1e-9)
        np.testing.assert_allclose(
            a["final_reputation_honest"], b["final_reputation_honest"],
            atol=1e-9)


@pytest.mark.slow
def test_full_sweep_control_parity():
    """run_sweep with the batched control plane reproduces the
    host-control sweep run for run: same selections, curves, objectives."""
    from repro.federated.simulation import run_sweep
    a = run_sweep(["dqs", "max_count"], seeds=[0, 1],
                  attack_pairs=[EASY_PAIR], control="batched", **KW)
    b = run_sweep(["dqs", "max_count"], seeds=[0, 1],
                  attack_pairs=[EASY_PAIR], control="host", **KW)
    assert len(a.runs) == len(b.runs)
    for ra, rb in zip(a.runs, b.runs):
        assert (ra["policy"], ra["seed"]) == (rb["policy"], rb["seed"])
        np.testing.assert_allclose(ra["acc"], rb["acc"], atol=1e-7)
        assert ra["malicious_selected"] == rb["malicious_selected"]
        np.testing.assert_allclose(ra["objective"], rb["objective"],
                                   atol=1e-9)
        assert ra["forced"] == rb["forced"]
        np.testing.assert_allclose(
            ra["final_reputation_malicious"],
            rb["final_reputation_malicious"], atol=1e-9)
    for rowa, rowb in zip(a.rows, b.rows):
        assert rowa["round"] == rowb["round"]
        np.testing.assert_allclose(rowa["acc"], rowb["acc"], atol=1e-7)
