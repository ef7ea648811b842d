"""Population plane (core/population.py, DESIGN.md §12).

The contracts under test:

- PREFILTER PRESERVATION: ``prefilter_schedule_runs`` (top-M candidate
  cut + certificate + escalation) selects exactly the same cohort as the
  exact N-wide ``control.schedule_runs`` — for every packing policy,
  every M (certificate-passing AND escalated rows), both kernel layouts.
- SCATTER PARITY: ``scatter_finalize`` (O(K) sparse update of the
  N-wide state) is bitwise identical to the dense ``finalize_runs``
  hybrid path, and the ``t - last_sel`` age encoding reproduces the
  dense age trajectory in exact integers.
- N == K PINNING: ``population=n_ues`` is the legacy regime — same RNG
  streams, same schedules, same curves as ``population=None``.
- The revived mesh plumbing (launch.mesh + sharding.specs) shards the
  population axis without changing the schedule (subprocess, forced
  2-device host CPU).
"""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.base import FeelConfig
from repro.core import control as ctl
from repro.core import population as pop
from repro.core.scheduler import POLICY_IDS

ALL_POLICIES = list(POLICY_IDS)


def _instance(seed, k, n, r=10):
    """Random (R, N) control instance cycling all five policies."""
    rng = np.random.default_rng(seed)
    cfg = FeelConfig(n_ues=k, population=n)
    state = ctl.ControlState(
        policy_id=np.array([POLICY_IDS[ALL_POLICIES[i % 5]]
                            for i in range(r)], np.int32),
        sizes=rng.uniform(100, 3000, (r, n)),
        divs=rng.uniform(0, 1, (r, n)),
        r_min=rng.uniform(1e4, 1e7, (r, n)),
        reputations=rng.uniform(0, 1, (r, n)),
        ages=rng.integers(1, 10, (r, n)).astype(float),
        cfg=cfg)
    gains = rng.exponential(1e-9, (r, n))
    rand_rank = np.stack([np.argsort(rng.permutation(n))
                          for _ in range(r)])
    omega = (np.full(r, cfg.omega_rep), np.full(r, cfg.omega_div))
    return cfg, state, gains, rand_rank, omega


# ---------------------------------------------------------------------- #
# Prefilter preservation
# ---------------------------------------------------------------------- #
@given(st.integers(0, 2**31 - 1), st.integers(4, 12),
       st.sampled_from([2, 5, 12]))
@settings(max_examples=12, deadline=None)
def test_prefilter_matches_exact_all_policies(seed, k, n_factor):
    """Top-M prefilter == exact N-wide schedule, across all five packing
    policies, for M values that exercise BOTH the certificate-pass fast
    path and the escalation path (m down to min_selected)."""
    n = k * n_factor
    cfg, state, gains, rand_rank, omega = _instance(seed, k, n)
    exact = ctl.schedule_runs(state, gains, rand_rank, *omega,
                              kernel="hybrid")
    for m in {cfg.min_selected, max(k, cfg.min_selected), 2 * k, n}:
        out = pop.prefilter_schedule_runs(state, gains, rand_rank, *omega,
                                          m=m, kernel="hybrid")
        x, alpha, costs, values, forced, info = out
        np.testing.assert_array_equal(x, exact[0], err_msg=f"m={m}")
        np.testing.assert_array_equal(alpha, exact[1], err_msg=f"m={m}")
        np.testing.assert_array_equal(costs, exact[2], err_msg=f"m={m}")
        np.testing.assert_array_equal(values, exact[3], err_msg=f"m={m}")
        np.testing.assert_array_equal(forced, exact[4], err_msg=f"m={m}")
        assert info["m"] == min(m, n)


@given(st.integers(0, 2**31 - 1), st.integers(4, 10))
@settings(max_examples=6, deadline=None)
def test_prefilter_jax_matches_exact(seed, k):
    """The jax prefilter layout (lax.top_k cut, shardable) picks the
    same UEs/costs/forced as the exact path. Its alpha is rebuilt on the
    host by the hybrid path's own float64 expressions, so it is equal
    where no row escalates; escalated rows take ``schedule_runs``' jax
    layout, whose alpha is ~1 ulp off."""
    n = 8 * k
    cfg, state, gains, rand_rank, omega = _instance(seed, k, n)
    exact = ctl.schedule_runs(state, gains, rand_rank, *omega,
                              kernel="hybrid")
    for m in (cfg.min_selected + 1, 2 * k):
        x, alpha, costs, values, forced, info = \
            pop.prefilter_schedule_runs(state, gains, rand_rank, *omega,
                                        m=m, kernel="jax")
        np.testing.assert_array_equal(x, exact[0], err_msg=f"m={m}")
        np.testing.assert_array_equal(costs, exact[2], err_msg=f"m={m}")
        np.testing.assert_array_equal(forced, exact[4], err_msg=f"m={m}")
        np.testing.assert_allclose(alpha, exact[1], rtol=1e-14, atol=0)
        if info["n_escalated"] == 0:
            np.testing.assert_array_equal(alpha, exact[1], err_msg=f"m={m}")


def _branch_instance():
    """An R = 5 instance whose rows reach every alpha branch of the jax
    kernel: row 0 (dqs) takes the modified-greedy fallback (two feasible
    UEs costing 2 and 7 of K = 8, the dearer one worth more), row 3
    (max_count) is forced (no UE feasible), row 4 is top_value; rows 1
    and 2 pack normally."""
    cfg, state, gains, rand_rank, omega = _instance(0, 8, 64, r=5)
    gains[0] *= 10
    costs = ctl.schedule_runs(state, gains, rand_rank, *omega,
                              kernel="hybrid")[2][0]
    a, b = np.flatnonzero(costs == 2)[0], np.flatnonzero(costs == 7)[0]
    others = np.ones(64, bool)
    others[[a, b]] = False
    gains[0, others] *= 1e-6
    state.reputations[0, b] = 1.0
    gains[3] *= 1e-6
    return (cfg, state, gains, rand_rank, omega), b


def _float64_fetch_prefilter(state, gains, rand_rank, omega, m):
    """The jax layout as it read its kernel before alpha left it:
    ``values`` fetched as one (R, N) float64 array, alpha by the
    kernel's own jnp expression, failing certificates escalated."""
    import jax
    import jax.numpy as jnp
    cfg = state.cfg
    k, n_sel = cfg.n_ues, cfg.min_selected
    with jax.enable_x64(True):
        rows = jax.jit(pop._prefilter_rows,
                       static_argnames=("k", "n_sel", "m"))
        x, costs, values, forced, cert = rows(
            state.policy_id, state.reputations, state.ages, state.divs,
            state.sizes, state.r_min, gains, rand_rank, *omega,
            np.asarray(cfg.gamma, float), cfg.bandwidth_hz, cfg.p_watt,
            cfg.n0_watt_hz, k=k, n_sel=n_sel, m=m)
        pid = jnp.asarray(state.policy_id)[:, None]
        alpha = jnp.where(x, costs.astype(values.dtype) / k, 0.0)
        alpha = jnp.where(pid == 4, jnp.where(x, 1.0 / max(n_sel, 1), 0.0),
                          alpha)
        alpha = jnp.where(forced[:, None], jnp.where(x, 1.0, 0.0), alpha)
    x, alpha, values, forced = (np.array(a) for a in (x, alpha, values,
                                                      forced))
    costs = np.array(costs).astype(int)
    bad = np.flatnonzero(~np.asarray(cert))
    if bad.size:
        sub = ctl.ControlState(
            policy_id=state.policy_id[bad], sizes=state.sizes[bad],
            divs=state.divs[bad], r_min=state.r_min[bad],
            reputations=state.reputations[bad], ages=state.ages[bad],
            cfg=cfg)
        xs, als, cs, vs, fs = ctl.schedule_runs(
            sub, gains[bad], rand_rank[bad], omega[0][bad], omega[1][bad],
            kernel="jax")
        x[bad], alpha[bad], costs[bad], values[bad], forced[bad] = \
            xs, als, cs, vs, fs
    return x, alpha, costs, values, forced, bad.size


@pytest.mark.parametrize("case", ["branches", "escalated"])
def test_prefilter_jax_bit_identical_to_float64_fetch(case):
    """The jax layout's x, costs, values and forced are bit-identical to
    the float64 fetch it replaced, and its host-built alpha equals the
    kernel's old expression, on rows that reach each alpha branch:
    forced, top_value, the dqs fallback and an escalated row."""
    if case == "branches":
        inst, b = _branch_instance()
        m = 16
    else:
        inst = _instance(1, 8, 64)
        m = inst[0].min_selected
    cfg, state, gains, rand_rank, omega = inst
    *new, info = pop.prefilter_schedule_runs(state, gains, rand_rank,
                                             *omega, m=m, kernel="jax")
    *old, n_bad = _float64_fetch_prefilter(state, gains, rand_rank, omega,
                                           m)
    x, alpha, costs, values, forced = new
    if case == "branches":
        assert forced[3] and not forced[[0, 1, 2, 4]].any()
        assert state.policy_id[4] == 4 and x[4].sum() == cfg.min_selected
        assert np.flatnonzero(x[0]).tolist() == [b] and costs[0, b] == 7
        assert alpha[0, b] == 7 / 8 and alpha[3].sum() == 1.0
    else:
        assert info["n_escalated"] == n_bad > 0
    for name, a, o in zip(("x", "costs", "values", "forced"),
                          (x, costs, values, forced),
                          (old[0], old[2], old[3], old[4])):
        assert a.dtype == o.dtype, name
        np.testing.assert_array_equal(a.view(np.uint8), o.view(np.uint8),
                                      err_msg=name)
    np.testing.assert_array_equal(alpha, old[1])


@pytest.mark.parametrize("mesh", [False, True])
def test_prefilter_values_cross_bit_exact(mesh):
    """``values`` crosses as R per-run float64 buffers and reads back as
    the one (R, N) float64 fetch did, bit for bit, without and with a
    1-device mesh. With w_rep = 1 and w_div = -0.0, Eq. 3 is the
    identity on the reputations, so 0.0, -0.0, 1.0 and random float64
    planted there come back unchanged; subnormals come back as the
    kernel's arithmetic leaves them (XLA's CPU flushes them to zero)."""
    cfg, state, gains, rand_rank, _ = _instance(4, 8, 64, r=5)
    omega = (np.ones(5), np.full(5, -0.0))
    rng = np.random.default_rng(4)
    planted = np.r_[0.0, -0.0, 5e-324, np.finfo(float).tiny / 3, 1.0,
                    rng.standard_normal(20)
                    * 10.0 ** rng.integers(-300, 300, 20)]
    state.reputations[:, :planted.size] = planted
    values = pop.prefilter_schedule_runs(
        state, gains, rand_rank, *omega, m=16, kernel="jax",
        mesh=pop.population_mesh() if mesh else None)[3]
    old = _float64_fetch_prefilter(state, gains, rand_rank, omega, 16)[3]
    np.testing.assert_array_equal(values.view(np.uint64),
                                  old.view(np.uint64))
    rep = state.reputations
    normal = (rep == 0) | (np.abs(rep) >= np.finfo(float).tiny)
    np.testing.assert_array_equal(values.view(np.uint64)[normal],
                                  rep.view(np.uint64)[normal])


def test_prefilter_escalation_is_exercised():
    """A tiny M must trip the preservation certificate on some rows (and
    the escalated rows still match the exact schedule — covered above);
    a full-width M never escalates."""
    cfg, state, gains, rand_rank, omega = _instance(0, 8, 64)
    esc = 0
    for seed in range(5):
        _, state, gains, rand_rank, omega = _instance(seed, 8, 64)
        *_, info = pop.prefilter_schedule_runs(
            state, gains, rand_rank, *omega, m=cfg.min_selected,
            kernel="hybrid")
        esc += info["n_escalated"]
    assert esc > 0, "certificate never failed at the minimum M"
    *_, info = pop.prefilter_schedule_runs(state, gains, rand_rank,
                                           *omega, m=64, kernel="hybrid")
    assert info["n_escalated"] == 0 and info["m"] == 64


@given(st.integers(0, 2**31 - 1), st.integers(3, 30))
@settings(max_examples=20, deadline=None)
def test_topm_prefix_is_stable_argsort_prefix(seed, m):
    """_topm_prefix == the stable ascending argsort prefix, including
    heavy ties (small integer key alphabet)."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 6, (4, 40)).astype(float)
    m = min(m, keys.shape[1])
    got = pop._topm_prefix(keys, m)
    want = np.argsort(keys, axis=-1, kind="stable")[:, :m]
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------- #
# Scatter finalize / PopulationState
# ---------------------------------------------------------------------- #
@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_scatter_finalize_bitwise_matches_dense(seed):
    """scatter_finalize (sparse K-sized writes into the N-wide state) ==
    finalize_runs (dense hybrid path) bitwise, over several rounds with
    empty cohorts and defense penalties mixed in; the t - last_sel age
    encoding reproduces the dense ages exactly."""
    rng = np.random.default_rng(seed)
    R, N, K = 6, 50, 10
    cfg = FeelConfig(n_ues=K, population=N)
    dense = ctl.ControlState(
        policy_id=np.zeros(R, np.int32),
        sizes=rng.uniform(100, 3000, (R, N)),
        divs=rng.uniform(0, 1, (R, N)),
        r_min=rng.uniform(1e4, 1e7, (R, N)),
        reputations=rng.uniform(0, 1, (R, N)),
        ages=np.ones((R, N)), cfg=cfg)
    ps = pop.PopulationState.from_control(dense, t=0)
    assert np.all(ps.last_sel == -1)            # dense ages started at 1
    for t in range(4):
        np.testing.assert_array_equal(ps.ages(t), dense.ages)
        sels, als, ats, pens = [], [], [], []
        for i in range(R):
            sel = rng.choice(N, size=rng.integers(0, K), replace=False)
            sels.append(sel)
            als.append(rng.uniform(0, 1, sel.size))
            ats.append(rng.uniform(0, 1, sel.size))
            pens.append(rng.uniform(0, 0.01, sel.size) if i % 2 else None)
        ctl.finalize_runs(dense, sels, als, ats, penalties=pens,
                          kernel="hybrid")
        pop.scatter_finalize(ps, t, sels, als, ats, penalties=pens)
        np.testing.assert_array_equal(ps.reputations, dense.reputations)
    np.testing.assert_array_equal(ps.ages(4), dense.ages)


def test_control_view_shares_buffers():
    """control_view is a zero-copy scheduling view: reputations are the
    SAME buffer, ages are materialized for the requested round."""
    _, state, *_ = _instance(3, 6, 24)
    ps = pop.PopulationState.from_control(state, t=2)
    cv = ps.control_view(t=2)
    assert cv.reputations is ps.reputations
    np.testing.assert_array_equal(cv.ages, state.ages)
    assert ps.n_population == 24 and ps.n_runs == state.n_runs
    assert ps.nbytes() > 0
    assert pop.bytes_per_device(ps, 2) < ps.nbytes()


def test_population_config_contract():
    cfg = FeelConfig(n_ues=10)
    assert cfg.n_population == 10                 # legacy N == K
    assert FeelConfig(n_ues=10, population=40).n_population == 40
    with pytest.raises(AssertionError):
        FeelConfig(n_ues=10, population=5).n_population
    assert pop.default_m(FeelConfig(n_ues=10, population=1000)) == 80
    assert pop.default_m(FeelConfig(n_ues=10, population=40)) == 40


# ---------------------------------------------------------------------- #
# N == K pinning + end-to-end population runs
# ---------------------------------------------------------------------- #
KW = dict(n_train=2500, n_test=300, rounds=2)


def test_population_equal_k_is_legacy_regime():
    """population=n_ues must reproduce population=None bit-for-bit: same
    RNG streams, same schedules (the prefilter delegates at M >= N),
    same curves."""
    from repro.federated.simulation import run_experiment
    a = run_experiment(policy="dqs", seed=0, **KW)
    b = run_experiment(policy="dqs", seed=0, population=50, **KW)
    assert a["acc"] == b["acc"]
    assert a["malicious"] == b["malicious"]
    assert a["objective"] == b["objective"]


def test_population_cut_end_to_end():
    """N > K: the sweep schedules over all N candidates through the
    prefilter, trains only the scheduled cohorts, and matches its
    sequential run_experiment twin exactly."""
    from repro.federated.simulation import run_experiment, run_sweep
    r = run_experiment(policy="dqs", seed=0, population=120, **KW)
    assert np.isfinite(r["acc"]).all()
    res = run_sweep(["dqs"], seeds=[0], population=120, **KW)
    assert res.select(policy="dqs", seed=0)[0]["acc"] == r["acc"]


# ---------------------------------------------------------------------- #
# Mesh plumbing (launch.mesh + sharding.specs revival)
# ---------------------------------------------------------------------- #
def test_mesh_helpers_single_device():
    import jax
    from jax.sharding import NamedSharding

    mesh = pop.population_mesh()
    assert mesh.axis_names == ("data", "model")
    arr = np.arange(12.0).reshape(3, 4)
    sharded = pop.shard_population(mesh, arr)
    assert isinstance(sharded.sharding, NamedSharding)
    np.testing.assert_array_equal(np.asarray(sharded), arr)


_MESH_PARITY = r"""
import numpy as np, jax
from tests.test_population import _instance
from repro.core import control as ctl
from repro.core import population as pop

assert len(jax.devices()) == 2
mesh = pop.population_mesh()
assert mesh.devices.size == 2
cfg, state, gains, rand_rank, omega = _instance(11, 8, 160)
exact = ctl.schedule_runs(state, gains, rand_rank, *omega,
                          kernel="hybrid")
x, _, costs, values, forced, info = pop.prefilter_schedule_runs(
    state, gains, rand_rank, *omega, m=32, kernel="jax", mesh=mesh)
np.testing.assert_array_equal(x, exact[0])
np.testing.assert_array_equal(costs, exact[2])
np.testing.assert_array_equal(forced, exact[4])
one = pop.prefilter_schedule_runs(state, gains, rand_rank, *omega, m=32,
                                  kernel="jax")
np.testing.assert_array_equal(values.view(np.uint64), one[3].view(np.uint64))
print("MESH-PARITY-OK")
"""


def test_prefilter_sharded_mesh_parity():
    """Forced 2-device host mesh (subprocess: conftest pins no XLA_FLAGS
    in-process): the GSPMD-sharded prefilter kernel still selects the
    exact cohort, and its per-run values buffers, sharded by UE, read
    back bit for bit as on one device."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _MESH_PARITY], capture_output=True,
        text=True, timeout=600,
        env={**os.environ,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
             "PYTHONPATH": os.pathsep.join(
                 [os.path.join(root, "src"), os.path.join(root, "tests"),
                  root, os.environ.get("PYTHONPATH", "")])})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "MESH-PARITY-OK" in r.stdout


# ---------------------------------------------------------------------- #
# Telemetry: the prefilter's host<->chip split (obs spans, byte counters)
# ---------------------------------------------------------------------- #
PREFILTER_CHILDREN = ["schedule.prefilter.put", "schedule.prefilter.kernel",
                      "schedule.prefilter.fetch"]


def _prefilter_jax(traced: bool, seed: int = 3, k: int = 8, n: int = 96):
    """One jax-layout prefilter call with tracing on or off: (instance,
    outputs, spans, counters)."""
    from repro.obs import trace
    inst = _instance(seed, k, n, r=5)
    _, state, gains, rand_rank, omega = inst
    trace.configure(enabled=traced)
    try:
        out = pop.prefilter_schedule_runs(state, gains, rand_rank, *omega,
                                          m=2 * k, kernel="jax")
        spans = list(trace.tracer().spans)
        counters = dict(trace.tracer().metrics.snapshot()["counters"])
    finally:
        trace.configure(enabled=False)
    return inst, out, spans, counters


def test_prefilter_child_spans_nest_under_prefilter():
    _, _, spans, _ = _prefilter_jax(True)
    (parent,) = [s for s in spans if s.name == "schedule.prefilter"]
    kids = sorted((s for s in spans if s.parent == parent.sid
                   and s.name in PREFILTER_CHILDREN), key=lambda s: s.t0)
    assert [s.name for s in kids] == PREFILTER_CHILDREN
    for s in kids:
        assert s.depth == parent.depth + 1
        assert parent.t0 <= s.t0 <= s.t1 <= parent.t1
    assert all(a.t1 <= b.t0 for a, b in zip(kids, kids[1:]))


def test_prefilter_child_durations_fit_in_parent():
    _, _, spans, _ = _prefilter_jax(True)
    (parent,) = [s for s in spans if s.name == "schedule.prefilter"]
    kids = [s for s in spans if s.parent == parent.sid
            and s.name in PREFILTER_CHILDREN]
    assert len(kids) == 3
    assert sum(s.dur for s in kids) <= parent.dur


def test_prefilter_byte_counters_equal_operand_and_output_nbytes():
    inst, out, _, counters = _prefilter_jax(True)
    _, state, gains, rand_rank, _ = inst
    operands = (state.reputations, state.ages, state.divs, state.sizes,
                state.r_min, gains, rand_rank)
    assert counters["population.h2d_bytes"] == sum(
        np.asarray(a).nbytes for a in operands)
    # fetched as the kernel returns them: x, costs (int32), values (R
    # per-run float64 buffers), forced, cert; alpha is built on the host
    x, _, _, values, forced, _ = out
    r, n = x.shape
    assert counters["population.d2h_bytes"] == (
        x.nbytes + r * n * 4 + values.nbytes + forced.nbytes + r)


def test_prefilter_schedule_bit_identical_with_tracing_on_and_off():
    _, off, spans_off, counters_off = _prefilter_jax(False)
    _, on, spans_on, _ = _prefilter_jax(True)
    assert spans_off == [] and counters_off == {} and spans_on
    for a, b in zip(off[:5], on[:5]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert off[5] == on[5]
