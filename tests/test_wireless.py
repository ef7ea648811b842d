"""Wireless model (paper Eq. 4-7, 9) properties, including the O(K log K)
monotone-bisection cost against the exhaustive (K, K) scan oracle."""
import jax
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs.base import FeelConfig
from repro.core.wireless import WirelessModel, cost_bisect, dbm_to_watt


def _wm(seed=0, **kw):
    cfg = FeelConfig(**kw)
    return WirelessModel(cfg, np.random.default_rng(seed)), cfg


def test_dbm():
    assert dbm_to_watt(0) == pytest.approx(1e-3)
    assert dbm_to_watt(30) == pytest.approx(1.0)


def test_rate_monotone_in_bandwidth():
    """Eq. 4: r(alpha) is increasing in alpha (log concavity)."""
    wm, _ = _wm()
    g = np.array([1e-9])
    alphas = np.linspace(0.01, 1.0, 50)
    r = wm.rate(g, alphas[None, :] * np.ones((1, 50)))[0]
    r = wm.rate(np.full(50, 1e-9), alphas)
    assert np.all(np.diff(r) > 0)


@given(st.integers(0, 10_000))
@settings(max_examples=25, deadline=None)
def test_cost_is_minimal(seed):
    """Eq. 9: c_k is the MINIMUM feasible fraction count."""
    wm, cfg = _wm(seed)
    ch = wm.draw_channels()
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, 31, cfg.n_ues) * 50.0
    cpu = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max, cfg.n_ues)
    tt = wm.train_time(sizes, cpu)
    costs = wm.cost(ch.gains, tt)
    r_min = wm.min_rate(tt)
    K = cfg.n_ues
    for k in range(K):
        c = costs[k]
        if c <= K:
            assert wm.rate(ch.gains[k:k+1], np.array([c / K]))[0] >= r_min[k]
            if c > 1:
                assert wm.rate(ch.gains[k:k+1],
                               np.array([(c - 1) / K]))[0] < r_min[k]
        else:
            assert wm.rate(ch.gains[k:k+1], np.array([1.0]))[0] < r_min[k]


def test_train_time_scales_with_data_and_epochs():
    wm, cfg = _wm()
    t1 = wm.train_time(np.array([100.0]), np.array([1e8]))
    t2 = wm.train_time(np.array([200.0]), np.array([1e8]))
    assert t2 == pytest.approx(2 * t1)
    wm2, _ = _wm(local_epochs=cfg.local_epochs * 2)
    assert wm2.train_time(np.array([100.0]), np.array([1e8])) \
        == pytest.approx(2 * t1)


def test_deadline_violation_infeasible():
    """A UE whose training alone blows T can never upload (cost K+1)."""
    wm, cfg = _wm()
    tt = np.full(cfg.n_ues, cfg.deadline_s + 1.0)
    costs = wm.cost(wm.draw_channels().gains, tt)
    assert np.all(costs == cfg.n_ues + 1)


def _random_cost_instance(seed, k):
    """Random gains/deadlines with the Eq. 9 edges forced in: blown
    deadlines (t_train >= T -> r_min = inf), near-deadline stragglers, and
    a boosted-gain row that should resolve at c = 1."""
    cfg = FeelConfig(n_ues=k)
    rng = np.random.default_rng(seed)
    wm = WirelessModel(cfg, rng)
    gains = wm.draw_channels().gains
    sizes = rng.integers(1, 31, k) * 50.0
    cpu = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max, k)
    tt = wm.train_time(sizes, cpu)
    tt[0] = cfg.deadline_s                 # exactly blown (slack == 0)
    tt[1] = cfg.deadline_s + 1.0           # blown
    tt[2] = cfg.deadline_s * (1 - 1e-6)    # near-blown straggler
    gains[3] = gains.max() * 1e3           # excellent channel
    return cfg, wm, gains, tt


@given(st.integers(0, 2**31 - 1), st.sampled_from([7, 23, 50, 211]))
@settings(max_examples=25, deadline=None)
def test_cost_bisection_equals_exhaustive_scan(seed, k):
    """Eq. 9 bisection == the dense (K, K) scan, EXACTLY, on random
    instances including the infeasible (c = K+1) and blown-deadline
    (t_train >= T) edges."""
    cfg, wm, gains, tt = _random_cost_instance(seed, k)
    bisected = wm.cost(gains, tt)
    scanned = wm.cost_scan(gains, tt)
    np.testing.assert_array_equal(bisected, scanned)
    assert bisected[0] == k + 1 and bisected[1] == k + 1
    assert np.all((bisected >= 1) & (bisected <= k + 1))


@given(st.integers(0, 2**31 - 1), st.sampled_from([7, 50, 211]))
@settings(max_examples=15, deadline=None)
def test_cost_bisect_jnp_matches_numpy(seed, k):
    """The jnp twin (batched control plane) reproduces the numpy bisection
    exactly in float64, same edges included."""
    cfg, wm, gains, tt = _random_cost_instance(seed, k)
    with jax.enable_x64(True):
        jc = np.asarray(cost_bisect(
            gains, np.asarray(wm.min_rate(tt)), k, cfg.bandwidth_hz,
            cfg.p_watt, cfg.n0_watt_hz))
    np.testing.assert_array_equal(jc, wm.cost(gains, tt))


def test_cost_bisect_fraction_is_float64_at_a_near_boundary_rate():
    """A UE whose float64 rate at c = 5 of K = 64 clears r_min by only
    5e-9 (relative): the jnp twin must still give 5, as the float64
    reference does. With the fraction c / K left float32 (int32 / int),
    Eq. 4's denominator rounds to float32 and the cost reads 6."""
    k, bw = 64, 1e6
    p, n0 = dbm_to_watt(-23.0), dbm_to_watt(-174.0)
    g = np.array([2.9260976020125843e-09])
    r_min = np.array([436681.20528104715])

    def rate(c):
        a = c / k
        return a * bw * np.log2(1.0 + g * p / (a * bw * n0))

    assert rate(4)[0] < r_min[0] <= rate(5)[0]
    assert rate(5)[0] / r_min[0] - 1.0 < 1e-8
    with jax.enable_x64(True):
        got = np.asarray(cost_bisect(g, r_min, k, bw, p, n0))
    assert got.tolist() == [5]


def test_cost_bisect_jnp_batched_axes():
    """cost_bisect accepts leading batch (run) axes — the (R, K) layout the
    control plane feeds it."""
    cfg, wm, gains, tt = _random_cost_instance(0, 23)
    r_min = np.asarray(wm.min_rate(tt))
    with jax.enable_x64(True):
        single = np.asarray(cost_bisect(
            gains, r_min, 23, cfg.bandwidth_hz, cfg.p_watt,
            cfg.n0_watt_hz))
        stacked = np.asarray(cost_bisect(
            np.stack([gains, gains * 2.0]), np.stack([r_min, r_min]), 23,
            cfg.bandwidth_hz, cfg.p_watt, cfg.n0_watt_hz))
    np.testing.assert_array_equal(stacked[0], single)
    feas = single <= 23
    assert np.all(stacked[1][feas] <= single[feas])   # better channel


# ---------------------------------------------------------------------- #
# AR(1)/Gauss-Markov block fading (cfg.channel_corr, DESIGN.md §13)
# ---------------------------------------------------------------------- #
def test_channel_corr_zero_is_legacy_draw_bit_for_bit():
    """rho = 0 (the default) must consume the EXACT legacy RNG stream:
    uniform positions, then one exponential per draw_channels() call."""
    wm, cfg = _wm(seed=7)
    twin = np.random.default_rng(7)
    half = cfg.cell_side_m / 2.0
    xy = twin.uniform(-half, half, size=(cfg.n_population, 2))
    dist = np.maximum(np.linalg.norm(xy, axis=1), 1.0)
    for _ in range(3):
        ch = wm.draw_channels()
        h2 = twin.exponential(1.0, size=dist.shape)
        np.testing.assert_array_equal(
            ch.gains, dist ** (-cfg.pathloss_exp) * h2)
        np.testing.assert_array_equal(wm.last_gains, ch.gains)
    assert wm._h is None                     # no fading state materialised


def test_channel_corr_state_persists_and_positive():
    wm, _ = _wm(seed=3, channel_corr=0.8)
    g1 = wm.draw_channels().gains
    h_after_first = wm._h.copy()
    g2 = wm.draw_channels().gains
    assert wm._h is not None and not np.array_equal(wm._h, h_after_first)
    assert np.all(g1 > 0) and np.all(g2 > 0)
    assert not np.array_equal(g1, g2)        # fading evolves, not frozen


def test_channel_corr_stationary_stats():
    """|h|^2 stays Exp(1) (mean 1) and its lag-1 correlation is ~rho^2."""
    rho = 0.8
    wm, cfg = _wm(seed=11, n_ues=200, channel_corr=rho)
    d_alpha = wm.distances ** cfg.pathloss_exp
    # divide out the pathloss to recover the (T, N) small-scale power
    h2 = np.stack([wm.draw_channels().gains * d_alpha
                   for _ in range(400)])
    assert abs(h2.mean() - 1.0) < 0.05
    x, y = h2[:-1].ravel(), h2[1:].ravel()
    corr = np.corrcoef(x, y)[0, 1]
    assert abs(corr - rho ** 2) < 0.05
