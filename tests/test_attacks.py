"""Threat-model plane (core/attacks.py): the masked batched application vs
the per-client oracle, a parity matrix over every registered scenario x
both engines x both control planes, attack-invariant property tests, and
the legacy-knob (model_poison_scale x no_attack) contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core.poisoning import pick_malicious
from repro.data.partition import pad_clients, partition
from repro.data.synthetic_mnist import generate
from repro.federated.server import FeelServer
from repro.federated.simulation import run_experiment, run_sweep
from repro.models.mlp import mlp_init

KW = dict(n_train=1200, n_test=300, rounds=2)


def _cfg():
    return FeelConfig(n_ues=8, n_malicious=2, min_selected=3)


# ---------------------------------------------------------------------- #
# Tentpole acceptance: EVERY registered scenario, batched == oracle under
# both engines and both control planes (K=10-style parity runs).
# ---------------------------------------------------------------------- #
_REFS = {}


def _matrix_args(name):
    """Token-space scenarios run the matrix on the LM task (their data
    attack refuses feature/label datasets loudly); everything else on
    the default MNIST task."""
    scn = atk.as_scenario(name)
    if scn.data is not None and hasattr(scn.data, "poison_tokens"):
        return (FeelConfig(n_ues=8, n_malicious=2, min_selected=3,
                           task="lm_tiny"),
                dict(n_train=960, n_test=240, rounds=2))
    return _cfg(), KW


def _reference(name):
    """(loop, host) oracle run for a scenario — cached across the matrix."""
    if name not in _REFS:
        cfg, kw = _matrix_args(name)
        _REFS[name] = run_experiment("dqs", scenario=name, cfg=cfg,
                                     seed=0, engine="loop",
                                     control="host", **kw)
    return _REFS[name]


@pytest.mark.parametrize("engine,control", [("vectorized", "batched"),
                                            ("vectorized", "host"),
                                            ("loop", "batched")])
@pytest.mark.parametrize("name", sorted(atk.SCENARIOS))
def test_scenario_parity_matrix(name, engine, control):
    """Batched jnp attack application == host oracle for every registered
    scenario, under both cohort engines and both control planes."""
    ref = _reference(name)
    cfg, kw = _matrix_args(name)
    got = run_experiment("dqs", scenario=name, cfg=cfg, seed=0,
                         engine=engine, control=control, **kw)
    np.testing.assert_allclose(got["acc"], ref["acc"], atol=1e-5)
    np.testing.assert_allclose(got["source_acc"], ref["source_acc"],
                               atol=1e-5)
    np.testing.assert_allclose(got["attack_success"],
                               ref["attack_success"], atol=1e-5)
    assert got["malicious_selected"] == ref["malicious_selected"]
    np.testing.assert_allclose(got["rep_gap"], ref["rep_gap"], atol=1e-6)
    assert got["recovery_rounds"] == ref["recovery_rounds"]


def test_heterogeneous_scenario_sweep():
    """Acceptance: >= 4 distinct threat models (label flip, noise,
    free-rider, model poison) run in ONE sweep, each reproducing its
    ``run_experiment`` twin."""
    scns = ["flip_6to2", "noise_0.8", "free_rider", "sign_flip"]
    res = run_sweep(["dqs"], seeds=[0], scenarios=scns, cfg=_cfg(), **KW)
    assert [r["scenario"] for r in res.runs] == scns
    for a in res.runs:
        b = run_experiment("dqs", scenario=a["scenario"], cfg=_cfg(),
                           seed=0, **KW)
        np.testing.assert_allclose(a["acc"], b["acc"], atol=1e-7)
        np.testing.assert_allclose(a["attack_success"],
                                   b["attack_success"], atol=1e-6)
        assert a["malicious_selected"] == b["malicious_selected"]
    # scenario key threads through rows and select()
    assert {r["scenario"] for r in res.rows} == set(scns)
    assert len(res.select(scenario="free_rider")) == 1
    # the two partition families are shared: both pure-model-attack runs
    # report the same malicious set as the noise run's seed
    assert (res.select(scenario="free_rider")[0]["malicious"]
            == res.select(scenario="sign_flip")[0]["malicious"])


# ---------------------------------------------------------------------- #
# Satellite: masked _apply_attacks == the per-client .at[i].set oracle,
# bit for bit.
# ---------------------------------------------------------------------- #
def _random_stack(key, n):
    params = mlp_init(jax.random.PRNGKey(key))
    leaves, treedef = jax.tree.flatten(params)
    rng = np.random.default_rng(key)
    stacked = [jnp.asarray(rng.normal(size=(n,) + l.shape)
                           .astype(np.float32)) for l in leaves]
    return params, jax.tree.unflatten(treedef, stacked)


@pytest.mark.parametrize("scale", [-1.0, 0.0, 3.0])
def test_masked_apply_stacked_matches_per_client_loop(scale):
    """ONE masked tree_map == the replaced O(n_malicious) dispatch loop,
    bitwise, for sign-flip / free-rider / boosted scales."""
    g, stacked = _random_stack(0, 6)
    mal = np.array([True, False, True, True, False, False])
    attack = atk.ModelAttack(scale=scale)

    got = attack.apply_stacked(stacked, g, mal)

    want = stacked
    for i in np.flatnonzero(mal):
        poisoned = attack.apply_loop(
            g, jax.tree.map(lambda l, i=int(i): l[i], stacked))
        want = jax.tree.map(lambda l, p, i=int(i): l.at[i].set(p),
                            want, poisoned)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_server_masked_apply_matches_oracle_end_to_end():
    """A full vectorized experiment with the masked ``_apply_attacks``
    must equal the same experiment routed through the kept per-client
    twin (``_apply_attacks_loop``) — bit-for-bit global params."""
    cfg = _cfg()
    train, test = generate(1200, 300, seed=3)

    def build():
        rng = np.random.default_rng(3)
        malicious = pick_malicious(cfg.n_ues, cfg.n_malicious, rng)
        clients = partition(train, cfg.n_ues, rng, malicious)
        return FeelServer(cfg, clients, test, rng,
                          scenario=atk.model_poison(-1.0))

    a, b = build(), build()
    b._apply_attacks = b._apply_attacks_loop
    for t in range(2):
        a.run_round(t)
        b.run_round(t)
        for la, lb in zip(jax.tree.leaves(a.params),
                          jax.tree.leaves(b.params)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------- #
# Satellite: property tests for attack invariants (hypothesis_compat).
# ---------------------------------------------------------------------- #
@given(st.integers(0, 1000), st.floats(0.05, 1.0))
@settings(max_examples=15, deadline=None)
def test_label_flip_touches_only_source_rows_exact_count(seed, frac):
    """Label flip touches only source-class rows and flips exactly
    round(flip_fraction * n_source) of them; features untouched."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(20, 200))
    y = rng.integers(0, 10, n).astype(np.int32)
    x = rng.random((n, 4)).astype(np.float32)
    attack = atk.LabelFlip(((6, 2),), flip_fraction=frac)
    x2, y2 = attack.poison(x, y, rng)
    changed = np.flatnonzero(y2 != y)
    assert (y[changed] == 6).all()                    # only source rows
    assert (y2[changed] == 2).all()                   # flipped to target
    n_src = int((y == 6).sum())
    want = n_src if frac >= 1.0 else int(np.round(frac * n_src))
    assert changed.size == want
    np.testing.assert_array_equal(x2, x)              # labels only


@given(st.integers(0, 1000), st.floats(0.1, 0.9))
@settings(max_examples=10, deadline=None)
def test_label_flip_batched_twin_matches_host(seed, frac):
    """The jnp twin applied to the stacked padded layout == the per-client
    host oracle, given the same float32 draws."""
    rng = np.random.default_rng(seed)
    train, _ = generate(800, 50, seed=seed % 7)
    clients = partition(train, 4, rng)
    padded = pad_clients(clients, multiple_of=50)
    mal = np.array([True, False, True, False])
    attack = atk.LabelFlip(((6, 2), (8, 4)), flip_fraction=frac)
    u = np.zeros(padded.y.shape, np.float32)
    want = padded.y.copy()
    for i, c in enumerate(clients):
        ui = attack.draw(rng, c.data.x, c.data.y)
        u[i, :c.size] = ui
        if mal[i]:
            _, yi = attack.apply_host(c.data.x, c.data.y, ui)
            want[i, :c.size] = yi
    _, got = attack.apply_rows(padded.x, padded.y, padded.mask, mal, u)
    np.testing.assert_array_equal(np.asarray(got), want)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_free_rider_update_equals_global_params(seed):
    """scale=0: the uploaded update IS the (reference) global model."""
    g, stacked = _random_stack(seed, 3)
    out = atk.ModelAttack(scale=0.0).apply_loop(
        g, jax.tree.map(lambda l: l[0], stacked))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_sign_flip_is_involution(seed):
    """Applying the scale=-1 attack twice recovers the local model (up to
    float rounding of g + (g - l))."""
    g, stacked = _random_stack(seed, 1)
    l = jax.tree.map(lambda x: x[0], stacked)
    attack = atk.ModelAttack(scale=-1.0)
    twice = attack.apply_loop(g, attack.apply_loop(g, l))
    for a, b in zip(jax.tree.leaves(twice), jax.tree.leaves(l)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@given(st.integers(0, 1000), st.floats(0.1, 2.0))
@settings(max_examples=10, deadline=None)
def test_noise_attack_preserves_labels_and_shapes(seed, sigma):
    rng = np.random.default_rng(seed)
    x = rng.random((30, 8)).astype(np.float32)
    y = rng.integers(0, 10, 30).astype(np.int32)
    attack = atk.FeatureNoise(sigma=sigma)
    x2, y2 = attack.poison(x, y, np.random.default_rng(seed + 1))
    assert x2.shape == x.shape and x2.dtype == x.dtype
    np.testing.assert_array_equal(y2, y)              # labels preserved
    assert (x2 >= 0.0).all() and (x2 <= 1.0).all()    # stays in-domain
    assert np.any(x2 != x)
    # batched twin: noise lands only on malicious rows' REAL samples
    K, S = 3, 40
    xs = rng.random((K, S, 8)).astype(np.float32)
    valid = np.zeros((K, S), np.float32)
    valid[:, :25] = 1.0
    xs[:, 25:] = 0.0                                  # padding is zero
    eps = attack.draw(np.random.default_rng(seed + 2), xs, None)
    mal = np.array([True, False, True])
    got, _ = attack.apply_rows(xs, np.zeros((K, S), np.int32), valid,
                               mal, eps)
    got = np.asarray(got)
    np.testing.assert_array_equal(got[1], xs[1])      # honest untouched
    np.testing.assert_array_equal(got[:, 25:], xs[:, 25:])  # padding zero
    assert np.any(got[0, :25] != xs[0, :25])


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_pick_malicious_rng_determinism(seed):
    a = pick_malicious(50, 5, np.random.default_rng(seed))
    b = pick_malicious(50, 5, np.random.default_rng(seed))
    np.testing.assert_array_equal(a, b)
    assert a.size == 5 and np.unique(a).size == 5
    assert (a >= 0).all() and (a < 50).all()


def test_malicious_schedules():
    """Intermittent gates whole rounds; the colluding round-robin
    partitions the malicious set across a period."""
    mal = np.array([True, True, False, True, False])
    rank = np.array([0, 1, -1, 2, -1])
    inter = atk.MaliciousSchedule("intermittent", period=3, duty=1)
    assert (inter.active(0, mal, rank) == mal).all()
    assert not inter.active(1, mal, rank).any()
    assert not inter.active(2, mal, rank).any()
    rr = atk.MaliciousSchedule("roundrobin", period=2, duty=2)
    a0, a1 = rr.active(0, mal, rank), rr.active(1, mal, rank)
    assert not (a0 & a1).any()                        # disjoint groups
    np.testing.assert_array_equal(a0 | a1, mal)       # cover the set
    np.testing.assert_array_equal(rr.active(2, mal, rank), a0)  # periodic


# ---------------------------------------------------------------------- #
# Satellite: the model_poison_scale x no_attack legacy contract.
# ---------------------------------------------------------------------- #
def test_legacy_model_poison_replaces_data_attack():
    scn = atk.legacy_scenario((6, 2), False, -1.0, 0.0)
    assert scn.data is None and scn.model.scale == -1.0
    assert scn.data_key() == "mal_only"               # clean partition
    flip = atk.legacy_scenario((6, 2), False, None, 0.0)
    assert isinstance(flip.data, atk.LabelFlip) and flip.model is None


def test_legacy_no_attack_wins_over_model_poison():
    """no_attack=True disables EVERYTHING, including model poisoning: the
    run is the benign control (no malicious flags set)."""
    scn = atk.legacy_scenario((6, 2), True, -1.0, 0.5)
    assert scn.benign and scn.watch == (6, 2)
    r = run_experiment("dqs", (6, 2), cfg=_cfg(), seed=1, no_attack=True,
                       model_poison_scale=-1.0, **KW)
    clean = run_experiment("dqs", (6, 2), cfg=_cfg(), seed=1,
                           no_attack=True, **KW)
    assert r["malicious_selected"] == [0] * KW["rounds"]
    np.testing.assert_allclose(r["acc"], clean["acc"], atol=1e-7)
    # benign run: Eq. 1 never separates anyone
    assert all(np.isnan(g) for g in r["rep_gap"])


def test_legacy_model_poison_branch_equals_explicit_scenario():
    """The legacy knob path and the equivalent explicit scenario are the
    same experiment (both on run_experiment and run_sweep)."""
    legacy = run_experiment("dqs", (8, 4), cfg=_cfg(), seed=0,
                            model_poison_scale=-1.0, **KW)
    scn = dataclasses.replace(atk.model_poison(-1.0), watch=(8, 4))
    explicit = run_experiment("dqs", cfg=_cfg(), seed=0, scenario=scn,
                              **KW)
    np.testing.assert_allclose(legacy["acc"], explicit["acc"], atol=1e-7)
    np.testing.assert_allclose(legacy["source_acc"],
                               explicit["source_acc"], atol=1e-6)
    sweep = run_sweep(["dqs"], seeds=[0], attack_pairs=[(8, 4)],
                      cfg=_cfg(), model_poison_scale=-1.0, **KW)
    np.testing.assert_allclose(sweep.runs[0]["acc"], legacy["acc"],
                               atol=1e-7)


def test_scenario_supersedes_legacy_knobs():
    with pytest.raises(AssertionError):
        run_experiment("dqs", cfg=_cfg(), seed=0, scenario="sign_flip",
                       model_poison_scale=-1.0, **KW)
    with pytest.raises(AssertionError):
        run_sweep(["dqs"], seeds=[0], scenarios=["sign_flip"],
                  cfg=_cfg(), no_attack=True, **KW)
    # a conflicting pair axis fails loudly instead of being dropped
    with pytest.raises(AssertionError):
        run_experiment("dqs", (8, 4), cfg=_cfg(), seed=0,
                       scenario="sign_flip", **KW)
    with pytest.raises(AssertionError):
        run_sweep(["dqs"], seeds=[0], attack_pairs=[(8, 4)],
                  scenarios=["sign_flip"], cfg=_cfg(), **KW)
    # ... as does an explicit watch_class on a scenario-driven server
    train, test = generate(800, 150, seed=0)
    rng = np.random.default_rng(0)
    clients = partition(train, 4, rng)
    with pytest.raises(AssertionError):
        FeelServer(FeelConfig(n_ues=4, n_malicious=0), clients, test,
                   rng, scenario="sign_flip", watch_class=3)


# ---------------------------------------------------------------------- #
# Registry / shim / metric helpers.
# ---------------------------------------------------------------------- #
def test_registry_and_shim():
    assert atk.as_scenario("sign_flip") is atk.SCENARIOS["sign_flip"]
    pair = atk.as_scenario((6, 2))
    assert pair.data.pairs == ((6, 2),) and pair.watch == (6, 2)
    assert atk.as_scenario(pair) is pair
    with pytest.raises(AssertionError):
        atk.register(atk.model_poison(-1.0))          # duplicate name
    with pytest.raises(TypeError):
        atk.as_scenario(12)
    # data attacks compose with round schedules: the server's twin-array
    # gather substitutes a clean copy of the poisoned data in OFF rounds
    # (tests/test_task_lm.py pins the round-gating behaviour end to end)
    scn = atk.intermittent(atk.label_flip(6, 2), 2)
    assert scn.data is not None and scn.schedule.period == 2


def test_recovery_rounds_metric():
    assert atk.recovery_rounds([np.nan, np.nan]) == -1
    assert atk.recovery_rounds([]) == -1
    assert atk.recovery_rounds([0.9, 0.8, 0.4, 0.2]) == 2
    assert atk.recovery_rounds([0.9, 0.2, 0.6, 0.1]) == 3
    assert atk.recovery_rounds([0.1, 0.2, 0.3]) == 0
    assert atk.recovery_rounds([0.2, 0.9], threshold=0.95) == 0
    # final round still at/above threshold == not recovered within the
    # horizon: the return equals the curve length, never less
    assert atk.recovery_rounds([0.9, 0.9, 0.9]) == 3
    assert atk.recovery_rounds([0.1, 0.1, 0.9]) == 3


def test_reputation_gap_metric():
    rep = np.array([1.0, 0.2, 0.8, 0.4])
    mal = np.array([False, True, False, True])
    assert atk.reputation_gap(rep, mal) == pytest.approx(0.9 - 0.3)
    assert np.isnan(atk.reputation_gap(rep, np.zeros(4, bool)))


# ---------------------------------------------------------------------- #
# registry completeness (auto-generated from SCENARIOS — a new entry is
# exercised here with zero test edits; repro.check pins the coverage)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("name", sorted(atk.SCENARIOS))
def test_scenario_registry_contract(name):
    """Every registered scenario satisfies the AttackScenario interface:
    registry key == name, frozen/hashable (partition-cache identity),
    well-typed components, and a live schedule."""
    scn = atk.SCENARIOS[name]
    assert scn.name == name
    hash(scn)                                   # frozen dataclass
    assert hash(scn.data_key()) is not None     # partition-cache key
    assert scn.benign == (scn.data is None and scn.model is None
                          and scn.report is None)
    if scn.data is not None:
        assert (hasattr(scn.data, "poison")
                or hasattr(scn.data, "poison_tokens"))
    if scn.model is not None:
        assert hasattr(scn.model, "apply_stacked")
        assert hasattr(scn.model, "apply_loop")
    if scn.report is not None:
        assert hasattr(scn.report, "apply")
    mal = np.array([True, False, True, False])
    rank = np.array([0, -1, 1, -1])
    for t in range(3):
        act = scn.schedule.active(t, mal, rank)
        assert act.dtype == bool and act.shape == mal.shape
        assert not act[~mal].any()              # honest UEs never act
    if isinstance(scn.data, (atk.LabelFlip, atk.TokenFlip)):
        assert scn.watch == scn.data.pairs[0]
