"""Multi-run sweep (run_sweep) vs run_experiment, its one-run case: each
run of a sweep equals its twin, the sweep's caches are shared across
runs, and the tidy-table / averaged() API contracts hold."""
import numpy as np
import pytest

from repro.core import attacks as atk
from repro.core.poisoning import EASY_PAIR
from repro.federated import simulation
from repro.federated.simulation import (SweepResult, averaged,
                                        run_experiment, run_sweep)

KW = dict(n_train=3000, n_test=400, rounds=4)


@pytest.fixture(scope="module")
def sweep() -> SweepResult:
    return run_sweep(["dqs", "random"], seeds=[0, 1],
                     attack_pairs=[EASY_PAIR], **KW)


def test_sweep_matches_sequential_run_experiment(sweep):
    """Every run of the sweep must reproduce its ``run_experiment`` twin:
    same RNG streams, same schedules, same accuracy curves, although the
    sweep shares its dataset, partition and layout caches across runs."""
    for run in sweep.runs:
        ref = run_experiment(run["policy"], run["attack_pair"],
                             seed=run["seed"], **KW)
        np.testing.assert_allclose(run["acc"], ref["acc"], atol=1e-7)
        np.testing.assert_allclose(run["source_acc"], ref["source_acc"],
                                   atol=1e-6)
        np.testing.assert_allclose(run["attack_success"],
                                   ref["attack_success"], atol=1e-6)
        np.testing.assert_allclose(run["rep_gap"], ref["rep_gap"],
                                   atol=1e-7)
        assert run["recovery_rounds"] == ref["recovery_rounds"]
        assert run["malicious_selected"] == ref["malicious_selected"]
        np.testing.assert_allclose(run["objective"], ref["objective"],
                                   atol=1e-9)
        assert run["malicious"] == ref["malicious"]
        np.testing.assert_allclose(
            run["final_reputation_honest"], ref["final_reputation_honest"],
            atol=1e-7)
        np.testing.assert_allclose(
            run["final_reputation_malicious"],
            ref["final_reputation_malicious"], atol=1e-7)


def test_sweep_shares_layouts_across_runs(monkeypatch):
    """The sweep's caches: one device layout per (task, seed, data attack),
    and every run on that partition is built with the same ``CohortData``
    object. The two model-space scenarios share the clean partition."""
    built, handed = [], []
    build, server = simulation.build_cohort_data, simulation.FeelServer

    def counting_build(*args, **kw):
        built.append(build(*args, **kw))
        return built[-1]

    def recording_server(*args, **kw):
        handed.append(kw["cohort_data"])
        return server(*args, **kw)

    monkeypatch.setattr(simulation, "build_cohort_data", counting_build)
    monkeypatch.setattr(simulation, "FeelServer", recording_server)
    res = run_sweep(["dqs", "random"], seeds=[0],
                    scenarios=["free_rider", "sign_flip", "flip_6to2"],
                    n_train=3000, n_test=400, rounds=1)
    assert len(built) == 2
    assert len(handed) == len(res.runs) == 6
    by_data = {}
    for run, cd in zip(res.runs, handed):
        data_key = atk.as_scenario(run["scenario"]).data_key()
        by_data.setdefault(data_key, []).append(cd)
    assert len(by_data) == 2
    for cds in by_data.values():
        assert all(cd is cds[0] for cd in cds)
    assert {id(cd) for cd in handed} == {id(cd) for cd in built}


def test_sweep_tidy_table(sweep):
    """rows is one record per (policy, seed, round) with the per-round
    metrics; mean_curve reduces over seeds."""
    assert len(sweep.rows) == 2 * 2 * KW["rounds"]
    r0 = sweep.rows[0]
    for field in ("policy", "seed", "scenario", "attack_pair", "round",
                  "acc", "source_acc", "attack_success",
                  "malicious_selected", "objective", "rep_gap", "forced"):
        assert field in r0, field
    curve = sweep.mean_curve("acc", policy="dqs")
    assert curve.shape == (KW["rounds"],)
    manual = np.mean([r["acc"] for r in sweep.runs
                      if r["policy"] == "dqs"], axis=0)
    np.testing.assert_allclose(curve, manual)
    assert len(sweep.select(policy="random", seed=1)) == 1


def test_partition_shared_across_policies(sweep):
    """Policies of the same (seed, attack pair) must see the same
    partition: identical malicious sets."""
    by_seed = {}
    for run in sweep.runs:
        by_seed.setdefault(run["seed"], []).append(run["malicious"])
    for mal_lists in by_seed.values():
        assert all(m == mal_lists[0] for m in mal_lists)


def test_averaged_runs_on_sweep():
    out = averaged("dqs", EASY_PAIR, n_runs=2, **KW)
    assert len(out["acc"]) == KW["rounds"]
    assert len(out["malicious_selected"]) == KW["rounds"]
    assert np.isfinite(out["rep_gap"])


def test_sweep_loop_engine_falls_back():
    """engine='loop' executes sequentially but returns the same table."""
    res = run_sweep(["dqs"], seeds=[0], attack_pairs=[EASY_PAIR],
                    engine="loop", n_train=3000, n_test=200, rounds=2)
    assert len(res.rows) == 2
    ref = run_experiment("dqs", EASY_PAIR, seed=0, engine="loop",
                         n_train=3000, n_test=200, rounds=2)
    np.testing.assert_allclose(res.runs[0]["acc"], ref["acc"], atol=1e-7)


def test_mean_curve_nan_aware_watch_metrics():
    """Regression (defense-plane PR): NaN watch-metric rows — a watch-less
    scenario's attack_success, undefined det_precision — must not poison
    cross-run means, and all-NaN slices stay NaN without numpy's all-NaN
    RuntimeWarning."""
    import warnings
    res = run_sweep(["dqs"], seeds=[0], scenarios=["none", "flip_6to2"],
                    n_train=1200, n_test=300, rounds=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # any RuntimeWarning fails
        # "none" has no watched pair -> attack_success all NaN
        none_curve = res.mean_curve("attack_success", scenario="none")
        assert np.isnan(none_curve).all()
        # mixing the NaN run with the watched run keeps the finite values
        mixed = res.mean_curve("attack_success")
        flip = res.mean_curve("attack_success", scenario="flip_6to2")
        np.testing.assert_allclose(mixed, flip)
        assert np.isfinite(mixed).all()
        # the bundle API rides the same reduction
        out = res.averaged(scenario="none")
        assert np.isfinite(out["acc"]).all()
        assert np.isnan(out["attack_success"]).all()


def test_sweep_rows_carry_defense_fields(sweep):
    r0 = sweep.rows[0]
    for field in ("defense", "n_clipped", "n_rejected", "n_flagged",
                  "det_precision", "det_recall"):
        assert field in r0, field
    assert r0["defense"] == "none"
