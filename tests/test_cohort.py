"""Vectorized cohort engine vs the sequential loop oracle, the
padding/masking contract, the degenerate-schedule fallback, and the Eq. 1
reputation ordering."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core.poisoning import EASY_PAIR, LabelFlipAttack, pick_malicious
from repro.core.reputation import ReputationTracker
from repro.data.partition import pad_clients, partition
from repro.data.synthetic_mnist import generate
from repro.federated import cohort
from repro.federated.server import FeelServer
from repro.federated.simulation import run_experiment
from repro.federated.task import MnistTask
from repro.models.common import count_accuracy
from repro.models.mlp import (mlp_accuracy, mlp_init, mlp_sgd_epoch,
                              mlp_sgd_epoch_masked)

KW = dict(n_train=3000, n_test=400, rounds=5)


def _k10_cfg():
    return FeelConfig(n_ues=10, n_malicious=2)


# ---------------------------------------------------------------------- #
# Tentpole acceptance: the engines produce the same experiment.
# ---------------------------------------------------------------------- #
def test_vectorized_matches_loop_fixed_seed_k10():
    """Identical accuracy curve (within 1e-5 per round) on a fixed-seed
    K=10 experiment — the loop engine is the correctness oracle."""
    a = run_experiment("dqs", EASY_PAIR, cfg=_k10_cfg(), seed=0,
                       engine="loop", **KW)
    b = run_experiment("dqs", EASY_PAIR, cfg=_k10_cfg(), seed=0,
                       engine="vectorized", **KW)
    np.testing.assert_allclose(b["acc"], a["acc"], atol=1e-5)
    np.testing.assert_allclose(b["source_acc"], a["source_acc"], atol=1e-5)
    # same schedules round for round -> same malicious-selection counts
    assert b["malicious_selected"] == a["malicious_selected"]
    assert b["final_reputation_malicious"] == pytest.approx(
        a["final_reputation_malicious"], abs=1e-5)


# ---------------------------------------------------------------------- #
# Padding / masking contract
# ---------------------------------------------------------------------- #
def test_masked_epoch_padding_is_a_no_op():
    """Training on a zero-padded, masked dataset reproduces the unpadded
    epoch: padding batches contribute exactly zero gradient."""
    rng = np.random.default_rng(0)
    n, d, pad_to = 100, 784, 250
    x = rng.random((n, d)).astype(np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    params = mlp_init(jax.random.PRNGKey(0))

    plain = mlp_sgd_epoch(params, jnp.asarray(x), jnp.asarray(y), 0.1, 50)

    xp = np.zeros((pad_to, d), np.float32)
    yp = np.zeros(pad_to, np.int32)
    m = np.zeros(pad_to, np.float32)
    xp[:n], yp[:n], m[:n] = x, y, 1.0
    masked = mlp_sgd_epoch_masked(params, jnp.asarray(xp), jnp.asarray(yp),
                                  jnp.asarray(m), 0.1, 50)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(masked)):
        np.testing.assert_allclose(a, b, atol=1e-7)


def test_pad_clients_layout():
    train, _ = generate(1500, 100, seed=0)
    rng = np.random.default_rng(0)
    clients = partition(train, 6, rng)
    padded = pad_clients(clients, multiple_of=50)
    assert padded.x.shape[0] == 6
    assert padded.max_samples % 50 == 0
    assert padded.max_samples >= max(c.size for c in clients)
    for k, c in enumerate(clients):
        n = c.size
        assert padded.sizes[k] == n
        np.testing.assert_array_equal(padded.x[k, :n], c.data.x)
        np.testing.assert_array_equal(padded.y[k, :n], c.data.y)
        assert padded.mask[k, :n].all()
        assert not padded.mask[k, n:].any()
        assert not padded.x[k, n:].any()


def test_cohort_eval_matches_subset_eval():
    """The vmapped masked test evaluation equals per-model subset scoring."""
    _, test = generate(200, 300, seed=1)
    task = MnistTask()
    params = [mlp_init(jax.random.PRNGKey(i)) for i in range(3)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *params)
    masks = np.stack([np.isin(test.y, [0, 1, 2]),
                      np.isin(test.y, [5]),
                      np.ones_like(test.y, bool)]).astype(np.float32)
    got = count_accuracy(cohort.cohort_eval(
        task, stacked, task.eval_inputs(test), jnp.asarray(test.y),
        jnp.asarray(masks)))
    for i, p in enumerate(params):
        m = masks[i].astype(bool)
        want = float(mlp_accuracy(p, jnp.asarray(test.x[m]),
                                  jnp.asarray(test.y[m])))
        assert got[i] == pytest.approx(want, abs=1e-6)


# ---------------------------------------------------------------------- #
# Size-bucketed sub-cohorts: parity with the single-bucket path and the
# padding-waste reclaim the ROADMAP item targets.
# ---------------------------------------------------------------------- #
def test_bucket_levels_quantized():
    from repro.data.partition import assign_buckets, bucket_levels
    levels = bucket_levels(1500, 3, multiple_of=50)
    np.testing.assert_array_equal(levels, [500, 1000, 1500])
    # quantized step: nearby maxima share the same level grid (compile
    # cache stays warm across seeds)
    np.testing.assert_array_equal(bucket_levels(1451, 3, 50), levels)
    np.testing.assert_array_equal(
        assign_buckets(np.array([50, 500, 501, 1000, 1500]), levels),
        [0, 0, 1, 1, 2])


def test_pad_clients_bucketed_layout():
    from repro.data.partition import pad_clients_bucketed
    train, _ = generate(4000, 100, seed=0)
    rng = np.random.default_rng(0)
    clients = partition(train, 8, rng)
    buckets = pad_clients_bucketed(clients, n_buckets=3, multiple_of=50)
    seen = np.concatenate([ids for ids, _ in buckets])
    assert sorted(seen) == list(range(8))        # every client, exactly once
    sizes = np.array([c.size for c in clients])
    for ids, pd in buckets:
        assert (pd.sizes == sizes[ids]).all()
        assert pd.max_samples >= sizes[ids].max()
        for j, k in enumerate(ids):
            n = clients[k].size
            np.testing.assert_array_equal(pd.x[j, :n], clients[k].data.x)
            assert pd.mask[j, :n].all() and not pd.mask[j, n:].any()
    # bucketed padding is never worse than the single global pad
    total_bucketed = sum(len(ids) * pd.max_samples for ids, pd in buckets)
    global_pad = pad_clients(clients, multiple_of=50)
    assert total_bucketed <= 8 * global_pad.max_samples


def test_bucketed_k500_parity_and_padding_waste():
    """K=500 regression for the ROADMAP item: the bucketed engine must
    reproduce the single-bucket vectorized accuracy curve while cutting
    per-round padded-sample waste below 1.25x (single global pad wastes
    ~1.5-2x after the partition pool truncates)."""
    from repro.core.poisoning import pick_malicious
    cfg = FeelConfig(n_ues=500, n_malicious=50, rounds=2)
    train, test = generate(50_000, 400, seed=0)
    rng = np.random.default_rng(0)
    mal = pick_malicious(cfg.n_ues, cfg.n_malicious, rng)
    clients = partition(train, cfg.n_ues, rng, mal,
                        LabelFlipAttack(*EASY_PAIR))
    curves, wastes = {}, {}
    for nb in (1, 3):
        server = FeelServer(cfg, clients, test, np.random.default_rng(0),
                            policy="dqs", n_buckets=nb)
        server.run(2)
        curves[nb] = [l.global_acc for l in server.logs]
        wastes[nb] = np.mean(server.pad_waste)
    np.testing.assert_allclose(curves[3], curves[1], atol=1e-5)
    assert wastes[3] < 1.25, wastes
    assert wastes[3] < wastes[1], wastes


# ---------------------------------------------------------------------- #
# The vectorized round's glue (one gather program, cohort_train per bucket,
# one assemble program, cohort_eval, one host read) against the eager
# composition it replaced, kept here as the oracle.
# ---------------------------------------------------------------------- #
def _k10_server(scenario):
    cfg = _k10_cfg()
    scn = atk.as_scenario(scenario)
    train, test = generate(KW["n_train"], KW["n_test"], seed=0)
    rng = np.random.default_rng(0)
    mal = pick_malicious(cfg.n_ues, cfg.n_malicious, rng)
    clients = partition(train, cfg.n_ues, rng, mal, scn.data)
    return FeelServer(cfg, clients, test, rng, scenario=scn)


def _eager_round(srv, sel, t):
    """(padded uploads, acc_local, acc_test) of round ``t`` by the eager
    composition: ``jnp.take`` per bucket, ``cohort_train``, each bucket's
    real rows cut on the host, ``merge_stacks`` into selection order, the
    attacks on the merged stack, ``pad_stacked``, ``cohort_eval``."""
    parts = []
    for bkt, pos, rows in srv._cohort_parts(sel, t):
        idx = jnp.asarray(rows)
        data = {f: jnp.take(a, idx, axis=0) for f, a in bkt["data"].items()}
        stacked_b, acc_b = cohort.cohort_train(
            srv.task, srv.params, data, jnp.take(bkt["mask"], idx, axis=0),
            srv.lr, srv.cfg.local_epochs, srv.batch_size)
        parts.append((pos,
                      jax.tree.map(lambda l, m=pos.size: l[:m], stacked_b),
                      count_accuracy(acc_b)[:pos.size]))
    inv = np.argsort(np.concatenate([p[0] for p in parts]), kind="stable")
    stacked = cohort.merge_stacks([p[1] for p in parts], inv)
    acc_local = np.concatenate([p[2] for p in parts])[inv]
    scn, mal = srv.scenario, srv._active_malicious(sel, t)
    if scn.model is not None and mal.any():
        stacked = scn.model.apply_stacked(stacked, srv.params, mal)
    if scn.report is not None:
        acc_local = scn.report.apply(acc_local, mal)
    n_pad = cohort.pad_count(sel.size, srv._N_BUCKET)
    stacked_p = cohort.pad_stacked(stacked, n_pad)
    acc_test = count_accuracy(cohort.cohort_eval(
        srv.task, stacked_p, srv._ex, srv._ey,
        srv._eval_masks(sel, n_pad)))[:sel.size]
    return stacked_p, acc_local, acc_test


@pytest.mark.parametrize("scenario", ["flip_6to2", "flip_6to2_int2",
                                      "sign_flip", "lying_flip_8to4"])
def test_vectorized_round_matches_eager_composition(scenario):
    """Uploads, acc_local and acc_test of three K=10 rounds are bit-equal
    to the eager composition's, under a data attack (always on, and
    scheduled: clean twin rows), a model attack and a report attack."""
    srv = _k10_server(scenario)
    active = 0
    for t in range(3):
        values, sched, sel, forced = srv._schedule_round(t)
        want_up, want_local, want_test = _eager_round(srv, sel, t)
        uploads, weights, acc_local, acc_test, acc_val = \
            srv._train_cohort(sel, t)
        for a, b in zip(jax.tree.leaves(uploads), jax.tree.leaves(want_up)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(acc_local, want_local)
        np.testing.assert_array_equal(acc_test, want_test)
        active += int(srv._active_malicious(sel, t).sum())
        srv._aggregate_uploads(sel, uploads, weights)
        g_acc, g_loss, src_acc, atk_succ = srv._global_metrics()
        srv._finalize_round(t, values, sched, sel, forced, acc_local,
                            acc_test, g_acc, src_acc, atk_succ, acc_val,
                            g_loss)
    assert active > 0          # the attacks had rows to act on


# ---------------------------------------------------------------------- #
# Degenerate-schedule fallback (satellite): the log must describe the
# forced participant set, not the empty schedule.
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ["loop", "vectorized"])
def test_degenerate_schedule_log_reflects_forced_participant(engine):
    train, test = generate(800, 150, seed=2)
    rng = np.random.default_rng(2)
    cfg = FeelConfig(n_ues=4, n_malicious=0, rounds=1)
    clients = partition(train, cfg.n_ues, rng)
    # control="host": this test stubs wireless.cost, which only the host
    # oracle calls (the batched plane bisects from precomputed min rates —
    # its forced-round behaviour is pinned by test_control.py and
    # test_impossible_deadline_forces_round_with_zero_objective below)
    server = FeelServer(cfg, clients, test, rng, engine=engine,
                        control="host")
    # all-infeasible channel draw: every UE costs more than the K-fraction
    # budget, so the scheduler returns the empty schedule
    server.wireless.cost = lambda gains, t_train: np.full(
        cfg.n_ues, cfg.n_ues + 1, float)

    before = server.reputation.values.copy()
    params_before = jax.tree.map(np.asarray, server.params)
    log = server.run_round(0)

    assert log.selected.size == 1
    k = int(log.selected[0])
    assert k == int(np.argmax(log.values))
    # problem (8) had no feasible point: the round is marked forced and its
    # objective is 0.0 — the forced UE's V_k is not credited (accounting
    # regression: the seed reported objective = V_k for infeasible rounds)
    assert log.forced
    assert log.objective == 0.0
    # the forced UE really trained: the global model moved
    moved = any(np.abs(np.asarray(a) - b).max() > 0
                for a, b in zip(jax.tree.leaves(server.params),
                                jax.tree.leaves(params_before)))
    assert moved
    # only the forced participant's reputation was touched
    np.testing.assert_array_equal(np.delete(log.reputations, k),
                                  np.delete(before, k))


def test_impossible_deadline_forces_round_with_zero_objective():
    """A deadline no UE can meet (Eq. 8b infeasible for every UE) makes the
    wireless costs K+1 across the board; every round must come back forced
    with objective 0.0, and a normal deadline must not set the flag."""
    train, test = generate(800, 150, seed=3)
    rng = np.random.default_rng(3)
    cfg = FeelConfig(n_ues=4, n_malicious=0, rounds=2, deadline_s=1e-9)
    clients = partition(train, cfg.n_ues, rng)
    server = FeelServer(cfg, clients, test, rng)
    logs = server.run()
    assert all(l.forced for l in logs)
    assert all(l.objective == 0.0 for l in logs)
    assert all(l.selected.size == 1 for l in logs)

    ok = FeelServer(dataclasses.replace(cfg, deadline_s=300.0), clients,
                    test, np.random.default_rng(3))
    log = ok.run_round(0)
    assert not log.forced
    assert log.objective > 0.0


# ---------------------------------------------------------------------- #
# Eq. 1 reputation ordering (satellite audit): honest UEs must end above
# a poisoner even though the beta1 term penalises above-average reports.
# ---------------------------------------------------------------------- #
def test_reputation_orders_honest_above_poisoner():
    cfg = FeelConfig(n_ues=4)
    tracker = ReputationTracker(cfg)
    everyone = np.arange(4)
    # honest UEs report what the server then measures (acc_local==acc_test);
    # UE 3 is a label-flip poisoner: high self-report, poor test accuracy
    acc_local = np.array([0.85, 0.70, 0.75, 0.90])
    acc_test = np.array([0.85, 0.70, 0.75, 0.30])
    for _ in range(5):
        tracker.update(everyone, acc_local, acc_test)
    assert tracker.values[3] < tracker.values[:3].min()
    # the best honest UE (above-average report, beta1 penalty applies)
    # still outranks the poisoner by a wide margin
    assert tracker.values[0] - tracker.values[3] > 0.5


def test_reputation_beta1_penalises_above_average_reports():
    """Documented Eq. 1 behaviour (see core/reputation.py): with beta2
    silent (report == test), the relative beta1 term alone moves
    above-average reporters down and below-average reporters up."""
    cfg = FeelConfig(n_ues=2, eta=1.0)
    tracker = ReputationTracker(cfg)
    tracker.values[:] = 0.5
    acc = np.array([0.9, 0.5])           # both honest: report == test
    tracker.update(np.arange(2), acc, acc)
    assert tracker.values[0] < 0.5 < tracker.values[1]
