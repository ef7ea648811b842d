"""The streamed cohort path (federated/cohort.py, FeelServer
``_run_cohort_streamed``): a model too large to stack trains one client at
a time, each upload scored for Eq. 1 and folded into a running FedAvg sum.

The tests steer the path choice by the device's memory alone
(``cohort.device_memory_bytes``), the one thing it observes besides the
model's bytes and the cohort's rows.
"""
import numpy as np
import pytest

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core.poisoning import pick_malicious
from repro.federated import cohort
from repro.federated.server import FeelServer
from repro.federated.task import as_task
from repro.obs import trace


def _server(task="lm_tiny", scenario="token_flip_1to5", defense=None,
            n_ues=8, n_train=960, n_test=240):
    tsk = as_task(task)
    cfg = FeelConfig(n_ues=n_ues, n_malicious=2, task=tsk.name)
    rng = np.random.default_rng(0)
    train, test = tsk.generate_data(n_train, n_test, 0)
    scn = atk.as_scenario(scenario)
    mal = pick_malicious(cfg.n_population, cfg.n_malicious, rng)
    clients = tsk.partition_clients(train, cfg.n_population, rng,
                                    None if scn.benign else mal, scn.data)
    return FeelServer(cfg, clients, test, rng, scenario=scn, defense=defense,
                      task=tsk)


@pytest.fixture
def no_room(monkeypatch):
    """A device too small to stack even one model copy."""
    monkeypatch.setattr(cohort, "device_memory_bytes", lambda: 1)


def _rounds(srv, n=2):
    logs = [srv.run_round(t) for t in range(n)]
    return logs, {k: np.asarray(v) for k, v in
                  _flat(srv.params).items()}


def _flat(params):
    import jax
    return {jax.tree_util.keystr(p): l for p, l in
            jax.tree_util.tree_leaves_with_path(params)}


def test_streamed_round_matches_stacked_on_lm_tiny(monkeypatch):
    """Two DQS rounds of lm_tiny under a token flip: the streamed path
    selects what the stacked path selects, its Eq. 1 inputs give the same
    reputations to float32 rounding, and its global model lies within
    1e-5 of each leaf's norm of the stacked one (the same steps; the sums
    run in another order, per client against vmapped)."""
    a_logs, a = _rounds(_server())
    monkeypatch.setattr(cohort, "device_memory_bytes", lambda: 1)
    srv = _server()
    called = []
    run = srv._run_cohort_streamed
    srv._run_cohort_streamed = lambda sel, t: called.append(t) or run(sel, t)
    b_logs, b = _rounds(srv)
    assert called == [0, 1]
    for x, y in zip(a_logs, b_logs):
        assert np.array_equal(np.sort(x.selected), np.sort(y.selected))
        np.testing.assert_allclose(x.reputations, y.reputations, atol=1e-6)
        assert abs(x.global_loss - y.global_loss) < 1e-5
    for k in a:
        gap = np.linalg.norm(a[k] - b[k])
        assert gap <= 1e-5 * max(np.linalg.norm(a[k]), 1e-12), k


def test_streamed_round_emits_client_spans_and_counters(no_room):
    srv = _server()
    trace.configure(enabled=True, ring_size=trace._RING)
    try:
        log = srv.run_round(0)
        names = [s.name for s in trace.tracer().spans]
        counters = trace.tracer().metrics.snapshot()["counters"]
    finally:
        trace.configure(enabled=False)
    n = len(log.selected)
    assert names.count("train.client") == n == names.count("eval.upload")
    assert counters["cohort.streamed"] == n
    sizes = [srv.clients[k].size for k in log.selected]
    assert counters["train.tokens"] == sum(sizes) * 32 * srv.cfg.local_epochs


@pytest.mark.parametrize("spec", [dict(defense="trimmed_mean"),
                                  dict(scenario="sign_flip")])
def test_streamed_path_refuses_what_needs_stacked_uploads(no_room, spec):
    """A robust defense or a model-space attack needs every upload at
    once; on the streamed path it raises rather than run elsewhere."""
    srv = _server(**spec)
    with pytest.raises(ValueError, match="stacked uploads"):
        srv.run_round(0)


def test_mnist_round_keeps_the_stacked_path():
    """The §V MLP's cohort (50,890 parameters, 50 UEs) never streams."""
    srv = _server("mnist_mlp", "flip_6to2", n_ues=50, n_train=6000,
                  n_test=1000)
    assert not srv._streams(50)
    srv._run_cohort_streamed = None           # would raise if reached
    srv.run_round(0)


def test_the_moonlight_share_streams_on_a_16_gb_chip(monkeypatch):
    monkeypatch.setattr(cohort, "device_memory_bytes", lambda: 16 * 2**30)
    share = 568_484_608 * 4
    assert cohort.streams(share, 1)            # even one row does not fit
    assert not cohort.streams(50_890 * 4, cohort.pad_count(50))
