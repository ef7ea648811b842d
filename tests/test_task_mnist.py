"""The MNIST task's global metrics: one jitted program over the
device-resident test set and one host read of its counts, bit-equal to
the eager ``mlp_accuracy`` readings they replaced (kept here as the
oracle), the NaN cases included."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic_mnist import Dataset, generate
from repro.federated.task import MnistTask
from repro.models.mlp import mlp_accuracy, mlp_init, mlp_sgd_epoch
from repro.obs import trace


def _eager_metrics(params, test, ey, watch_class, watch_target):
    """The replaced composition: three un-jitted ``mlp_accuracy``
    readings, the watched class's rows cut out on the host."""
    g_acc = float(mlp_accuracy(params, jnp.asarray(test.x), ey))
    src_acc = atk_succ = float("nan")
    if watch_class is not None:
        m = test.y == watch_class
        if m.any():
            xs = jnp.asarray(test.x[m])
            src_acc = float(mlp_accuracy(params, xs, jnp.asarray(test.y[m])))
            if watch_target is not None:
                tgt = jnp.full(int(m.sum()), watch_target, ey.dtype)
                atk_succ = float(mlp_accuracy(params, xs, tgt))
    return g_acc, float("nan"), src_acc, atk_succ


@pytest.fixture(scope="module")
def data():
    train, test = generate(1500, 400, seed=4)
    init = mlp_init(jax.random.PRNGKey(4))
    trained = init
    for _ in range(2):
        trained = mlp_sgd_epoch(trained, jnp.asarray(train.x),
                                jnp.asarray(train.y), 0.1, 50)
    return test, {"init": init, "trained": trained}


# (watch_class, watch_target, drop the watched class from the test set)
CASES = {
    "watched_pair": (6, 2, False),
    "no_watched_class": (None, None, False),
    "class_absent_from_test": (6, 2, True),
    "no_target": (6, None, False),
}


@pytest.mark.parametrize("model", ["init", "trained"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_global_metrics_match_eager_readings(data, case, model):
    test, params = data
    params = params[model]
    watch_class, watch_target, drop = CASES[case]
    if drop:
        keep = test.y != watch_class
        test = Dataset(test.x[keep], test.y[keep])
    task = MnistTask()
    ei, ey = task.eval_inputs(test), task.unit_targets(test)
    got = task.global_metrics(params, test, ei, ey, watch_class,
                              watch_target)
    want = _eager_metrics(params, test, ey, watch_class, watch_target)
    np.testing.assert_array_equal(np.array(got), np.array(want))
    assert np.isnan(got[2]) == (watch_class is None or drop)
    assert np.isnan(got[3]) == (np.isnan(got[2]) or watch_target is None)


def test_global_metrics_read_the_chip_once(data):
    test, params = data
    task = MnistTask()
    ei, ey = task.eval_inputs(test), task.unit_targets(test)
    trace.configure(enabled=True)
    try:
        task.global_metrics(params["trained"], test, ei, ey, 6, 2)
        counters = trace.tracer().metrics.snapshot()["counters"]
    finally:
        trace.configure(enabled=False)
    assert counters["data.host_reads"] == 1
