"""Operation counts from shapes against XLA's own count on the CPU."""
import jax
import jax.numpy as jnp
import pytest

import counts

LAYERS = [784, 64, 10]


def _params(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (784, 64)), "b1": jnp.zeros(64),
            "w2": jax.random.normal(k2, (64, 10)), "b2": jnp.zeros(10)}


def _forward(p, x):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    return h @ p["w2"] + p["b2"]


def _loss(p, x, y):
    logits = _forward(p, x)
    ll = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, -1) - ll)


def _step(p, x, y):
    g = jax.grad(_loss)(p, x, y)
    return jax.tree.map(lambda a, b: a - 0.1 * b, p, g)


def _xla_flops(fn, *args):
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"])


@pytest.mark.parametrize("batch", [50, 200])
def test_train_step_flops_match_xla(batch):
    p = _params(jax.random.key(0))
    x = jnp.ones((batch, 784))
    y = jnp.zeros((batch,), jnp.int32)
    ours = counts.mlp_train_flops(LAYERS) * batch
    # XLA also counts the elementwise work, which counts.py leaves out
    assert _xla_flops(_step, p, x, y) == pytest.approx(ours, rel=0.02)


@pytest.mark.parametrize("batch", [50, 1000])
def test_forward_flops_match_xla(batch):
    p = _params(jax.random.key(1))
    x = jnp.ones((batch, 784))
    ours = counts.mlp_forward_flops(LAYERS) * batch
    assert _xla_flops(_forward, p, x) == pytest.approx(ours, rel=0.02)


def test_param_count_is_the_papers_mlp():
    assert counts.mlp_params(LAYERS) == 50_890


def test_prefilter_bytes_per_round():
    assert counts.prefilter_round(5, 1000, 2)["prefilter_bytes"] == \
        5 * 1000 * 77 * 2
