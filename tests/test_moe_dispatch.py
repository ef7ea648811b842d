"""MoE dispatch paths: the group-local (§Perf iteration 3) path must be
exactly equivalent to the global path whenever capacity admits every token,
for any grouping that divides the tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import get, reduced
from repro.models import moe as moe_mod


def _cfg(cf=8.0, groups=0, **kw):
    cfg = reduced(get("qwen2-moe-a2.7b"))
    return dataclasses.replace(
        cfg, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=cf,
                                dispatch_groups=groups, **kw))


@given(st.sampled_from([1, 2, 4, 8]), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_grouped_equals_global_no_drops(g, seed):
    cfg0 = _cfg(groups=0)
    cfgg = _cfg(groups=g)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg0)
    x = jax.random.normal(jax.random.PRNGKey(seed), (8, 16, cfg0.d_model))
    y0, a0 = moe_mod.moe_apply(cfg0, p, x)
    yg, ag = moe_mod.moe_apply(cfgg, p, x)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(yg),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(a0["balance"]), float(ag["balance"]),
                               rtol=1e-5)


def test_grouped_finite_under_drops():
    cfg = _cfg(cf=0.5, groups=4)      # force capacity overflow
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
    y, aux = moe_mod.moe_apply(cfg, p, x)
    assert bool(jnp.all(jnp.isfinite(y)))
    assert bool(jnp.isfinite(aux["balance"]))


def test_grouped_gradients_flow():
    cfg = _cfg(groups=2)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, cfg.d_model))

    def loss(p):
        y, aux = moe_mod.moe_apply(cfg, p, x)
        return jnp.sum(jnp.square(y)) + aux["balance"]

    g = jax.grad(loss)(p)
    norms = [float(jnp.linalg.norm(v.astype(jnp.float32)))
             for v in jax.tree.leaves(g)]
    assert all(np.isfinite(n) for n in norms)
    assert sum(norms) > 0


def test_model_poison_signflip():
    from repro.core.poisoning import ModelPoisonAttack
    g = {"w": jnp.ones((3,))}
    l = {"w": jnp.asarray([2.0, 0.0, 1.0])}
    out = ModelPoisonAttack(scale=-1.0).apply(g, l)
    np.testing.assert_allclose(np.asarray(out["w"]), [0.0, 2.0, 1.0])


def test_capacity_rounding():
    cfg = _cfg()
    assert moe_mod.capacity(64, cfg) % 8 == 0
    assert moe_mod.capacity(1, cfg) >= 8


def test_capacity_layer_reports_no_held_expert_work():
    """The capacity layer's stats carry its Switch balance loss and zero
    held-expert pairs and load, so layer stacks combine them with the
    dropless layer's alike."""
    cfg = _cfg(groups=0)
    p = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, cfg.d_model))
    _, st = moe_mod.moe_apply(cfg, p, x)
    assert set(st) == {"balance", "pairs", "load_max"}
    assert float(st["balance"]) > 0
    assert float(st["pairs"]) == 0 and float(st["load_max"]) == 0
    both = moe_mod.add_aux(st, st)
    assert float(both["balance"]) == 2 * float(st["balance"])
