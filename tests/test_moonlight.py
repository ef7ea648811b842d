"""Moonlight-16B-A3B's layers against the plain reference
(``models/reference_moonlight.py``) at a small size on seeded random
weights: d_model 64, 8 routed experts of which 4 are held, 3 per token,
windows of 32 tokens. Both sides run at float32 under ``highest``.

Tolerances: the two sides compute the same mathematics in a different
order (sorted ragged products against a dense masked sum; a layer scan
against a Python loop), so they differ by float32 rounding: logits and
losses agree to a few 1e-6 relative (limit 1e-5), gradients to ~1e-5 of
their leaf's norm.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.configs.base import MLAConfig
from repro.configs.moonlight_16b_a3b import CONFIG, ONE_CHIP
from repro.models import moe as moe_mod
from repro.models import reference_moonlight as ref
from repro.models import transformer as tf
from repro.models.common import swiglu_apply

SMALL = dataclasses.replace(
    CONFIG, name="moonlight-small", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=128, dtype="float32",
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=dataclasses.replace(CONFIG.moe, n_routed=8, top_k=3, d_ff_expert=32,
                            held_experts=4, router_aux_weight=0.01))


def spec_of(cfg, first=0):
    m, a = cfg.moe, cfg.mla
    return {"n_heads": cfg.n_heads, "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps, "n_routed_experts": m.n_routed,
            "num_experts_per_tok": m.top_k,
            "routed_scaling_factor": m.routed_scaling,
            "norm_topk_prob": m.normalize_gates,
            "aux_weight": m.router_aux_weight, "first_expert": first}


def _model(seed=0, cfg=SMALL):
    params = tf.lm_init(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 32), 0,
                                cfg.vocab_size)
    return params, tokens


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_config_holds_the_published_values():
    c = get("moonlight-16b-a3b")
    assert (c.n_layers, c.d_model, c.first_dense_layers, c.d_ff,
            c.vocab_size, c.rope_theta) == (27, 2048, 1, 11264, 163840,
                                            50000.0)
    assert c.mla == MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                              qk_nope_head_dim=128, qk_rope_head_dim=64,
                              v_head_dim=128)
    assert (c.moe.n_routed, c.moe.top_k, c.moe.d_ff_expert, c.moe.n_shared,
            c.moe.scoring, c.moe.routed_scaling) == (64, 6, 1408, 2,
                                                     "sigmoid", 2.446)
    # one chip's share: depth, held experts and vocabulary cut, no width
    params = jax.eval_shape(lambda k: tf.lm_init(k, ONE_CHIP),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert ONE_CHIP.moe.n_held == 8 and ONE_CHIP.vocab_size == 20480
    assert abs(n - 568.5e6) < 0.1e6, n
    # the analytic count leaves out the latent norms and selection biases
    assert abs(ONE_CHIP.param_count() - n) < 1e-5 * n


def test_forward_loss_and_gradients_match_the_reference():
    params, tokens = _model()
    spec = spec_of(SMALL)
    with jax.default_matmul_precision("highest"):
        logits, _, _, _ = tf.lm_forward(SMALL, params, tokens)
        for b in range(tokens.shape[0]):
            want, _ = ref.forward(spec, params, tokens[b])
            assert _rel(logits[b], want) < 1e-5
        lp, gp = jax.value_and_grad(lambda p: tf.lm_loss_masked(
            SMALL, p, {"tokens": tokens, "m": jnp.ones(2)})[0])(params)
        lr_, gr = jax.value_and_grad(
            lambda p: ref.loss(spec, p, tokens))(params)
    assert abs(float(lp) - float(lr_)) < 1e-6 * abs(float(lr_))
    for path, a in jax.tree_util.tree_leaves_with_path(gp):
        b = _at(gr, path)
        gap = np.linalg.norm(np.asarray(a) - np.asarray(b))
        assert gap <= 1e-5 * max(np.linalg.norm(np.asarray(b)), 1e-12), path
    # the selection bias takes no gradient: SGD leaves it at its seed
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in
               [gp["blocks"]["layers"][0]["mlp"]["e_bias"]])


def _at(tree, path):
    for k in path:
        tree = tree[k.key if hasattr(k, "key") else k.idx]
    return tree


def test_dropless_routing_under_a_skewed_router():
    """A router that sends every token to held expert 0 (a capacity layer
    at 1.25 would drop ~70% of those pairs): the program's layer still
    equals the dense reference, and its stats count every pair."""
    params, _ = _model(3)
    p = params["blocks"]["layers"][0]["mlp"]
    p = jax.tree.map(lambda l: l[0], p)
    p["e_bias"] = p["e_bias"].at[0].set(10.0)     # selection skewed to 0
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, SMALL.d_model))
    with jax.default_matmul_precision("highest"):
        y, st = moe_mod.moe_held_apply(SMALL, p, x)
        want, bal = ref.experts(spec_of(SMALL), p, x[0])
        s = jax.nn.sigmoid(x[0] @ p["router"])
        _, top = jax.lax.top_k(s + p["e_bias"], SMALL.moe.top_k)
    assert _rel(y[0], want) < 1e-5
    assert abs(float(st["balance"]) - float(bal)) < 1e-6 * float(bal)
    top = np.asarray(top)
    assert np.all(top[:, 0] == 0)                    # expert 0 on every token
    held = top < SMALL.moe.n_held
    assert float(st["pairs"]) == held.sum()
    assert float(st["load_max"]) == np.bincount(top[held]).max() == 64


def test_expert_shares_add_up_to_the_whole_layer():
    """The expert-share test: 8 experts over 2 chips of 4 each; the parts
    that the two disjoint shares compute, plus the shared experts once,
    equal the uncut layer's output, the program's and the reference's."""
    whole = dataclasses.replace(SMALL, moe=dataclasses.replace(
        SMALL.moe, held_experts=0))
    p = moe_mod.moe_init(jax.random.PRNGKey(5), whole)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, SMALL.d_model))
    # each chip computes its own experts' part; the shared experts, which
    # every chip computes alike, are counted once
    share_cfg = dataclasses.replace(SMALL, moe=dataclasses.replace(
        SMALL.moe, n_shared=0))
    routed = {k: v for k, v in p.items() if k != "shared"}
    with jax.default_matmul_precision("highest"):
        full, _ = moe_mod.moe_held_apply(whole, p, x)
        total = swiglu_apply(p["shared"], x)
        for first in (0, 4):
            share = dict(routed, **{k: p[k][first:first + 4]
                                    for k in ("wg", "wu", "wd")})
            total = total + moe_mod.moe_held_apply(share_cfg, share, x,
                                                   first=first)[0]
        want = [ref.experts(spec_of(whole), p, x[b])[0] for b in range(2)]
    assert _rel(total, full) < 1e-5
    assert _rel(total, jnp.stack(want)) < 1e-5


def test_sgd_and_fedavg_of_the_reference_are_plain():
    params, tokens = _model(9)
    r = ref.Reference(spec_of(SMALL))
    a = r.sgd(params, np.asarray(tokens), 0.1, 1, 1)
    b = r.sgd(params, np.asarray(tokens), 0.1, 1, 1)
    avg = ref.fedavg([a, b], [1.0, 3.0])
    leaf = lambda t: np.asarray(t["lm_head"], np.float64)
    assert np.array_equal(leaf(avg), leaf(a))   # two equal uploads
    assert not np.allclose(leaf(a), np.asarray(params["lm_head"]))


def test_streamed_round_counts_the_held_experts_work(monkeypatch):
    """One FEEL round of the small model through the streamed cohort
    path: the trained-token and streamed-UE counters count the cohort,
    and the held-expert counters lie within what the routing can give
    (each step's tokens choose k experts in each expert layer)."""
    from repro.configs.base import FeelConfig
    from repro.core.poisoning import pick_malicious
    from repro.federated import cohort
    from repro.federated.server import FeelServer
    from repro.federated.task import MOONLIGHT_FT
    from repro.obs import trace

    task = dataclasses.replace(MOONLIGHT_FT, name="moonlight_small",
                               model=SMALL, seq=32)
    cfg = FeelConfig(n_ues=6, n_malicious=1, task=task.name, local_epochs=1)
    rng = np.random.default_rng(0)
    train, test = task.generate_data(20, 2, 0)
    clients = task.partition_clients(
        train, 6, rng, pick_malicious(6, 1, rng), None)
    monkeypatch.setattr(cohort, "device_memory_bytes", lambda: 1)
    srv = FeelServer(cfg, clients, test, rng, task=task)
    trace.configure(enabled=True)
    try:
        log = srv.run_round(0)
        c = trace.tracer().metrics.snapshot()["counters"]
    finally:
        trace.configure(enabled=False)
    sel = log.selected
    assert c["cohort.streamed"] == len(sel)
    assert c["train.tokens"] == 32 * sum(clients[k].size for k in sel)
    assert 0 < c["moe.held_load_max"] <= c["moe.held_pairs"]
    steps = sum(clients[k].size for k in sel)
    n_moe = SMALL.n_layers - SMALL.first_dense_layers
    assert c["moe.held_pairs"] <= steps * n_moe * 32 * SMALL.moe.top_k


def test_rows_past_the_held_pairs_reach_no_value_or_gradient(monkeypatch):
    """On the TPU the ragged kernel leaves the rows past the last group
    unwritten, in its forward and in its transposes. Emulated here by a
    ragged product whose such rows hold NaN (the weight gradient still
    reads only the groups' rows): the layer's output and every gradient
    stay finite and equal to the plain product's."""
    real = jax.lax.ragged_dot

    def tail(x, gs):
        rows = jnp.arange(x.shape[0])[:, None] < gs.sum()
        return rows, jnp.where(rows, x, jnp.nan)

    @jax.custom_vjp
    def fake(lhs, rhs, gs):
        return tail(real(lhs, rhs, gs), gs)[1]

    def fwd(lhs, rhs, gs):
        return fake(lhs, rhs, gs), (lhs, rhs, gs)

    def bwd(res, g):
        lhs, rhs, gs = res
        rows = tail(lhs, gs)[0]
        _, vjp = jax.vjp(lambda a, b: real(a, b, gs),
                         jnp.where(rows, lhs, 0), rhs)
        d_lhs, d_rhs = vjp(jnp.where(rows, g, 0))
        return tail(d_lhs, gs)[1], d_rhs, None

    fake.defvjp(fwd, bwd)
    params, _ = _model(11)
    p = jax.tree.map(lambda l: l[0], params["blocks"]["layers"][0]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 32, SMALL.d_model))

    def run():
        def f(p, x):
            y, st = moe_mod.moe_held_apply(SMALL, p, x)
            return jnp.sum(jnp.sin(y)) + st["balance"]
        return jax.value_and_grad(f, argnums=(0, 1))(p, x)

    with jax.default_matmul_precision("highest"):
        want = run()
        monkeypatch.setattr(jax.lax, "ragged_dot", fake)
        got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_reference_expert_loads_count_the_programs_held_pairs():
    """The reference's per-layer expert loads (what the selection bias's
    balance rule reads) give each token k experts in every expert layer,
    and their held experts' part is the pairs the program's layers
    computed on the same window."""
    params, tokens = _model(13)
    with jax.default_matmul_precision("highest"):
        loads = np.asarray(ref.expert_loads(spec_of(SMALL), params,
                                            tokens[0]))
        _, aux, _, _ = tf.lm_forward(SMALL, params, tokens[:1])
    n_moe = SMALL.n_layers - SMALL.first_dense_layers
    assert loads.shape == (n_moe, SMALL.moe.n_routed)
    assert np.all(loads.sum(-1) == tokens.shape[1] * SMALL.moe.top_k)
    assert float(aux["pairs"]) == loads[:, :SMALL.moe.n_held].sum()


def test_sigmoid_routing_and_held_experts_need_the_dropless_layer():
    from repro.configs.base import MoEConfig
    for kw in (dict(scoring="sigmoid"), dict(held_experts=2)):
        with pytest.raises(ValueError, match="dropless"):
            MoEConfig(n_routed=8, top_k=2, d_ff_expert=16, **kw)
    m = MoEConfig(n_routed=8, top_k=2, d_ff_expert=16, dropless=True)
    assert m.n_held == 8


def test_dropless_layer_routes_by_softmax_too():
    """The dropless layer is chosen by ``dropless``, not by the scoring
    rule: under softmax scoring it computes every chosen pair of the held
    experts, as the dense sum of gate x expert over held experts does."""
    cfg = dataclasses.replace(SMALL, moe=dataclasses.replace(
        SMALL.moe, scoring="softmax", routed_scaling=1.0))
    p = moe_mod.moe_init(jax.random.PRNGKey(21), cfg)
    assert "e_bias" not in p
    x = jax.random.normal(jax.random.PRNGKey(22), (1, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, st = moe_mod.moe_apply(cfg, p, x)
        probs = jax.nn.softmax(x[0] @ p["router"], -1)
        g, top = jax.lax.top_k(probs, cfg.moe.top_k)
        g = g / g.sum(-1, keepdims=True)
        want = swiglu_apply(p["shared"], x[0])
        for j in range(cfg.moe.n_held):
            e = {"wg": p["wg"][j], "wu": p["wu"][j], "wd": p["wd"][j]}
            gate = jnp.sum(jnp.where(top == j, g, 0.0), -1, keepdims=True)
            want = want + gate * swiglu_apply(e, x[0])
    assert _rel(y[0], want) < 1e-5
    assert float(st["pairs"]) == int(np.sum(np.asarray(top)
                                            < cfg.moe.n_held))
