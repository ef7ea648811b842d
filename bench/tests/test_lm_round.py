"""The ``moonlight_round`` cell rehearsed on the CPU at a tiny size: the
same driver, generator, reference and checks with the model cut to
d_model 64 (8 routed experts, 4 held, windows of 64 tokens), and the
device's memory reported as one byte so that the cohort streams as it
does on the chip. Never measured."""
import copy
import dataclasses
import time

import jax
import pytest

import harness
import run


@pytest.fixture
def tiny_lm(monkeypatch):
    from repro.configs.base import MLAConfig
    from repro.configs.moonlight_16b_a3b import ONE_CHIP
    from repro.federated import cohort
    from repro.federated import task as taskmod
    from repro.models import transformer as tf

    model = dataclasses.replace(
        ONE_CHIP, name="moonlight-tiny", n_layers=3, d_model=64, n_heads=4,
        n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=256,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(ONE_CHIP.moe, n_routed=8, top_k=3,
                                d_ff_expert=32, held_experts=4))
    task = dataclasses.replace(taskmod.MOONLIGHT_FT, name="moonlight_tiny",
                               model=model, seq=64)
    monkeypatch.setitem(taskmod.TASKS, task.name, task)
    monkeypatch.setattr(cohort, "device_memory_bytes", lambda: 1)
    cell = harness.resolve("moonlight_round")
    cfg = copy.deepcopy(cell.config)
    shapes = jax.eval_shape(lambda k: tf.lm_init(k, model),
                            jax.random.PRNGKey(0))
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
               n_routed_experts=4, router_experts=8, num_experts_per_tok=3,
               num_hidden_layers=3, vocab_size=256,
               params=int(sum(l.size for l in jax.tree.leaves(shapes))))
    cfg["task"]["name"] = task.name
    cfg["data"]["seq"] = 64
    cell.config = cfg
    return cell


def test_tiny_cell_runs_correct_and_repeats(tiny_lm):
    out = run.run_cell(tiny_lm, 2 ** 31 + 29, 1.0, False, jax.devices(),
                       t_start=time.time())
    assert out["correct"], out["checks"]
    assert out["checks"]["repeat_mismatch"]["value"] == 0
    assert out["checks"]["upload_redo_diff"]["value"] == 0


def test_bf16_parameter_control_fails(tiny_lm):
    """The configuration states a float32 model; the program with its
    parameters held in bfloat16 reads not correct, by the round-0
    aggregate's gap from the reference's FedAvg."""
    out = run.run_cell(tiny_lm, 2 ** 31 + 29, 0.3, False, jax.devices(),
                       control="model", t_start=time.time())
    assert not out["correct"], out["checks"]
    assert out["checks"]["aggregate_gap"]["value"] > \
        out["checks"]["aggregate_gap"]["limit"], out["checks"]


def test_balance_bias_evens_a_skewed_router(tiny_lm):
    """Set-up's balance rule brings a router whose bias sends every token
    to expert 0 of each layer back near even loads."""
    import numpy as np
    from repro.federated import task as taskmod
    drv = tiny_lm.driver
    cfg = tiny_lm.config
    task = taskmod.TASKS[cfg["task"]["name"]]
    shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    w0 = drv.init_params(shapes, 7)
    w0 = drv._with_bias(w0, drv._bias(w0).at[:, 0].set(0.5))
    windows = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], (2, cfg["data"]["seq"]), dtype=np.int32)
    rates = [0.01] * 150 + [0.001] * 100
    out, before, after = drv.balance_bias(drv.spec(cfg), w0, windows, rates)
    k, e = cfg["num_experts_per_tok"], cfg["router_experts"]
    assert abs(before - e / k) < 1e-6      # expert 0 chosen by every token
    assert after < 1.15, after
    assert float(drv._bias(out)[:, 0].max()) < 0.5
