"""The main path's device programs compile for a TPU v5e, at real widths.

The chip is described, not attached: ``get_topology_desc`` hands the TPU
compiler a v5e it can target from a CPU-only host, so a kernel Mosaic
refuses (an unaligned block, a primitive it cannot lower) fails here at
no chip time. Nothing runs; these tests say nothing about results or
speed. The topology is described inside a fixture only — one process at
a time may load the TPU library, and the suite runs with several workers.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FeelConfig
from repro.core import control as ctl
from repro.core import population as pop
from repro.federated.task import LM_TINY
from repro.kernels.flash_attention import flash_attention
from repro.kernels.robust_aggregate import _robust_call
from repro.kernels.weighted_aggregate import weighted_aggregate

# the paper MLP's flattened update (784*64 + 64 + 64*10 + 10) from a
# 64-upload stacked cohort
N_STACK, MLP_PARAMS = 64, 50_890
# control plane: a 12-run sweep over the paper's K=50 UEs
R_RUNS = 12
# population prefilter: 5 stacked runs over a 4,096-UE pool, K=64, M=512
POP_R, POP_N, POP_K, POP_M = 5, 4096, 64, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_weighted_aggregate_compiles_to_mosaic(one_chip):
    compiled = jax.jit(weighted_aggregate).lower(
        _sds(one_chip, (N_STACK, MLP_PARAMS), jnp.float32),
        _sds(one_chip, (N_STACK,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mode", ["trimmed_mean", "median"])
def test_robust_aggregate_compiles_to_mosaic(one_chip, mode):
    compiled = jax.jit(
        lambda x, nb: _robust_call(x, nb, mode=mode, block_m=2048,
                                   interpret=False)).lower(
        _sds(one_chip, (N_STACK, MLP_PARAMS), jnp.float32),
        _sds(one_chip, (2,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_to_mosaic_at_lm_tiny(one_chip):
    # lm_tiny's attention as models/attention.py dispatches it: (B, H, S,
    # head_dim) with blocks min(128, S)
    seq = 32
    q = _sds(one_chip, (8, LM_TINY.n_heads, seq, LM_TINY.head_dim),
             jnp.float32)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=seq,
                                        block_k=seq)).lower(q, q, q).compile()
    assert LM_TINY.head_dim == 16
    assert "tpu_custom_call" in compiled.as_text()


def test_control_schedule_kernel_compiles_in_f64(one_chip):
    cfg = FeelConfig()
    k = cfg.n_ues
    with jax.enable_x64(True):
        f64 = lambda *shape: _sds(one_chip, shape, jnp.float64)  # noqa: E731
        args = (_sds(one_chip, (R_RUNS,), jnp.int32),            # policy_id
                f64(R_RUNS, k), f64(R_RUNS, k), f64(R_RUNS, k),  # rep/ages/divs
                f64(R_RUNS, k), f64(R_RUNS, k), f64(R_RUNS, k),  # sizes/r_min/g
                _sds(one_chip, (R_RUNS, k), jnp.int64),          # rand_rank
                f64(R_RUNS), f64(R_RUNS), f64(3),                # w_rep/w_div/gamma
                f64(), f64(), f64())                             # B, P, N0
        compiled = jax.jit(
            lambda *a: ctl._schedule_kernel(*a, k=k,
                                            n_sel=cfg.min_selected)
        ).lower(*args).compile()
        outs = jax.eval_shape(
            lambda *a: ctl._schedule_kernel(*a, k=k,
                                            n_sel=cfg.min_selected), *args)
    x, alpha, costs, values, forced = outs
    assert x.shape == (R_RUNS, k) and x.dtype == np.bool_
    assert alpha.dtype == np.float64 and values.dtype == np.float64
    assert compiled.as_text()


def test_prefilter_kernel_compiles_in_f64(one_chip):
    # the v5e compiler emulates float64 as float32 pairs and refuses some
    # float64 ops (a bitcast to integer words among them): an output
    # encoding it cannot lower fails here
    with jax.enable_x64(True):
        f64 = lambda *shape: _sds(one_chip, shape, jnp.float64)  # noqa: E731
        pool = f64(POP_R, POP_N)
        args = (_sds(one_chip, (POP_R,), jnp.int32),        # policy_id
                pool, pool, pool, pool, pool, pool,         # rep..gains
                _sds(one_chip, (POP_R, POP_N), jnp.int64),  # rand_rank
                f64(POP_R), f64(POP_R), f64(3),             # w_rep/w_div/gamma
                f64(), f64(), f64())                        # B, P, N0
        statics = dict(k=POP_K, n_sel=FeelConfig().min_selected, m=POP_M)
        compiled = pop._prefilter_kernel.lower(*args, **statics).compile()
        outs = jax.eval_shape(
            lambda *a: pop._prefilter_kernel(*a, **statics), *args)
    x, costs, values, forced, cert = outs
    assert x.shape == (POP_R, POP_N) and costs.dtype == np.int32
    assert [(v.shape, v.dtype) for v in values] == [((POP_N,),
                                                     np.float64)] * POP_R
    assert forced.shape == cert.shape == (POP_R,)
    assert compiled.as_text()


def test_moonlight_client_step_compiles_at_published_widths(one_chip):
    """The streamed cohort's local step on one chip's share of
    Moonlight-16B-A3B (568.5 M float32 parameters; MLA, 8 held experts
    through ragged products, per-layer remat) at one 2,048-token window.
    Its arguments are one model, donated to its output: the program has
    no cohort axis, so its memory does not grow with the cohort."""
    from repro.federated import cohort
    from repro.federated.task import MOONLIGHT_FT as task
    params = jax.tree.map(
        lambda s: _sds(one_chip, s.shape, s.dtype),
        jax.eval_shape(task.init_params, jax.random.PRNGKey(0)))
    nbytes = sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    with jax.default_matmul_precision("default"):
        compiled = cohort.client_sgd_step.lower(
            task, params, {"tokens": _sds(one_chip, (1, task.seq), jnp.int32)},
            _sds(one_chip, (1,), jnp.float32),
            _sds(one_chip, (), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    assert nbytes == 568_484_608 * 4
    assert mem.argument_size_in_bytes - nbytes < 2**20
    assert mem.alias_size_in_bytes >= nbytes          # params donated
    # gradients plus one layer's rematerialised activations
    assert mem.temp_size_in_bytes < 8e9
