"""Sharding rules + a real (subprocess) mini dry-run on 8 fake devices.

The subprocess is needed because XLA_FLAGS device-count is locked at first
jax init — the main test process must keep its single CPU device.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_specs_divisible_everywhere():
    """Every sharded dim divides exactly (NamedSharding requirement) for every
    assigned arch on the production mesh shape."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import functools, jax
from repro.configs import get, list_archs
from repro.models import api
from repro.sharding.specs import param_specs, _axis_size
from repro.launch.mesh import make_production_mesh

mesh = make_production_mesh()
bad = []
for arch in list_archs():
    cfg = get(arch)
    params = jax.eval_shape(functools.partial(api.init, cfg),
                            jax.random.PRNGKey(0))
    specs = param_specs(cfg, params, mesh)
    def check(path, leaf, spec):
        for i, s in enumerate(spec):
            if s is not None and leaf.shape[i] % _axis_size(mesh, s):
                bad.append((arch, path, leaf.shape, tuple(spec)))
    jax.tree_util.tree_map_with_path(
        lambda p, l, s: check(p, l, s), params, specs,
        is_leaf=lambda x: hasattr(x, "shape"))
print("BAD" if bad else "OK", bad[:3])
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC},
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().startswith("OK"), r.stdout + r.stderr[-500:]


@pytest.mark.slow
def test_mini_dryrun_lowers_and_compiles(tmp_path):
    """A reduced arch lowers + compiles on a small fake mesh, proving the
    jit/shard pipeline end-to-end inside the test suite."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, functools, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import TrainConfig, get, reduced
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models import api
from repro.sharding.specs import param_specs, opt_state_specs
from repro.optim import make_optimizer

cfg = dataclasses.replace(reduced(get("qwen2-moe-a2.7b")), vocab_size=1024)
mesh = make_host_mesh(model_parallel=4)        # (2, 4) over 8 devices
tcfg = TrainConfig(optimizer="adamw")
params = jax.eval_shape(functools.partial(api.init, cfg), jax.random.PRNGKey(0))
pspecs = param_specs(cfg, params, mesh)
opt = make_optimizer(tcfg)
opt_sds = jax.eval_shape(opt.init, params)
ospecs = opt_state_specs("adamw", params, pspecs, mesh)
mk = lambda t, s: jax.tree.map(
    lambda x, sp: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=NamedSharding(mesh, sp)), t, s)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                        sharding=NamedSharding(mesh, P("data", None)))}
step_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
fn = make_train_step(cfg, tcfg)
with mesh:
    compiled = jax.jit(fn).lower(mk(params, pspecs), mk(opt_sds, ospecs),
                                 step_in, batch).compile()
cost = compiled.cost_analysis()
# newer JAX returns a per-device list of dicts (same logic as
# repro.launch.dryrun.cost_dict, inlined here: importing dryrun would
# clobber this subprocess's 8-device XLA_FLAGS with its 512)
if isinstance(cost, (list, tuple)):
    cost = cost[0] if cost else {}
cost = cost or {}
assert cost.get("flops", 0) > 0
print("COMPILED_OK", int(cost.get("flops", 0)))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC},
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "COMPILED_OK" in r.stdout, r.stdout


def test_zero_shard_adds_data_axis():
    from repro.sharding.specs import _zero_shard
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 4, "model": 2}
        axis_names = ("data", "model")
    from jax.sharding import PartitionSpec as P
    out = _zero_shard(P(None, "model"), (16, 8), FakeMesh)
    assert out == P("data", "model")
    # refuses non-divisible
    out = _zero_shard(P(None, "model"), (3, 8), FakeMesh)
    assert out == P(None, "model")
