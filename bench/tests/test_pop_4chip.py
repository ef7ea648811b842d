"""The four-chip pool cell on four forced host devices, at a size the CPU
holds: the sharded prefilter runs every round and ``check`` is exact."""
import os
import subprocess
import sys

import harness

SCRIPT = r"""
import copy, time
import jax, tiny, harness, run
assert len(jax.devices()) == 4
c = harness.resolve("pop1m_schedule_4chip")
c.config = copy.deepcopy(c.config)
c.traffic = copy.deepcopy(c.traffic)
c.config["feel"].update(population=20000)
c.traffic.update(pool_rounds=3)
out = run.run_cell(c, 2 ** 31 + 19, 0.5, False, jax.devices()[:4],
                   t_start=time.time())
assert out["correct"], out["checks"]
assert out["device"]["count"] == 4, out["device"]
assert out["attempted"] > 0 and out["failed"] == 0, out
print("POP-4CHIP-OK", out["attempted"])
"""


def test_sharded_pool_cell_runs_exact_on_four_devices():
    cell = harness.resolve("pop1m_schedule_4chip")
    assert cell.chips == 4 and cell.traffic["data_shards"] == 4
    here = os.path.dirname(os.path.abspath(__file__))
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600, cwd=here,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "PYTHONPATH": here})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "POP-4CHIP-OK" in r.stdout
