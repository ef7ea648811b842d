"""A run drives the timed path with a fault planted underneath
(bench/faults.py) and its ``correct`` comes out false, once per fault
the cell can have; the same run with no fault comes out true. CPU,
cells cut small (tiny.py)."""
import time

import jax
import pytest

import faults
import run
import tiny


def _run(name):
    return run.run_cell(tiny.cell(name), 2 ** 31 + 11, 0.3, False,
                        jax.devices(), t_start=time.time())


@pytest.mark.parametrize("name", sorted(faults.FAULTS))
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("name,fault", [(n, f) for n in sorted(faults.FAULTS)
                                        for f in sorted(faults.FAULTS[n])])
def test_fault_is_caught(name, fault):
    with faults.FAULTS[name][fault]():
        out = _run(name)
    assert not out["correct"], out["checks"]
