"""The controls come out not correct against the cells' limits: the
reference one step below a precision the configuration states, in the
program's place, on the CPU. The §V cell's `matmul` control, the
program's own `high` path, exists only on the chip (the CPU has no
`high` pass, and a bf16 x 3 emulation in numpy parts from float64 ten
times less than the chip's `high` does); bench/control.py reads it there
at the cell's own size."""
import time

import jax

import run
import tiny


def test_round_control_plane_control_fails():
    """Round 0's schedule by the float32 reference in the program's
    place: the §V configuration states a float64 control plane."""
    out = run.run_cell(tiny.cell("sec5_round"), 2 ** 31 + 17, 0.3, False,
                       jax.devices(), control="control",
                       t_start=time.time())
    assert not out["correct"], out["checks"]
    assert out["checks"]["value_gap_r0"]["value"] > \
        out["checks"]["value_gap_r0"]["limit"], out["checks"]


def test_population_control_fails():
    """The float32 schedule against the float64 one: the control plane
    states float64."""
    out = run.run_cell(tiny.cell("pop1m_schedule"), 2 ** 31 + 13, 0.3,
                       False, jax.devices(), control="control",
                       t_start=time.time())
    assert not out["correct"], out["checks"]
