"""Production mesh builders. v5e pod = 16x16 = 256 chips; multi-pod = 2 pods.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module never touches jax device state — required because the
dry-run launcher must set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link (~4 links usable/chip)

# 398B/671B configs need a factored-moment optimizer to fit 16 GB/chip
ADAFACTOR_ARCHS = {"deepseek-v3-671b", "jamba-1.5-large-398b"}


def _auto_mesh(shape, axes):
    # the model and population code is written for GSPMD propagation
    # (with_sharding_constraint, sharded placement); jax.make_mesh's
    # default Explicit axes would demand an out_sharding at every
    # ambiguous gather and contraction instead
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Small ("data", "model") mesh over whatever devices exist.

    Live consumers: the population plane (``core/population.py``) shards
    the N-candidate axis over this mesh's data axis (DESIGN.md §12), and
    tests / CPU examples use it as the stand-in production mesh. When
    ``model_parallel`` does not divide the device count the remainder
    devices are left out of the mesh (n // mp data slices).
    """
    n = len(jax.devices())
    mp = min(model_parallel, n)
    return _auto_mesh((n // mp, mp), ("data", "model"))
