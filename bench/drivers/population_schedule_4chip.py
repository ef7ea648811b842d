"""Scheduling rounds over a 10^6-UE candidate pool with the pool state
sharded by UE over the cell's chips.

The ``population_schedule`` session as it is, except that every
``prefilter_schedule_runs`` call gets a mesh of the first ``data_shards``
devices on its ``data`` axis (``model`` 1), so the put places each
(R, N) operand split along N and the kernel runs partitioned. Warm-up
compiles the sharded program; ``check`` is the same exact comparison.
"""
from __future__ import annotations

import numpy as np

from drivers import population_schedule


class _Sharded:
    """``repro.core.population`` with the prefilter bound to a mesh."""

    def __init__(self, ppop, mesh):
        self._ppop, self._mesh = ppop, mesh

    def __getattr__(self, name):
        return getattr(self._ppop, name)

    def prefilter_schedule_runs(self, *args, **kw):
        return self._ppop.prefilter_schedule_runs(*args, mesh=self._mesh,
                                                  **kw)


class Session(population_schedule.Session):
    @property
    def _ppop(self):
        return self.__dict__["_sharded"]

    @_ppop.setter
    def _ppop(self, ppop):
        import jax
        from jax.sharding import Mesh
        n = int(self.ctx.traffic["data_shards"])
        devices = np.array(jax.devices()[:n]).reshape(n, 1)
        self.__dict__["_sharded"] = _Sharded(ppop,
                                             Mesh(devices, ("data", "model")))
