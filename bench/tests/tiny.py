"""Cells cut to a size a CPU test run holds (never measured)."""
from __future__ import annotations

import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SIZES = {
    "round": {"data": {"n_train": 3000, "n_test": 500, "max_groups": 5},
              "feel": {"n_ues": 10, "n_malicious": 2},
              "traffic": {"cycle_rounds": 4}},
    "population_schedule": {"feel": {"population": 20000},
                            "traffic": {"pool_rounds": 3}},
}


def cell(name: str, root: str = harness.ROOT) -> harness.Cell:
    c = harness.resolve(name, root)
    c.config = copy.deepcopy(c.config)
    c.traffic = copy.deepcopy(c.traffic)
    for key, upd in SIZES[c.traffic["driver"]].items():
        (c.traffic if key == "traffic" else c.config[key]).update(upd)
    return c
