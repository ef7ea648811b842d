"""A new configuration, traffic, driver and per-layer metric are found by
name alone: files and BENCHMARK.json entries added, no edit elsewhere."""
import json
import os
import shutil
import time

import jax
import pytest

import harness
import run

DRIVER = '''
"""A driver that asks the program for nothing (discovery test)."""


class Session:
    def __init__(self, ctx):
        self.ctx = ctx

    def step(self):
        return self.ctx.traffic["per_step"]

    def finish(self):
        pass

    def check(self):
        return [("nothing", 0.0, 0.0)]

    def counts(self):
        return {}
'''

METRIC = '''
def read(run):
    return float(run.units)
'''


@pytest.fixture
def copy(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return root


def test_new_cell_and_metric_found_by_name(copy):
    b = copy / "bench"
    (b / "drivers" / "idle.py").write_text(DRIVER)
    (b / "traffic" / "idle_steps.json").write_text(json.dumps(
        {"driver": "idle", "per_step": 3}))
    (b / "configs" / "nothing.json").write_text(json.dumps(
        {"name": "nothing", "reduced": []}))
    (b / "metrics" / "units_in_window.idle.py").write_text(METRIC)
    bm = json.loads((copy / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "nothing", "source": "https://example.org",
                          "file": "bench/configs/nothing.json",
                          "reduced": [], "why": "discovery test"})
    bm["workloads"].append({"name": "nothing.idle", "config": "nothing",
                            "traffic": "idle_steps", "chips": 1,
                            "why": "discovery test"})
    bm["per_layer"].append({"name": "units_in_window.idle", "unit": "steps",
                            "better": "higher", "source": "host_clock",
                            "layer": "entry", "moves": "setup_s",
                            "workloads": ["nothing.idle"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = harness.resolve("nothing.idle", str(copy))
    assert cell.traffic["per_step"] == 3
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["units_in_window.idle"]
    out = run.run_cell(cell, 7, 0.05, False, jax.devices(),
                       t_start=time.time())
    assert out["correct"] and "setup_s" in out["metrics"]
    assert out["checks"] == {"nothing": {"value": 0.0, "limit": 0.0}}
    reader = harness.metric_reader("units_in_window.idle", str(copy))
    assert reader(run.RunView(0, 1, [1], 3, [], {}, {}, 1)) == 3.0


def test_every_metric_in_benchmark_has_a_reader():
    bm = harness.benchmark()
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in bm["workloads"]:
        cell = harness.resolve(w["name"])
        assert hasattr(cell.driver, "Session")


def test_escalations_reader_reads_the_programs_counter():
    read = harness.metric_reader("escalations.pop")
    view = run.RunView(0, 1, [0.5] * 4, 4, [], {}, {}, 1)
    assert read(view) is None
    view.counters = {"population.escalations": 2}
    assert read(view) == 0.5
