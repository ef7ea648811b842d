"""Tier-1 gate for benchmarks/bench_round.py: the smoke mode runs a tiny
instance of the engine, sweep, control-plane, threat-model, defense-plane
and LM-task benchmarks with loud internal assertions — a bench
regression (engine crash, padding-waste regression, sweep/sequential
divergence, host/batched control-plane selection mismatch,
masked/per-client attack-application mismatch, host/batched robust
aggregation mismatch, LM loop/vectorized loss divergence,
prefilter/exact population-schedule divergence, async/sync
zero-latency parity break) fails here
instead of rotting silently until the next manual bench run."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_round_smoke():
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_round", "--smoke"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ,
             "PYTHONPATH": os.path.join(ROOT, "src") + os.pathsep
             + os.environ.get("PYTHONPATH", "")},
        timeout=1200)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "smoke OK" in r.stderr
    # CSV rows for both engines + the control-plane bench made it out
    assert any(line.startswith("unbucketed,") for line in
               r.stdout.splitlines())
    assert any(line.startswith("vectorized,") for line in
               r.stdout.splitlines())
    assert any(line.startswith("control,") for line in
               r.stdout.splitlines())
    # threat-model plane: masked-vs-loop apply rows + the scenario sweep
    assert any(line.startswith("attacks,") and not line.endswith("speedup")
               for line in r.stdout.splitlines())
    assert any(line.startswith("attacks_sweep,") for line in
               r.stdout.splitlines())
    # defense plane: host-vs-batched robust-aggregator rows for all four
    # aggregators made it out (parity asserted inside the worker)
    for agg in ("trimmed_mean", "median", "norm_clip", "krum"):
        assert any(line.startswith(f"defense,{agg},") for line in
                   r.stdout.splitlines()), agg
    # LM task plane: loop + vectorized rows (loss bit-parity asserted in
    # bench_llm itself; the flash rows are manual-only — interpret mode)
    for eng in ("loop", "vectorized"):
        assert any(line.startswith(f"llm,{eng},") for line in
                   r.stdout.splitlines()), eng
    # population plane: exact-vs-prefilter scaling rows + the forced
    # 2-device mesh row (prefilter == exact asserted inside the worker)
    assert any(line.startswith("population,") for line in
               r.stdout.splitlines())
    assert any(line.startswith("population_mesh,")
               and line.split(",")[2] == "2"
               for line in r.stdout.splitlines())
    # async plane: event-driven rows (sync/buffer/deadline cells; the
    # zero-latency bit-parity gate is asserted inside the worker)
    for mode in ("sync", "async_buffer", "async_deadline"):
        assert any(line.startswith(f"async,{mode},") for line in
                   r.stdout.splitlines()), mode
    # observability plane: the traced cell's summary row made it out
    # (smoke itself asserts the report sees the schedule/train phases —
    # DESIGN.md §14)
    assert any(line.startswith("trace,") for line in
               r.stdout.splitlines())
