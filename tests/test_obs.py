"""Observability plane (src/repro/obs/, DESIGN.md §14).

The contract under test, in order of importance:

1. Zero semantic footprint — ``run_experiment`` with the tracer ON is
   BIT-EQUAL to the same run with the tracer OFF, across engines
   (vectorized, loop), control planes (batched, host), modes (sync,
   async) and tasks (mnist_mlp, lm_tiny). Telemetry that perturbs the
   RNG stream of record or the f64 accumulation order fails here.
2. The disabled path is a true no-op: the shared ``NULL_SPAN``
   singleton, an empty ring, silent metric helpers, and a near-zero
   allocation bound on the hot path.
3. Span discipline: well-formed nesting (parent interval contains the
   child, depth is parent+1), and in async mode every span inside the
   event loop carries both clocks with sim_t0 <= sim_t1.
4. Sinks round-trip: JSONL file -> (meta, spans, metrics), per-phase
   summaries, the ``repro.obs.report`` summarizer, and the native
   profiler annotations (one ``TraceAnnotation`` per span, opened and
   closed in nesting order). The ``jax.compiles`` counter hears every
   program JAX lowers while tracing is on.
5. ``write_bench_json`` attaches the per-phase summary to the
   BENCH_history.jsonl line when tracing is on (satellite of §14).
"""
import dataclasses
import io
import json
import os
import sys
import tracemalloc

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from repro.configs.base import FeelConfig
from repro.federated.simulation import run_experiment
from repro.obs import report as obs_report
from repro.obs import trace
from repro.obs.metrics import MetricRegistry
from repro.obs.trace import NULL_SPAN

from benchmarks.bench_round import write_bench_json  # noqa: E402

CFG = FeelConfig(n_ues=10, n_malicious=2, min_selected=3, rounds=3)
KW = dict(n_train=1500, n_test=300, seed=0)
LM_KW = dict(n_train=960, n_test=240, seed=0)


@pytest.fixture(autouse=True)
def _tracer_off_after():
    """Every test leaves the singleton disabled and empty — the default
    (REPRO_TRACE=0) state the rest of tier-1 runs under."""
    yield
    trace.configure(enabled=False)


def _async(cfg):
    return dataclasses.replace(cfg, mode="async")


def _run(obs_on: bool, **kw):
    trace.configure(enabled=obs_on)
    try:
        return run_experiment(**kw)
    finally:
        if not obs_on:
            trace.configure(enabled=False)


def _assert_bitwise_equal(a, b):
    assert a.keys() == b.keys()
    for f in a:
        x, y = a[f], b[f]
        if isinstance(x, list) and x and isinstance(x[0], (int, float)):
            assert np.array_equal(np.asarray(x, float),
                                  np.asarray(y, float),
                                  equal_nan=True), (f, x, y)
        else:
            assert x == y, (f, x, y)


# ---------------------------------------------------------------------- #
# 1. zero semantic footprint: obs-on == obs-off, bitwise
# ---------------------------------------------------------------------- #
MATRIX = [
    ("vectorized", "batched", "sync", "mnist"),
    ("vectorized", "batched", "async", "mnist"),
    ("vectorized", "host", "async", "mnist"),
    ("loop", "host", "sync", "mnist"),
    ("vectorized", "batched", "sync", "lm"),
    ("vectorized", "batched", "async", "lm"),
]


@pytest.mark.parametrize("engine,control,mode,task", MATRIX)
def test_obs_on_off_parity(engine, control, mode, task):
    if task == "lm":
        cfg = dataclasses.replace(CFG, rounds=2)
        kw = dict(LM_KW, task="lm_tiny", scenario="token_flip_1to5")
    else:
        cfg = CFG
        kw = dict(KW, scenario="flip_6to2")
    if mode == "async":
        cfg = _async(cfg)
    kw.update(cfg=cfg, engine=engine, control=control)
    off = _run(False, **kw)
    on = _run(True, **kw)
    _assert_bitwise_equal(off, on)
    # and the traced run actually traced something
    assert trace.tracer().spans, "obs-on run recorded no spans"


# ---------------------------------------------------------------------- #
# 2. the disabled path is a true no-op
# ---------------------------------------------------------------------- #
def test_disabled_path_null_span_and_empty_ring():
    trace.configure(enabled=False)
    s1, s2 = trace.span("a"), trace.span("b")
    assert s1 is NULL_SPAN and s2 is NULL_SPAN       # shared singleton
    with trace.span("x") as sp:
        sp.set(anything=1)                           # no-op, chains
    trace.counter_inc("c")
    trace.gauge_set("g", 1.0)
    trace.observe("o", 1.0)
    trace.set_sim_clock(lambda: 0.0)
    tr = trace.tracer()
    assert tr.spans == [] and tr.sim_clock is None
    snap = tr.metrics.snapshot()
    assert (snap["counters"] == {} and snap["gauges"] == {}
            and snap["observations"] == {})


def test_disabled_path_allocation_bound():
    trace.configure(enabled=False)
    tracemalloc.start()
    try:
        base, _ = tracemalloc.get_traced_memory()
        for _ in range(10_000):
            with trace.span("hot"):
                pass
        cur, _ = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # the shared NULL_SPAN allocates nothing per call; allow slack for
    # interpreter noise but forbid anything per-iteration
    assert cur - base < 16_384, (base, cur)


def test_traced_decorator_disabled_is_passthrough():
    trace.configure(enabled=False)
    calls = []

    @trace.traced("work")
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2 and calls == [1]
    assert trace.tracer().spans == []
    trace.configure(enabled=True)
    assert fn(2) == 3
    assert [s.name for s in trace.tracer().spans] == ["work"]


# ---------------------------------------------------------------------- #
# 3. span discipline: nesting + dual clock
# ---------------------------------------------------------------------- #
def _traced_experiment(cfg, **kw):
    trace.configure(enabled=True)
    run_experiment(cfg=cfg, **kw)
    return list(trace.tracer().spans)


def test_span_nesting_well_formed():
    spans = _traced_experiment(CFG, scenario="flip_6to2", **KW)
    by_sid = {s.sid: s for s in spans}
    names = {s.name for s in spans}
    for phase in ("experiment", "round", "schedule", "schedule.pack",
                  "schedule.finalize", "train", "train.bucket", "eval",
                  "attack.apply", "defense.aggregate", "finalize",
                  "eval.global"):
        assert phase in names, (phase, sorted(names))
    roots = 0
    for s in spans:
        assert s.t1 >= s.t0
        if s.parent == -1:
            roots += 1
            assert s.depth == 0
            continue
        p = by_sid[s.parent]                  # parent completed + kept
        assert p.depth == s.depth - 1
        assert p.t0 <= s.t0 and s.t1 <= p.t1, (p.name, s.name)
    assert roots >= 1
    assert trace.tracer()._stack == []        # all spans closed


def test_async_dual_clock():
    spans = _traced_experiment(_async(CFG), scenario="flip_6to2", **KW)
    stamped = [s for s in spans if s.sim_t0 is not None]
    assert stamped, "no span carried the simulated clock in async mode"
    assert {"async.dispatch", "async.aggregate"} <= {s.name
                                                     for s in stamped}
    for s in stamped:
        assert s.sim_t1 >= s.sim_t0 >= 0.0
        assert s.t1 >= s.t0
    # the event clock advances monotonically across aggregations
    aggs = [s for s in stamped if s.name == "async.aggregate"]
    sims = [s.sim_t1 for s in aggs]
    assert sims == sorted(sims) and sims[-1] > 0.0
    # and the engine uninstalled the sim clock on exit
    assert trace.tracer().sim_clock is None
    # async-plane metrics landed
    snap = trace.tracer().metrics.snapshot()
    assert snap["gauges"]["async.heap_depth"]["max"] >= 1
    assert snap["observations"]["async.upload_age"]["count"] >= 1


# ---------------------------------------------------------------------- #
# 4. sinks: JSONL round-trip, Perfetto export, report
# ---------------------------------------------------------------------- #
def test_jsonl_and_trace_event_round_trip(tmp_path):
    spans = _traced_experiment(CFG, scenario="flip_6to2", **KW)
    snap = trace.tracer().metrics.snapshot()
    path = str(tmp_path / "trace.jsonl")
    assert trace.flush_jsonl(path) == path
    meta, recs, metrics = trace.load_jsonl(path)
    assert meta["kind"] == "meta" and "commit" in meta
    assert len(recs) == len(spans)
    assert [r["name"] for r in recs] == [s.name for s in spans]
    for r, s in zip(recs, spans):
        assert (r["sid"], r["parent"], r["depth"]) == (s.sid, s.parent,
                                                       s.depth)
        assert r["t0"] == s.t0 and r["t1"] == s.t1
    assert metrics["counters"] == snap["counters"]
    assert metrics["gauges"] == snap["gauges"]
    # phase summary computed from the file == from the live ring
    assert trace.phase_summary(recs) == trace.phase_summary(spans)


def test_report_summarize_and_render(tmp_path):
    # an n_train no other test uses -> fresh shapes -> the jit cache is
    # cold and round 0's compile probe marks its train.bucket span
    _traced_experiment(CFG, scenario="flip_6to2",
                       **dict(KW, n_train=1230, n_test=246))
    path = str(tmp_path / "trace.jsonl")
    trace.flush_jsonl(path)
    rep = obs_report.summarize(path)
    for phase in ("round", "schedule", "train", "eval"):
        assert phase in rep["phases"], sorted(rep["phases"])
        assert rep["phases"][phase]["count"] >= CFG.rounds
    # compile offenders: the cold jit cache means round 0 compiled
    assert any(o["name"] == "train.bucket"
               for o in rep["compile_offenders"])
    # the jax.compiles counter hears more than the data plane's entry
    # points: the control kernels and eager ops lowered too
    marked = sum(1 for s in trace.load_jsonl(path)[1]
                 if (s.get("attrs") or {}).get("compiled"))
    assert rep["metrics"]["counters"][trace.COMPILES] > marked
    out = io.StringIO()
    obs_report.render(rep, out=out)
    text = out.getvalue()
    assert text.startswith("# trace commit=")
    assert "phase,count,total_s,p50_s,p95_s" in text
    assert f"metric,counters,{trace.COMPILES}," in text
    # the CLI entry point agrees with the library path
    rc = obs_report.main([path, "--json"])
    assert rc == 0


def test_report_cli_module_runs(tmp_path):
    import subprocess
    _traced_experiment(CFG, scenario="none", **KW)
    path = str(tmp_path / "trace.jsonl")
    trace.flush_jsonl(path)
    r = subprocess.run(
        [sys.executable, "-m", "repro.obs.report", path],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": os.path.join(ROOT, "src") + os.pathsep
             + os.environ.get("PYTHONPATH", "")})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "phase,count,total_s,p50_s,p95_s" in r.stdout


# ---------------------------------------------------------------------- #
# 4b. native profiler annotations, ready(), the compile counter
# ---------------------------------------------------------------------- #
def _annotation_recorder(monkeypatch):
    """Replace jax.profiler.TraceAnnotation by a recorder of (open|close,
    name) events; returns the event log."""
    import jax.profiler
    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("close", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


def test_spans_open_profiler_annotations_in_nesting_order(monkeypatch):
    log = _annotation_recorder(monkeypatch)
    trace.configure(enabled=True)
    with trace.span("round"):
        with trace.span("schedule") as sp:
            sp.set(t=0)
            with trace.span("schedule.pack"):
                pass
        with trace.span("train"):
            pass
    assert log == [("open", "round"), ("open", "schedule"),
                   ("open", "schedule.pack"), ("close", "schedule.pack"),
                   ("close", "schedule"), ("open", "train"),
                   ("close", "train"), ("close", "round")]
    # the ring keeps the spans and their attributes as before
    spans = trace.tracer().spans
    assert [s.name for s in spans] == ["schedule.pack", "schedule",
                                       "train", "round"]
    assert spans[1].attrs == {"t": 0}


def test_disabled_path_opens_no_annotation_and_ready_is_identity(
        monkeypatch):
    import jax.numpy as jnp
    log = _annotation_recorder(monkeypatch)
    trace.configure(enabled=False)
    with trace.span("round"):
        with trace.span("schedule"):
            pass
    assert log == [] and trace.tracer().spans == []
    x, arr = object(), jnp.arange(3)
    assert trace.ready(x) is x and trace.ready(arr) is arr


def test_ready_blocks_and_keeps_values_when_enabled():
    import jax.numpy as jnp
    trace.configure(enabled=True)
    y = jnp.arange(4.0) * 2.0
    out = trace.ready({"y": y, "n": [y + 1.0]})
    assert np.array_equal(np.asarray(out["y"]), [0.0, 2.0, 4.0, 6.0])
    assert np.array_equal(np.asarray(out["n"][0]), [1.0, 3.0, 5.0, 7.0])


def test_compile_counter_hears_a_control_kernel_and_stops_when_off():
    from repro.core import control as ctl
    trace.configure(enabled=True)
    assert trace._LISTENING
    c0 = trace.compiles()
    # a shape and budget no other test uses: the first call lowers
    ctl._pack_kernel(np.ones((3, 37), np.int32), k=7)
    assert trace.compiles() == c0 + 1
    ctl._pack_kernel(np.ones((3, 37), np.int32), k=7)   # cached
    assert trace.compiles() == c0 + 1
    trace.configure(enabled=False, reset=False)
    assert not trace._LISTENING
    ctl._pack_kernel(np.ones((3, 38), np.int32), k=7)
    assert trace.compiles() == c0 + 1


# ---------------------------------------------------------------------- #
# 5. metrics registry + bench-writer integration
# ---------------------------------------------------------------------- #
def test_metric_registry_snapshot():
    reg = MetricRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.0)
    reg.gauge("g").set(7.0)
    reg.gauge("g").set(3.0)
    for v in (1.0, 2.0, 3.0):
        reg.observation("o").add(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == {"value": 3.0, "max": 7.0}
    o = snap["observations"]["o"]
    assert (o["count"], o["sum"], o["min"], o["max"]) == (3, 6.0, 1.0,
                                                          3.0)
    assert o["mean"] == 2.0
    reg.reset()
    assert reg.snapshot()["counters"] == {}


def test_experiment_metrics_captured():
    trace.configure(enabled=True)
    run_experiment(cfg=CFG, scenario="flip_6to2", **KW)
    snap = trace.tracer().metrics.snapshot()
    # padding-waste + bucket occupancy ride the train phase; the jit
    # compile-cache gauges are snapshotted at end of run
    assert snap["observations"]["train.pad_waste"]["count"] >= CFG.rounds
    occ = snap["observations"]["train.bucket_occupancy"]
    assert occ["count"] >= CFG.rounds and 0.0 < occ["max"] <= 1.0
    assert snap["gauges"]["compile.cohort_train"]["value"] >= 1


def test_write_bench_json_attaches_phase_summary(tmp_path):
    trace.configure(enabled=True)
    with trace.span("round"):
        pass
    write_bench_json("obs_probe", {"bench": "obs_probe", "rows": []},
                     results_dir=str(tmp_path))
    hist = (tmp_path / "BENCH_history.jsonl").read_text().splitlines()
    rec = json.loads(hist[-1])
    assert "round" in rec["trace"] and rec["trace"]["round"]["count"] == 1
    # tracer off -> no trace block on the history line
    trace.configure(enabled=False)
    write_bench_json("obs_probe", {"bench": "obs_probe", "rows": []},
                     results_dir=str(tmp_path))
    rec = json.loads((tmp_path / "BENCH_history.jsonl")
                     .read_text().splitlines()[-1])
    assert "trace" not in rec


def test_configure_env_equivalent_and_reset(tmp_path):
    tr = trace.configure(enabled=True, ring_size=8)
    try:
        for i in range(20):
            with trace.span(f"s{i}"):
                pass
        assert len(tr.spans) <= 8                 # ring bounded
    finally:
        trace.configure(enabled=False, ring_size=trace._RING)
    assert tr.spans == [] and tr.enabled is False
