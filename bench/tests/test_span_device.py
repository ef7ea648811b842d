"""Chip time per program span (span_device.py) and the readers of the
prefilter's split metrics."""
import os
from types import SimpleNamespace

import pytest

import harness
import run
import span_device as sd
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "pop_small.xplane.pb.gz")
NATIVE = os.path.join(DATA, "pop_small_native.xplane.pb.gz")
CHILDREN = ("schedule.prefilter.put", "schedule.prefilter.kernel",
            "schedule.prefilter.fetch")


def test_owners_is_the_innermost_open_span():
    spans = [(0, 100, "round"), (10, 50, "train"), (20, 30, "eval"),
             (60, 70, "schedule")]
    assert sd.owners(spans, [25, 40, 55, 65, 5, 100, 200]) == [
        "eval", "train", "round", "schedule", "round", None, None]
    assert sd.owners([], [1]) == [None]


def test_module_name_is_jaxs_program_name():
    assert sd.module_name("_prefilter_kernel") == "jit__prefilter_kernel"
    assert sd.module_name("cohort_train") == "jit_cohort_train"
    assert sd.module_name("<lambda>") == "jit__lambda_"


def _event(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _profile(host, runs):
    """A profile of one host thread's events and one chip's program runs."""
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)
    return SimpleNamespace(planes=[
        SimpleNamespace(name=sd.HOST, lines=[line("main", host)]),
        SimpleNamespace(name="/device:TPU:0",
                        lines=[line("XLA Modules", runs)])])


def test_runs_pair_by_order_and_unmatched_counts_stay_unattributed():
    """The k-th run of ``jit_f`` goes to the k-th outermost call of ``f``,
    even where the run reads as starting before its call; a program whose
    counts of calls and runs differ is left unattributed."""
    host = [_event("bench.step", 0, 1000),
            _event("train", 100, 200), _event("eval", 400, 200),
            _event("PjitFunction(step)", 150, 20),
            _event("PjitFunction(step)", 152, 10),     # nested re-record
            _event("PjitFunction(step)", 450, 20),
            _event("PjitFunction(cast)", 460, 5)]
    runs = [_event("jit_step(1)", 140, 100),    # before its call's start
            _event("jit_step(1)", 500, 50),
            _event("jit_cast(2)", 470, 10), _event("jit_cast(2)", 480, 10)]
    got = sd.attribute(_profile(host, runs), {"train", "eval"})
    assert got.device_s == {"train": pytest.approx(100e-9),
                            "eval": pytest.approx(50e-9)}
    assert got.module_s == pytest.approx(170e-9)
    assert got.attributed_share == pytest.approx(150 / 170)


def _load(path):
    if not os.path.exists(path):
        pytest.fail(f"missing recorded trace {path}")
    return sd.load(path)


def test_pairs_every_kernel_run_with_its_call():
    """The trace recorded before the spans were native: the harness's own
    annotation stands in for a program span, and every kernel run lies in
    one."""
    pd, side = _load(OLD)
    got = sd.attribute(pd, {"bench.prefilter"}, side["chips"])
    red = tr.reduce_profile(pd, side["mark"], side["spans"], side["chips"])
    assert got.attributed_share == pytest.approx(1.0)
    assert got.device_s["bench.prefilter"] == pytest.approx(
        red.program_s("jit__prefilter_kernel"))
    assert got.module_s == pytest.approx(sum(red.programs.values()))


def test_native_spans_hold_every_kernel_run():
    pd, side = _load(NATIVE)
    names = {s["name"] for s in side["spans"]}
    assert set(CHILDREN) <= names
    got = sd.attribute(pd, names, side["chips"], side["spans"], side["mark"])
    red = tr.reduce_profile(pd, side["mark"], side["spans"], side["chips"])
    assert got.attributed_share >= 0.95
    assert got.device_s["schedule.prefilter.kernel"] == pytest.approx(
        red.program_s("jit__prefilter_kernel"))
    assert got.align_error_s is not None and got.align_error_s >= 0.0


def test_native_trace_keeps_the_existing_reduction():
    """reduce_profile reads the new trace as it read the old one; its gap
    labels may now name the prefilter's child spans."""
    pd, side = _load(NATIVE)
    red = tr.reduce_profile(pd, side["mark"], side["spans"], side["chips"])
    assert 0.0 < red.busy_s < red.window_s
    known = {"bench.step", "bench.prefilter", "bench.finalize",
             "schedule.prefilter", "schedule.pack",
             "host (no span)"} | set(CHILDREN)
    assert {k for k, _ in red.gap_list} <= known


def _span(sid, name, dur, parent=-1):
    return {"name": name, "sid": sid, "parent": parent, "dur": dur}


def test_prefilter_split_and_transfer_readers():
    view = run.RunView(0, 1, [0.5] * 4, 4, [], {}, {}, 1)
    for name in ("prefilter_put_ms.pop", "prefilter_fetch_ms.pop",
                 "transfer_mb.pop"):
        assert harness.metric_reader(name)(view) is None
    view.spans = [_span(0, "schedule.prefilter", 0.4),
                  _span(1, "schedule.prefilter.put", 0.1, 0),
                  _span(2, "schedule.prefilter.kernel", 0.08, 0),
                  _span(3, "schedule.prefilter.fetch", 0.2, 0)]
    view.counters = {"population.h2d_bytes": 280e6 * 4,
                     "population.d2h_bytes": 105e6 * 4}
    assert harness.metric_reader("prefilter_put_ms.pop")(view) == \
        pytest.approx(25.0)
    assert harness.metric_reader("prefilter_fetch_ms.pop")(view) == \
        pytest.approx(50.0)
    assert harness.metric_reader("transfer_mb.pop")(view) == \
        pytest.approx(385.0)
