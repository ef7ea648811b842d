"""The trace reduction: interval arithmetic, span self times, and the
reduction of a small trace recorded on a TPU v5e (record_trace.py)."""
import gzip
import json
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "pop_small.xplane.pb.gz")


def test_union_clip_gaps():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]
    assert tr.union(iv) == [(0, 3), (5, 9), (10, 11)]
    assert tr.clip(tr.union(iv), 2, 10) == [(2, 3), (5, 9)]
    assert tr.gaps([(2, 3), (5, 9)], 0, 12) == [(0, 2), (3, 5), (9, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_self_times_subtract_direct_children():
    spans = [{"name": "round", "sid": 0, "parent": -1, "dur": 10.0},
             {"name": "train", "sid": 1, "parent": 0, "dur": 6.0},
             {"name": "eval", "sid": 2, "parent": 1, "dur": 2.0},
             {"name": "schedule", "sid": 3, "parent": 0, "dur": 3.0}]
    own = tr.self_times(spans)
    assert own == {"round": 1.0, "train": 4.0, "eval": 2.0, "schedule": 3.0}


def test_label_is_innermost_open_event():
    ev = [(0, 100, "bench.step"), (10, 50, "train"), (20, 30, "eval")]
    assert tr.label_at(ev, 25) == "eval"
    assert tr.label_at(ev, 40) == "train"
    assert tr.label_at(ev, 60) == "bench.step"
    assert tr.label_at(ev, 200) == "host (no span)"


def test_program_and_op_names():
    assert tr.program_name("jit__prefilter_kernel(3)") == \
        "jit__prefilter_kernel"
    assert tr.op_name("%fusion.2 = f32[8] fusion(f32[8] %p)") == "fusion.2"


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(DATA):
        pytest.fail(f"missing recorded trace {DATA}")
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with open(DATA + ".json") as f:
        side = json.load(f)
    return pd, side


def _sweep_busy(pd, lo, hi):
    """Busy ns by a boundary sweep (an independent union)."""
    pts = []
    for plane in pd.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    a = max(e.start_ns, lo)
                    b = min(e.start_ns + e.duration_ns, hi)
                    if b > a:
                        pts += [(a, 1), (b, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, at = 0, 0, None
    for t, d in pts:
        if depth > 0:
            busy += t - at
        depth += d
        at = t
    return busy


def test_recorded_trace_reduction(recorded):
    pd, side = recorded
    red = tr.reduce_profile(pd, side["mark"], side["spans"], side["chips"])
    steps = [(e.start_ns, e.start_ns + e.duration_ns)
             for p in pd.planes if p.name == "/host:CPU"
             for l in p.lines for e in l.events if e.name == "bench.step"]
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    assert red.window_s == pytest.approx((hi - lo) / 1e9)
    assert red.busy_s == pytest.approx(_sweep_busy(pd, lo, hi) / 1e9,
                                       abs=1e-9)
    assert 0.0 < red.busy_s < red.window_s
    assert red.programs.get("jit__prefilter_kernel", 0.0) > 0.0
    # every program's device time fits in the window, and the busy union
    # covers at least the longest program
    assert max(red.programs.values()) <= red.window_s
    assert len(red.annotations["bench.step"]) == len(steps)
    assert len(red.annotations["bench.prefilter"]) == len(steps)
    bd = red.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(k.split("/")[0] in red.programs for k, _ in bd["device_ops"])
    assert 0 < len(bd["idle_gaps"]) <= 10
    gaps = [g for _, g in red.gap_list]
    assert gaps == sorted(gaps, reverse=True)
    known = {"bench.step", "bench.prefilter", "bench.finalize",
             "schedule.prefilter", "schedule.pack", "host (no span)"}
    assert {k for k, _ in red.gap_list} <= known


def test_idle_gaps_sum_with_busy_to_window(recorded):
    pd, side = recorded
    red = tr.reduce_profile(pd, side["mark"], side["spans"], side["chips"])
    old, tr.TOP = tr.TOP, 10 ** 9
    try:
        every = tr.reduce_profile(pd, side["mark"], side["spans"],
                                  side["chips"])
    finally:
        tr.TOP = old
    total = sum(g for _, g in every.gap_list)
    assert total + every.busy_s == pytest.approx(red.window_s, rel=1e-9)
