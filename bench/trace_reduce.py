"""From a profiler trace and the program's spans to per-layer numbers.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it. Device planes are named
``/device:TPU:<i>``: their "XLA Modules" line holds one event per program
run, their "XLA Ops" line one per operation. Host threads are lines of the
``/host:CPU`` plane; the harness's ``TraceAnnotation``s (``bench.*``) are
events there. The program's obs spans are on ``time.monotonic``; the
harness records that clock inside a ``bench.clock`` annotation, which puts
them on the trace's clock.

Busy time is the union of a chip's operation intervals inside the window
(the first ``bench.step`` start to the last one's end), averaged over the
chips; idle is the rest. Each idle gap is labelled with the innermost host
span or annotation open at its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(spans: Sequence[dict]) -> Dict[str, float]:
    """Seconds per span name of each span's duration less its direct
    children's (the obs tracer's ``parent`` links)."""
    child: Dict[int, float] = {}
    for s in spans:
        if s.get("parent", -1) >= 0:
            child[s["parent"]] = child.get(s["parent"], 0.0) + s["dur"]
    out: Dict[str, float] = {}
    for s in spans:
        out[s["name"]] = (out.get(s["name"], 0.0) + s["dur"]
                          - child.get(s["sid"], 0.0))
    return out


TOP = 10          # idle gaps labelled and listed in the breakdown
_ID = re.compile(r"[.(].*$")


def program_name(name: str) -> str:
    """'jit_cohort_train(12)' -> 'jit_cohort_train'."""
    return _ID.sub("", name) or name


def op_name(name: str) -> str:
    """'%fusion.2 = f32[...] fusion(...)' -> 'fusion.2'."""
    return name.split(" = ", 1)[0].lstrip("%")


def _owner(modules: List[Tuple[int, int, str]], t: int) -> str:
    """The program whose run on this chip holds time ``t``."""
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return program_name(modules[i][2])
    return "?"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    programs: Dict[str, float]         # device seconds per program
    ops: Dict[str, float]              # device s per program/operation
    gap_list: List[Tuple[str, float]]  # TOP longest (host label, s)
    annotations: Dict[str, List[float]]  # harness annotation durations, s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_s(self, *names: str) -> float:
        return sum(v for k, v in self.programs.items() if k in names)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gap_list[:10]]}


def _device_index(plane_name: str) -> Optional[int]:
    m = re.match(r"^/device:TPU:(\d+)$", plane_name)
    return int(m.group(1)) if m else None


def reduce_profile(pd, mark_monotonic: float,
                   spans: Sequence[dict] = (), n_chips: int = 1
                   ) -> Reduction:
    host_events: List[Tuple[int, int, str]] = []
    ann: Dict[str, List[Interval]] = {}
    device: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            if idx >= n_chips:
                continue
            d = device.setdefault(idx, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    d[key].extend((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        ann.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    steps = ann.get("bench.step", [])
    if not steps or not device:
        raise ValueError("trace holds no bench.step annotation or no "
                         "device plane")
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    clock = ann.get("bench.clock", [(lo, lo)])[0][0]
    offset = clock - int(round(mark_monotonic * 1e9))
    for name, iv in ann.items():
        if name != "bench.clock":
            host_events.extend((a, b, name) for a, b in iv)
    for s in spans:
        host_events.append((int(round(s["t0"] * 1e9)) + offset,
                            int(round(s["t1"] * 1e9)) + offset, s["name"]))

    busy_total, programs, ops = 0.0, {}, {}
    all_gaps: List[Interval] = []
    for d in device.values():
        src = d["ops"] or d["modules"]
        busy = union(clip([(a, b) for a, b, _ in src], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        all_gaps.extend(gaps(busy, lo, hi))
        for a, b, n in d["modules"]:
            if b > lo and a < hi:
                k = program_name(n)
                programs[k] = programs.get(k, 0.0) + (min(b, hi)
                                                      - max(a, lo)) / 1e9
        mods = sorted(d["modules"])
        for a, b, n in d["ops"]:
            if b > lo and a < hi:
                k = f"{_owner(mods, a)}/{op_name(n)}"
                ops[k] = ops.get(k, 0.0) + (min(b, hi) - max(a, lo)) / 1e9
    n = len(device)
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]
    labelled = [(label_at(host_events, (a + b) // 2), (b - a) / 1e9)
                for a, b in longest]
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy_total / n / 1e9,
        programs={k: v / n for k, v in programs.items()},
        ops={k: v / n for k, v in ops.items()},
        gap_list=labelled,
        annotations={k: [(b - a) / 1e9 for a, b in v]
                     for k, v in ann.items() if k != "bench.clock"})


def label_at(events: Sequence[Tuple[int, int, str]], t: int) -> str:
    """The innermost (shortest) host event open at ``t``."""
    best, best_len = "host (no span)", None
    for a, b, name in events:
        if a <= t < b and (best_len is None or b - a < best_len):
            best, best_len = name, b - a
    return best


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str, mark_monotonic: float,
               spans: Sequence[dict] = (), n_chips: int = 1) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(trace_dir)),
                          mark_monotonic, spans, n_chips)
