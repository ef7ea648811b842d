"""Chip time per program span, from a profiler trace that holds the
program's obs spans as native annotations.

With tracing enabled, every obs span opens a ``jax.profiler.TraceAnnotation``
of its name, so the spans lie on the profiler's clock, on the host thread
that dispatched their device programs: the thread that holds the harness's
``bench.step`` annotations. Each jitted call is a ``PjitFunction(<f>)`` event
there (JAX records it twice, nested; the outermost counts), and each run of
its program an event of the ``XLA Modules`` line of every chip, named
``jit_<f>(<id>)``. The k-th call of ``f`` is paired with the k-th run of
``jit_<f>`` on each chip, in time order (not by start times: the device
planes' clock is mapped onto the host's, and a run can read up to ~0.2 ms
before the call that dispatched it on TPU v5e). Where a program's counts of
calls and runs differ, its runs stay unattributed. Each run's time inside
the window (the first ``bench.step`` start to the last one's end) is given
to the innermost program span open at its dispatch.

    python3 bench/span_device.py <trace.xplane.pb.gz> [<units>]

reads a profile kept by ``run.run_cell(..., keep_trace=...)`` and its JSON
sidecar, and prints the attribution, per unit of work where ``units`` is
given.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import trace_reduce

HOST = "/host:CPU"
_DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")
_SANITIZE = re.compile(r"[^A-Za-z0-9_]")


@dataclasses.dataclass
class SpanDevice:
    device_s: Dict[str, float]     # device s per span name, mean over chips
    module_s: float                # device s of all program runs in window
    attributed_s: float            # of which given to a span
    align_error_s: Optional[float]  # max |offset-aligned - native| start

    @property
    def attributed_share(self) -> float:
        return self.attributed_s / self.module_s if self.module_s else 0.0


def module_name(fun: str) -> str:
    """'_prefilter_kernel' -> 'jit__prefilter_kernel' (JAX's name for the
    program of a jitted function)."""
    return "jit_" + _SANITIZE.sub("_", fun)


def _outermost(events: List[Tuple[int, int, str]]):
    """Drop events nested in an earlier one of the same name."""
    out, open_until = [], {}
    for a, b, name in sorted(events):
        if a < open_until.get(name, -1):
            continue
        open_until[name] = b
        out.append((a, b, name))
    return out


def owners(spans: Sequence[Tuple[int, int, str]], times: Sequence[int]
           ) -> List[Optional[str]]:
    """The innermost span open at each time; the spans nest, as those of
    one thread do."""
    spans = sorted(spans, key=lambda s: (s[0], -s[1]))
    out: List[Optional[str]] = [None] * len(times)
    stack: List[Tuple[int, int, str]] = []
    i = 0
    for j in sorted(range(len(times)), key=times.__getitem__):
        t = times[j]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out[j] = stack[-1][2] if stack else None
    return out


def attribute(pd, span_names: Iterable[str], n_chips: int = 1,
              ring: Sequence[dict] = (), mark_monotonic: Optional[float] = None
              ) -> SpanDevice:
    """Device seconds per program span in the window of profile ``pd``.
    ``ring`` and ``mark_monotonic`` (the obs ring's span dicts and the
    harness's clock mark) also give the largest distance between a ring
    span's start moved by the ``bench.clock`` offset and its native start."""
    names = set(span_names)
    step_line = clock = None
    modules: Dict[int, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        idx = trace_reduce._device_index(plane.name)
        if idx is not None and idx < n_chips:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.setdefault(idx, []).extend(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         trace_reduce.program_name(e.name))
                        for e in line.events)
        elif plane.name == HOST:
            for line in plane.lines:
                evs = list(line.events)
                if any(e.name == "bench.step" for e in evs):
                    step_line = evs
    if step_line is None or not modules:
        raise ValueError("trace holds no bench.step thread or no device "
                         "plane")
    steps = [(e.start_ns, e.start_ns + e.duration_ns) for e in step_line
             if e.name == "bench.step"]
    lo, hi = min(a for a, _ in steps), max(b for _, b in steps)
    spans, calls = [], []
    for e in step_line:
        a, b = e.start_ns, e.start_ns + e.duration_ns
        if e.name in names:
            spans.append((a, b, e.name))
        elif e.name == "bench.clock":
            clock = a
        else:
            m = _DISPATCH.match(e.name)
            if m:
                calls.append((a, b, module_name(m.group(1))))
    by_prog: Dict[str, List[int]] = {}
    for a, _, prog in _outermost(calls):
        by_prog.setdefault(prog, []).append(a)

    module_ns = 0
    pending: List[Tuple[int, int]] = []     # (ns in window, dispatch)
    for runs in modules.values():
        runs = sorted(runs)
        count: Dict[str, int] = {}
        for _, _, prog in runs:
            count[prog] = count.get(prog, 0) + 1
        seen: Dict[str, int] = {}
        for a, b, prog in runs:
            k = seen.get(prog, 0)
            seen[prog] = k + 1
            inside = min(b, hi) - max(a, lo)
            if inside <= 0:
                continue
            module_ns += inside
            starts = by_prog.get(prog, [])
            if len(starts) == count[prog]:
                pending.append((inside, starts[k]))
    device_s: Dict[str, float] = {}
    given_ns = 0
    for (inside, _), owner in zip(pending,
                                  owners(spans, [t for _, t in pending])):
        if owner is not None:
            given_ns += inside
            device_s[owner] = device_s.get(owner, 0.0) + inside / 1e9
    n = len(modules)
    return SpanDevice(
        device_s={k: v / n for k, v in device_s.items()},
        module_s=module_ns / n / 1e9, attributed_s=given_ns / n / 1e9,
        align_error_s=_align_error(spans, ring, clock, mark_monotonic))


def _align_error(native: Sequence[Tuple[int, int, str]], ring: Sequence[dict],
                 clock: Optional[int], mark: Optional[float]
                 ) -> Optional[float]:
    """The largest |offset-aligned ring start - native start| over spans
    paired by name and order; None where a name's counts differ."""
    if not ring or clock is None or mark is None:
        return None
    offset = clock - int(round(mark * 1e9))
    worst = 0
    for name in {s["name"] for s in ring}:
        a = sorted(int(round(s["t0"] * 1e9)) + offset
                   for s in ring if s["name"] == name)
        b = sorted(s for s, _, n in native if n == name)
        if len(a) != len(b):
            return None
        worst = max([worst] + [abs(x - y) for x, y in zip(a, b)])
    return worst / 1e9


def load(path: str):
    """A kept profile (``*.xplane.pb.gz``) and its JSON sidecar."""
    import gzip
    import json
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with open(path + ".json") as f:
        return pd, json.load(f)


def attribute_kept(path: str) -> SpanDevice:
    pd, side = load(path)
    return attribute(pd, {s["name"] for s in side["spans"]},
                     side["chips"], side["spans"], side["mark"])


if __name__ == "__main__":
    import json
    import sys
    res = attribute_kept(sys.argv[1])
    per = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0
    print(json.dumps({
        "device_ms": {k: v * 1e3 / per for k, v in
                      sorted(res.device_s.items(), key=lambda kv: -kv[1])},
        "module_ms": res.module_s * 1e3 / per,
        "attributed_share": res.attributed_share,
        "align_error_s": res.align_error_s}, indent=1))
