"""The chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. Resolves the cell in BENCHMARK.json to
its configuration (``bench/configs``), traffic (``bench/traffic``) and
driver (``bench/drivers``); builds the cell from the seed and warms it
up (set-up), measures for ``--seconds`` seconds, checks what the timed
path produced against the plain reference, and prints one JSON line as
the last line of standard output: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics (each read
by ``bench/metrics/<name>.py``) from a traced window. The compared
numbers and their limits end standard error and the JSON line. Exits 2,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402


def process_start() -> float:
    """This process's start on the ``time.time`` clock (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(l.split()[1]) for l in f if l.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return time.time()


T_START = process_start()

LOWER_PRECISION = {"highest": "high"}   # the matmul control's precision


@dataclasses.dataclass
class RunView:
    """What a metric reader is given."""
    setup_s: float
    window_s: float
    step_s: List[float]
    units: int
    compiles: List[str]
    counts: Dict[str, float]
    peaks: Dict[str, Any]
    chips: int
    spans: Optional[List[dict]] = None
    counters: Optional[Dict[str, float]] = None
    trace: Any = None


class CompileLog:
    """Programs JAX lowers (and so compiles, or loads from the persistent
    cache) while ``on``; a context manager that listens for its life."""

    def __init__(self):
        from jax._src import dispatch
        self.event = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        self.names: List[str] = []
        self.on = False

    def _hear(self, event, duration, **kw):
        if self.on and event == self.event:
            self.names.append(str(kw.get("fun_name")))

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._hear)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._hear)
        return False


def pin_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout,
    whatever the environment says; every program is kept."""
    import jax
    path = os.path.join(root, "bench", ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def profile_options():
    """Device activity and the host's annotations; no Python call
    tracing, whose events would outnumber the device's."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool,
             devices, control: Optional[str] = None,
             t_start: float = T_START,
             keep_trace: Optional[str] = None) -> dict:
    """Set up, measure, check; return the result line as a dict (with
    the compared numbers under ``checks``). ``control`` names a stated
    precision to run one step below it (Context). ``keep_trace`` names a file
    to keep the traced window's profile in, gzipped."""
    import jax
    ctx = harness.Context(cell.config, cell.traffic, seed, control=control)
    if trace:
        ctx.annotate = harness.trace_annotation
    prec = cell.config.get("precision", {}).get("matmul")
    if prec and control == "matmul":
        prec = LOWER_PRECISION[prec]
    precision = (lambda: jax.default_matmul_precision(prec)) if prec \
        else contextlib.nullcontext
    ctx.mark("start")
    with CompileLog() as compiles:
        with precision():
            session = cell.driver.Session(ctx)
        setup_s = time.time() - t_start
        if trace:
            with Traced(cell, keep_trace) as traced, precision():
                steps, units, window_s = window(session, ctx, seconds,
                                                compiles)
            ctx.mark("trace read")
        else:
            with precision():
                steps, units, window_s = window(session, ctx, seconds,
                                                compiles)

    peak = memory_peak(devices)
    ctx.n_steps = len(steps)
    counts = session.counts()
    session.finish()
    checks = session.check()
    ctx.mark("check")

    view = RunView(setup_s=setup_s, window_s=window_s, step_s=steps,
                   units=units, compiles=list(compiles.names),
                   counts=counts, peaks=peaks_for(devices[0]),
                   chips=cell.chips)
    if trace:
        view.spans, view.trace = traced.spans, traced.reduction
        view.counters = traced.counters
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = harness.metric_reader(m["name"], cell.root)(view)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = device_info(devices[:cell.chips])
    dev["memory_peak_bytes"] = peak
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": len(steps), "failed": 0, "metrics": metrics,
           "device": dev}
    if trace:
        dev["busy_s"] = view.trace.busy_s
        dev["window_s"] = view.trace.window_s
        out["breakdown"] = view.trace.breakdown()
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    prev = t_start
    for name, t in ctx.marks:
        print(f"phase {name} {t - prev:.3f} s", file=sys.stderr)
        prev = t
    return out


def window(session, ctx, seconds: float, compiles: CompileLog):
    """Steps until ``seconds`` have passed; each step's host-clock time."""
    steps, units = [], 0
    compiles.on = True
    t0 = b = time.perf_counter()
    while b - t0 < seconds:
        a = time.perf_counter()
        with ctx.annotate("bench.step"):
            units += session.step()
        b = time.perf_counter()
        steps.append(b - a)
    compiles.on = False
    ctx.mark("window")
    return steps, units, b - t0


class Traced:
    """The profiler and the program's obs spans and counters around the
    window; on exit, the trace reduced (trace_reduce.py) and its files
    removed."""

    def __init__(self, cell: harness.Cell, keep: Optional[str]):
        self.cell, self.keep = cell, keep
        self.dir = os.path.join(harness.CACHE, "trace", cell.name)

    def __enter__(self):
        import jax
        from repro.obs import trace as obs
        shutil.rmtree(self.dir, ignore_errors=True)
        obs.configure(enabled=True)
        jax.profiler.start_trace(self.dir, profiler_options=profile_options())
        with harness.trace_annotation("bench.clock"):
            self.mark = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax
        import trace_reduce
        from repro.obs import trace as obs
        jax.profiler.stop_trace()
        obs.configure(enabled=False, reset=False)
        self.spans = [s.to_dict() for s in obs.tracer().spans]
        self.counters = obs.tracer().metrics.snapshot()["counters"]
        obs.configure(enabled=False)
        if exc[0] is None:
            self.reduction = trace_reduce.reduce_dir(
                self.dir, self.mark, spans=self.spans,
                n_chips=self.cell.chips)
            if self.keep:
                self._keep(trace_reduce.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        return False

    def _keep(self, xplane: str) -> None:
        import gzip
        with open(xplane, "rb") as f, gzip.open(self.keep, "wb") as g:
            shutil.copyfileobj(f, g)
        with open(self.keep + ".json", "w") as f:
            json.dump({"mark": self.mark, "spans": self.spans,
                       "chips": self.cell.chips}, f)


def peaks_for(device) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error (the CPU rehearsal in the tests passes its own)."""
    table = harness.load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device.device_kind not in table:
        if device.platform == "cpu":
            return {}
        raise KeyError(f"no peaks for device kind {device.device_kind!r}")
    return table[device.device_kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.resolve(args.workload)
    import repro  # noqa: F401  (the system under test must be present)
    import jax
    pin_compile_cache(harness.ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell.chips])
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
