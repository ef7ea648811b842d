"""Observability plane (DESIGN.md §14).

Structured telemetry for the round pipeline: a span tracer with a
context-manager API (``obs/trace.py``), a counter/gauge registry
(``obs/metrics.py``), and the repo's ONLY sanctioned wall-clock site
(``obs/clock.py`` — enforced by the ``repro.check`` nondeterminism
lint).  The hard contract is **zero semantic footprint**: telemetry
never touches the RNG stream of record, f64 accumulation order, or any
traced value, and the disabled tracer (``REPRO_TRACE=0``, the default)
is a shared-singleton no-op.

Sinks: in-memory ring, JSONL trace file keyed commit+env (like
``BENCH_history.jsonl``), native JAX profiler annotations (one per span,
on the profiler's clock), and ``python -m repro.obs.report`` for
per-phase p50/p95 and compile offenders.
"""
from repro.obs import trace  # noqa: F401

__all__ = ["trace"]
