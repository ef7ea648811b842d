"""Milliseconds per run-round scoring the streamed uploads for Eq. 1
(``eval.upload``, one per UE) and the global model (``eval.global``).

The self time (a span's duration less its direct children's) of the
program's obs spans named in SPANS, summed over the traced window and
divided by the run-rounds completed in it.
"""
import trace_reduce

SPANS = ("eval", "eval.upload", "eval.global")


def read(run):
    if not run.spans:
        return None
    own = trace_reduce.self_times(run.spans)
    total = sum(own.get(n, 0.0) for n in SPANS)
    return total * 1e3 / run.units if total > 0 else None
