"""Megabytes (10^6 B) per scheduling round that cross between host and
chip in the prefilter: the program's `population.h2d_bytes` (operands
placed) plus `population.d2h_bytes` (outputs fetched) counters over the
traced window, over its rounds. Counted from array metadata."""

COUNTERS = ("population.h2d_bytes", "population.d2h_bytes")


def read(run):
    if not run.counters or any(c not in run.counters for c in COUNTERS):
        return None
    total = sum(float(run.counters[c]) for c in COUNTERS)
    return total / 1e6 / len(run.step_s)
