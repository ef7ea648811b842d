"""Faults planted under the timed path, to show that ``correct`` catches
them (bench/tests/test_faults.py on the CPU; bench/control.py --fault on
the chip). Each fault patches the program for the duration of a
``with`` block. One chip: there is no exchange between chips to leave
out."""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


# -- §V round ------------------------------------------------------------ #
def round_state_unchanged():
    """The round's aggregation leaves the global model as it was."""
    from repro.federated.server import FeelServer
    return _patched(FeelServer, "_aggregate_uploads",
                    lambda self, sel, uploads, weights: None)


def round_half_batch():
    """FedAvg over the first half of the cohort's uploads only."""
    import repro.federated.server as srv
    orig = srv.fedavg_stacked

    def half(stacked, weights, kernel=None):
        w = np.array(weights, float)
        real = np.flatnonzero(w)
        w[real[real.size // 2:]] = 0.0
        return orig(stacked, w, kernel)
    return _patched(srv, "fedavg_stacked", half)


def round_answer_altered():
    """One scheduled UE dropped from the selection where it is made."""
    from repro.core.scheduler import Schedule
    from repro.federated.server import FeelServer
    orig = FeelServer._schedule_round_batched

    def altered(self, t):
        values, sched, sel, forced = orig(self, t)
        x = sched.x.copy()
        x[sel[-1]] = False
        sched = Schedule(x=x, alpha=sched.alpha, cost=sched.cost,
                         value=sched.value)
        return values, sched, sched.selected, forced
    return _patched(FeelServer, "_schedule_round_batched", altered)


# -- population scheduling ------------------------------------------------ #
def pop_state_unchanged():
    """Eq. 1 and the ages are never written back."""
    import repro.core.population as pop
    return _patched(pop, "scatter_finalize", lambda *a, **k: None)


def pop_half_batch():
    """Half of the candidate pool left out of the schedule."""
    import repro.core.population as pop
    orig = pop.prefilter_schedule_runs

    def half(state, gains, *a, **k):
        g = np.array(gains, float)
        g[:, g.shape[1] // 2:] = 0.0
        return orig(state, g, *a, **k)
    return _patched(pop, "prefilter_schedule_runs", half)


def pop_answer_altered():
    """One selected UE of the first run dropped where it is produced."""
    import repro.core.population as pop
    orig = pop.prefilter_schedule_runs

    def altered(*a, **k):
        x, alpha, costs, values, forced, info = orig(*a, **k)
        x = x.copy()
        x[0, np.flatnonzero(x[0])[0]] = False
        return x, alpha, costs, values, forced, info
    return _patched(pop, "prefilter_schedule_runs", altered)


FAULTS = {
    "sec5_round": {"state_unchanged": round_state_unchanged,
                   "half_batch": round_half_batch,
                   "answer_altered": round_answer_altered},
    "pop1m_schedule": {"state_unchanged": pop_state_unchanged,
                       "half_batch": pop_half_batch,
                       "answer_altered": pop_answer_altered},
}
