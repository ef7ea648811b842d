"""Runs per scheduling round whose top-M certificate failed, so that
``prefilter_schedule_runs`` rescheduled them on the exact path over the
whole pool: the program's ``population.escalations`` counter over the
traced window, per round."""


def read(run):
    if not run.counters or "population.escalations" not in run.counters:
        return None
    return float(run.counters["population.escalations"]) / len(run.step_s)
