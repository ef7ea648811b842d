"""Mean milliseconds per scheduling round reading the prefilter kernel's
six outputs back to the host: the self time of the program's obs span
`schedule.prefilter.fetch`, summed over the traced window, over its
rounds."""
import trace_reduce

SPAN = "schedule.prefilter.fetch"


def read(run):
    if not run.spans:
        return None
    own = trace_reduce.self_times(run.spans).get(SPAN)
    return None if own is None else own * 1e3 / len(run.step_s)
