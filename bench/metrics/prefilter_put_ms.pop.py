"""Mean milliseconds per scheduling round placing the prefilter's seven
(R, N) operands on the chip(s): the self time of the program's obs span
`schedule.prefilter.put` (one `jax.device_put`, closed once the arrays
are on the device), summed over the traced window, over its rounds."""
import trace_reduce

SPAN = "schedule.prefilter.put"


def read(run):
    if not run.spans:
        return None
    own = trace_reduce.self_times(run.spans).get(SPAN)
    return None if own is None else own * 1e3 / len(run.step_s)
