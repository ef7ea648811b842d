"""Share of the traced window in which no operation ran on the chip, in
%: 100 x (1 - busy / window), busy being the union of the device's
operation intervals in the profiler trace."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
