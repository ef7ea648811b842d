"""Run-rounds completed over the window's seconds (host clock, each step
ending in a host read of its results)."""


def read(run):
    return run.units / run.window_s
