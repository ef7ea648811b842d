"""Window seconds over scheduling rounds, in ms."""


def read(run):
    return run.window_s * 1e3 / len(run.step_s)
