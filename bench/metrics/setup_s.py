"""Seconds from process start to the window's start: loading, input
generation, compilation or cache loading, and warm-up."""


def read(run):
    return run.setup_s
