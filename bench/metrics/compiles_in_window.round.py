"""Programs JAX lowered (compiled, or loaded from the persistent cache)
inside the measured window; the harness listens for JAX's lowering
events. Warm-up should leave none."""


def read(run):
    return float(len(run.compiles))
