"""Mean milliseconds per scheduling round inside the harness's
`bench.finalize` annotation, read from the profiler trace's host plane."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.annotations.get("bench.finalize", [])
    return 1e3 * sum(d) / len(d) if d else None
