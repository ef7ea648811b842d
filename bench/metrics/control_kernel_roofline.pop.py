"""The prefilter kernel's share of its roofline, in %: the (R, N) bytes
it must read and write (bench/counts.py) over HBM bandwidth, over its
device time in the trace. Bytes only: the chip publishes no float64
peak."""

PROGRAMS = ("jit__prefilter_kernel",)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    dev_s = run.trace.program_s(*PROGRAMS)
    nbytes = run.counts.get("prefilter_bytes", 0.0)
    if dev_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / dev_s
