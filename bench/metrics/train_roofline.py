"""The cohort training programs' share of their roofline, in %: the least
time the chip could take for the window's useful training work (real
samples x epochs; bench/counts.py), the larger of operations over the
bf16 peak and bytes over HBM bandwidth, over those programs' device time
in the trace."""

PROGRAMS = ("jit_cohort_train", "jit_cohort_train_multi")


def read(run):
    if run.trace is None or not run.peaks:
        return None
    dev_s = run.trace.program_s(*PROGRAMS)
    flops = run.counts.get("train_flops", 0.0)
    if dev_s <= 0 or flops <= 0:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                run.counts["train_bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev_s
