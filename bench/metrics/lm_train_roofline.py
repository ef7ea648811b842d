"""The streamed client step's share of its roofline, in %: the least time
the chip could take for the window's local training (bench/counts_lm.py:
model operations of every step, and each step's parameter read and
write), the larger of operations over the bf16 peak and bytes over HBM
bandwidth, over the device time of the step program in the trace."""

PROGRAMS = ("jit_client_sgd_step",)


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
