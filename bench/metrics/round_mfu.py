"""The whole round's share of the chip's peak, in %: the model operations
of the window's local training and evaluation (bench/counts.py) over
window seconds x chips x the bf16 peak (the only published one)."""


def read(run):
    if not run.peaks:
        return None
    flops = run.counts.get("train_flops", 0.0) + run.counts.get(
        "eval_flops", 0.0)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.window_s * run.chips
                            * run.peaks["bf16_flops_per_s"])
