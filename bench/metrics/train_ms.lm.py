"""Milliseconds per run-round in local training of the streamed cohort:
each scheduled UE's local steps and local metric (``train.client``) and
the host work around them, with the weighted uploads' fold into the
FedAvg sum (``train``'s own time), and the threat model's rewrite.

The self time (a span's duration less its direct children's) of the
program's obs spans named in SPANS, summed over the traced window and
divided by the run-rounds completed in it.
"""
import trace_reduce

SPANS = ("train", "train.client", "attack.apply")


def read(run):
    if not run.spans:
        return None
    own = trace_reduce.self_times(run.spans)
    total = sum(own.get(n, 0.0) for n in SPANS)
    return total * 1e3 / run.units if total > 0 else None
