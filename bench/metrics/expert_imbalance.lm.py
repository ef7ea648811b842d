"""How unevenly the held experts are loaded: the busiest held expert's
token-expert pairs in one local step (counter ``moe.held_load_max``, the
round's largest, per run-round) over the mean held expert's pairs in one
step (counter ``moe.held_pairs`` over bench/counts_lm.py's
``expert_slots``, the window's (step, expert layer, held expert)
triples). 1 is an even load; the dropless layer's step time follows the
busiest expert."""


def read(run):
    c = run.counters or {}
    slots = run.counts.get("expert_slots", 0.0)
    if not c.get("moe.held_pairs") or slots <= 0 or run.units <= 0:
        return None
    return (c["moe.held_load_max"] / run.units) / (c["moe.held_pairs"]
                                                   / slots)
