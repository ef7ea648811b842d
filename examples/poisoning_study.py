"""Full paper-protocol reproduction of Fig. 2 and Fig. 3.

    PYTHONPATH=src python examples/poisoning_study.py [--fast]

Fig. 2 (§V-B.1): selection of the 5 highest-V_k UEs per round under three
omega weightings (diversity-only / reputation-only / both), for the easy
(6->2) and hard (8->4) label-flip pairs — no wireless constraint.

Fig. 3 (§V-B.2): full DQS (greedy knapsack + bandwidth costs) under the
wireless model. Reported in two regimes: the paper's literal 100 KB update
(bandwidth is slack -> near-full participation) and a constrained 5 MB update
where the knapsack binds (see EXPERIMENTS.md §Repro).

Writes results/poisoning_study.json and prints round-by-round curves.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.federated.simulation import run_sweep

OMEGAS = [("div_only", (0.0, 1.0)), ("rep_only", (1.0, 0.0)),
          ("both", (0.5, 0.5))]
PAIRS = [("easy_6to2", (6, 2)), ("hard_8to4", (8, 4))]


def curves(policies, scenario, omega, cfg, seeds, **kw):
    """One sweep over (policies x seeds) of one threat scenario;
    per-policy seed-averaged summaries. The runs of a setting share each
    seed's dataset, partition and device layout."""
    res = run_sweep(policies, seeds=seeds, scenarios=[scenario], cfg=cfg,
                    omega=omega, **kw)
    out = {}
    for policy in policies:
        runs = res.select(policy=policy)
        out[policy] = {
            "acc": [round(float(a), 4)
                    for a in res.mean_curve("acc", policy=policy)],
            "source_acc": [round(float(a), 4) for a in
                           res.mean_curve("source_acc", policy=policy)],
            "attack_success": [round(float(a), 4) for a in
                               res.mean_curve("attack_success",
                                              policy=policy)],
            "malicious_selected_mean":
                [round(float(m), 2) for m in
                 res.mean_curve("malicious_selected", policy=policy)],
            "recovery_rounds": [r["recovery_rounds"] for r in runs],
            "rep_gap": round(float(np.mean(
                [r["final_reputation_honest"]
                 - r["final_reputation_malicious"] for r in runs])), 4)}
    return out


def curve(policy, scenario, omega, cfg, seeds, **kw):
    return curves([policy], scenario, omega, cfg, seeds, **kw)[policy]


def _flip(pair):
    return atk.label_flip(*pair)


def _control(pair, tag):
    """Benign control that still watches the would-be pair's metrics."""
    return atk.AttackScenario(f"none_{tag}", watch=pair)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced scale (12k samples, 8 rounds, 2 seeds)")
    ap.add_argument("--engine", choices=["vectorized", "loop"],
                    default="vectorized",
                    help="cohort execution engine (the vectorized engine + "
                         "run_sweep's shared caches make this multi-seed "
                         "study feasible; 'loop' is the sequential oracle)")
    args = ap.parse_args()
    if args.fast:
        kw = dict(n_train=12_000, n_test=2_000, rounds=8)
        seeds = (0, 1)
    else:
        kw = dict(n_train=50_000, n_test=10_000, rounds=15)  # paper protocol
        seeds = (0, 1, 2)
    kw["engine"] = args.engine

    results = {}
    t0 = time.time()
    for pair_tag, pair in PAIRS:
        # no-attack control: quantifies the damage the flip causes
        key = f"control_{pair_tag}_no_attack"
        results[key] = curve("dqs", _control(pair, pair_tag), (0.5, 0.5),
                             None, seeds, **kw)
        print(f"{key}: {results[key]['acc']} src={results[key]['source_acc']}")
        for om_tag, omega in OMEGAS:
            key = f"fig2_{pair_tag}_{om_tag}"
            results[key] = curve("top_value", _flip(pair), omega, None,
                                 seeds, **kw)
            print(f"{key}: {results[key]['acc']}")
        for regime, bits in [("paper_100KB", 100e3 * 8),
                             ("constrained_5MB", 5e6 * 8)]:
            cfg = FeelConfig(model_size_bits=bits)
            for om_tag, omega in OMEGAS:
                key = f"fig3_{pair_tag}_{regime}_{om_tag}"
                results[key] = curve("dqs", _flip(pair), omega, cfg,
                                     seeds, **kw)
                print(f"{key}: {results[key]['acc']}")
        # baselines for context — one sweep over all three policies
        base = curves(["random", "best_channel", "max_count"], _flip(pair),
                      (0.5, 0.5), FeelConfig(model_size_bits=5e6 * 8),
                      seeds, **kw)
        for pol, summary in base.items():
            key = f"baseline_{pair_tag}_{pol}"
            results[key] = summary
            print(f"{key}: {summary['acc']}")

    os.makedirs("results", exist_ok=True)
    with open("results/poisoning_study.json", "w") as f:
        json.dump(results, f, indent=1)
    print(f"\nwrote results/poisoning_study.json ({time.time()-t0:.0f}s)")


if __name__ == "__main__":
    main()
