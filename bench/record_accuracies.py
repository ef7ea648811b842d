"""Per-UE (local, test) accuracies of the §V experiment as the program
serves it: every selected UE of every round of ``sec5_round``'s set-up
pass (the 15 rounds under the configuration's precision), one set-up per
seed. The population cell draws its cohorts' Eq. 1 inputs from this
file (bench/gen/population.py); the benchmark's own runs do not run it.

    python3 bench/record_accuracies.py --seeds 1 2 3 \
        --out bench/traffic/sec5_cohort_accuracies.csv

Writes a CSV with the columns seed,round,malicious,acc_local,acc_test.
"""
from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.resolve("sec5_round")
    import jax
    run.pin_compile_cache(harness.ROOT)
    if jax.devices()[0].platform != "tpu":
        print("record_accuracies: needs a TPU", file=sys.stderr)
        return 2
    prec = cell.config["precision"]["matmul"]
    rows = []
    for seed in args.seeds:
        ctx = harness.Context(cell.config, cell.traffic, seed)
        with jax.default_matmul_precision(prec):
            session = cell.driver.Session(ctx)
        rows += [(seed, t, int(m), a, b) for t, m, a, b in session.cohort_acc]
        session.finish()
    with open(args.out, "w") as f:
        f.write("seed,round,malicious,acc_local,acc_test\n")
        for r in rows:
            f.write("%d,%d,%d,%r,%r\n" % r)
    print(f"{len(rows)} rows to {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
