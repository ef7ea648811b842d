"""Readings of a cell's compared numbers over several seeds, in one
process: the program's; with ``--control <precision>`` a control's, one
step below a precision the configuration states (``matmul``: the
program's own ``high`` path where ``highest`` is stated; ``control``: the
float32 reference of the control plane in the program's place where
float64 is stated); with ``--fault <name>`` the program's with a fault of
bench/faults.py planted. Used to set the limits in
``bench/traffic/*.json``; the benchmark's own runs run none of these.

    python3 bench/control.py --workload <cell> --control matmul --seconds 3 \
        --seeds 11 12 13

Prints one JSON line per seed: {"seed", "correct", "checks"}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", choices=("none", "matmul", "control"),
                    required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None,
                    help="plant a fault of bench/faults.py in the program")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    cell = harness.resolve(args.workload)
    import jax
    run.pin_compile_cache(harness.ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    import contextlib
    import time
    import faults
    plant = (faults.FAULTS[args.workload][args.fault] if args.fault
             else contextlib.nullcontext)
    for seed in args.seeds:
        with plant():
            out = run.run_cell(cell, seed, args.seconds, False,
                               devices[:cell.chips],
                               control=(None if args.control == "none"
                                        else args.control),
                               t_start=time.time())
        print(json.dumps({"seed": seed, "control": args.control,
                          "fault": args.fault,
                          "correct": out["correct"],
                          "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
