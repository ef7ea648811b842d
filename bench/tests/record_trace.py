"""Record the small chip trace that tests/test_trace_reduce.py reads.

    python3 bench/tests/record_trace.py <out.xplane.pb.gz>

Runs the population cell cut small (tests/tiny.py) for half a second,
traced, on the chip, and keeps the profile gzipped beside a JSON sidecar
(the clock mark and the program's spans).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402
import run  # noqa: E402

if __name__ == "__main__":
    import jax
    run.pin_compile_cache(tiny.harness.ROOT)
    out = run.run_cell(tiny.cell("pop1m_schedule"), 5, 0.5, True,
                       jax.devices()[:1], t_start=time.time(),
                       keep_trace=sys.argv[1])
    print(out["device"], out["metrics"], out["correct"])
