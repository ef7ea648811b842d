"""Input generators of the benchmark, driven by ``--seed`` alone."""
