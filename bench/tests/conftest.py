"""bench/tests: the benchmark's own tests, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repository's tier-1 run (``testpaths = ["tests"]``) does not collect
them."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import tiny  # noqa: E402,F401  (puts bench/ and src/ on sys.path)
