"""Plain references the benchmark's ``correct`` is decided against.

Straightforward float64 numpy from the paper's equations. They import
nothing of the program and take nothing it made: only the inputs the
benchmark generated from ``--seed``.
"""
