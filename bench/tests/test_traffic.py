"""The population cell's cohort accuracies are pairs of the §V readings
that its traffic file names, drawn whole, from the seed."""
import numpy as np

import tiny
from gen import population as gen


def test_pool_draws_recorded_accuracy_pairs():
    c = tiny.cell("pop1m_schedule")
    pairs = gen.cohort_accuracies(c.traffic["cohort_accuracies"])
    known = set(map(tuple, pairs))
    p = gen.pool(c.config, c.traffic, np.random.default_rng(5))
    q = gen.pool(c.config, c.traffic, np.random.default_rng(5))
    for a, b, a2 in zip(p.acc_local, p.acc_test, q.acc_local):
        assert a.shape == (len(c.config["policies"]), c.config["feel"]["n_ues"])
        assert set(zip(a.ravel(), b.ravel())) <= known
        np.testing.assert_array_equal(a, a2)
