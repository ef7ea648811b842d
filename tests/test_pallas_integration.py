"""Model-level Pallas dispatch: REPRO_USE_PALLAS=1 routes full-sequence
attention through the flash kernel (interpret mode on CPU) and must agree
with the default XLA path."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dataclasses

from repro.configs import get, reduced
from repro.models import transformer as tf, api


@pytest.mark.parametrize("arch", ["yi-34b", "starcoder2-15b"])
def test_flag_dispatch_matches_oracle(arch, monkeypatch):
    cfg = dataclasses.replace(reduced(get(arch)), dtype="float32")
    key = jax.random.PRNGKey(0)
    params = api.init(cfg, key)
    tok = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)

    monkeypatch.setenv("REPRO_USE_PALLAS", "0")
    base, _, _, _ = tf.lm_forward(cfg, params, tok, window=cfg.sliding_window)
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    fused, _, _, _ = tf.lm_forward(cfg, params, tok, window=cfg.sliding_window)
    np.testing.assert_allclose(np.asarray(base), np.asarray(fused),
                               atol=2e-4, rtol=2e-4)


def test_flag_off_by_default():
    from repro.kernels import ops
    assert not ops.use_pallas() or os.environ.get("REPRO_USE_PALLAS") not in (None, "0")
