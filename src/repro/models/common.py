"""Shared model building blocks: norms, RoPE, initializers."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def dtype_of(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------- #
# Accuracy as counts
# ---------------------------------------------------------------------- #
def correct_counts(correct, m):
    """(2,) float32 (correct, valid) counts of a {0,1} score under mask
    ``m`` — exact integers below 2^24. ``count_accuracy`` divides them."""
    return jnp.stack([jnp.sum(correct * m), jnp.sum(m)])


def count_accuracy(counts) -> np.ndarray:
    """Accuracy from (..., 2) (correct, valid) counts: the float32
    quotient taken on the host, 0.0 where nothing is valid. A device
    divide is not correctly rounded on every backend (the TPU's is not),
    so the engines' device evals and the loop oracle's host evals all
    divide here and agree bit for bit on any backend."""
    c = np.asarray(counts, np.float32)
    return (c[..., 0] / np.maximum(c[..., 1], np.float32(1.0))).astype(float)


# ---------------------------------------------------------------------- #
# Initialisation
# ---------------------------------------------------------------------- #
def dense_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / np.sqrt(max(fan_in, 1))
    return (std * jax.random.truncated_normal(key, -3.0, 3.0, shape,
                                              jnp.float32)).astype(dtype)


def embed_init(key, shape, dtype):
    return (0.02 * jax.random.truncated_normal(key, -3.0, 3.0, shape,
                                               jnp.float32)).astype(dtype)


def zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------- #
# Norms (computed in fp32, cast back)
# ---------------------------------------------------------------------- #
def rms_norm(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(x, z, scale, eps=1e-5):
    """Mamba2 output norm: RMSNorm(x * silu(z))."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    scale, eps)


# ---------------------------------------------------------------------- #
# RoPE
# ---------------------------------------------------------------------- #
def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta), jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, D/2)
    sin = jnp.sin(ang)[..., None, :]                            # (..., S, 1, D/2)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
# MLP
# ---------------------------------------------------------------------- #
def swiglu_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wg": dense_init(k1, (d_model, d_ff), dtype),
        "wu": dense_init(k2, (d_model, d_ff), dtype),
        "wd": dense_init(k3, (d_ff, d_model), dtype, fan_in=d_ff),
    }


def swiglu_apply(p, x):
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    return h @ p["wd"]


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE; logits (..., V) fp32-safe."""
    logits = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
