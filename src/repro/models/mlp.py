"""The paper's experimental model: a two-fully-connected-layer MLP for
(synthetic) MNIST, trained with FedAvg (Section V: "simple multi-layer
perceptron (MLP) model with two fully connected layers").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import correct_counts, count_accuracy, dense_init


def mlp_init(key, n_in: int = 28 * 28, n_hidden: int = 64, n_out: int = 10,
             dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w1": dense_init(k1, (n_in, n_hidden), dtype),
        "b1": jnp.zeros((n_hidden,), dtype),
        "w2": dense_init(k2, (n_hidden, n_out), dtype),
        "b2": jnp.zeros((n_out,), dtype),
    }


def mlp_apply(params, x):
    """x (B, 784) -> logits (B, 10)."""
    h = jax.nn.relu(x @ params["w1"] + params["b1"])
    return h @ params["w2"] + params["b2"]


def mlp_loss(params, batch):
    logits = mlp_apply(params, batch["x"])
    labels = batch["y"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - ll)


def mlp_accuracy(params, x, y):
    """Fraction of correct predictions, divided on the host like every
    masked eval's counts (``models.common.count_accuracy``)."""
    correct = (jnp.argmax(mlp_apply(params, x), -1) == y).astype(jnp.float32)
    return count_accuracy(correct_counts(correct, jnp.ones_like(correct)))


from functools import partial


@partial(jax.jit, static_argnums=(4,))
def mlp_sgd_epoch(params, x, y, lr, batch_size: int = 50):
    """One epoch of mini-batch SGD over a client dataset (used by the
    federated client loop; dataset is padded to a multiple of batch_size)."""
    n = x.shape[0]
    nb = max(n // batch_size, 1)

    def body(params, i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * batch_size, batch_size)
        yb = jax.lax.dynamic_slice_in_dim(y, i * batch_size, batch_size)
        g = jax.grad(mlp_loss)(params, {"x": xb, "y": yb})
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, 0.0

    params, _ = jax.lax.scan(body, params, jnp.arange(nb))
    return params


# ---------------------------------------------------------------------- #
# Masked variants — the vectorized cohort engine's contract: client
# datasets are zero-padded to a uniform length with a {0,1} validity mask;
# a padded sample must contribute *exactly* zero gradient so the padded run
# reproduces the unpadded one. For a fully valid batch the masked mean
# reduces to ``jnp.mean`` (mask sum == batch_size), so batches the plain
# epoch would see are numerically identical, and a fully padded batch is a
# strict no-op (zero gradient -> params unchanged bit-for-bit).
# ---------------------------------------------------------------------- #
def mlp_loss_masked(params, batch):
    """Mean cross-entropy over the valid samples of a batch.

    batch["m"] (B,) float validity mask; padding rows carry m == 0.
    """
    logits = mlp_apply(params, batch["x"])
    labels, m = batch["y"], batch["m"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum((logz - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)


def mlp_correct_counts(params, x, y, m):
    """(correct, valid) counts over the valid samples
    (``models.common.count_accuracy`` turns them into an accuracy)."""
    correct = (jnp.argmax(mlp_apply(params, x), -1) == y).astype(jnp.float32)
    return correct_counts(correct, m)


@partial(jax.jit, static_argnums=(5,))
def mlp_sgd_epoch_masked(params, x, y, m, lr, batch_size: int = 50):
    """Masked twin of ``mlp_sgd_epoch`` over a padded client dataset.

    x (S, D), y (S,), m (S,) with S a multiple of batch_size; batches that
    fall entirely in the padding leave params untouched. The batch grid is
    a reshape (row-major, so batch i covers the same rows the plain epoch
    slices) scanned on the leading axis — cheaper to trace/compile under
    vmap than per-step dynamic slicing, with identical values.
    """
    n = x.shape[0]
    assert n % batch_size == 0, (
        f"padded length {n} must be a multiple of batch_size {batch_size} "
        "(pad_clients(multiple_of=batch_size) guarantees this)")
    nb = n // batch_size
    xb = x.reshape(nb, batch_size, -1)
    yb = y.reshape(nb, batch_size)
    mb = m.reshape(nb, batch_size)

    def body(params, batch):
        bx, by, bm = batch
        g = jax.grad(mlp_loss_masked)(params, {"x": bx, "y": by, "m": bm})
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, 0.0

    params, _ = jax.lax.scan(body, params, (xb, yb, mb))
    return params
