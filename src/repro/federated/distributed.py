"""FEEL mapped onto a TPU mesh (DESIGN.md §3): the jax-native expression of
the paper's per-round communication pattern.

Each slice of the ``data`` axis hosts one cohort client: it trains a local
replica for ``local_steps`` SGD steps (``lax.fori_loop``), then the round's
FedAvg aggregation (Alg. 1 line 13) is a masked, size-weighted ``psum`` over
the client axes — with the DQS selection vector ``x_k`` as the mask, so an
unscheduled client contributes exactly nothing, like a UE that missed the
deadline. On the multi-pod mesh aggregation is hierarchical: intra-pod psum
(ICI) then inter-pod psum (DCI), mirroring BS -> MEC -> cloud edge
aggregation.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_cohort_step(mesh: Mesh, loss_fn: Callable, lr: float,
                     local_steps: int, client_axes: Tuple[str, ...] = ("data",),
                     agg_dtype=None):
    """Build the jitted distributed FEEL round step.

    loss_fn(params, batch) -> scalar. Batch leaves have a leading
    per-client axis sharded over ``client_axes``; ``weights`` and ``select``
    are (n_clients,) arrays sharded likewise. Params are replicated in and
    replicated (aggregated) out.
    """
    def local_sgd(params, batch):
        def step(_, p):
            g = jax.grad(loss_fn)(p, batch)
            return jax.tree.map(
                lambda w, gg: (w.astype(jnp.float32)
                               - lr * gg.astype(jnp.float32)).astype(w.dtype),
                p, g)
        return jax.lax.fori_loop(0, local_steps, step, params)

    def cohort_body(params, batch, weights, select):
        # strip the per-client leading axis (size 1 inside the shard)
        local_batch = jax.tree.map(lambda x: x[0], batch)
        w = (weights[0] * select[0]).astype(jnp.float32)
        local = local_sgd(params, local_batch)
        # hierarchical FedAvg: ICI first, then cross-pod. agg_dtype=bf16 is
        # the quantized-aggregation hillclimb lever (halves collective bytes;
        # the FedAvg mean itself stays fp32-accumulated per psum stage).
        def agg(leaf):
            dt = agg_dtype or jnp.float32
            s = jax.lax.psum((leaf.astype(jnp.float32) * w).astype(dt),
                             client_axes[-1])
            for ax in client_axes[:-1][::-1]:
                s = jax.lax.psum(s, ax)
            return s.astype(jnp.float32)
        wsum = agg(jnp.asarray(1.0))
        out = jax.tree.map(
            lambda l, p: (agg(l) / jnp.maximum(wsum, 1e-9)).astype(p.dtype),
            local, params)
        return out

    client_spec = P(client_axes)
    fn = jax.shard_map(cohort_body, mesh=mesh,
                       in_specs=(P(), client_spec, client_spec, client_spec),
                       out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)


def cohort_input_specs(mesh: Mesh, n_clients: int, batch_shapes: dict,
                       client_axes: Tuple[str, ...] = ("data",)):
    """ShapeDtypeStructs for the cohort step (dry-run helper)."""
    batch = {k: jax.ShapeDtypeStruct((n_clients,) + tuple(s), d)
             for k, (s, d) in batch_shapes.items()}
    vec = jax.ShapeDtypeStruct((n_clients,), jnp.float32)
    return batch, vec, vec
