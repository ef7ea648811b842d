"""Jit'd public wrappers around the Pallas kernels.

On the ``tpu`` backend the kernels compile to Mosaic; on the ``cpu``
backend (the test suite) they run with ``interpret=True``; any other
backend is an error. ``use_pallas()`` gates the model-level dispatch
(models default to the XLA path; tests and benchmarks exercise the
kernels explicitly).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.moe_gemm import moe_gemm as _moe_gemm
from repro.kernels.robust_aggregate import robust_aggregate as _robust
from repro.kernels.ssd_scan import ssd_scan as _ssd
from repro.kernels.weighted_aggregate import weighted_aggregate as _agg


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"no Pallas path for backend {backend!r}")
    return backend == "cpu"


def use_pallas() -> bool:
    return os.environ.get("REPRO_USE_PALLAS", "0") not in ("0", "false")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, causal, window, block_q, block_k):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=_interpret())


def _flash_diff_fwd(q, k, v, causal, window, block_q, block_k):
    return _flash_diff(q, k, v, causal, window, block_q, block_k), (q, k, v)


def _flash_diff_bwd(causal, window, block_q, block_k, res, g):
    # pallas_call has no autodiff rule; the backward pass differentiates
    # the jnp oracle instead (flash-attention forward is where the fused
    # kernel pays — the recomputed XLA backward is numerically the exact
    # VJP of the attention the kernel approximates bit-for-bit in tests)
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: ref.flash_attention_ref(a, b, c, causal=causal,
                                                window=window), q, k, v)
    return vjp(g)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, *, causal=True, window=None,
                    block_q=128, block_k=128):
    """Differentiable wrapper: Pallas kernel forward, reference-VJP
    backward — model code (models/attention.py) can route training
    forwards through the kernel under ``jax.grad``."""
    return _flash_diff(q, k, v, causal, window, block_q, block_k)


def decode_attention(q, k, v, length, **kw):
    return _decode(q, k, v, length, interpret=_interpret(), **kw)


def ssd_scan(x, dt, A, B_, C_, *, chunk=128, **kw):
    """Broadcasts grouped B/C (B,L,G,N) to per-head before the kernel."""
    H = x.shape[2]
    if B_.shape[2] != H:
        rep = H // B_.shape[2]
        B_ = jnp.repeat(B_, rep, axis=2)
        C_ = jnp.repeat(C_, rep, axis=2)
    return _ssd(x, dt, A, B_, C_, chunk=chunk, interpret=_interpret(), **kw)


def moe_gemm(x, w, **kw):
    return _moe_gemm(x, w, interpret=_interpret(), **kw)


def weighted_aggregate(stacked, weights, **kw):
    return _agg(stacked, weights, interpret=_interpret(), **kw)


def robust_aggregate(stacked, n, **kw):
    """Coordinate-wise trimmed mean / median over the stacked-client axis
    (defense plane, core/defenses.py)."""
    return _robust(stacked, n, interpret=_interpret(), **kw)


def weighted_aggregate_tree(updates_stacked, weights, **kw):
    """Apply the FedAvg kernel leaf-wise over a pytree of stacked updates."""
    def per(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        return weighted_aggregate(flat, weights, **kw).reshape(leaf.shape[1:])
    return jax.tree.map(per, updates_stacked)


__all__ = ["flash_attention", "decode_attention", "ssd_scan", "moe_gemm",
           "weighted_aggregate", "weighted_aggregate_tree",
           "robust_aggregate", "use_pallas", "ref"]
