"""Pallas TPU robust aggregation for the defense plane (core/defenses.py):
coordinate-wise trimmed mean / median over N stacked client updates,
flattened to (N, M) — the same block layout as ``weighted_aggregate``.

Grid (n_m,) over the parameter dimension; each step loads an (N, block_m)
tile as N row vectors of (block_m,) lanes, pushes the padding rows
(row >= n) to the top of a full sort over the small stacked-client axis
(N <= ~128 uploads), then reduces the selected rank window row by row.
The sort is Batcher's odd-even merge network, statically unrolled over a
Python list of rows like the weight loop of ``weighted_aggregate``
(543 compare-exchanges at N=64, against 2016 for odd-even
transposition — the unrolled program, and so its compile time, scales
with that count). Mosaic lowers no scatter, so no row is ever written
back into a stacked array:

    trimmed_mean — mean of ranks [b, n-b)   (b values dropped per end)
    median       — midpoint of ranks (n-1)//2 and n//2

``n`` (real row count) and ``b`` (per-end trim count) ride in SMEM, so one
compiled kernel serves every cohort size at a fixed (N, M) padding. The
reduction is bandwidth-bound like FedAvg (reads N x M, writes M); the sort
adds O(N log^2 N) VPU min/max per tile, which stays VMEM-resident at the
default block_m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def sort_network(n_rows):
    """Compare-exchange pairs (i, j), i < j, of Batcher's odd-even merge
    sort over ``n_rows`` keys: the network for the next power of two with
    every pair that touches a row >= n_rows dropped — those rows stand for
    +inf keys, which never move."""
    n = 1 << max(n_rows - 1, 0).bit_length()
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    lo, hi = i + j, i + j + k
                    if lo // (2 * p) == hi // (2 * p) and hi < n_rows:
                        pairs.append((lo, hi))
            k //= 2
        p *= 2
    return pairs


def _robust_kernel(nb_ref, x_ref, o_ref, *, n_rows, mode):
    n = nb_ref[0]
    b = nb_ref[1]
    x = x_ref[...].astype(jnp.float32)                    # (N, bm)
    # padding sorts past rank n-1
    rows = [jnp.where(i < n, x[i], jnp.inf) for i in range(n_rows)]

    # full sort along the client axis: statically unrolled
    # compare-exchanges on (bm,) lanes
    for i, j in sort_network(n_rows):
        a, c = rows[i], rows[j]
        rows[i], rows[j] = jnp.minimum(a, c), jnp.maximum(a, c)

    if mode == "trimmed_mean":
        acc = jnp.zeros_like(rows[0])
        for i, r in enumerate(rows):
            acc = acc + jnp.where((i >= b) & (i < n - b), r, 0.0)
        o_ref[...] = (acc / jnp.maximum(n - 2 * b, 1)
                      .astype(jnp.float32)).astype(o_ref.dtype)
    else:   # median
        lo = hi = jnp.zeros_like(rows[0])
        for i, r in enumerate(rows):
            lo = jnp.where(i == (n - 1) // 2, r, lo)
            hi = jnp.where(i == n // 2, r, hi)
        o_ref[...] = ((lo + hi) * 0.5).astype(o_ref.dtype)


def robust_aggregate(stacked, n, *, trim=0, mode="trimmed_mean",
                     block_m=2048, interpret=False):
    """stacked (N, M) float, first ``n`` rows real -> (M,) robust reduce.

    trim — rows dropped per end (``mode="trimmed_mean"`` only; the caller
    computes it from its trim fraction so kernel and oracle agree on the
    integer rank window). ``n``/``trim`` ride in SMEM — one compiled
    kernel per (N, M, mode, block_m), NOT per cohort size.
    """
    assert mode in ("trimmed_mean", "median"), mode
    N = stacked.shape[0]
    assert 0 < n <= N and 0 <= 2 * trim < n, (n, N, trim)
    return _robust_call(stacked, jnp.asarray([n, trim], jnp.int32),
                        mode=mode, block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("mode", "block_m",
                                             "interpret"))
def _robust_call(stacked, nb, *, mode, block_m, interpret):
    N, M = stacked.shape
    block_m = min(block_m, M)
    pad = (-M) % block_m
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    Mp = M + pad

    kernel = functools.partial(_robust_kernel, n_rows=N, mode=mode)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // block_m,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((N, block_m), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_m,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((Mp,), stacked.dtype),
        interpret=interpret,
    )(nb, stacked)
    return out[:M]
