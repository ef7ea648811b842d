"""Vectorized cohort execution engine (Alg. 1, all scheduled UEs at once).

The paper trains every scheduled UE independently per round; the seed
implemented that as a sequential Python loop (`FeelServer.run_round` ->
`local_train`) that re-traced the per-client epoch for every distinct
client dataset size. Here the round's cohort is stacked into
(N, max_samples, ...) arrays (see ``data.partition.pad_clients`` for the
padding/masking contract) and all N local trainings run in ONE jitted,
vmapped program:

    cohort_train — vmap of (masked epochs + masked local metric) over the
        leading client axis; global params are broadcast in, per-client
        trained params come back stacked on axis 0, ready for
        ``fedavg_stacked`` / the Pallas ``weighted_aggregate`` kernel.
    cohort_eval  — one vmapped pass scoring every uploaded model on the
        (per-UE masked) public test set, replacing the server's per-model
        evaluation loop (Alg. 1 line 14).

The engine is task-generic (federated/task.py): the per-sample arrays are
a pytree ``data`` dict ({"x", "y"} feature/label arrays for the MNIST MLP,
{"tokens"} int32 windows for the LM task) and the per-client train/metric
steps are the TASK's jit-static methods — the vmap/scan/bucket machinery
never mentions a concrete model. Tasks are frozen dataclasses, so passing
them via ``static_argnames`` keys one compile cache entry per task.

Evaluation is over the task's prediction UNITS (test samples for MNIST,
next-token target positions for the LM): ``eval_inputs`` is the task's
device-side test pytree, ``y_units``/``masks`` are (U,)/(N, U) unit-level
labels and per-UE support masks.

Shapes are cohort-size dependent, so each distinct (N, max_samples) pair
compiles once and is cached for all later rounds; padding max_samples to a
round-stable value keeps the number of distinct shapes small.

Size-bucketed sub-cohorts: padding every client to the *global* maximum
wastes ~2x the real sample count under the paper's 1-30 group allocation,
so the server splits a round's cohort into 2-3 ``max_samples`` buckets
(``data.partition.bucket_levels`` — quantized so compiles stay cached),
trains each bucket with ``cohort_train``, and merges the per-bucket stacks
back into selection order (``merge_stacks``) for ONE ``fedavg_stacked``
call whose weights span all buckets. The server's round runs that glue
as two programs around the buckets' ``cohort_train`` calls:
``gather_buckets`` gathers every bucket's rows, and ``assemble_cohort``
merges the trained rows into selection order, pads the cohort and
gathers its eval masks. ``cohort_train_multi`` is the
multi-run variant (per-row parameters) used by the batched sweep runner in
``federated/simulation.py`` — seeds/policies become one more slice of the
client axis.

Streamed cohort: a model too large to hold one copy per cohort row (the
Moonlight share, 2.27 GB a copy) trains one client at a time instead.
``streams`` decides from the model's bytes and the cohort's padded rows
against the device's memory; ``client_train`` runs one UE's local epochs
as ``client_sgd_step`` calls that donate the local model's buffers,
``upload_eval`` scores its upload for Eq. 1, and ``add_scaled`` folds the
weighted upload into the running FedAvg sum — no (K, ...) stack exists.
"""
from __future__ import annotations

import functools
import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.models.common import correct_counts


@partial(jax.jit, static_argnames=("task", "epochs", "batch_size"))
def cohort_train(task, params, data, mask, lr, epochs: int,
                 batch_size: int = 50):
    """Train the whole cohort in one vmapped step.

    task — the jit-static FeelTask whose ``sgd_epoch``/``local_metric``
    define the per-client step; params — global model (broadcast to every
    client); data — per-sample array pytree with leaves (N, S, ...),
    mask (N, S) — the padded, stacked cohort.
    Returns (stacked_params with leaves (N, ...), acc_local (N, 2)) where
    acc_local is each client's self-reported metric on its own (valid)
    samples after local training (Alg. 1 line 11), as (correct, valid)
    counts for ``models.common.count_accuracy``.
    """
    def one(di, mi):
        # fori_loop (not Python unrolling) keeps the traced epoch body
        # single-copy — compile time is the cohort engine's main fixed cost
        p = jax.lax.fori_loop(
            0, epochs,
            lambda _, q: task.sgd_epoch(q, di, mi, lr, batch_size),
            params)
        return p, task.local_metric(p, di, mi)

    return jax.vmap(one)(data, mask)


@partial(jax.jit, static_argnames=("task", "epochs", "batch_size"))
def cohort_train_multi(task, stacked_params, data, mask, lr, epochs: int,
                       batch_size: int = 50):
    """``cohort_train`` with *per-client* parameters (leaves (N, ...)).

    The batched sweep runner's entry point: rows gathered from different
    runs (policy x seed x attack-pair) carry different global models, so the
    run axis folds into the client vmap axis — one compiled program trains
    an arbitrary mix of runs as long as the padded (N, S) shape matches.
    Row results are independent, so a row trains identically whether its
    run's cohort is stacked alone or with other runs.
    """
    def one(p, di, mi):
        q = jax.lax.fori_loop(
            0, epochs,
            lambda _, r: task.sgd_epoch(r, di, mi, lr, batch_size),
            p)
        return q, task.local_metric(q, di, mi)

    return jax.vmap(one)(stacked_params, data, mask)


def pad_count(n: int, multiple: int = 8) -> int:
    """Cohort-axis padding target: next power of two below ``multiple``
    (1, 2, 4), multiples of ``multiple`` above. Keeps the set of compiled
    cohort shapes small WITHOUT ballooning small sub-cohorts — padding a
    2-row bucket to 8 rows would quadruple its training work, which at
    small K costs more than size-bucketing saves."""
    assert n >= 1
    if n >= multiple:
        return -(-n // multiple) * multiple
    p = 1
    while p < n:
        p *= 2
    return p


def merge_stacks(stacked_list, order=None):
    """Concatenate per-bucket stacked pytrees on axis 0; ``order`` (optional
    int array) then permutes rows — the bucketed engine uses it to restore
    the schedule's selection order so FedAvg accumulates in exactly the
    order the loop oracle uses (bit-for-bit parity)."""
    merged = (stacked_list[0] if len(stacked_list) == 1 else
              jax.tree.map(lambda *ls: jnp.concatenate(ls, axis=0),
                           *stacked_list))
    if order is not None:
        idx = jnp.asarray(order)
        merged = jax.tree.map(lambda l: jnp.take(l, idx, axis=0), merged)
    return merged


def pad_stacked(stacked, n_total: int):
    """Zero-pad a stacked pytree's leading axis to ``n_total`` rows.

    Null rows get weight 0 in ``fedavg_stacked`` (exact +0.0 contribution)
    and an all-zero eval mask (score 0.0, discarded), so padding the cohort
    axis to a stable multiple keeps compiled eval/aggregate programs
    cache-hot without perturbing results.
    """
    def pad(l):
        n = l.shape[0]
        if n == n_total:
            return l
        return jnp.concatenate(
            [l, jnp.zeros((n_total - n,) + l.shape[1:], l.dtype)], axis=0)
    return jax.tree.map(pad, stacked)


@jax.jit
def gather_buckets(buckets, rows):
    """One round's training inputs: bucket b's (data pytree, mask) rows
    ``rows[b]``, gathered on the device for every bucket in one program."""
    return tuple(jax.tree.map(lambda a, r=r: jnp.take(a, r, axis=0), bkt)
                 for bkt, r in zip(buckets, rows))


@jax.jit
def assemble_cohort(stacks, counts, take, masks, mask_rows):
    """The trained buckets as one padded cohort, in one program.

    stacks / counts — per-bucket ``cohort_train`` outputs (padded rows
    included); take (n_pad,) — each cohort row's row in the buckets'
    concatenation, in selection order, with pad rows pointing past its
    end (filled with zeros: weight-0, all-zero-mask null rows); masks
    (K+1, U) — the per-UE eval unit masks, gathered at ``mask_rows``.
    The same data movement as ``merge_stacks`` + ``pad_stacked`` and a
    mask gather, so the values are bit-equal. Returns (stacked (n_pad,
    ...), local-metric counts (n_pad, 2), eval masks (n_pad, U)).
    """
    def merge(*parts):
        return jnp.take(jnp.concatenate(parts), take, axis=0, mode="fill",
                        fill_value=0)
    return (jax.tree.map(merge, *stacks), merge(*counts),
            jnp.take(masks, mask_rows, axis=0))


def broadcast_params(params, n: int):
    """Tile a single parameter pytree to (n, ...) rows (sweep stacking)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p, (n,) + p.shape),
                        params)


@partial(jax.jit, static_argnames=("task",))
def cohort_eval(task, stacked_params, eval_inputs, y_units, masks):
    """Score every uploaded model on the public test set in one vmap.

    stacked_params — leaves (N, ...); eval_inputs — the task's device-side
    test pytree; y_units (U,) — unit-level labels (test labels for MNIST,
    next-token targets for the LM); masks (N, U) — per-UE evaluation unit
    masks (the server restricts Eq. 1's acc_test to the symbols a UE
    claims to hold). Returns (N, 2) (correct, valid) unit counts;
    ``models.common.count_accuracy`` divides them on the host.
    """
    def one(p, m):
        correct = (task.predict_units(p, eval_inputs)
                   == y_units).astype(jnp.float32)
        return correct_counts(correct, m)

    return jax.vmap(one)(stacked_params, masks)


@partial(jax.jit, static_argnames=("task",))
def cohort_eval_rows(task, stacked_params, eval_inputs, y_rows, masks):
    """``cohort_eval`` with per-row labels: y_rows (N, U).

    The sweep's metric phase uses it to score the attack success rate —
    a row whose unit labels are relabelled to the attack's target over
    the source mask — alongside the plain accuracy rows, in the same
    vmapped call.
    """
    def one(p, yr, m):
        correct = (task.predict_units(p, eval_inputs)
                   == yr).astype(jnp.float32)
        return correct_counts(correct, m)

    return jax.vmap(one)(stacked_params, y_rows, masks)


def unstack(stacked_params, i: int):
    """Extract client ``i``'s parameter pytree from the stacked cohort."""
    return jax.tree.map(lambda l: l[i], stacked_params)


# ---------------------------------------------------------------------- #
# Streamed cohort (one client at a time)
# ---------------------------------------------------------------------- #
STACK_SHARE = 0.5   # of device memory the stacked path may plan to fill


@functools.lru_cache(maxsize=None)
def device_memory_bytes() -> int:
    """The default device's memory; the host's where the backend reports
    none (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    if "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")


def streams(param_bytes: int, rows: int) -> bool:
    """Whether a cohort of ``rows`` padded rows trains streamed: the
    stacked path holds the global model and, per row, a model, its
    gradient and the trained upload ((1 + 3 rows) x the model's bytes);
    beyond ``STACK_SHARE`` of the device's memory the cohort streams."""
    return (1 + 3 * rows) * param_bytes > STACK_SHARE * device_memory_bytes()


@partial(jax.jit, static_argnames=("task",), donate_argnames=("params",))
def client_sgd_step(task, params, data, mask, lr):
    """One local step of one client; ``params`` is donated."""
    return task.sgd_step(params, data, mask, lr)


@partial(jax.jit, static_argnames=("task",))
def client_metric(task, params, data, mask):
    return task.local_metric(params, data, mask)


@partial(jax.jit, static_argnames=("task",))
def upload_eval(task, params, eval_inputs, y_units, mask):
    """One upload's (correct, valid) counts on its UE's masked units."""
    correct = (task.predict_units(params, eval_inputs)
               == y_units).astype(jnp.float32)
    return correct_counts(correct, mask)


@jax.jit
def _copy(params):
    return jax.tree.map(jnp.copy, params)


@jax.jit
def scaled(params, w):
    """w * params, accumulated in float32."""
    return jax.tree.map(lambda p: (w * p.astype(jnp.float32)).astype(p.dtype),
                        params)


@partial(jax.jit, donate_argnums=(0,))
def add_scaled(total, params, w):
    """total + w * params in float32; ``total`` is donated."""
    return jax.tree.map(
        lambda t, p: (t.astype(jnp.float32)
                      + w * p.astype(jnp.float32)).astype(t.dtype),
        total, params)


def client_train(task, params, batches, lr, epochs: int):
    """One client's local epochs from ``params`` over its ``batches``
    (a list of (data, mask) device pairs): returns (trained params, its
    (correct, valid) local-metric counts, its step stats folded — summed
    ``pairs``, largest ``load_max``), all still on the device."""
    p = _copy(params)
    stats = None
    for _ in range(epochs):
        for d, m in batches:
            p, st = client_sgd_step(task, p, d, m, lr)
            stats = st if stats is None else {
                "pairs": stats["pairs"] + st["pairs"],
                "load_max": jnp.maximum(stats["load_max"], st["load_max"])}
    counts = None
    for d, m in batches:
        c = client_metric(task, p, d, m)
        counts = c if counts is None else counts + c
    return p, counts, stats


# ---------------------------------------------------------------------- #
# telemetry probe surface (DESIGN.md §14)
# ---------------------------------------------------------------------- #
# The jitted entry points of the data plane. The sweep/serve drivers
# snapshot their compile-cache sizes (obs.trace.jit_cache_size) as
# compile-cache gauges at end of run.
JITTED_ENTRY_POINTS = {
    "cohort_train": cohort_train,
    "cohort_train_multi": cohort_train_multi,
    "gather_buckets": gather_buckets,
    "assemble_cohort": assemble_cohort,
    "cohort_eval": cohort_eval,
    "cohort_eval_rows": cohort_eval_rows,
    "client_sgd_step": client_sgd_step,
    "client_metric": client_metric,
    "upload_eval": upload_eval,
}


def cache_sizes() -> dict:
    """Compile-cache entry count per jitted entry point (-1 if the
    probe API is unavailable on this jax version)."""
    from repro.obs.trace import jit_cache_size
    return {k: jit_cache_size(f) for k, f in JITTED_ENTRY_POINTS.items()}
