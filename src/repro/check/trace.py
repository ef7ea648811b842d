"""Abstract-trace contract checks (DESIGN.md §11c).

AST lints can't see through helper calls, so the dtype-pinning contract
is additionally enforced on the *jaxprs* of the key entry points:

trace-f64
    The f32 data-plane programs — ``cohort_train``, ``cohort_eval``,
    ``fedavg_stacked``, the trimmed-mean/median defended aggregation,
    ``ModelAttack.apply_stacked`` — are traced UNDER ``jax.enable_x64(True)``
    (so any stray literal f64 promotion becomes visible instead of
    being silently squashed to f32) with explicitly f32-dtyped inputs,
    and their jaxprs must contain no float64 value and no
    ``convert_element_type`` to float64. NormClip/Krum are the
    documented exception: their norm/distance reductions are f64 by
    design (core/defenses.py) and are excluded.

control-f64-pin
    The mirror contract: the control-plane kernels
    (``_schedule_kernel``, ``_finalize_kernel``) traced under
    ``enable_x64`` with f64 inputs must produce f64 outputs — Eq. 1-3
    and Eq. 9 run in double precision, matching the host oracle's
    numpy dtype, or reputation streams fork.

static-args
    Every ``static_argnames`` / ``static_argnums`` in ``src/repro``
    must be a literal (computed static specs silently change compile
    keys), and every value the repo actually passes statically — the
    ``TASKS`` registry entries — must be hashable frozen dataclasses.

Any exception while building inputs or tracing is itself reported as a
``trace-error`` violation: a trace check that cannot run must fail
loudly, not pass silently.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Callable, List, Tuple

import numpy as np

from repro.check.common import (CheckContext, Violation, dotted_name,
                                iter_functions)


# --------------------------------------------------------------------- #
# jaxpr scanning
# --------------------------------------------------------------------- #
def _jaxpr_f64_sites(jaxpr) -> List[str]:
    """Human-readable descriptions of every f64 occurrence in a closed
    jaxpr (recursing into sub-jaxprs)."""
    sites: List[str] = []

    def strong_f64(v) -> bool:
        aval = getattr(v, "aval", None)
        if aval is None or getattr(aval, "dtype", None) is None:
            return False
        # weak-typed f64 literals (python scalars under x64) promote to
        # the array dtype at the op — only strongly-typed f64 forks f32
        if getattr(aval, "weak_type", False):
            return False
        return np.dtype(aval.dtype) == np.dtype("float64")

    def scan(jx):
        for v in list(jx.invars) + list(jx.outvars) + list(jx.constvars):
            if strong_f64(v):
                sites.append(f"f64 value {v}")
        for eqn in jx.eqns:
            for v in eqn.outvars:
                if strong_f64(v):
                    sites.append(
                        f"f64 intermediate {v} <- {eqn.primitive.name}")
            if eqn.primitive.name == "convert_element_type" \
                    and np.dtype(eqn.params.get("new_dtype")) == \
                    np.dtype("float64"):
                sites.append("convert_element_type -> float64")
            for sub in eqn.params.values():
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    scan(inner)

    scan(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr)
    return sites


def assert_no_f64(name: str, trace_fn: Callable[[], object]
                  ) -> List[Violation]:
    """Trace ``trace_fn`` (must return a jaxpr) under x64 and report
    every f64 site. Self-test entry point: any f32 program can be
    checked through this."""
    import jax
    try:
        with jax.enable_x64(True):
            jaxpr = trace_fn()
    except Exception as e:                          # noqa: BLE001
        return [Violation(rule="trace-error", path=name, line=0,
                          message=f"tracing `{name}` failed: {e!r}")]
    return [Violation(
        rule="trace-f64", path=name, line=0,
        message=f"f32-path `{name}`: {site} — the data plane is "
                "f32-pinned (DESIGN.md §11)")
        for site in _jaxpr_f64_sites(jaxpr)[:5]]


def assert_f64_outputs(name: str, trace_fn: Callable[[], object]
                       ) -> List[Violation]:
    import jax
    try:
        with jax.enable_x64(True):
            jaxpr = trace_fn()
    except Exception as e:                          # noqa: BLE001
        return [Violation(rule="trace-error", path=name, line=0,
                          message=f"tracing `{name}` failed: {e!r}")]
    bad = [str(v) for v in jaxpr.jaxpr.outvars
           if getattr(v.aval, "dtype", None) is not None
           and np.dtype(v.aval.dtype).kind == "f"
           and np.dtype(v.aval.dtype) != np.dtype("float64")]
    return [Violation(
        rule="control-f64-pin", path=name, line=0,
        message=f"control kernel `{name}` output {v} is not f64 under "
                "enable_x64 — Eq. 1-3/9 must match the host oracle's "
                "double precision") for v in bad]


# --------------------------------------------------------------------- #
# repo entry points
# --------------------------------------------------------------------- #
def check_traces(ctx: CheckContext) -> List[Violation]:
    import jax
    import jax.numpy as jnp

    from repro.configs.base import FeelConfig
    from repro.core import control as ctl
    from repro.core import defenses as dfs
    from repro.federated import cohort
    from repro.federated.aggregation import fedavg_stacked
    from repro.federated.task import TASKS

    out: List[Violation] = []
    task = TASKS["mnist_mlp"]
    params = task.init_params(jax.random.PRNGKey(0))
    N, S, U = 2, 8, 6
    f32 = jnp.float32
    data = {"x": jnp.zeros((N, S, 784), f32),
            "y": jnp.zeros((N, S), jnp.int32)}
    mask = jnp.ones((N, S), f32)
    lr = jnp.asarray(0.1, f32)

    out += assert_no_f64(
        "cohort.cohort_train",
        lambda: jax.make_jaxpr(
            lambda p, d, m, r: cohort.cohort_train(task, p, d, m, r, 1, 4)
        )(params, data, mask, lr))

    stacked = cohort.broadcast_params(params, N)
    ei = {"x": jnp.zeros((U, 784), f32)}
    yu = jnp.zeros((U,), jnp.int32)
    masks = jnp.ones((N, U), f32)
    out += assert_no_f64(
        "cohort.cohort_eval",
        lambda: jax.make_jaxpr(
            lambda sp, e, y, m: cohort.cohort_eval(task, sp, e, y, m)
        )(stacked, ei, yu, masks))

    w = jnp.asarray(np.array([1.0, 3.0], np.float32))
    out += assert_no_f64(
        "aggregation.fedavg_stacked",
        lambda: jax.make_jaxpr(fedavg_stacked)(stacked, w))

    # the defended aggregation's batched jnp path stages its sort through
    # the host on CPU (core/defenses._sorted_rows — an eager, documented
    # perf choice), so the traceable f32 contract lives in the pure-jnp
    # oracle twin the kernel is pinned against
    from repro.kernels import ref as kref
    flat = jnp.zeros((4, 16), f32)
    for mode, trim in (("trimmed_mean", 1), ("median", 0)):
        out += assert_no_f64(
            f"kernels.robust_aggregate_ref[{mode}]",
            lambda mode=mode, trim=trim: jax.make_jaxpr(
                lambda fl: kref.robust_aggregate_ref(
                    fl, 4, trim=trim, mode=mode))(flat))
    out += assert_no_f64(
        "kernels.weighted_aggregate_ref",
        lambda: jax.make_jaxpr(kref.weighted_aggregate_ref)(
            flat, jnp.ones((4,), f32)))

    from repro.core.attacks import ModelAttack
    ma = ModelAttack(scale=-1.0)
    mal = np.array([True, False])
    out += assert_no_f64(
        "attacks.ModelAttack.apply_stacked",
        lambda: jax.make_jaxpr(
            lambda sp, gp: ma.apply_stacked(sp, gp, mal))(stacked, params))

    # control plane: f64-pinned under enable_x64
    cfg = FeelConfig()
    R, K = 2, 4
    f64 = np.float64
    out += assert_f64_outputs(
        "control._finalize_kernel",
        lambda: jax.make_jaxpr(ctl._finalize_kernel)(
            np.zeros((R, K), f64), np.zeros((R, K), f64),
            np.zeros((R, K), f64), np.zeros((R, K), f64),
            np.zeros((R, K), f64), np.zeros((R, K), f64),
            f64(cfg.eta), f64(cfg.beta1), f64(cfg.beta2)))
    out += assert_f64_outputs(
        "control._schedule_kernel",
        lambda: jax.make_jaxpr(
            lambda *a: ctl._schedule_kernel(*a, k=K, n_sel=2)[1:4]
        )(np.zeros(R, np.int32), np.zeros((R, K), f64),
          np.ones((R, K), f64), np.full((R, K), 0.5, f64),
          np.full((R, K), 100.0, f64), np.full((R, K), 1e4, f64),
          np.full((R, K), 1.0, f64),
          np.tile(np.arange(K), (R, 1)).astype(f64),
          np.full(R, 0.5, f64), np.full(R, 0.5, f64),
          f64(cfg.gamma), f64(cfg.bandwidth_hz), f64(cfg.p_watt),
          f64(cfg.n0_watt_hz)))
    return out


# --------------------------------------------------------------------- #
# static-arg discipline
# --------------------------------------------------------------------- #
def _static_spec_literal(call: ast.Call) -> List[Tuple[str, bool]]:
    """[(kwarg, is_literal)] for static_argnames/static_argnums kwargs."""
    out = []
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            try:
                ast.literal_eval(kw.value)
                out.append((kw.arg, True))
            except (ValueError, SyntaxError):
                out.append((kw.arg, False))
    return out


def check_static_args(ctx: CheckContext) -> List[Violation]:
    out: List[Violation] = []
    # (a) AST: every static spec in src is a literal
    for src in ctx.sources:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if name.split(".")[-1] not in ("jit", "partial"):
                continue
            for kwarg, ok in _static_spec_literal(node):
                if not ok and not src.waived("static-args", node.lineno):
                    out.append(Violation(
                        rule="static-args", path=src.rel,
                        line=node.lineno,
                        message=f"`{kwarg}` is not a literal — computed "
                                "static specs make compile-cache keys "
                                "unauditable"))
    # (b) runtime: statically-passed registry values are hashable+frozen
    from repro.federated.task import TASKS
    for name, t in sorted(TASKS.items()):
        try:
            hash(t)
        except TypeError:
            out.append(Violation(
                rule="static-args", path="src/repro/federated/task.py",
                line=1,
                message=f"task `{name}` is unhashable — tasks pass "
                        "through jit static_argnames and must hash"))
            continue
        if not (dataclasses.is_dataclass(t)
                and type(t).__dataclass_params__.frozen):
            out.append(Violation(
                rule="static-args", path="src/repro/federated/task.py",
                line=1,
                message=f"task `{name}` is not a frozen dataclass — "
                        "mutable static args silently stale the "
                        "compile cache"))
    return out
