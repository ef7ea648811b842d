"""AST lints over ``src/repro`` — the statically checkable half of the
parity discipline (DESIGN.md §11).

Rules (kebab-case ids double as waiver names, ``common.parse_waivers``):

oracle-purity
    Functions named ``*_oracle`` / ``*_host`` are the host plane of
    record: plain numpy, bit-reproducible, importable without touching
    a device. Any reference to a ``jax``/``jnp`` alias inside one is a
    violation — a "host oracle" that silently routes through XLA can
    drift with backend/fusion choices and stops being an oracle.

tracer-leak
    Inside ``jax.jit``-decorated functions, value-dependent host
    escapes break tracing or silently constant-fold: ``float()`` /
    ``int()`` / ``bool()`` on a non-static argument, ``.item()``,
    any ``np.*(...)`` call, and Python ``if`` on a non-static argument
    (``.shape`` / ``.dtype`` / ``.ndim`` / ``.size`` accesses are
    static under tracing and exempt). Static parameters — declared via
    literal ``static_argnames`` / ``static_argnums`` — are genuinely
    Python values and may branch/convert freely.

nondeterminism
    Simulation code (core/, federated/, data/, kernels/, models/) must
    draw all randomness from explicitly seeded generators — the host
    RNG stream of record — and never from wall clocks: module-singleton
    ``np.random.<draw>()`` calls, unseeded ``default_rng()`` /
    ``RandomState()``, ``time.time()`` and friends, and
    ``datetime.now()`` are violations. Outside the simulation dirs the
    wall-clock half still applies repo-wide: direct ``time.time`` /
    ``time.perf_counter`` / ``time.monotonic`` (and the ``_ns`` /
    ``sleep`` variants) anywhere under ``src/repro`` are violations
    EXCEPT in ``obs/clock.py`` — the repo's only sanctioned wall-clock
    site (DESIGN.md §14); host tooling that wants a timer routes
    through ``repro.obs.clock.wall_clock``.

dtype-f64
    Device-side float64 belongs to the control plane only and always
    under ``jax.enable_x64(True)`` — a ``jnp.float64`` reference
    outside a ``with jax.enable_x64(True):`` block either fails at
    runtime (x64 disabled) or silently forks the f32 data plane.

masked-mean-pin
    The masked-mean idiom must guard its denominator:
    ``jnp.sum(x * m) / jnp.sum(m)`` is a violation — an empty mask
    yields NaN and the unguarded form invites f64 ``.mean()``
    rewrites that fork the reputation streams (federated/task.py).
    Write ``jnp.sum(x * m) / jnp.maximum(jnp.sum(m), 1.0)``.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.check.common import (CheckContext, SourceFile, Violation,
                                dotted_name, iter_functions)

# directories (relative to src/repro) holding deterministic simulation
# code; launch/ + sharding/ + checkpoint/ are host tooling where wall
# clocks and ad-hoc seeds are fine
SIM_DIRS = ("core", "federated", "data", "kernels", "models")

# np.random constructors that are deterministic WHEN given a seed
_SEEDED_CTORS = {"default_rng", "RandomState", "SeedSequence", "PCG64",
                 "Philox", "SFC64", "MT19937"}
# "sleep" rides along: a sleep in simulation code means something is
# waiting on the wall clock — the async engine's event clock
# (federated/async_engine.py) must advance ONLY through the Eq. 6/7
# latency model on seeded draws
_CLOCK_FUNCS = {"time", "perf_counter", "monotonic", "time_ns",
                "perf_counter_ns", "monotonic_ns", "sleep"}
_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size"}


def module_aliases(tree: ast.Module) -> Dict[str, str]:
    """Top-level import alias -> dotted module path (best effort)."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


def _aliases_of(aliases: Dict[str, str], prefix: str) -> Set[str]:
    return {name for name, mod in aliases.items()
            if mod == prefix or mod.startswith(prefix + ".")}


def _violate(out: List[Violation], src: SourceFile, rule: str, line: int,
             msg: str) -> None:
    if not src.waived(rule, line):
        out.append(Violation(rule=rule, path=src.rel, line=line,
                             message=msg))


# --------------------------------------------------------------------- #
# oracle-purity
# --------------------------------------------------------------------- #
def lint_oracle_purity(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    jaxish = _aliases_of(module_aliases(src.tree), "jax")
    if not jaxish:
        return out
    for fn in iter_functions(src.tree):
        if not (fn.name.endswith("_oracle") or fn.name.endswith("_host")):
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id in jaxish \
                    and isinstance(node.ctx, ast.Load):
                _violate(out, src, "oracle-purity", node.lineno,
                         f"host oracle `{fn.name}` references jax alias "
                         f"`{node.id}` — oracles are numpy-only "
                         "(rename the function if it is a device-side "
                         "sequential twin, not a host oracle)")
    return out


# --------------------------------------------------------------------- #
# tracer-leak
# --------------------------------------------------------------------- #
def _jit_static_params(fn: ast.FunctionDef) -> Optional[Set[str]]:
    """None if ``fn`` is not jit-decorated; else its static param names.

    Recognizes ``@jax.jit``, ``@jit``, and
    ``@[functools.]partial(jax.jit, static_argnames=..., static_argnums=...)``
    with literal name/num values (the static-args checker separately
    enforces that they ARE literal).
    """
    params = [a.arg for a in (fn.args.posonlyargs + fn.args.args)]
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = dotted_name(target) or ""
        if name in ("jax.jit", "jit"):
            static: Set[str] = set()
            if isinstance(dec, ast.Call):
                static |= _literal_statics(dec, params)
            return static
        if name.endswith("partial") and isinstance(dec, ast.Call) \
                and dec.args:
            inner = dotted_name(dec.args[0]) or ""
            if inner in ("jax.jit", "jit"):
                return _literal_statics(dec, params)
    return None


def _literal_statics(call: ast.Call, params: List[str]) -> Set[str]:
    static: Set[str] = set()
    for kw in call.keywords:
        try:
            val = ast.literal_eval(kw.value)
        except (ValueError, SyntaxError):
            continue
        if kw.arg == "static_argnames":
            static |= {val} if isinstance(val, str) else set(val)
        elif kw.arg == "static_argnums":
            nums = (val,) if isinstance(val, int) else tuple(val)
            static |= {params[i] for i in nums if i < len(params)}
    return static


class _TestNames(ast.NodeVisitor):
    """Bare Name loads in an expression, NOT behind a shape-like
    attribute access (``x.shape[0] > 4`` is trace-static)."""

    def __init__(self):
        self.names: List[ast.Name] = []

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _SHAPE_ATTRS:
            return                      # skip subtree: static under jit
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name):
        if isinstance(node.ctx, ast.Load):
            self.names.append(node)


def lint_tracer_leak(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    aliases = module_aliases(src.tree)
    np_names = _aliases_of(aliases, "numpy")
    for fn in iter_functions(src.tree):
        static = _jit_static_params(fn)
        if static is None:
            continue
        all_params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)}
        traced = all_params - static
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func) or ""
                if callee in ("float", "int", "bool"):
                    hit = _traced_names(node, traced)
                    if hit:
                        _violate(out, src, "tracer-leak", node.lineno,
                                 f"`{callee}()` on traced argument "
                                 f"`{hit}` inside jitted `{fn.name}` — "
                                 "host conversion breaks tracing")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item":
                    _violate(out, src, "tracer-leak", node.lineno,
                             f"`.item()` inside jitted `{fn.name}` — "
                             "forces a device sync / fails under trace")
                elif callee.split(".")[0] in np_names:
                    _violate(out, src, "tracer-leak", node.lineno,
                             f"numpy call `{callee}(...)` inside jitted "
                             f"`{fn.name}` — np ops constant-fold or "
                             "fail on tracers; use jnp")
            elif isinstance(node, ast.If):
                hit = _traced_names(node.test, traced)
                if hit:
                    _violate(out, src, "tracer-leak", node.lineno,
                             f"Python `if` on traced argument `{hit}` "
                             f"inside jitted `{fn.name}` — branch on "
                             "jnp.where/lax.cond, or declare the "
                             "argument static")
    return out


def _traced_names(expr: ast.AST, traced: Set[str]) -> Optional[str]:
    v = _TestNames()
    v.visit(expr)
    for n in v.names:
        if n.id in traced:
            return n.id
    return None


# --------------------------------------------------------------------- #
# nondeterminism
# --------------------------------------------------------------------- #
def lint_nondeterminism(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    aliases = module_aliases(src.tree)
    np_names = _aliases_of(aliases, "numpy")
    time_mods = _aliases_of(aliases, "time") & {
        k for k, v in aliases.items() if "." not in v}
    dt_mods = {k for k, v in aliases.items() if v == "datetime"}
    clock_funcs = {k for k, v in aliases.items()
                   if v in {f"time.{f}" for f in _CLOCK_FUNCS}}
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func) or ""
        parts = callee.split(".")
        # np.random.* draws on the module singleton / unseeded ctors
        if len(parts) >= 3 and parts[0] in np_names \
                and parts[1] == "random":
            fname = parts[2]
            if fname not in _SEEDED_CTORS and fname != "Generator":
                _violate(out, src, "nondeterminism", node.lineno,
                         f"`{callee}(...)` draws from the global numpy "
                         "RNG — route through a seeded "
                         "np.random.Generator (the stream of record)")
            elif fname in _SEEDED_CTORS and not node.args:
                _violate(out, src, "nondeterminism", node.lineno,
                         f"unseeded `{callee}()` — pass an explicit "
                         "seed so the stream is reproducible")
        elif len(parts) == 2 and parts[0] in np_names \
                and parts[1] in ("default_rng", "RandomState") \
                and not node.args:
            _violate(out, src, "nondeterminism", node.lineno,
                     f"unseeded `{callee}()` — pass an explicit seed")
        # wall clocks
        elif (len(parts) == 2 and parts[0] in time_mods
                and parts[1] in _CLOCK_FUNCS) \
                or (len(parts) == 1 and parts[0] in clock_funcs):
            _violate(out, src, "nondeterminism", node.lineno,
                     f"wall clock `{callee}()` in simulation code — "
                     "results must be a function of config + seeds (the "
                     "async engine's event clock advances only through "
                     "the Eq. 6/7 latency model on seeded draws)")
        elif parts[-1] in ("now", "utcnow", "today") and (
                (len(parts) >= 2 and parts[0] in dt_mods)
                or (len(parts) >= 2
                    and aliases.get(parts[0], "") == "datetime.datetime")):
            _violate(out, src, "nondeterminism", node.lineno,
                     f"wall clock `{callee}()` in simulation code")
    return out


def lint_wall_clock(src: SourceFile) -> List[Violation]:
    """The wall-clock half of the nondeterminism rule, applied repo-wide.

    Direct ``time.<clock>()`` calls (``time``, ``perf_counter``,
    ``monotonic``, the ``_ns`` variants, ``sleep``) anywhere under
    ``src/repro`` are violations outside the one sanctioned site,
    ``obs/clock.py`` — host tooling that wants a timer routes through
    ``repro.obs.clock.wall_clock`` so the telemetry plane (DESIGN.md
    §14) owns every wall-clock read. Same rule id as the simulation
    lint, so existing ``# repro: allow nondeterminism`` waivers apply.
    """
    out: List[Violation] = []
    aliases = module_aliases(src.tree)
    time_mods = _aliases_of(aliases, "time") & {
        k for k, v in aliases.items() if "." not in v}
    clock_funcs = {k for k, v in aliases.items()
                   if v in {f"time.{f}" for f in _CLOCK_FUNCS}}
    if not time_mods and not clock_funcs:
        return out
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func) or ""
        parts = callee.split(".")
        if (len(parts) == 2 and parts[0] in time_mods
                and parts[1] in _CLOCK_FUNCS) \
                or (len(parts) == 1 and parts[0] in clock_funcs):
            _violate(out, src, "nondeterminism", node.lineno,
                     f"wall clock `{callee}()` outside repro.obs.clock — "
                     "route through `repro.obs.clock.wall_clock`, the "
                     "repo's only sanctioned wall-clock site "
                     "(DESIGN.md §14)")
    return out


# --------------------------------------------------------------------- #
# dtype-f64 / masked-mean-pin
# --------------------------------------------------------------------- #
def _x64_ranges(tree: ast.Module) -> List[Tuple[int, int]]:
    """(start, end) line ranges of ``with jax.enable_x64(True):`` blocks."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            expr = item.context_expr
            target = expr.func if isinstance(expr, ast.Call) else expr
            if (dotted_name(target) or "").endswith("enable_x64"):
                out.append((node.lineno, node.end_lineno or node.lineno))
                break
    return out


def lint_dtype_f64(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    jnp_names = _aliases_of(module_aliases(src.tree), "jax.numpy")
    if not jnp_names:
        return out
    ranges = _x64_ranges(src.tree)
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64" \
                and isinstance(node.value, ast.Name) \
                and node.value.id in jnp_names:
            if not any(a <= node.lineno <= b for a, b in ranges):
                _violate(out, src, "dtype-f64", node.lineno,
                         "`jnp.float64` outside a `with jax.enable_x64(True):` "
                         "block — device f64 is control-plane only and "
                         "must be x64-scoped (DESIGN.md §11)")
    return out


def lint_masked_mean(src: SourceFile) -> List[Violation]:
    out: List[Violation] = []
    jnp_names = _aliases_of(module_aliases(src.tree), "jax.numpy")
    if not jnp_names:
        return out

    def is_jnp_sum(node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and (dotted_name(node.func) or "") in
                {f"{a}.sum" for a in jnp_names})

    for node in ast.walk(src.tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Div) \
                and is_jnp_sum(node.left) and is_jnp_sum(node.right):
            _violate(out, src, "masked-mean-pin", node.lineno,
                     "unguarded masked mean `jnp.sum(..)/jnp.sum(..)` — "
                     "pin the denominator: "
                     "`/ jnp.maximum(jnp.sum(mask), 1.0)`")
    return out


# --------------------------------------------------------------------- #
# checker entry points (scope filtering + dispatch)
# --------------------------------------------------------------------- #
def _in_scope(src: SourceFile, dirs=SIM_DIRS) -> bool:
    rel = src.rel
    if not rel.startswith("src/repro/"):
        return False
    sub = rel[len("src/repro/"):]
    return sub.split("/")[0] in dirs or "/" not in sub


def check_oracle_purity(ctx: CheckContext) -> List[Violation]:
    return [v for s in ctx.sources if _in_scope(s, SIM_DIRS + (
        "launch", "sharding", "checkpoint", "optim", "configs"))
            for v in lint_oracle_purity(s)]


def check_tracer_leak(ctx: CheckContext) -> List[Violation]:
    return [v for s in ctx.sources if _in_scope(s)
            for v in lint_tracer_leak(s)]


# the ONE file allowed to read the wall clock (DESIGN.md §14)
_CLOCK_SITE = "src/repro/obs/clock.py"


def check_nondeterminism(ctx: CheckContext) -> List[Violation]:
    out: List[Violation] = []
    for s in ctx.sources:
        if _in_scope(s):
            out.extend(lint_nondeterminism(s))
        elif s.rel.startswith("src/repro/") and s.rel != _CLOCK_SITE:
            out.extend(lint_wall_clock(s))
    return out


def check_dtype(ctx: CheckContext) -> List[Violation]:
    out = []
    for s in ctx.sources:
        if _in_scope(s, SIM_DIRS + ("optim", "configs")):
            out.extend(lint_dtype_f64(s))
            out.extend(lint_masked_mean(s))
    return out
