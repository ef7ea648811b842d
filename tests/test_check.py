"""Tier-1 gate + self-tests for the static contract suite (DESIGN.md §11).

Three layers:
- the repo itself must be CLEAN under every checker (`run_checks` with
  the trace pass included — this is `python -m repro.check --strict`);
- the CLI contract: `--json` writes the commit-keyed report, `--strict`
  exit codes;
- per-checker self-tests: mutate a known-good snippet (inject `jnp`
  into an oracle, branch on a tracer, draw from the global RNG, drop a
  registry entry, delete a kernel's `_ref` twin, promote to f64) and
  assert the checker catches exactly that injection — a checker that
  cannot detect its own target rule is silently useless.
"""
import ast
import json
import textwrap
import types

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.check import CHECKERS, run_checks
from repro.check.__main__ import main as check_main
from repro.check.common import SourceFile, parse_waivers
from repro.check.lints import (check_nondeterminism, lint_dtype_f64,
                               lint_masked_mean, lint_nondeterminism,
                               lint_oracle_purity, lint_tracer_leak,
                               lint_wall_clock)
from repro.check.registry import kernel_ref_twins, registry_coverage
from repro.check.trace import (_static_spec_literal, assert_f64_outputs,
                               assert_no_f64)


def _src(text: str) -> SourceFile:
    return SourceFile.from_text(textwrap.dedent(text))


# ---------------------------------------------------------------------- #
# the repo is clean (the tier-1 gate: `python -m repro.check --strict`)
# ---------------------------------------------------------------------- #
def test_repo_is_clean_strict():
    report = run_checks()
    assert [v.format() for v in report.violations] == []
    assert report.ok
    assert set(report.per_checker) == set(CHECKERS)
    inv = report.inventory
    assert inv["n_modules"] == inv["n_live"] + inv["n_dead"]
    assert inv["n_modules"] > 50            # the import graph was walked
    assert inv["dead_loc"] == sum(m["loc"] for m in inv["dead"])


def test_population_plane_revived_sharding_stack():
    """The population plane (DESIGN.md §12) revived part of the seed's
    big-model serving inheritance: core.population imports launch.mesh
    and sharding.specs, so both must now be LIVE in the
    dead-inheritance inventory — if either falls back onto the dead
    list, the million-UE mesh path silently lost its only caller."""
    inv = run_checks().inventory
    dead = {m["module"] for m in inv["dead"]}
    for mod in ("repro.core.population", "repro.launch.mesh",
                "repro.sharding.specs"):
        assert mod not in dead, f"{mod} regressed to dead inheritance"
    assert not any(m.startswith("repro.sharding") for m in dead), dead


def test_async_plane_revived_serve_launcher():
    """The async engine (DESIGN.md §13) revived launch/serve.py from the
    seed's dead decode launcher into the event-driven simulation driver:
    it and the engine itself must be LIVE in the dead-inheritance
    inventory — falling back onto the dead list means the async plane
    silently lost its only caller."""
    inv = run_checks().inventory
    dead = {m["module"] for m in inv["dead"]}
    for mod in ("repro.launch.serve", "repro.federated.async_engine"):
        assert mod not in dead, f"{mod} regressed to dead inheritance"


def test_observability_plane_live_obs_and_roofline():
    """The obs package (DESIGN.md §14) and launch/roofline.py (the
    dry-run's roofline terms) must be LIVE in the dead-inheritance
    inventory, or the telemetry plane silently lost its last caller."""
    inv = run_checks().inventory
    dead = {m["module"] for m in inv["dead"]}
    for mod in ("repro.obs", "repro.obs.trace", "repro.obs.clock",
                "repro.obs.metrics", "repro.obs.report",
                "repro.launch.roofline"):
        assert mod not in dead, f"{mod} regressed to dead inheritance"


def test_cli_strict_json_report(tmp_path):
    out = tmp_path / "check_report.json"
    rc = check_main(["--strict", "--json", "--out", str(out),
                     "--no-trace"])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["check"] == "contracts" and payload["ok"]
    # same meta schema as the BENCH_* writers
    assert set(payload["meta"]) == {"commit", "python", "jax", "numpy",
                                    "timestamp"}
    assert payload["violations"] == []
    assert payload["per_checker"]["trace"] == -1        # --no-trace
    assert payload["per_checker"]["oracle-purity"] == 0
    assert payload["inventory"]["n_modules"] > 0


def test_checker_registry_names():
    assert list(CHECKERS) == [
        "oracle-purity", "tracer-leak", "nondeterminism", "dtype",
        "registry-coverage", "kernel-ref-twin", "static-args", "trace"]


# ---------------------------------------------------------------------- #
# self-test: oracle purity
# ---------------------------------------------------------------------- #
def test_oracle_purity_catches_injected_jnp():
    good = _src("""
        import numpy as np
        import jax.numpy as jnp

        def agg_oracle(x):
            return np.sum(x, axis=0)

        def agg_batched(x):
            return jnp.sum(x, axis=0)     # non-oracle: jnp is fine
    """)
    assert lint_oracle_purity(good) == []
    bad = _src("""
        import numpy as np
        import jax.numpy as jnp

        def agg_oracle(x):
            return jnp.sum(x, axis=0)
    """)
    vs = lint_oracle_purity(bad)
    assert len(vs) == 1 and vs[0].rule == "oracle-purity"
    assert "agg_oracle" in vs[0].message
    # the *_host suffix is reserved too
    host = _src("""
        import jax

        def eval_host(p):
            return jax.tree.map(lambda l: l, p)
    """)
    assert [v.rule for v in lint_oracle_purity(host)] == ["oracle-purity"]


# ---------------------------------------------------------------------- #
# self-test: tracer leaks
# ---------------------------------------------------------------------- #
def test_tracer_leak_catches_branch_on_traced_arg():
    bad = _src("""
        import jax

        @jax.jit
        def step(x):
            if x > 0:
                return x
            return -x
    """)
    vs = lint_tracer_leak(bad)
    assert len(vs) == 1 and vs[0].rule == "tracer-leak"
    assert "`if`" in vs[0].message


def test_tracer_leak_catches_host_conversion():
    bad = _src("""
        import jax

        @jax.jit
        def step(x):
            return float(x) * 2.0
    """)
    assert [v.rule for v in lint_tracer_leak(bad)] == ["tracer-leak"]


def test_tracer_leak_exemptions():
    # static args are Python values; shape attrs are trace-static;
    # un-jitted functions may do anything
    good = _src("""
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("k",))
        def step(x, k):
            if k > 2:
                return x
            if x.shape[0] > 4:
                return -x
            return x

        def host_side(x):
            if x > 0:
                return float(x)
            return 0.0
    """)
    assert lint_tracer_leak(good) == []


# ---------------------------------------------------------------------- #
# self-test: nondeterminism
# ---------------------------------------------------------------------- #
def test_nondeterminism_catches_global_rng_and_clocks():
    bad = _src("""
        import time
        import numpy as np

        def sample():
            t = time.time()
            u = np.random.normal(size=3)
            rng = np.random.default_rng()
            return t, u, rng
    """)
    vs = lint_nondeterminism(bad)
    assert len(vs) == 3
    assert all(v.rule == "nondeterminism" for v in vs)
    good = _src("""
        import numpy as np

        def sample(seed):
            rng = np.random.default_rng(seed)
            return rng.normal(size=3)
    """)
    assert lint_nondeterminism(good) == []


def test_nondeterminism_catches_wall_clock_in_async_engine_style_code():
    """The async engine's event clock must come from the Eq. 6/7 latency
    model on seeded draws — wall-clock reads (and sleeps) in an
    async-engine-styled event loop are violations, and the engine's
    module path is inside the lint's simulation scope."""
    from repro.check.lints import _in_scope
    bad = SourceFile.from_text(textwrap.dedent("""
        import heapq
        import time

        def run(heap):
            while heap:
                t_arr, e = heapq.heappop(heap)
                time.sleep(t_arr - time.time())
                yield e
    """), rel="src/repro/federated/async_engine.py")
    vs = lint_nondeterminism(bad)
    assert len(vs) == 2 and all(v.rule == "nondeterminism" for v in vs)
    assert any("sleep" in v.message for v in vs)
    assert any("time.time" in v.message for v in vs)
    assert _in_scope(bad)
    # launch/ is outside the SIMULATION lint's scope (ad-hoc seeds are
    # fine there) — but the wall-clock half still applies repo-wide
    # through lint_wall_clock (tests below)
    assert not _in_scope(SourceFile.from_text(
        "x = 1", rel="src/repro/launch/serve.py"))


def test_wall_clock_lint_outside_sanctioned_site():
    """Telemetry contract (DESIGN.md §14): direct time-module clock
    reads anywhere under src/repro are violations EXCEPT in
    obs/clock.py — host tooling routes through
    ``repro.obs.clock.wall_clock``."""
    bad = SourceFile.from_text(textwrap.dedent("""
        import time

        def timed():
            return time.perf_counter()
    """), rel="src/repro/launch/serve.py")
    vs = lint_wall_clock(bad)
    assert len(vs) == 1 and vs[0].rule == "nondeterminism"
    assert "repro.obs.clock" in vs[0].message
    # the from-import alias form is caught too
    alias = SourceFile.from_text(textwrap.dedent("""
        from time import monotonic

        def timed():
            return monotonic()
    """), rel="src/repro/launch/dryrun.py")
    assert len(lint_wall_clock(alias)) == 1
    # no time import at all -> clean
    assert lint_wall_clock(SourceFile.from_text(
        "x = 1", rel="src/repro/launch/serve.py")) == []
    # the shared rule id means the existing waiver mechanism covers the
    # repo-wide rule too
    waived = SourceFile.from_text(textwrap.dedent("""
        import time

        def timed():
            # repro: allow(nondeterminism)
            return time.time()
    """), rel="src/repro/launch/serve.py")
    assert lint_wall_clock(waived) == []


def test_check_nondeterminism_exempts_only_obs_clock():
    """check_nondeterminism dispatch: simulation dirs get the full
    lint, every other src/repro file gets the wall-clock half, and
    obs/clock.py — the one sanctioned site — is exempt."""
    code = "import time\n\ndef t():\n    return time.monotonic()\n"

    def ctx(rel):
        return types.SimpleNamespace(
            sources=[SourceFile.from_text(code, rel=rel)])

    assert check_nondeterminism(ctx("src/repro/obs/clock.py")) == []
    assert len(check_nondeterminism(ctx("src/repro/obs/trace.py"))) == 1
    assert len(check_nondeterminism(
        ctx("src/repro/launch/serve.py"))) == 1
    assert len(check_nondeterminism(
        ctx("src/repro/federated/server.py"))) == 1     # full sim lint
    assert check_nondeterminism(ctx("tests/whatever.py")) == []


def test_waiver_comment_suppresses_rule():
    waived = _src("""
        import numpy as np

        def sample():
            # repro: allow(nondeterminism)
            return np.random.normal(size=3)
    """)
    assert lint_nondeterminism(waived) == []
    # a waiver for a DIFFERENT rule does not suppress
    other = _src("""
        import numpy as np

        def sample():
            # repro: allow(dtype-f64)
            return np.random.normal(size=3)
    """)
    assert len(lint_nondeterminism(other)) == 1


# ---------------------------------------------------------------------- #
# self-test: dtype discipline
# ---------------------------------------------------------------------- #
def test_dtype_f64_requires_x64_scope():
    bad = _src("""
        import jax.numpy as jnp

        def promote(x):
            return x.astype(jnp.float64)
    """)
    assert [v.rule for v in lint_dtype_f64(bad)] == ["dtype-f64"]
    good = _src("""
        import jax
        import jax.numpy as jnp

        def promote(x):
            with jax.enable_x64(True):
                return x.astype(jnp.float64)
    """)
    assert lint_dtype_f64(good) == []


def test_masked_mean_pin():
    bad = _src("""
        import jax.numpy as jnp

        def mean(x, m):
            return jnp.sum(x * m) / jnp.sum(m)
    """)
    assert [v.rule for v in lint_masked_mean(bad)] == ["masked-mean-pin"]
    good = _src("""
        import jax.numpy as jnp

        def mean(x, m):
            return jnp.sum(x * m) / jnp.maximum(jnp.sum(m), 1.0)
    """)
    assert lint_masked_mean(good) == []


# ---------------------------------------------------------------------- #
# self-test: registry completeness
# ---------------------------------------------------------------------- #
def test_registry_coverage_catches_dropped_entry():
    covered = ast.parse("def test_a():\n    run('alpha')\n"
                        "def test_b():\n    run('beta')\n")
    assert registry_coverage({"alpha", "beta"}, "REG",
                             covered, "tests/t.py") == []
    partial_ = ast.parse("def test_a():\n    run('alpha')\n")
    vs = registry_coverage({"alpha", "beta"}, "REG",
                           partial_, "tests/t.py")
    assert len(vs) == 1 and vs[0].rule == "registry-coverage"
    assert "`beta`" in vs[0].message


def test_registry_coverage_parametrize_over_symbol_cannot_lag():
    para = ast.parse(
        "import pytest\n"
        "@pytest.mark.parametrize('name', sorted(REG))\n"
        "def test_all(name):\n    pass\n")
    # the registry can grow arbitrarily: coverage holds by construction
    assert registry_coverage({"a", "b", "zzz-new"}, "REG",
                             para, "tests/t.py") == []


# ---------------------------------------------------------------------- #
# self-test: kernel _ref twins
# ---------------------------------------------------------------------- #
def test_kernel_twin_catches_missing_ref():
    ref_mod = types.SimpleNamespace(foo_ref=object())
    tested = ast.parse("from k import foo, foo_ref\n"
                       "def test_foo():\n    assert foo and foo_ref\n")
    assert kernel_ref_twins(["foo"], ref_mod, tested, "tests/t.py") == []
    vs = kernel_ref_twins(["foo", "bar"], ref_mod, tested, "tests/t.py")
    assert len(vs) == 1 and vs[0].rule == "kernel-ref-twin"
    assert "bar_ref" in vs[0].message


def test_kernel_twin_requires_parity_test():
    ref_mod = types.SimpleNamespace(foo_ref=object())
    vs = kernel_ref_twins(["foo"], ref_mod, ast.parse("x = 1"),
                          "tests/t.py")
    assert len(vs) == 1 and "never referenced" in vs[0].message


# ---------------------------------------------------------------------- #
# self-test: abstract-trace dtype checks
# ---------------------------------------------------------------------- #
def test_trace_checker_catches_f64_promotion():
    import jax
    import jax.numpy as jnp

    x32 = np.ones(3, np.float32)
    assert assert_no_f64(
        "good", lambda: jax.make_jaxpr(lambda x: x * 2.0)(x32)) == []
    vs = assert_no_f64(
        "bad", lambda: jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) + 1.0)(x32))
    assert vs and all(v.rule == "trace-f64" for v in vs)


def test_trace_checker_reports_trace_errors():
    def boom():
        raise ValueError("no inputs")
    vs = assert_no_f64("broken", boom)
    assert len(vs) == 1 and vs[0].rule == "trace-error"


def test_control_f64_pin():
    import jax

    x64 = np.zeros(3)                       # f64 under enable_x64
    assert assert_f64_outputs(
        "good", lambda: jax.make_jaxpr(lambda x: x + 1.0)(x64)) == []
    vs = assert_f64_outputs(
        "bad", lambda: jax.make_jaxpr(
            lambda x: (x + 1.0).astype(np.float32))(x64))
    assert len(vs) == 1 and vs[0].rule == "control-f64-pin"


def test_static_spec_literal():
    lit = ast.parse("partial(jax.jit, static_argnames=('k',))",
                    mode="eval").body
    assert _static_spec_literal(lit) == [("static_argnames", True)]
    computed = ast.parse("partial(jax.jit, static_argnames=NAMES)",
                         mode="eval").body
    assert _static_spec_literal(computed) == [("static_argnames", False)]


# ---------------------------------------------------------------------- #
# property tests (exercise the st.dictionaries/st.text fallback too)
# ---------------------------------------------------------------------- #
@given(st.dictionaries(st.text(alphabet="abcdefgh_", min_size=1,
                               max_size=8),
                       st.booleans(), min_size=1, max_size=6))
@settings(max_examples=10, deadline=None)
def test_registry_coverage_property(reg):
    """For any registry: full literal coverage is clean, and dropping
    the first entry is reported as exactly that entry."""
    names = sorted(reg)
    full = ast.parse("\n".join(
        f"def test_{i}():\n    use({n!r})" for i, n in enumerate(names)))
    assert registry_coverage(names, "REG", full, "tests/t.py") == []
    kept = names[1:]
    partial_ = ast.parse("\n".join(
        f"def test_{i}():\n    use({n!r})"
        for i, n in enumerate(kept)) or "x = 1")
    vs = registry_coverage(names, "REG", partial_, "tests/t.py")
    assert {v.message.split("`")[1] for v in vs} == {names[0]}


@given(st.text(alphabet="abcdefgh-", min_size=1, max_size=10))
@settings(max_examples=10, deadline=None)
def test_waiver_parse_property(rule):
    """A waiver comment covers its own line and the next one, nothing
    else, for any well-formed rule name."""
    text = f"x = 1\ny = 2  # repro: allow({rule})\nz = 3\nw = 4\n"
    w = parse_waivers(text)
    assert rule in w.get(2, set()) and rule in w.get(3, set())
    assert 1 not in w and 4 not in w
