"""Bring-up smoke test: the paper's FEEL round, end to end, on one TPU chip.

    python chip_smoke.py

One process, four phases in order; any mismatch or exception exits
non-zero, and only a run where every phase passed prints the result line.

  a. device check — platform, device_kind, device count; not a TPU -> exit 2.
  b. the §V round at full size: FeelConfig() defaults (K=50, 5 malicious
     UEs, mnist_mlp, n_train=50,000) under flip_6to2 for 3 rounds through
     FeelServer.run_round — production path (engine="vectorized",
     control="batched") against the oracle (engine="loop",
     control="host") from the same seed, both at full f32 matmul
     precision: same selections and objectives every round, global
     accuracy within 1e-5, accuracy rising.
  c. the defended round (defense="trimmed_mean", both paths), then
     weighted_aggregate and robust_aggregate (both modes) on a
     (64, 50,890) stack against their kernels/ref.py twins, each compiled
     to a Mosaic kernel (tpu_custom_call).
  d. the control plane at N=10^4 candidates, K=64, 5 runs: the f64 "jax"
     layout selects exactly what the "hybrid" layout (host float64)
     selects.

Timings on the earlier lines are for information only. The compile cache
is JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache. The
last line of stdout is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
ROUNDS = 3
# the paper MLP's flattened update (784*64 + 64 + 64*10 + 10), 64 uploads
STACK = (64, 50_890)
# §V cohort of K=50 real uploads in the 64-row stack, 20% trimmed per end
N_REAL, TRIM = 50, 10
# population cell: N candidates, K budget, R stacked runs, rounds drawn
POP_N, POP_K, POP_RUNS, POP_ROUNDS = 10_000, 64, 5, 3


def _say(*parts):
    print(*parts, flush=True)


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def device_check():
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    _say(f"[a] device platform={info['platform']} kind={info['kind']} "
         f"count={info['count']}")
    return info


def feel_rounds(engine, control, data, *, defense=None, rounds=ROUNDS):
    """One seeded §V run on ``data`` (train, test) through
    FeelServer.run_round, as examples/quickstart.py drives it; returns
    (logs, per-round seconds).

    The run's f32 matmuls take full f32 precision. At the TPU default (one
    bf16 pass) the vmapped and the per-client programs round differently,
    a client's local accuracy on its few samples moves by a whole sample,
    and Eq. 1 reputations — so the next round's objective — part."""
    import jax
    import numpy as np
    from repro.configs.base import FeelConfig
    from repro.core.attacks import as_scenario
    from repro.core.poisoning import pick_malicious
    from repro.federated.server import FeelServer
    from repro.federated.task import as_task
    from repro.obs.clock import wall_clock

    cfg = FeelConfig()
    task = as_task(cfg.task)
    scn = as_scenario("flip_6to2")
    rng = np.random.default_rng(SEED)
    train, test = data
    malicious = pick_malicious(cfg.n_population, cfg.n_malicious, rng)
    clients = task.partition_clients(train, cfg.n_population, rng,
                                     malicious, scn.data)
    server = FeelServer(cfg, clients, test, rng, policy="dqs",
                        engine=engine, control=control, scenario=scn,
                        defense=defense, task=task)
    logs, secs = [], []
    with jax.default_matmul_precision("highest"):
        for t in range(rounds):
            t0 = wall_clock()
            logs.append(server.run_round(t))
            secs.append(wall_clock() - t0)
    return logs, secs


def compare_runs(tag, prod, oracle):
    """Same selections every round, objectives within 1e-9
    (tests/test_control.py: the f64 control layouts differ by FMA
    contraction only) and global accuracy within 1e-5
    (tests/test_cohort.py)."""
    import numpy as np
    for lp, lo in zip(prod, oracle):
        t = lp.round
        _check(np.array_equal(np.sort(lp.selected), np.sort(lo.selected)),
               f"{tag} round {t}: selected {sorted(lp.selected.tolist())} "
               f"!= oracle {sorted(lo.selected.tolist())}")
        _check(abs(lp.objective - lo.objective) <= 1e-9,
               f"{tag} round {t}: objective {lp.objective!r} != oracle "
               f"{lo.objective!r}")
        _check(abs(lp.global_acc - lo.global_acc) <= 1e-5,
               f"{tag} round {t}: global_acc {lp.global_acc!r} vs oracle "
               f"{lo.global_acc!r}")
        rep = float(np.max(np.abs(lp.reputations - lo.reputations)))
        _say(f"    {tag} round {t}: selected={len(lp.selected)} "
             f"objective={lp.objective!r} (oracle {lo.objective!r}) "
             f"acc={lp.global_acc!r} (oracle {lo.global_acc!r}) "
             f"max|rep-oracle|={rep!r}")


def phase_round(n_train=None, n_test=None):
    """(b) the §V round: production path against the oracle. Returns the
    generated (train, test) for phase (c)."""
    from repro.configs.base import FeelConfig
    from repro.federated.task import as_task
    task = as_task(FeelConfig().task)
    data = task.generate_data(n_train or task.default_n_train,
                              n_test or task.default_n_test, SEED)
    prod, t_prod = feel_rounds("vectorized", "batched", data)
    oracle, t_orc = feel_rounds("loop", "host", data)
    _say(f"    vectorized/batched round s={t_prod!r} "
         f"(round 0 includes compiles)")
    _say(f"    loop/host round s={t_orc!r}")
    compare_runs("[b]", prod, oracle)
    _check(prod[-1].global_acc > prod[0].global_acc,
           f"[b] accuracy did not rise: {prod[0].global_acc!r} -> "
           f"{prod[-1].global_acc!r}")
    return data


def _compiled_kernel(fn, *args):
    """jit + AOT-compile ``fn``; the compiled text must hold a Mosaic
    kernel. Returns (compiled, compile seconds)."""
    import jax
    from repro.obs.clock import wall_clock
    t0 = wall_clock()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = wall_clock() - t0
    _check("tpu_custom_call" in compiled.as_text(),
           f"no tpu_custom_call in {fn.__name__}'s compiled text")
    return compiled, secs


def phase_defense(data, stack=STACK, n_real=N_REAL, trim=TRIM):
    """(c) the defended round, then the aggregation kernels vs ref."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    prod, t_prod = feel_rounds("vectorized", "batched", data, rounds=1,
                               defense="trimmed_mean")
    oracle, _ = feel_rounds("loop", "host", data, rounds=1,
                            defense="trimmed_mean")
    _say(f"    defended round s={t_prod!r}")
    compare_runs("[c] trimmed_mean", prod, oracle)
    _check(prod[0].n_rejected == oracle[0].n_rejected,
           f"[c] n_rejected {prod[0].n_rejected} != {oracle[0].n_rejected}")

    kx, kw = jax.random.split(jax.random.PRNGKey(SEED))
    x = jax.random.normal(kx, stack, jnp.float32)
    w = jax.random.uniform(kw, (stack[0],), jnp.float32, 0.1, 1.0)

    def weighted_aggregate(x, w):
        return ops.weighted_aggregate(x, w)

    def trimmed_mean(x):
        return ops.robust_aggregate(x, n_real, trim=trim,
                                    mode="trimmed_mean")

    def median(x):
        return ops.robust_aggregate(x, n_real, mode="median")

    # the references are plain f32: their one contraction runs at full
    # f32 precision, not the chip's default single bf16 pass
    with jax.default_matmul_precision("highest"):
        want = {
            "weighted_aggregate": ref.weighted_aggregate_ref(x, w),
            "trimmed_mean": ref.robust_aggregate_ref(
                x, n_real, trim=trim, mode="trimmed_mean"),
            "median": ref.robust_aggregate_ref(x, n_real, mode="median"),
        }
    cases = [(weighted_aggregate, (x, w), 1e-5),     # tests/test_kernels.py
             (trimmed_mean, (x,), 1e-6), (median, (x,), 1e-6)]
    for fn, args, tol in cases:
        compiled, secs = _compiled_kernel(fn, *args)
        got = np.asarray(compiled(*args))
        exp = np.asarray(want[fn.__name__])
        err = float(np.max(np.abs(got - exp)))
        _say(f"    {fn.__name__} {stack}: compile s={secs!r} "
             f"max|kernel-ref|={err!r}")
        _check(got.shape == (stack[1],) and np.isfinite(got).all(),
               f"[c] {fn.__name__} output not finite/shape {got.shape}")
        np.testing.assert_allclose(got, exp, atol=tol, rtol=tol,
                                   err_msg=f"[c] {fn.__name__} vs ref")


def phase_control(n=POP_N, k=POP_K, n_runs=POP_RUNS, rounds=POP_ROUNDS):
    """(d) the f64 "jax" control layout against the "hybrid" one, on the
    bench_round population cell's state."""
    import numpy as np
    from repro.core import control as ctl
    from repro.core.population import synthetic_population
    from repro.obs.clock import wall_clock

    state, omega, draw = synthetic_population(n, k, n_runs)
    for t in range(rounds):
        g, rr = draw(t + 1)
        t0 = wall_clock()
        h = ctl.schedule_runs(state, g, rr, *omega, kernel="hybrid")
        t1 = wall_clock()
        j = ctl.schedule_runs(state, g, rr, *omega, kernel="jax")
        t2 = wall_clock()
        for name, i in (("selection", 0), ("costs", 2), ("forced", 4)):
            _check(np.array_equal(h[i], j[i]),
                   f"[d] round {t}: jax {name} != hybrid {name} "
                   f"({int(np.sum(h[i] != j[i]))} entries differ)")
        dv = float(np.max(np.abs(h[3] - j[3]) / np.maximum(
            np.abs(h[3]), np.finfo(float).tiny)))
        _say(f"    [d] round {t}: N={n} K={k} runs={n_runs} selected="
             f"{h[0].sum(-1).tolist()} hybrid s={t1 - t0!r} "
             f"jax s={t2 - t1!r} max rel |values| diff={dv!r}")


def main():
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke.py: the repro package (src/repro) is not next "
              "to this script", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    info = device_check()
    if info["platform"] != "tpu":
        print(f"chip_smoke.py: no TPU (JAX backend is "
              f"{info['platform']!r})", file=sys.stderr)
        return 2
    _say(f"    compile cache: {cache}")

    from repro.obs.clock import wall_clock
    t0 = wall_clock()
    data = phase_round()
    t1 = wall_clock()
    _say(f"[b] §V round, vectorized/batched vs loop/host: passed "
         f"(s={t1 - t0!r})")
    phase_defense(data)
    t2 = wall_clock()
    _say(f"[c] defended round + aggregation kernels: passed (s={t2 - t1!r})")
    phase_control()
    t3 = wall_clock()
    _say(f"[d] control plane jax vs hybrid: passed (s={t3 - t2!r})")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
