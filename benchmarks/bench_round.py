"""Wall-clock per FEEL round: sequential per-client loop vs the vectorized
cohort engine (federated/cohort.py), at the paper's K=50 and beyond.

    PYTHONPATH=src python -m benchmarks.bench_round                # K=50,200,500
    PYTHONPATH=src python -m benchmarks.bench_round --ks 500 \
        --engines unbucketed vectorized         # single pad vs 3 size buckets
    PYTHONPATH=src python -m benchmarks.bench_round --sweep        # run_sweep
    PYTHONPATH=src python -m benchmarks.bench_round --control \
        --ks 50 500 2000                        # host vs batched control plane
    PYTHONPATH=src python -m benchmarks.bench_round --attacks      # threat plane
    PYTHONPATH=src python -m benchmarks.bench_round --llm          # LM task plane
    PYTHONPATH=src python -m benchmarks.bench_round --population   # N-scaling
    PYTHONPATH=src python -m benchmarks.bench_round --smoke        # CI gate

Methodology — each (engine, K) measurement runs the §V unit of work in a
FRESH subprocess (cold jit cache): ``--seeds`` independent experiments
(fresh partition each — the paper averages over independent runs) of
``--rounds`` rounds. This charges each engine what the protocol actually
charges it. The loop engine re-traces per *shape*: one ``mlp_sgd_epoch``
per distinct client dataset size and one eager evaluation program per
distinct per-UE test-subset size — and almost every shape is new again in
every fresh partition. The cohort engine compiles a handful of bucketed
(N, max_samples) programs that are shape-stable across seeds. The
per-round median (compiles mostly excluded) is reported alongside.

Engines: ``loop`` (sequential oracle), ``vectorized`` (size-bucketed
cohort engine, ``--buckets`` levels), ``unbucketed`` (vectorized with a
single global pad — the pre-bucketing baseline).

``--sweep`` instead measures a (policies x seeds) study end-to-end:
``run_sweep`` (shared dataset/partition/layout caches) vs the same grid
as separate ``run_experiment`` calls (each mode in a fresh subprocess).

``--control`` measures the control plane alone — the per-round schedule
phase (Eq. 2/3 values -> Eq. 9 costs -> policy selection) of a
``--control-runs``-run sweep, host numpy per run vs ONE batched
``core.control.schedule_runs`` call (steady state, jit warm) — at each
``--ks``, asserts the two planes pick identical UEs, and writes the rows
to ``results/BENCH_control.json`` (the control-plane perf trajectory).

``--attacks`` measures the threat-model plane: the masked batched
``_apply_attacks`` (one masked tree_map) vs the replaced
per-malicious-client ``.at[i].set`` dispatch loop at growing n_malicious
(bit-equality asserted; the masked path must be flat, the loop linear),
plus a 4-scenario heterogeneous ``run_sweep`` (label flip, feature noise,
free-rider, sign-flip) vs each scenario's separate ``run_experiment`` —
written to ``results/BENCH_attacks.json``.

``--defenses`` measures the defense plane: every robust aggregator
(trimmed mean, median, norm clip, Krum) applied to a K-row stacked update
matrix, host compressed-numpy oracle vs the batched jnp twin, swept over
K and over n_malicious at K=64 (host/batched parity asserted per cell;
the batched path must be flat in n_malicious) — written to
``results/BENCH_defenses.json``.

``--llm`` measures the LM task plane: per-round cost of federated
``lm_tiny`` fine-tuning, loop vs vectorized cohort engine at K in {8, 16},
each engine with and without ``REPRO_USE_PALLAS=1`` (flash-attention
training forwards; interpret mode on CPU — path-exercise rows, not perf
claims). Loop/vectorized held-out loss is asserted bit-equal per cell —
written to ``results/BENCH_llm.json``.

``--population`` measures the population plane (DESIGN.md §12): the
per-round scheduling cost over N candidate UEs at N in {1e4, 1e5, 1e6} —
exact O(N log N) path vs the schedule-preserving top-M prefilter (both
kernel layouts; prefilter == exact selection asserted in every timed
cell) — plus the exact jax kernel re-benched with the population axis
sharded over a forced 2-device host mesh (the ``default_kernel``
multi-device crossover) — written to ``results/BENCH_population.json``.

``--smoke`` runs a tiny instance of every benchmark with loud assertions
(bucketed padding waste must not exceed the single-pad waste; curves must
be finite) — wired into tier-1 via tests/test_bench_smoke.py so bench
regressions fail loudly.

Every ``results/BENCH_*.json`` artifact goes through ONE writer
(``write_bench_json``) with a shared schema: ``{"bench": <name>, "meta":
{commit, python, jax, numpy, timestamp}, ...payload}``. Only canonical
grids overwrite the tracked artifacts — ad-hoc sizes print and skip.

CSV rows:

    engine,K,n_train,s_per_round,median_round_s,speedup,median_speedup,pad_waste
"""
import argparse
import datetime
import json
import os
import platform
import subprocess
import sys

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _bench_meta():
    """Environment/commit metadata stamped into every BENCH_* artifact."""
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, cwd=os.path.dirname(os.path.abspath(__file__))
        ).stdout.strip() or "unknown"
    except OSError:
        commit = "unknown"

    def ver(pkg):
        try:
            import importlib.metadata
            return importlib.metadata.version(pkg)
        except Exception:
            return "unknown"

    return {"commit": commit, "python": platform.python_version(),
            "jax": ver("jax"), "numpy": ver("numpy"),
            "timestamp": datetime.datetime.now(datetime.timezone.utc)
            .isoformat(timespec="seconds")}


def write_bench_json(name, payload, canonical=True, results_dir=None):
    """The ONE writer for results/BENCH_<name>.json.

    Shared schema: {"bench": ..., "meta": _bench_meta(), **payload}. A
    non-canonical run (ad-hoc --ks / sizes) must not clobber the tracked
    measurement, so it prints and skips instead.

    Every canonical write ALSO appends the record as one line to
    ``results/BENCH_history.jsonl`` — the commit+env-keyed trend log
    (the meta block carries commit, python/jax/numpy versions and a UTC
    timestamp), so re-running any bench on a new commit grows per-bench
    perf history instead of overwriting it. When the span tracer is on
    (REPRO_TRACE=1, DESIGN.md §14) the history line additionally carries
    the tracer's per-phase wall-time summary under ``"trace"`` — the
    per-phase trend rides the same log as the headline numbers.

    ``results_dir`` overrides the repo results/ directory (tests). The
    caller's ``payload`` dict is never mutated (tests/test_bench_writer.py
    regression: the old code popped "bench" out of the caller's dict).
    """
    if not canonical:
        print(f"# non-canonical sizes; results/BENCH_{name}.json left "
              "untouched", file=sys.stderr)
        return
    results = results_dir or os.path.join(os.path.dirname(__file__), "..",
                                          "results")
    path = os.path.join(results, f"BENCH_{name}.json")
    payload = dict(payload)
    record = {"bench": payload.pop("bench", name),
              "meta": _bench_meta(), **payload}
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    hist = record
    try:
        from repro.obs import trace
        if trace.enabled():
            phases = trace.phase_summary()
            if phases:
                hist = {**record, "trace": phases}
    except ImportError:
        pass
    with open(os.path.join(results, "BENCH_history.jsonl"), "a") as f:
        f.write(json.dumps(hist, separators=(",", ":")) + "\n")
    print(f"# wrote {os.path.normpath(path)} (+history)", file=sys.stderr)

_WORKER = r"""
import json, sys
import numpy as np
from repro.configs.base import FeelConfig
from repro.core.poisoning import EASY_PAIR, LabelFlipAttack, pick_malicious
from repro.data.partition import partition
from repro.data.synthetic_mnist import generate
from repro.federated.server import FeelServer
from repro.obs import trace

engine, k, n_train, n_test, rounds, seeds, n_buckets = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]), int(sys.argv[7]))
cfg = FeelConfig(n_ues=k, n_malicious=max(k // 10, 1))
# the tracer IS the timer: per-round wall times are the "round" spans'
# durations (keeps the trace path honest under the parity matrix), and
# REPRO_TRACE_FILE (if set by the driver) flushes the full trace at exit
trace.configure(enabled=True)
wastes = []
for seed in range(seeds):
    train, test = generate(n_train, n_test, seed=seed)
    rng = np.random.default_rng(seed)
    malicious = pick_malicious(cfg.n_ues, cfg.n_malicious, rng)
    clients = partition(train, cfg.n_ues, rng, malicious,
                        LabelFlipAttack(*EASY_PAIR))
    server = FeelServer(cfg, clients, test, rng, policy="dqs",
                        engine=engine, n_buckets=n_buckets)
    for t in range(rounds):
        server.run_round(t)
    wastes.extend(server.pad_waste)
times = [sp.dur for sp in trace.tracer().spans if sp.name == "round"]
assert len(times) == rounds * seeds, (len(times), rounds, seeds)
print(json.dumps({"times": times, "waste": wastes,
                  "trace": trace.phase_summary()}))
"""

_SWEEP_WORKER = r"""
import json, sys, time
import numpy as np
from repro.federated.simulation import run_experiment, run_sweep

mode, n_seeds, n_train, n_test, rounds = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]))
policies = ["dqs", "top_value"]
seeds = list(range(n_seeds))
t0 = time.perf_counter()
if mode == "sweep":
    res = run_sweep(policies, seeds=seeds, n_train=n_train, n_test=n_test,
                    rounds=rounds)
    accs = [r["acc"] for r in res.runs]
else:
    accs = [run_experiment(p, (6, 2), seed=s, n_train=n_train,
                           n_test=n_test, rounds=rounds)["acc"]
            for p in policies for s in seeds]
el = time.perf_counter() - t0
assert all(np.isfinite(a).all() for a in map(np.asarray, accs))
print(json.dumps({"s_total": el, "n_runs": len(accs)}))
"""

_CONTROL_WORKER = r"""
import json, sys, time
import numpy as np
from repro.configs.base import FeelConfig
from repro.core import control as ctl
from repro.core.diversity import diversity_index
from repro.core.quality import data_quality_value
from repro.core.scheduler import (POLICIES, POLICY_IDS, Schedule,
                                  greedy_pack, top_value_schedule)
from repro.core.wireless import WirelessModel

k, n_runs, rounds = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
cfg = FeelConfig(n_ues=k, n_malicious=max(k // 10, 1))
rng = np.random.default_rng(0)
policies = [list(POLICY_IDS)[i % len(POLICY_IDS)] for i in range(n_runs)]
wms = [WirelessModel(cfg, np.random.default_rng(1000 + i))
       for i in range(n_runs)]
sizes = (rng.integers(1, 31, (n_runs, k)) * 50).astype(float)
cpu = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max, (n_runs, k))
divs = rng.uniform(0.0, 0.9, (n_runs, k))
r_min = np.stack([wms[i].min_rate(wms[i].train_time(sizes[i], cpu[i]))
                  for i in range(n_runs)])
state = ctl.ControlState(
    policy_id=np.array([POLICY_IDS[p] for p in policies], np.int32),
    sizes=sizes, divs=divs, r_min=r_min,
    reputations=rng.uniform(0.0, 1.0, (n_runs, k)),
    ages=np.ones((n_runs, k)), cfg=cfg)
t_train = np.stack([wms[i].train_time(sizes[i], cpu[i])
                    for i in range(n_runs)])
omega = np.full(n_runs, cfg.omega_rep), np.full(n_runs, cfg.omega_div)

def draw(round_seed):
    g = np.stack([wms[i].rng.exponential(1.0, k) * wms[i].distances
                  ** (-cfg.pathloss_exp) for i in range(n_runs)])
    rr = np.stack([np.argsort(np.random.default_rng((round_seed, i))
                              .permutation(k)) for i in range(n_runs)])
    return g, rr

def host_round(gains, rr, cost_fn="cost"):
    xs = []
    for i, p in enumerate(policies):
        I = diversity_index(divs[i], sizes[i], state.ages[i], cfg.gamma)
        values = data_quality_value(state.reputations[i], I, cfg)
        costs = getattr(wms[i], cost_fn)(gains[i], t_train[i])
        if p == "top_value":
            s = top_value_schedule(values, costs, cfg, cfg.min_selected)
        elif p == "random":
            # consume the SAME shared permutation draw the batched plane
            # gets (rr is the inverse permutation): identical work +
            # decisions, so the parity gate covers all five policies
            x, alpha = greedy_pack(np.argsort(rr[i]), costs, k)
            s = Schedule(x=x, alpha=alpha, cost=costs, value=values)
        elif p == "best_channel":
            s = POLICIES[p](values, costs, cfg, gains[i])
        else:
            s = POLICIES[p](values, costs, cfg)
        x = s.x.copy()
        if not x.any():                       # forced-round rewrite
            x[np.argmax(values)] = True
        xs.append(x)
    return np.stack(xs)

def batched_round(gains, rr):
    x, *_ = ctl.schedule_runs(state, gains, rr, omega[0], omega[1])
    return x

# parity gate (all five policies) — doubles as the jit warmup. host_scan
# is the seed's control plane: per-run python + the dense (K, K) Eq. 9
# rate matrix (cost_scan); host is the post-bisection per-run oracle.
g0, rr0 = draw(0)
xh, xb = host_round(g0, rr0), batched_round(g0, rr0)
assert np.array_equal(xh, xb), "host/batched selection mismatch"
assert np.array_equal(xh, host_round(g0, rr0, "cost_scan")), "scan mismatch"

t_scan = t_host = t_batched = 0.0
scan_rounds = max(1, rounds // 3)           # O(K^2): keep its share small
for t in range(scan_rounds):
    g, rr = draw(t + 1)
    t0 = time.perf_counter(); host_round(g, rr, "cost_scan")
    t_scan += time.perf_counter() - t0
for t in range(rounds):
    g, rr = draw(t + 1)
    t0 = time.perf_counter(); host_round(g, rr)
    t1 = time.perf_counter(); batched_round(g, rr)
    t_host += t1 - t0; t_batched += time.perf_counter() - t1
print(json.dumps({"host_scan_ms": t_scan / scan_rounds * 1e3,
                  "host_ms": t_host / rounds * 1e3,
                  "batched_ms": t_batched / rounds * 1e3}))
"""

_ATTACKS_WORKER = r"""
import json, sys, time
import numpy as np, jax, jax.numpy as jnp

mode = sys.argv[1]
if mode == "apply":
    # masked batched _apply_attacks vs the per-client .at[i].set oracle:
    # the masked path must be O(1) in n_malicious; the oracle dispatches
    # one tree_map per malicious client. Bit-equality asserted per size.
    from repro.core import attacks as atk
    from repro.models.mlp import mlp_init

    n_rows, reps = int(sys.argv[2]), int(sys.argv[3])
    params = mlp_init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    leaves, treedef = jax.tree.flatten(params)
    stacked = jax.tree.unflatten(treedef, [
        jnp.asarray(rng.normal(size=(n_rows,) + l.shape)
                    .astype(np.float32)) for l in leaves])
    attack = atk.ModelAttack(scale=-1.0)

    def oracle(mal):
        out = stacked
        for i in np.flatnonzero(mal):
            poisoned = attack.apply_loop(
                params, jax.tree.map(lambda l, i=int(i): l[i], out))
            out = jax.tree.map(lambda l, p, i=int(i): l.at[i].set(p),
                               out, poisoned)
        return out

    def sync(t):
        jax.block_until_ready(jax.tree.leaves(t))
        return t

    rows = []
    for n_mal in sorted({1, 4, 16, n_rows // 2}):
        mal = np.zeros(n_rows, bool)
        mal[:n_mal] = True
        a = sync(attack.apply_stacked(stacked, params, mal))
        b = sync(oracle(mal))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), \
                "masked/oracle attack application mismatch"
        for _ in range(3):                       # dispatch-cache warmup
            sync(attack.apply_stacked(stacked, params, mal))
        t0 = time.perf_counter()
        for _ in range(reps):
            sync(attack.apply_stacked(stacked, params, mal))
        t_masked = (time.perf_counter() - t0) / reps * 1e3
        t0 = time.perf_counter()
        for _ in range(reps):
            sync(oracle(mal))
        t_loop = (time.perf_counter() - t0) / reps * 1e3
        rows.append({"n_malicious": n_mal, "loop_ms": round(t_loop, 3),
                     "masked_ms": round(t_masked, 3)})
    print(json.dumps({"apply": rows}))
else:
    # heterogeneous scenario sweep: 4 distinct threat models in ONE
    # run_sweep vs one run_experiment each (fresh subprocess per mode, cold
    # jit — the same methodology as --sweep); accs returned for the
    # parent's cross-mode divergence assertion.
    from repro.federated.simulation import run_experiment, run_sweep

    n_train, rounds = int(sys.argv[2]), int(sys.argv[3])
    scns = ["flip_6to2", "noise_0.8", "free_rider", "sign_flip"]
    n_test = max(n_train // 10, 200)
    t0 = time.perf_counter()
    if mode == "sweep":
        accs = [r["acc"] for r in run_sweep(
            ["dqs"], seeds=[0], scenarios=scns, n_train=n_train,
            n_test=n_test, rounds=rounds).runs]
    else:
        accs = [run_experiment("dqs", scenario=s, seed=0, n_train=n_train,
                               n_test=n_test, rounds=rounds)["acc"]
                for s in scns]
    el = time.perf_counter() - t0
    assert all(np.isfinite(a).all() for a in map(np.asarray, accs))
    print(json.dumps({"s_total": round(el, 2), "n_scenarios": len(scns),
                      "accs": accs}))
"""

_DEFENSES_WORKER = r"""
import json, sys, time
import numpy as np, jax, jax.numpy as jnp
from repro.core import defenses as dfs
from repro.models.mlp import mlp_init

k, reps = int(sys.argv[1]), int(sys.argv[2])
n_mals = [int(x) for x in sys.argv[3].split(",")]
rng = np.random.default_rng(0)
template = mlp_init(jax.random.PRNGKey(0))
leaves, treedef = jax.tree.flatten(template)
weights = (rng.integers(1, 31, k) * 50).astype(float)

def mk_updates(n_mal):
    # honest uploads cluster near the global model, malicious sit far out
    # (so Krum/clip actually have something to reject/clip)
    rows = []
    for i in range(k):
        s = 5.0 if i < n_mal else 0.1
        rows.append(jax.tree.unflatten(treedef, [
            (np.asarray(l) + s * rng.normal(size=l.shape))
            .astype(np.float32) for l in leaves]))
    return rows

AGGS = {"trimmed_mean": dfs.TrimmedMean(0.2), "median": dfs.Median(),
        "norm_clip": dfs.NormClip(1.0), "krum": dfs.Krum()}

def sync(t):
    jax.block_until_ready(jax.tree.leaves(t))
    return t

rows_out = []
for n_mal in n_mals:
    params_list = mk_updates(n_mal)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *params_list)
    sync(stacked)
    for name, agg in AGGS.items():
        # parity gate before timing: decisions exact, payload to 2e-6
        h, hs = dfs.aggregate_host(agg, params_list, weights, template,
                                   n_mal)
        b, bs = dfs.aggregate_stacked(agg, stacked, weights, template, k,
                                      n_mal)
        for x, y in zip(jax.tree.leaves(sync(h)), jax.tree.leaves(sync(b))):
            assert np.allclose(np.asarray(x), np.asarray(y), atol=2e-6), \
                f"host/batched {name} aggregate mismatch"
        assert (hs.n_clipped, hs.n_rejected) == (bs.n_clipped,
                                                 bs.n_rejected), name
        for _ in range(2):            # dispatch-cache warmup
            sync(dfs.aggregate_stacked(agg, stacked, weights, template,
                                       k, n_mal)[0])
        t0 = time.perf_counter()
        for _ in range(reps):
            sync(dfs.aggregate_stacked(agg, stacked, weights, template,
                                       k, n_mal)[0])
        t_b = (time.perf_counter() - t0) / reps * 1e3
        t0 = time.perf_counter()
        for _ in range(reps):
            sync(dfs.aggregate_host(agg, params_list, weights, template,
                                    n_mal)[0])
        t_h = (time.perf_counter() - t0) / reps * 1e3
        rows_out.append({"aggregator": name, "K": k, "n_malicious": n_mal,
                         "host_ms": round(t_h, 3),
                         "batched_ms": round(t_b, 3)})
print(json.dumps({"rows": rows_out}))
"""

_ASYNC_WORKER = r"""
import json, sys, time
import numpy as np
from repro.configs.base import FeelConfig
from repro.launch.serve import simulate

mode, scenario, k, n_train, n_test, rounds = (
    sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]), int(sys.argv[6]))
cfg = FeelConfig(n_ues=k, n_malicious=max(k // 8, 1),
                 min_selected=min(5, k))
kw = dict(cfg=cfg, scenario=scenario, rounds=rounds, n_train=n_train,
          n_test=n_test, seed=0)

# parity gate in EVERY timed cell: the zero-latency async engine must be
# bit-equal to the synchronous oracle (DESIGN.md S13) before the cell's
# timing is trusted
sync = simulate(mode="sync", **kw)
zero = simulate(mode="async", buffer=None, deadline=None,
                latency_scale=0.0, **kw)
for f in ("acc", "rep_gap", "objective"):
    a, b = np.asarray(sync[f], float), np.asarray(zero[f], float)
    assert np.array_equal(a, b, equal_nan=True), \
        f"zero-latency async != sync on {f}"

if mode == "sync":
    # the lockstep limit, but event-priced: full-wave triggers at real
    # Eq. 6/7 latencies give the synchronous baseline a sim-time axis
    spec = dict(buffer=None, deadline=None, latency_scale=1.0)
elif mode == "async_buffer":
    spec = dict(buffer=max(2, k // 8), deadline=None, latency_scale=1.0,
                staleness=0.5, channel_corr=0.3)
elif mode == "async_deadline":
    spec = dict(buffer=None, deadline=60.0, latency_scale=1.0,
                staleness=0.5, channel_corr=0.3)
else:
    raise KeyError(mode)
t0 = time.perf_counter()
res = simulate(mode="async", **spec, **kw)
wall = time.perf_counter() - t0
assert np.isfinite(np.asarray(res["acc"], float)).all()
st = np.asarray(res["sim_time"], float)
assert st.size == rounds and np.all(np.diff(st) >= 0), st
print(json.dumps({"acc": res["acc"], "sim_time": res["sim_time"],
                  "trigger": res["trigger"],
                  "n_uploads": res["n_uploads"],
                  "mean_age": res["mean_age"], "wall_s": wall,
                  "final_acc": res["acc"][-1]}))
"""


# engine CLI name -> (FeelServer engine, n_buckets override or None)
ENGINES = {"loop": ("loop", None),
           "vectorized": ("vectorized", None),
           "unbucketed": ("vectorized", 1)}

# argparse defaults of the default (engines) mode — ALSO the canonical
# grid that overwrites results/BENCH_engines.json, so the two can never
# drift apart (cf. CONTROL_KS / ATTACK_DEFAULTS / DEFENSE_KS)
ENGINE_DEFAULTS = {"ks": [50, 200, 500], "rounds": 3, "seeds": 3,
                   "engines": ["loop", "vectorized"], "buckets": 3}


# every worker starts here: the persistent compile cache, placed from
# outside through JAX_COMPILATION_CACHE_DIR or else at <checkout>/.jax_cache
_WORKER_PREAMBLE = (
    "from repro.launch.compile_cache import use_compile_cache\n"
    "use_compile_cache()\n")


def _run_worker(code, argv, timeout=3600, extra_env=None):
    r = subprocess.run(
        [sys.executable, "-c", _WORKER_PREAMBLE + code]
        + [str(a) for a in argv],
        capture_output=True, text=True,
        env={**os.environ,
             "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""),
             **(extra_env or {})},
        timeout=timeout)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def _measure(name, k, n_train, n_test, rounds, seeds, buckets):
    engine, nb = ENGINES[name]
    out = _run_worker(_WORKER, [engine, k, n_train, n_test, rounds, seeds,
                                nb if nb is not None else buckets])
    times = out["times"]
    mean = sum(times) / len(times)
    median = sorted(times)[(len(times) - 1) // 2]   # lower-biased: keeps
    waste = (sum(out["waste"]) / len(out["waste"])  # compile rounds out
             if out["waste"] else float("nan"))
    return mean, median, times, waste


def _auto_n_train(k: int) -> int:
    # keep the partition pool >= the clients' demand so datasets stay
    # size-diverse (K=50 matches the paper's regime scaled to bench time);
    # cap at the paper's 50k corpus
    return min(50_000, max(10_000, 100 * k))


def bench_k(k, n_train, n_test, rounds, seeds, engines, buckets):
    nt = n_train or _auto_n_train(k)
    out = {}
    for name in engines:
        out[name] = _measure(name, k, nt, n_test, rounds, seeds, buckets)
        print(f"# {name} K={k} per-round s: "
              f"{[round(x, 2) for x in out[name][2]]}", file=sys.stderr)
    base = engines[0]
    cl, sl = out[base][:2]
    for name in engines:
        c, s, _, w = out[name]
        print(f"{name},{k},{nt},{c:.3f},{s:.3f},{cl / c:.2f},{sl / s:.2f},"
              f"{w:.2f}", flush=True)
    return out


SWEEP_DEFAULTS = (3, 10_000, 1_000, 3)    # n_seeds, n_train, n_test, rounds


def bench_sweep(n_seeds, n_train, n_test, rounds, write_json=True):
    """Batched run_sweep vs the same grid of sequential run_experiment
    calls — each mode cold, in a fresh subprocess."""
    print("mode,n_runs,s_total,speedup")
    res = {}
    for mode in ("sequential", "sweep"):
        res[mode] = _run_worker(_SWEEP_WORKER,
                                [mode, n_seeds, n_train, n_test, rounds])
    base = res["sequential"]["s_total"]
    for mode in ("sequential", "sweep"):
        r = res[mode]
        print(f"{mode},{r['n_runs']},{r['s_total']:.1f},"
              f"{base / r['s_total']:.2f}", flush=True)
    if write_json:
        write_bench_json(
            "sweep",
            {"bench": "batched_sweep_vs_sequential",
             "rows": [{"mode": m, "n_runs": res[m]["n_runs"],
                       "s_total": res[m]["s_total"]}
                      for m in ("sequential", "sweep")]},
            canonical=(n_seeds, n_train, n_test,
                       rounds) == SWEEP_DEFAULTS)
    return base / res["sweep"]["s_total"]


CONTROL_KS = (50, 500, 2000)      # the tracked BENCH_control.json grid


def bench_control(ks, n_runs, rounds, write_json=True):
    """Host vs batched control plane: per-round schedule phase of an
    ``n_runs``-run sweep at each K (fresh subprocess per K; the worker
    asserts selection parity across ALL five policies before timing).

    The JSON trajectory artifact is only (over)written for the canonical
    ``CONTROL_KS`` grid — an ad-hoc ``--ks 8`` sanity run must not clobber
    the tracked measurement."""
    print("control,K,n_runs,host_scan_ms,host_ms,batched_ms,"
          "speedup_vs_scan,speedup")
    rows = []
    for k in ks:
        out = _run_worker(_CONTROL_WORKER, [k, n_runs, rounds])
        speedup = out["host_ms"] / out["batched_ms"]
        vs_scan = out["host_scan_ms"] / out["batched_ms"]
        rows.append({"K": k, "n_runs": n_runs,
                     "host_scan_ms": round(out["host_scan_ms"], 3),
                     "host_ms": round(out["host_ms"], 3),
                     "batched_ms": round(out["batched_ms"], 3),
                     "speedup_vs_scan": round(vs_scan, 2),
                     "speedup": round(speedup, 2)})
        print(f"control,{k},{n_runs},{out['host_scan_ms']:.2f},"
              f"{out['host_ms']:.2f},{out['batched_ms']:.2f},"
              f"{vs_scan:.2f},{speedup:.2f}", flush=True)
    if write_json:
        write_bench_json("control",
                         {"bench": "control_plane_schedule_phase",
                          "unit": "ms_per_round_all_runs", "rows": rows},
                         canonical=tuple(ks) == CONTROL_KS)
    return rows


ATTACK_DEFAULTS = (64, 50, 4000, 3)   # n_rows, reps, n_train, rounds


def bench_attacks(n_rows=64, reps=50, n_train=4000, rounds=3,
                  write_json=True):
    """Threat-model plane bench: (1) the masked batched ``_apply_attacks``
    vs the replaced per-malicious-client ``.at[i].set`` dispatch loop at
    growing n_malicious (bit-equality asserted in the worker — the masked
    path must be flat in n_malicious, the loop linear), and (2) a
    4-scenario heterogeneous sweep vs one run_experiment per scenario.

    The JSON artifact (results/BENCH_attacks.json) is only written for
    the canonical default sizes."""
    out = _run_worker(_ATTACKS_WORKER, ["apply", n_rows, reps])
    print("attacks,n_rows,n_malicious,loop_ms,masked_ms,speedup")
    for r in out["apply"]:
        print(f"attacks,{n_rows},{r['n_malicious']},{r['loop_ms']:.3f},"
              f"{r['masked_ms']:.3f},"
              f"{r['loop_ms'] / r['masked_ms']:.2f}", flush=True)
    res = {m: _run_worker(_ATTACKS_WORKER, [m, n_train, rounds])
           for m in ("sweep", "sequential")}
    for a, b in zip(res["sweep"]["accs"], res["sequential"]["accs"]):
        assert np.allclose(a, b, atol=1e-7), \
            "sweep/sequential scenario divergence"
    sw = {"n_scenarios": res["sweep"]["n_scenarios"],
          "sweep_s": res["sweep"]["s_total"],
          "sequential_s": res["sequential"]["s_total"]}
    print("attacks_sweep,n_scenarios,sweep_s,sequential_s,speedup")
    print(f"attacks_sweep,{sw['n_scenarios']},{sw['sweep_s']:.2f},"
          f"{sw['sequential_s']:.2f},"
          f"{sw['sequential_s'] / sw['sweep_s']:.2f}", flush=True)
    out["sweep"] = sw
    if write_json:
        write_bench_json("attacks",
                         {"bench": "threat_model_plane",
                          "apply_unit": "ms_per_application",
                          "apply": out["apply"], "sweep": sw},
                         canonical=(n_rows, reps, n_train,
                                    rounds) == ATTACK_DEFAULTS)
    return out


DEFENSE_KS = (16, 64, 128)        # the tracked BENCH_defenses.json K grid
DEFENSE_NMALS = (1, 4, 16, 32)    # n_malicious sweep at K=64


def bench_defenses(ks=DEFENSE_KS, n_mals=DEFENSE_NMALS, reps=10,
                   write_json=True):
    """Defense plane: every robust aggregator applied to a K-row stacked
    update matrix — host compressed oracle vs the batched jnp twin
    (parity asserted in the worker before timing). Two sweeps: cost vs K
    (n_malicious = K/8) and cost vs n_malicious at K=64, where the
    batched path must stay flat (the acceptance claim of
    results/BENCH_defenses.json)."""
    print("defense,aggregator,K,n_malicious,host_ms,batched_ms,speedup")
    rows = []

    def run(k, mals):
        out = _run_worker(_DEFENSES_WORKER,
                          [k, reps, ",".join(map(str, mals))])
        for r in out["rows"]:
            rows.append(r)
            print(f"defense,{r['aggregator']},{r['K']},{r['n_malicious']},"
                  f"{r['host_ms']:.2f},{r['batched_ms']:.2f},"
                  f"{r['host_ms'] / r['batched_ms']:.2f}", flush=True)

    # the n_malicious sweep runs at K=64 when the grid has it (the
    # tracked flatness claim), else at the grid's largest K
    nmal_k = 64 if 64 in ks else max(ks)
    for k in ks:
        if k == nmal_k:
            run(k, sorted(set(int(m) for m in n_mals if m < k)
                          | {max(k // 8, 1)}))
        else:
            run(k, [max(k // 8, 1)])
    if write_json:
        write_bench_json(
            "defenses",
            {"bench": "defense_plane_robust_aggregation",
             "unit": "ms_per_aggregation", "rows": rows},
            canonical=(tuple(ks) == DEFENSE_KS
                       and tuple(n_mals) == DEFENSE_NMALS))
    return rows


ASYNC_DEFAULTS = (16, 8000, 800, 8)   # k, n_train, n_test, rounds


def bench_async(k=16, n_train=8000, n_test=800, rounds=8,
                scenarios=("none", "stale_rider_2"), write_json=True):
    """Async engine plane: accuracy vs SIMULATED wall-clock for the
    {sync, async-buffer, async-deadline} triggers crossed with threat
    scenarios (federated/async_engine.py, DESIGN.md S13). Every cell's
    worker first pins the zero-latency parity gate (mode="async" at
    latency_scale=0 bit-equal to mode="sync") and only then times the
    cell; the "sync" cell itself is the event-priced lockstep limit, so
    all three curves share one simulated-clock axis. The JSON artifact
    (results/BENCH_async.json) is only written for the canonical default
    sizes."""
    print("async,mode,scenario,rounds,sim_s,final_acc,mean_age,wall_s")
    cells = []
    for scn in scenarios:
        for mode in ("sync", "async_buffer", "async_deadline"):
            out = _run_worker(_ASYNC_WORKER,
                              [mode, scn, k, n_train, n_test, rounds])
            cells.append({"mode": mode, "scenario": scn, **out})
            print(f"async,{mode},{scn},{rounds},{out['sim_time'][-1]:.1f},"
                  f"{out['final_acc']:.4f},"
                  f"{float(np.mean(out['mean_age'])):.2f},"
                  f"{out['wall_s']:.1f}", flush=True)
    if write_json:
        write_bench_json(
            "async",
            {"bench": "async_engine_acc_vs_sim_time",
             "K": k, "n_train": n_train, "n_test": n_test,
             "rounds": rounds, "cells": cells},
            canonical=((k, n_train, n_test, rounds) == ASYNC_DEFAULTS
                       and tuple(scenarios) == ("none", "stale_rider_2")))
    return cells


_POPULATION_WORKER = r"""
import json, sys, time
import jax
import numpy as np
from repro.core import control as ctl
from repro.core import population as pop

mode, n, k, n_runs, rounds = (sys.argv[1], int(sys.argv[2]),
                              int(sys.argv[3]), int(sys.argv[4]),
                              int(sys.argv[5]))
state, omega, draw = pop.synthetic_population(n, k, n_runs)
cfg = state.cfg

if mode == "mesh":
    # exact N-wide schedule_runs on the forced multi-device host mesh:
    # hybrid (host numpy, cannot shard) vs the jitted jax kernel with the
    # population axis GSPMD-sharded over the mesh data axes — the
    # measurement behind default_kernel()'s multi-device "jax" choice
    mesh = pop.population_mesh()
    n_dev = len(jax.devices())

    def jax_round(g, rr):
        with jax.enable_x64(True):
            ops = pop.shard_population(
                mesh, state.reputations, state.ages, state.divs,
                state.sizes, state.r_min, g, rr)
            out = ctl._schedule_kernel(
                state.policy_id, *ops, omega[0], omega[1],
                np.asarray(cfg.gamma, float), cfg.bandwidth_hz,
                cfg.p_watt, cfg.n0_watt_hz, k=k, n_sel=cfg.min_selected)
            return np.asarray(out[0])

    g0, rr0 = draw(0)
    xh = ctl.schedule_runs(state, g0, rr0, *omega, kernel="hybrid")[0]
    assert np.array_equal(jax_round(g0, rr0), xh), "mesh/hybrid mismatch"
    t_h = t_j = 0.0
    for t in range(rounds):
        g, rr = draw(t + 1)
        t0 = time.perf_counter()
        xh = ctl.schedule_runs(state, g, rr, *omega, kernel="hybrid")[0]
        t1 = time.perf_counter()
        xj = jax_round(g, rr)
        t_j += time.perf_counter() - t1; t_h += t1 - t0
        assert np.array_equal(xh, xj), "mesh/hybrid selection mismatch"
    print(json.dumps({"devices": n_dev,
                      "hybrid_ms": t_h / rounds * 1e3,
                      "jax_ms": t_j / rounds * 1e3}))
else:
    # exact O(N) path vs the top-M prefilter (both layouts); prefilter ==
    # exact selection asserted in EVERY timed cell (the preservation
    # certificate + escalation guarantee, core/population.py)
    def exact(g, rr):
        return ctl.schedule_runs(state, g, rr, *omega, kernel="hybrid")

    def pre(g, rr, kern):
        return pop.prefilter_schedule_runs(state, g, rr, *omega,
                                           kernel=kern)

    g0, rr0 = draw(0)                     # warmup + parity gate
    x0 = exact(g0, rr0)[0]
    for kern in ("hybrid", "jax"):
        assert np.array_equal(pre(g0, rr0, kern)[0], x0), kern
    times = {"exact": 0.0, "hybrid": 0.0, "jax": 0.0}
    esc = {"hybrid": 0, "jax": 0}
    m = pop.default_m(cfg)
    for t in range(rounds):
        g, rr = draw(t + 1)
        t0 = time.perf_counter()
        xe = exact(g, rr)[0]
        times["exact"] += time.perf_counter() - t0
        for kern in ("hybrid", "jax"):
            t0 = time.perf_counter()
            xp, _, _, _, _, info = pre(g, rr, kern)
            times[kern] += time.perf_counter() - t0
            assert np.array_equal(xp, xe), (kern, t)
            esc[kern] += info["n_escalated"]
            m = info["m"]
    # selection-tail micro-bench: both paths share the irreducibly O(N)
    # feature math (diversity / quality / Eq. 9 bisection — every
    # scheduler must read the N-wide inputs once), so the SUB-linear
    # claim lives in the stage the prefilter actually shrinks: the
    # visit-order sort + budget pack, O(N log N + N) exact vs
    # O(N) argpartition + O(M log M + M) prefiltered. Timed here on
    # precomputed dqs keys/costs (key choice does not change sort cost).
    from repro.core.diversity import diversity_index_rows
    from repro.core.quality import data_quality_value
    g, _ = draw(rounds + 1)
    I = diversity_index_rows(state.divs, state.sizes, state.ages,
                             cfg.gamma)
    values = data_quality_value(state.reputations, I, cfg,
                                omega=(omega[0][:, None],
                                       omega[1][:, None]))
    with jax.enable_x64(True):
        costs = np.asarray(ctl._cost_kernel(
            g, state.r_min, cfg.bandwidth_hz, cfg.p_watt,
            cfg.n0_watt_hz, k=k)).astype(np.int32)
    keys = -(values / costs)
    rows_i = np.arange(n_runs)[:, None]
    order = np.argsort(keys, axis=-1, kind="stable")     # warm both pack
    np.asarray(ctl._pack_kernel(np.take_along_axis(costs, order, -1),
                                k=k))                    # shapes (jit)
    np.asarray(ctl._pack_kernel(costs[rows_i, pop._topm_prefix(keys, m)],
                                k=k))
    t_et = t_pt = 0.0
    for _ in range(rounds):
        t0 = time.perf_counter()
        order = np.argsort(keys, axis=-1, kind="stable")
        np.asarray(ctl._pack_kernel(np.take_along_axis(costs, order, -1),
                                    k=k))
        t1 = time.perf_counter()
        kept = pop._topm_prefix(keys, m)
        np.asarray(ctl._pack_kernel(costs[rows_i, kept], k=k))
        t_pt += time.perf_counter() - t1; t_et += t1 - t0
    bytes1 = pop.PopulationState.from_control(state).nbytes()
    print(json.dumps({
        "exact_ms": times["exact"] / rounds * 1e3,
        "prefilter_hybrid_ms": times["hybrid"] / rounds * 1e3,
        "prefilter_jax_ms": times["jax"] / rounds * 1e3,
        "exact_tail_ms": t_et / rounds * 1e3,
        "prefilter_tail_ms": t_pt / rounds * 1e3,
        "m": m, "escalated_per_round": (esc["hybrid"] + esc["jax"])
        / (2.0 * rounds), "state_bytes": bytes1}))
"""

_LLM_WORKER = r"""
import json, sys
import numpy as np
from repro.configs.base import FeelConfig
from repro.core.attacks import as_scenario
from repro.core.poisoning import pick_malicious
from repro.federated.server import FeelServer
from repro.federated.task import as_task
from repro.obs import trace

engine, k, n_train, n_test, rounds = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]))
task = as_task("lm_tiny")
cfg = FeelConfig(n_ues=k, n_malicious=max(k // 4, 1), task="lm_tiny")
scn = as_scenario("token_flip_1to5")
train, test = task.generate_data(n_train, n_test, 0)
rng = np.random.default_rng(0)
malicious = pick_malicious(k, cfg.n_malicious, rng)
clients = task.partition_clients(train, k, rng, malicious, scn.data)
server = FeelServer(cfg, clients, test, rng, policy="dqs", engine=engine,
                    scenario=scn)
trace.configure(enabled=True)   # per-round times = the "round" spans
losses = []
for t in range(rounds):
    log = server.run_round(t)
    losses.append(log.global_loss)
assert all(np.isfinite(l) for l in losses), losses
times = [sp.dur for sp in trace.tracer().spans if sp.name == "round"]
assert len(times) == rounds, (len(times), rounds)
print(json.dumps({"times": times, "loss": losses,
                  "trace": trace.phase_summary()}))
"""

LLM_KS = (8, 16)          # the tracked BENCH_llm.json K grid
LLM_DEFAULTS = (LLM_KS, 2)


def bench_llm(ks=LLM_KS, rounds=2, flash=True, write_json=True):
    """LM-task plane: per-round cost of federated lm_tiny fine-tuning,
    loop vs vectorized cohort engine at each K, each engine also under
    ``REPRO_USE_PALLAS=1`` (training forwards through the Pallas flash
    kernel). Loop/vectorized loss parity is asserted bitwise per (K,
    flash) cell — the LM engine-parity contract of tests/test_task_lm.py
    at bench scale. On CPU the flash rows run the kernel in interpret
    mode (~50x XLA), so they are path-exercise measurements, not perf
    claims, and run a single round."""
    print("llm,engine,K,flash,n_train,s_per_round,loss_r0")
    rows = []
    for k in ks:
        n_train, n_test = k * 60, 120
        for use_flash in ((False, True) if flash else (False,)):
            env = {"REPRO_USE_PALLAS": "1"} if use_flash else None
            r = 1 if use_flash else rounds
            out = {eng: _run_worker(_LLM_WORKER,
                                    [eng, k, n_train, n_test, r],
                                    extra_env=env)
                   for eng in ("loop", "vectorized")}
            assert np.array_equal(out["loop"]["loss"],
                                  out["vectorized"]["loss"]), \
                f"LM engine loss divergence at K={k} flash={use_flash}"
            for eng in ("loop", "vectorized"):
                mean = sum(out[eng]["times"]) / len(out[eng]["times"])
                rows.append({"engine": eng, "K": k, "flash": use_flash,
                             "n_train": n_train,
                             "s_per_round": round(mean, 3),
                             "loss_r0": round(out[eng]["loss"][0], 6)})
                print(f"llm,{eng},{k},{int(use_flash)},{n_train},"
                      f"{mean:.3f},{out[eng]['loss'][0]:.4f}", flush=True)
    if write_json:
        write_bench_json(
            "llm", {"bench": "lm_task_per_round", "rows": rows},
            canonical=(tuple(ks), rounds) == LLM_DEFAULTS and flash)
    return rows


POPULATION_NS = (10_000, 100_000, 1_000_000)   # tracked N grid
POPULATION_DEFAULTS = (POPULATION_NS, 64, 5, 3)    # ns, K, n_runs, rounds
POPULATION_MESH_DEVICES = 2


def bench_population(ns=POPULATION_NS, k=64, n_runs=5, rounds=3,
                     mesh_devices=POPULATION_MESH_DEVICES,
                     write_json=True):
    """Population plane (DESIGN.md §12): per-round scheduling cost over N
    candidate UEs — the exact O(N log N) path vs the schedule-preserving
    top-M prefilter (hybrid + jax layouts) — at each N (fresh subprocess
    per N, cold jit; the worker asserts prefilter == exact selection in
    EVERY timed cell). A second worker re-benches the exact
    ``schedule_runs`` on a forced ``mesh_devices``-device host mesh:
    hybrid (host numpy, unshardable) vs the jax kernel with the
    population axis GSPMD-sharded — the measurement behind
    ``default_kernel()`` choosing "jax" on any multi-device mesh.

    results/BENCH_population.json is only (over)written for the
    canonical grid, where the acceptance claims are asserted below:
    (a) the full prefilter round beats the exact path at EVERY N, and
    (b) the selection tail (visit-order sort + budget pack — the stage
    the prefilter shrinks from O(N log N + N) to O(N) + O(M log M + M))
    grows SUB-linearly in the exact path's cost over the N span: its
    share of the exact tail must shrink as N grows. Raw wall-clock of
    ANY O(N) DRAM-resident stage on this box grows slightly
    super-linearly once it falls out of cache, so sub-linearity is
    asserted against the exact path, not against raw N; and total round
    time cannot be sub-linear on either path — the Eq. 2/3/9 feature
    math reads every one of the N candidates once, an irreducibly
    linear floor both paths share."""
    print("population,N,K,n_runs,exact_ms,prefilter_hybrid_ms,"
          "prefilter_jax_ms,exact_tail_ms,prefilter_tail_ms,m,"
          "escalated_per_round,bytes_per_device")
    rows = []
    for n in ns:
        out = _run_worker(_POPULATION_WORKER,
                          ["paths", n, k, n_runs, rounds])
        bpd = out["state_bytes"] // mesh_devices
        rows.append({"N": n, "K": k, "n_runs": n_runs,
                     "exact_ms": round(out["exact_ms"], 3),
                     "prefilter_hybrid_ms":
                         round(out["prefilter_hybrid_ms"], 3),
                     "prefilter_jax_ms":
                         round(out["prefilter_jax_ms"], 3),
                     "exact_tail_ms": round(out["exact_tail_ms"], 3),
                     "prefilter_tail_ms":
                         round(out["prefilter_tail_ms"], 3),
                     "m": out["m"],
                     "escalated_per_round": out["escalated_per_round"],
                     "state_bytes": out["state_bytes"],
                     "bytes_per_device": bpd})
        r = rows[-1]
        print(f"population,{n},{k},{n_runs},{r['exact_ms']:.2f},"
              f"{r['prefilter_hybrid_ms']:.2f},"
              f"{r['prefilter_jax_ms']:.2f},{r['exact_tail_ms']:.2f},"
              f"{r['prefilter_tail_ms']:.2f},{r['m']},"
              f"{r['escalated_per_round']:.2f},{bpd}", flush=True)
    mesh_rows = []
    print("population_mesh,N,devices,hybrid_ms,jax_ms,speedup")
    for n in [n for n in ns if n <= 100_000]:
        out = _run_worker(
            _POPULATION_WORKER, ["mesh", n, k, n_runs, rounds],
            extra_env={"XLA_FLAGS": "--xla_force_host_platform_"
                                    f"device_count={mesh_devices}"})
        mesh_rows.append({"N": n, "devices": out["devices"],
                          "hybrid_ms": round(out["hybrid_ms"], 3),
                          "jax_ms": round(out["jax_ms"], 3)})
        print(f"population_mesh,{n},{out['devices']},"
              f"{out['hybrid_ms']:.2f},{out['jax_ms']:.2f},"
              f"{out['hybrid_ms'] / out['jax_ms']:.2f}", flush=True)
    canonical = (tuple(ns), k, n_runs, rounds) == POPULATION_DEFAULTS
    if canonical and len(rows) >= 2:
        # the acceptance claims: (a) the prefilter beats the exact path
        # in every cell, and (b) its selection tail (the stage the top-M
        # cut shrinks) grows sub-linearly in the exact path's cost over
        # the N span (shrinking share of the exact tail) — see the
        # docstring for why raw-N wall-clock ratios are not the claim
        for r in rows:
            assert r["prefilter_hybrid_ms"] < r["exact_ms"], r
            assert r["prefilter_tail_ms"] < r["exact_tail_ms"], r
        tail_pre = (rows[-1]["prefilter_tail_ms"]
                    / rows[0]["prefilter_tail_ms"])
        tail_exact = (rows[-1]["exact_tail_ms"]
                      / rows[0]["exact_tail_ms"])
        assert tail_pre < tail_exact, (tail_pre, tail_exact)
    if write_json:
        write_bench_json(
            "population",
            {"bench": "population_plane_schedule_scaling",
             "unit": "ms_per_round_all_runs", "rows": rows,
             "mesh": mesh_rows}, canonical=canonical)
    return rows, mesh_rows


def smoke():
    """Tiny end-to-end run of both benchmarks with loud assertions.

    K=40 is the smallest scale where size bucketing reliably beats the
    single global pad (below ~3x _N_BUCKET the cohort-axis padding of 2-3
    sub-cohorts outweighs the max_samples savings)."""
    out = bench_k(40, 4000, 300, 2, 1,
                  ["unbucketed", "vectorized"], buckets=3)
    w_un, w_b = out["unbucketed"][3], out["vectorized"][3]
    assert w_b <= w_un + 1e-9, (
        f"bucketed padding waste {w_b:.2f}x exceeds single-pad {w_un:.2f}x")
    assert all(t > 0 for name in out for t in out[name][2])
    speedup = bench_sweep(2, 3000, 300, 2, write_json=False)
    assert speedup > 0, speedup
    # control plane: the worker's internal parity assertion (host ==
    # batched selections for all five policies) is the actual gate
    ctl_rows = bench_control([50], n_runs=6, rounds=3, write_json=False)
    assert all(r["host_ms"] > 0 and r["batched_ms"] > 0 for r in ctl_rows)
    # threat-model plane: the worker asserts masked == per-client-loop
    # attack application bitwise and sweep == sequential scenario runs
    atk_out = bench_attacks(n_rows=16, reps=3, n_train=2500, rounds=2,
                            write_json=False)
    assert all(r["masked_ms"] > 0 for r in atk_out["apply"])
    # defense plane: the worker asserts host == batched robust
    # aggregation (decisions exact, payload 2e-6) for every aggregator
    def_rows = bench_defenses(ks=[8], n_mals=[2], reps=2,
                              write_json=False)
    # 4 aggregators x the {requested 2, default k//8=1} n_malicious grid
    assert len(def_rows) == 8 and all(r["batched_ms"] > 0
                                      for r in def_rows)
    # LM task plane: the in-bench assertion (loop == vectorized loss,
    # bitwise) is the gate; flash rows stay out of smoke — the CPU
    # interpret-mode kernel is ~50x XLA and belongs to the manual --llm run
    llm_rows = bench_llm(ks=[4], rounds=1, flash=False, write_json=False)
    assert len(llm_rows) == 2 and all(r["s_per_round"] > 0
                                      for r in llm_rows)
    # population plane: the worker asserts prefilter == exact selection
    # in every timed cell (incl. the forced 2-device mesh row)
    pop_rows, pop_mesh = bench_population(ns=[2000], k=16, n_runs=5,
                                          rounds=1, write_json=False)
    assert (pop_rows[0]["exact_ms"] > 0
            and pop_rows[0]["prefilter_hybrid_ms"] > 0
            and pop_rows[0]["prefilter_jax_ms"] > 0
            and pop_rows[0]["prefilter_tail_ms"] > 0)
    assert pop_mesh and pop_mesh[0]["devices"] == 2, pop_mesh
    # async plane: every cell's worker runs the zero-latency parity gate
    # (async == sync bitwise) before timing — that assertion is the gate
    async_cells = bench_async(k=8, n_train=2000, n_test=300, rounds=2,
                              scenarios=("stale_rider_2",),
                              write_json=False)
    assert len(async_cells) == 3 and all(
        np.isfinite(c["final_acc"]) for c in async_cells), async_cells
    # observability plane (DESIGN.md §14): a traced engines cell — the
    # worker hands its trace back through REPRO_TRACE_FILE and the report
    # must see the schedule/train phase timings
    import tempfile
    from repro.obs import report as obs_report
    with tempfile.TemporaryDirectory() as td:
        tpath = os.path.join(td, "trace.jsonl")
        _run_worker(_WORKER, ["vectorized", 8, 1200, 200, 2, 1, 3],
                    extra_env={"REPRO_TRACE": "1",
                               "REPRO_TRACE_FILE": tpath})
        rep = obs_report.summarize(tpath)
    for ph in ("round", "schedule", "train", "eval"):
        assert ph in rep["phases"], (ph, sorted(rep["phases"]))
    n_spans = int(sum(p["count"] for p in rep["phases"].values()))
    print(f"trace,{n_spans},{len(rep['phases'])},"
          f"{rep['phases']['round']['total_s']:.3f},"
          f"{len(rep['compile_offenders'])}", flush=True)
    print(f"# smoke OK: waste {w_un:.2f}x -> {w_b:.2f}x, "
          f"sweep speedup {speedup:.2f}x, "
          f"control speedup {ctl_rows[0]['speedup']:.2f}x, "
          f"attack apply masked {atk_out['apply'][-1]['masked_ms']:.2f}ms "
          f"vs loop {atk_out['apply'][-1]['loop_ms']:.2f}ms, "
          f"defense agg host {def_rows[0]['host_ms']:.2f}ms "
          f"vs batched {def_rows[0]['batched_ms']:.2f}ms",
          file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ks", type=int, nargs="+",
                    default=ENGINE_DEFAULTS["ks"])
    ap.add_argument("--rounds", type=int,
                    default=ENGINE_DEFAULTS["rounds"])
    ap.add_argument("--seeds", type=int, default=ENGINE_DEFAULTS["seeds"],
                    help="independent fresh-partition runs per measurement")
    ap.add_argument("--n-train", type=int, default=None,
                    help="override the per-K automatic corpus size")
    ap.add_argument("--n-test", type=int, default=1_000)
    ap.add_argument("--engines", nargs="+",
                    default=ENGINE_DEFAULTS["engines"],
                    choices=sorted(ENGINES),
                    help="speedup columns are relative to the first")
    ap.add_argument("--buckets", type=int,
                    default=ENGINE_DEFAULTS["buckets"],
                    help="size-bucket count for the 'vectorized' engine "
                         "(the 'unbucketed' engine pins 1)")
    ap.add_argument("--sweep", action="store_true",
                    help="benchmark run_sweep vs sequential run_experiment "
                         "(uses --seeds as the seed count)")
    ap.add_argument("--control", action="store_true",
                    help="benchmark the control plane: host vs batched "
                         "schedule phase at each --ks; writes "
                         "results/BENCH_control.json")
    ap.add_argument("--control-runs", type=int, default=12,
                    help="number of stacked runs for --control (a 'sweep' "
                         "of ~ policies x seeds)")
    ap.add_argument("--attacks", action="store_true",
                    help="benchmark the threat-model plane: masked batched "
                         "attack application vs the per-malicious-client "
                         "dispatch loop, plus a 4-scenario heterogeneous "
                         "sweep; writes results/BENCH_attacks.json")
    ap.add_argument("--defenses", action="store_true",
                    help="benchmark the defense plane: robust aggregators "
                         "host vs batched, vs K and vs n_malicious at "
                         "K=64; writes results/BENCH_defenses.json")
    ap.add_argument("--llm", action="store_true",
                    help="benchmark the LM task plane: lm_tiny per-round "
                         "cost, loop vs vectorized engine, flash on/off; "
                         "writes results/BENCH_llm.json")
    ap.add_argument("--population", action="store_true",
                    help="benchmark the population plane: exact O(N) "
                         "schedule vs the top-M prefilter at N in "
                         "{1e4,1e5,1e6} plus the sharded-mesh jax "
                         "re-bench; writes results/BENCH_population.json")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="benchmark the async event engine: accuracy vs "
                         "simulated wall-clock for {sync, async-buffer, "
                         "async-deadline} x scenarios with a zero-latency "
                         "parity gate per cell; writes "
                         "results/BENCH_async.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny asserted run of every benchmark (CI gate)")
    args = ap.parse_args()

    if args.smoke:
        smoke()
        return
    if args.async_:
        bench_async(*ASYNC_DEFAULTS)
        return
    if args.population:
        bench_population()
        return
    if args.llm:
        bench_llm()
        return
    if args.defenses:
        bench_defenses()
        return
    if args.attacks:
        bench_attacks(*ATTACK_DEFAULTS)
        return
    if args.control:
        bench_control(args.ks, args.control_runs, max(args.rounds, 3))
        return
    if args.sweep:
        bench_sweep(args.seeds, args.n_train or 10_000, args.n_test,
                    args.rounds)
        return

    print("engine,K,n_train,s_per_round,median_round_s,"
          "speedup,median_speedup,pad_waste")
    rows_json = []
    for k in args.ks:
        out = bench_k(k, args.n_train, args.n_test, args.rounds,
                      args.seeds, args.engines, args.buckets)
        for name in args.engines:
            mean, med, _, waste = out[name]
            rows_json.append({"engine": name, "K": k,
                              "s_per_round": round(mean, 3),
                              "median_round_s": round(med, 3),
                              "pad_waste": round(waste, 3)
                              if np.isfinite(waste) else None})
        base, last = args.engines[0], args.engines[-1]
        if base != last:
            print(f"# K={k}: {last} per-round speedup over {base} "
                  f"{out[base][0] / out[last][0]:.2f}x", file=sys.stderr)
    write_bench_json(
        "engines", {"bench": "cohort_engine_per_round", "rows": rows_json},
        canonical=(args.n_train is None
                   and all(getattr(args, k) == v
                           for k, v in ENGINE_DEFAULTS.items())))


if __name__ == "__main__":
    main()
