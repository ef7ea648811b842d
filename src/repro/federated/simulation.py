"""End-to-end FEEL experiment driver — reproduces the paper's §V protocol.

    run_sweep(...)      -> tidy per-(run, round) table (many runs)
    run_experiment(...) -> one run's curves + summary (the one-run sweep)

Protocol (paper §V-A): synthetic-MNIST 50k/10k; sort-by-label groups of 50;
1-30 groups per UE; K=50 UEs, 5 random malicious with a label-flip attack
((6,2) easy / (8,4) hard); 2-layer MLP via FedAvg; 15 rounds; results
averaged over independent runs.

``run_sweep`` is the one place a run is built. It crosses tasks,
threat-model scenarios, defenses, seeds and policies; generates each
(task, seed) dataset once; builds each (task, seed, data-attack)
partition and its device-resident padded layout once, shared across
policies, defenses and scenarios with identical poisoned data; then runs
every run, one after another, through ``FeelServer.run_round`` (or
``AsyncFeelEngine`` in ``mode='async'``) — the one implementation of the
round. Every run draws the RNG streams it would draw alone, so each run
of a sweep equals its ``run_experiment`` twin (tests/test_sweep.py).

The model/data pair is a ``FeelTask`` (federated/task.py) and a sweep
axis: ``run_experiment(task="lm_tiny")`` runs the same DQS protocol on
federated LM fine-tuning. ``engine`` selects the cohort execution path:
"vectorized" (default) runs every scheduled UE in one vmapped step;
"loop" is the sequential per-client oracle (see federated/server.py).

The threat-model axis (``scenarios=[...]``) runs heterogeneous attack
scenarios — label-flip variants, feature noise, token attacks, free-riders,
model poisoning, colluding schedules (core/attacks.py, DESIGN.md §8);
``attack_pairs`` survives as a back-compat shim. Data attacks are
dataset-typed (label/feature attacks need feature datasets, token attacks
need token datasets — ``attacks.poison_dataset`` fails loudly on a
mismatch), so a mixed-task grid crosses tasks with data-free scenarios
(model/report attacks, "none") or task-compatible data attacks. The
defense axis (``defenses=[...]``) crosses every scenario with a
server-side counter-measure (core/defenses.py, DESIGN.md §9: robust
aggregation + validation detection) at zero extra partition/layout cost —
defenses are deterministic, so (scenario x defense) cells share the
scenario's partitions and RNG streams.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core import defenses as dfs
from repro.core.poisoning import pick_malicious
from repro.federated import cohort
from repro.federated.async_engine import AsyncFeelEngine
from repro.federated.server import FeelServer, build_cohort_data
from repro.federated.task import FeelTask, as_task
from repro.obs import trace


def run_experiment(policy: str = "dqs",
                   attack_pair: Tuple[int, int] = (6, 2),
                   cfg: Optional[FeelConfig] = None,
                   seed: int = 0,
                   n_train: Optional[int] = None,
                   n_test: Optional[int] = None,
                   omega: Optional[Tuple[float, float]] = None,
                   adaptive_omega: bool = False,
                   rounds: Optional[int] = None,
                   no_attack: bool = False,
                   model_poison_scale: Optional[float] = None,
                   lie_boost: float = 0.0,
                   engine: str = "vectorized",
                   control: str = "batched",
                   scenario=None, defense=None,
                   task: Optional[FeelTask] = None,
                   population: Optional[int] = None) -> Dict:
    """One FEEL experiment; returns the per-round curves + run summary.

    The one-run case of ``run_sweep``: the same data, partition, server
    and RNG streams, and the same summary without the sweep's key fields.

    ``population`` — candidate population size N (DESIGN.md §12): the
    scheduler ranks over N candidate UEs per round while ``cfg.n_ues``
    stays the bandwidth budget K. None (default) pins the legacy N == K
    regime — bit-identical streams and schedules to every pre-population
    caller. With N > K the batched control plane routes through the
    schedule-preserving top-M prefilter (core/population.py).

    ``task`` — a ``federated.task.FeelTask`` (object or registry name;
    None defers to ``cfg.task``, default the paper's ``mnist_mlp``).
    ``n_train``/``n_test`` default to the task's protocol sizes.

    Threat model — either an explicit ``scenario`` (an
    ``core.attacks.AttackScenario``, a registry name, or a legacy
    ``(source, target)`` pair) or the legacy knobs. The legacy-knob
    contract is regression-tested (tests/test_attacks.py):

    - ``model_poison_scale`` REPLACES the label-flip data attack —
      malicious UEs keep clean data and poison their *updates* instead
      (the two never compose through these knobs; compose explicitly via
      an ``AttackScenario`` if both are wanted);
    - ``no_attack=True`` wins over everything: no data attack, no model
      poisoning, no lie_boost, and malicious flags are not set;
    - ``lie_boost`` composes with whichever attack is active;
    - metrics always watch ``attack_pair``.

    ``scenario`` supersedes the legacy knobs (they must stay at their
    defaults when it is given).

    ``defense`` — a ``core.defenses.DefensePolicy`` spec (object or
    registry name; None defers to ``cfg.defense``): the server-side
    counter-measure plane (robust aggregation + validation detection,
    DESIGN.md §9).
    """
    res = run_sweep(
        [policy], [seed], [attack_pair], cfg,
        tasks=None if task is None else [task],
        scenarios=None if scenario is None else [scenario],
        defenses=None if defense is None else [defense],
        n_train=n_train, n_test=n_test, omega=omega,
        adaptive_omega=adaptive_omega, rounds=rounds, no_attack=no_attack,
        model_poison_scale=model_poison_scale, lie_boost=lie_boost,
        engine=engine, control=control, population=population)
    return {k: v for k, v in res.runs[0].items() if k not in _SWEEP_KEYS}


def _summary(server: FeelServer, malicious: np.ndarray,
             eng: Optional[AsyncFeelEngine]) -> Dict:
    """One finished run's curves and end state (``run_experiment``'s
    return value); an async run adds its event clock's per-aggregation
    extras from ``eng``."""
    logs = server.logs
    rep = server.reputation.values
    out = {
        "task": server.task.name,
        "scenario": server.scenario.name,
        "defense": server.defense.name,
        "acc": [l.global_acc for l in logs],
        "loss": [l.global_loss for l in logs],
        "source_acc": [l.source_acc for l in logs],
        "attack_success": [l.attack_success for l in logs],
        "malicious_selected": [l.n_malicious_selected for l in logs],
        "objective": [l.objective for l in logs],
        "rep_gap": [l.rep_gap for l in logs],
        "n_clipped": [l.n_clipped for l in logs],
        "n_rejected": [l.n_rejected for l in logs],
        "n_flagged": [l.n_flagged for l in logs],
        "det_precision": [l.det_precision for l in logs],
        "det_recall": [l.det_recall for l in logs],
        "recovery_rounds": atk.recovery_rounds(
            [l.attack_success for l in logs], server.cfg.recovery_threshold),
        "final_reputation_malicious": float(np.mean(rep[malicious])),
        "final_reputation_honest": float(np.mean(np.delete(rep, malicious))),
        "malicious": malicious.tolist(),
    }
    if eng is not None:
        out.update({
            "sim_time": [a.sim_time for a in eng.agg_logs],
            "trigger": [a.trigger for a in eng.agg_logs],
            "n_uploads": [a.n_uploads for a in eng.agg_logs],
            "mean_age": [float(np.mean(a.ages)) for a in eng.agg_logs],
        })
    return out


# fields a sweep's run record carries beyond ``_summary``
_SWEEP_KEYS = ("policy", "seed", "attack_pair", "forced")


# ---------------------------------------------------------------------- #
# Multi-run sweeps
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class SweepResult:
    """Tidy results of a (tasks x policies x seeds x scenarios x defenses)
    sweep.

    rows — one record per (task, policy, seed, scenario, defense, round)
        with the per-round metrics (acc, loss, source_acc, attack_success,
        malicious_selected, objective, rep_gap, forced, and the defense
        metrics n_clipped / n_rejected / n_flagged / det_precision /
        det_recall).
    runs — one record per run, shaped exactly like ``run_experiment``'s
        return value plus the (task, policy, seed, scenario, defense,
        attack_pair) key and the per-round ``forced`` flags
        (``attack_pair`` is the scenario's watched pair, None if it has
        none — kept for back-compat with pair-keyed callers).
    """
    rows: List[Dict]
    runs: List[Dict]

    def select(self, **key) -> List[Dict]:
        """Run summaries matching e.g. task=..., policy=..., seed=...,
        scenario=..., defense=..."""
        return [r for r in self.runs
                if all(r[k] == v for k, v in key.items())]

    def mean_curve(self, field: str = "acc", **key) -> np.ndarray:
        """Per-round mean of ``field`` over the runs matching ``key``
        (the paper's average-over-independent-runs reduction).

        NaN-aware: watch-metric entries (attack_success / source_acc /
        det_precision / det_recall) are NaN where undefined — a watch-less
        scenario, a round with nothing flagged — and must not poison the
        cross-seed mean of the runs that DO define them. A round where
        every matched run is NaN stays NaN (computed without numpy's
        all-NaN RuntimeWarning).
        """
        runs = self.select(**key)
        assert runs, key
        a = np.asarray([r[field] for r in runs], float)
        finite = np.isfinite(a)
        n = finite.sum(axis=0)
        s = np.where(finite, a, 0.0).sum(axis=0)
        return np.where(n > 0, s / np.maximum(n, 1), np.nan)

    def averaged(self, fields: Sequence[str] = ("acc", "source_acc",
                                                "attack_success",
                                                "malicious_selected",
                                                "rep_gap"),
                 **key) -> Dict[str, np.ndarray]:
        """NaN-aware mean curves of several fields at once (the standard
        averaged-over-seeds reduction of a sweep slice)."""
        return {f: self.mean_curve(f, **key) for f in fields}


def run_sweep(policies: Sequence[str], seeds: Sequence[int],
              attack_pairs: Sequence[Tuple[int, int]] = ((6, 2),),
              cfg: Optional[FeelConfig] = None, *,
              tasks: Optional[Sequence] = None,
              scenarios: Optional[Sequence] = None,
              defenses: Optional[Sequence] = None,
              n_train: Optional[int] = None,
              n_test: Optional[int] = None,
              omega: Optional[Tuple[float, float]] = None,
              adaptive_omega: bool = False,
              rounds: Optional[int] = None,
              no_attack: bool = False,
              model_poison_scale: Optional[float] = None,
              lie_boost: float = 0.0,
              engine: str = "vectorized",
              control: str = "batched",
              n_buckets: int = 3,
              population: Optional[int] = None) -> SweepResult:
    """Run the full (tasks x scenarios x defenses x seeds x policies) grid.

    Every run is one ``FeelServer`` driven through ``run_round`` (or
    ``AsyncFeelEngine`` in ``mode='async'``), one run after another,
    each inside an ``experiment`` span. What the runs share are the
    caches: each (task, seed) dataset is generated once, and each (task,
    seed, data-attack) partition and its device-resident padded bucket
    layout (``CohortData``) are built once and shared across policies,
    defenses, and scenarios whose poisoned data is identical (e.g. every
    pure model-poisoning scenario shares the clean ``mal_only``
    partition). One ``pad_to`` per task keeps the bucket levels, and so
    every compiled per-bucket program, the same across that task's runs.
    Each run restores its partition's post-partition RNG state, so it
    draws exactly the streams of its ``run_experiment`` twin.

    The task axis: ``tasks`` is a sequence of ``federated.task.FeelTask``
    specs (objects or registry names; None = the single ``cfg.task``
    default). Per-run metrics carry the ``task`` key and the task-defined
    ``loss`` curve (NaN for tasks without one). Data attacks are
    dataset-typed — cross tasks with data-free scenarios or
    task-compatible data attacks (module docstring).

    The defense axis: ``defenses`` is a sequence of
    ``core.defenses.DefensePolicy`` specs (objects or registry names;
    None = the single ``cfg.defense`` default). Defenses are
    deterministic server-side counter-measures, so every (scenario,
    defense) cell shares the scenario's partition, device layout and RNG
    streams, and a defended run's undefended twin differs only through
    the defense's model/reputation effects.

    The threat-model axis: ``scenarios`` is a sequence of
    ``core.attacks.AttackScenario`` specs (scenario objects, registry
    names, or legacy ``(source, target)`` pairs). When ``scenarios`` is
    None the legacy ``attack_pairs`` + ``no_attack`` /
    ``model_poison_scale`` / ``lie_boost`` knobs are shimmed into one
    scenario per pair (``attacks.legacy_scenario`` — the contract
    documented on ``run_experiment``); the legacy knobs must stay at
    their defaults when ``scenarios`` is given.

    ``n_train``/``n_test`` default per task (each task's protocol sizes);
    an explicit value applies to every task in the grid.

    ``population`` — candidate population size N for EVERY run of the
    sweep (DESIGN.md §12; None = the legacy N == cfg.n_ues regime). The
    data is partitioned over all N candidates, the control plane ranks
    over N through the schedule-preserving top-M prefilter, and only the
    per-round scheduled cohorts (<= K fractions' worth) train.
    """
    cfg = cfg or FeelConfig()
    if population is not None:
        cfg = dataclasses.replace(cfg, population=int(population))
    if omega is not None:
        cfg = dataclasses.replace(cfg, omega_rep=omega[0],
                                  omega_div=omega[1])
    policies = list(policies)
    seeds = [int(s) for s in seeds]
    tsks = ([as_task(cfg.task)] if tasks is None
            else [as_task(t) for t in tasks])
    assert len({t.name for t in tsks}) == len(tsks), \
        "duplicate task names in the tasks axis"
    if scenarios is None:
        scns = [atk.legacy_scenario(tuple(p), no_attack,
                                    model_poison_scale, lie_boost)
                for p in attack_pairs]
    else:
        assert (not no_attack and model_poison_scale is None
                and not lie_boost
                and tuple(map(tuple, attack_pairs)) == ((6, 2),)), \
            "the scenarios axis supersedes the legacy attack knobs " \
            "(incl. attack_pairs — set AttackScenario.watch instead)"
        scns = [atk.as_scenario(s) for s in scenarios]
    dfns = ([dfs.as_defense(cfg.defense)] if defenses is None
            else [dfs.as_defense(d) for d in defenses])

    # -- shared caches (all keyed per task) ------------------------------ #
    data_cache = {
        (tsk.name, s): tsk.generate_data(
            n_train if n_train is not None else tsk.default_n_train,
            n_test if n_test is not None else tsk.default_n_test, s)
        for tsk in tsks for s in set(seeds)}

    part_cache: Dict = {}
    for tsk in tsks:
        for seed in set(seeds):
            for scn in scns:
                key = (tsk.name, seed, scn.data_key())
                if key in part_cache:
                    continue
                train, test = data_cache[(tsk.name, seed)]
                rng = np.random.default_rng(seed)
                malicious = pick_malicious(cfg.n_population,
                                           cfg.n_malicious, rng)
                clients = tsk.partition_clients(
                    train, cfg.n_population, rng,
                    None if scn.benign else malicious, scn.data,
                    context=f"task={tsk.name}, scenario={scn.name}")
                # freeze the post-partition RNG state: each run restores it
                # so its downstream stream (wireless placement, channel
                # draws) matches its sequential run_experiment twin exactly
                part_cache[key] = (clients, malicious,
                                   rng.bit_generator.state)

    # one pad_to per task across the whole sweep => identical bucket
    # levels => every compiled per-bucket program is shared by that
    # task's runs (with one partition: its largest client, which is
    # the level grid pad_to=None gives)
    pad_to = {
        tsk.name: max(c.size for (tn, _, _), (clients, _, _)
                      in part_cache.items() if tn == tsk.name
                      for c in clients)
        for tsk in tsks}

    cohort_cache: Dict = {}
    if engine == "vectorized":
        for tsk in tsks:
            for (tn, seed, akey), (clients, _, _) in part_cache.items():
                if tn != tsk.name:
                    continue
                _, test = data_cache[(tn, seed)]
                unit_labels = tsk.unit_labels(test)
                hists = [tsk.histogram(c.data) for c in clients]
                mask_arr = np.stack(
                    [np.isin(unit_labels, np.flatnonzero(h > 0))
                     for h in hists]).astype(np.float32)
                cohort_cache[(tn, seed, akey)] = build_cohort_data(
                    clients, mask_arr, batch_size=tsk.batch_size,
                    pad_to=pad_to[tn], n_buckets=n_buckets)

    n_rounds = rounds or cfg.rounds
    rows: List[Dict] = []
    runs: List[Dict] = []
    for tsk, scn, dfn, seed, policy in itertools.product(
            tsks, scns, dfns, seeds, policies):
        key = (tsk.name, seed, scn.data_key())
        clients, malicious, rng_state = part_cache[key]
        _, test = data_cache[(tsk.name, seed)]
        rng = np.random.default_rng(seed)
        rng.bit_generator.state = rng_state
        server = FeelServer(
            dataclasses.replace(cfg, task=tsk.name), clients, test, rng,
            policy=policy, adaptive_omega=adaptive_omega, scenario=scn,
            engine=engine, defense=dfn, control=control,
            pad_to=pad_to[tsk.name], n_buckets=n_buckets, task=tsk,
            cohort_data=cohort_cache.get(key))
        with trace.span("experiment") as sp:
            if trace.enabled():
                sp.set(policy=policy, task=tsk.name, mode=cfg.mode,
                       engine=engine, control=control)
            if cfg.mode == "async":
                # event-driven engine (federated/async_engine.py, DESIGN.md
                # §13): one RoundLog per aggregation + simulated-clock
                # extras
                eng = AsyncFeelEngine(server)
                eng.run(n_rounds)
            else:
                eng = None
                server.run(n_rounds)
        run_key = {"task": tsk.name, "policy": policy, "seed": seed,
                   "scenario": scn.name, "defense": dfn.name,
                   "attack_pair": scn.watch}   # back-compat pair key
        runs.append({**run_key, **_summary(server, malicious, eng),
                     "forced": [l.forced for l in server.logs]})
        rows += [
            {**run_key, "round": l.round, "acc": l.global_acc,
             "loss": l.global_loss, "source_acc": l.source_acc,
             "attack_success": l.attack_success,
             "malicious_selected": l.n_malicious_selected,
             "objective": l.objective, "rep_gap": l.rep_gap,
             "forced": l.forced, "n_clipped": l.n_clipped,
             "n_rejected": l.n_rejected, "n_flagged": l.n_flagged,
             "det_precision": l.det_precision, "det_recall": l.det_recall}
            for l in server.logs]
    if trace.enabled():
        for k, v in cohort.cache_sizes().items():
            trace.gauge_set(f"compile.{k}", float(v))
    return SweepResult(rows=rows, runs=runs)


def averaged(policy, attack_pair, n_runs=3, **kw) -> Dict:
    """Paper reports the average of independent runs per setting —
    executed as one ``run_sweep`` over the seeds."""
    res = run_sweep([policy], seeds=range(n_runs),
                    attack_pairs=[attack_pair], **kw)
    return {"acc": res.mean_curve("acc").tolist(),
            "malicious_selected":
                res.mean_curve("malicious_selected").tolist(),
            "rep_gap": float(np.mean([r["final_reputation_honest"]
                                      - r["final_reputation_malicious"]
                                      for r in res.runs]))}
