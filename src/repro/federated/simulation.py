"""End-to-end FEEL experiment driver — reproduces the paper's §V protocol.

    run_experiment(...) -> accuracy curve per round (one run)
    run_sweep(...)      -> tidy per-(policy, seed, round) table (many runs)

Protocol (paper §V-A): synthetic-MNIST 50k/10k; sort-by-label groups of 50;
1-30 groups per UE; K=50 UEs, 5 random malicious with a label-flip attack
((6,2) easy / (8,4) hard); 2-layer MLP via FedAvg; 15 rounds; results
averaged over independent runs.

The model/data pair is a ``FeelTask`` (federated/task.py) and a first-class
sweep axis: ``run_experiment(task="lm_tiny")`` runs the same DQS protocol
on federated LM fine-tuning, and ``run_sweep(tasks=[...])`` crosses tasks
with scenarios, defenses, policies and seeds in ONE invocation — per-task
batched cohorts share one batched control plane, because the control plane
(Eq. 1-3, Eq. 9, Alg. 2) never touches the model.

``engine`` selects the cohort execution path: "vectorized" (default) runs
every scheduled UE in one vmapped step; "loop" is the original sequential
per-client oracle (see federated/server.py).

``run_sweep`` is the recommended entry point for multi-seed studies
(§V averages, robustness sweeps): it generates each (task, seed) dataset
once, shares each (task, seed, data-attack) partition and its
device-resident padded layout across policies (and across scenarios with
identical poisoned data), and — where shapes allow (same cfg => same
padded bucket levels) — stacks the per-round cohorts of a task's runs into
one ``cohort_train_multi``/``cohort_eval`` call per size bucket, so seeds,
policies and threat scenarios become one more slice of the vmapped client
axis. Every run reproduces its sequential ``run_experiment`` twin exactly
(same RNG streams; tests/test_sweep.py pins the parity).

The threat-model axis (``scenarios=[...]``) runs heterogeneous attack
scenarios — label-flip variants, feature noise, token attacks, free-riders,
model poisoning, colluding schedules (core/attacks.py, DESIGN.md §8) — in
the same stacked sweep; ``attack_pairs`` survives as a back-compat shim.
Data attacks are dataset-typed (label/feature attacks need feature
datasets, token attacks need token datasets — ``attacks.poison_dataset``
fails loudly on a mismatch), so a mixed-task grid crosses tasks with
data-free scenarios (model/report attacks, "none") or task-compatible
data attacks. The defense axis (``defenses=[...]``) crosses every scenario
with a server-side counter-measure (core/defenses.py, DESIGN.md §9: robust
aggregation + validation detection) at zero extra partition/layout cost —
defenses are deterministic, so (scenario x defense) cells share the
scenario's partitions and RNG streams.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core import control as ctl
from repro.core import defenses as dfs
from repro.core import population
from repro.core.poisoning import pick_malicious
from repro.core.scheduler import Schedule
from repro.federated import cohort
from repro.federated.async_engine import AsyncFeelEngine
from repro.federated.server import FeelServer, build_cohort_data
from repro.federated.task import FeelTask, as_task
from repro.models.common import count_accuracy
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
    cfg = cfg or FeelConfig()
    tsk = as_task(task if task is not None else cfg.task)
    cfg = dataclasses.replace(cfg, task=tsk.name)
    if population is not None:
        cfg = dataclasses.replace(cfg, population=int(population))
    if omega is not None:
        cfg = dataclasses.replace(cfg, omega_rep=omega[0], omega_div=omega[1])
    n_train = tsk.default_n_train if n_train is None else n_train
    n_test = tsk.default_n_test if n_test is None else n_test
    if scenario is not None:
        assert (not no_attack and model_poison_scale is None
                and not lie_boost and tuple(attack_pair) == (6, 2)), \
            "scenario supersedes the legacy attack knobs (incl. " \
            "attack_pair — set AttackScenario.watch instead)"
        scn = atk.as_scenario(scenario)
    else:
        scn = atk.legacy_scenario(attack_pair, no_attack,
                                  model_poison_scale, lie_boost)
    rng = np.random.default_rng(seed)
    train, test = tsk.generate_data(n_train, n_test, seed)
    malicious = pick_malicious(cfg.n_population, cfg.n_malicious, rng)
    clients = tsk.partition_clients(train, cfg.n_population, rng,
                                    None if scn.benign else malicious,
                                    scn.data,
                                    context=f"task={tsk.name}, "
                                            f"scenario={scn.name}")
    server = FeelServer(cfg, clients, test, rng, policy=policy,
                        adaptive_omega=adaptive_omega, scenario=scn,
                        engine=engine, control=control, defense=defense,
                        task=tsk)
    with trace.span("experiment") as sp:
        if trace.enabled():
            sp.set(policy=policy, task=tsk.name, mode=cfg.mode,
                   engine=engine, control=control)
        if cfg.mode == "async":
            # event-driven engine (federated/async_engine.py, DESIGN.md
            # §13): one RoundLog per aggregation + simulated-clock extras
            eng = AsyncFeelEngine(server)
            logs = eng.run(rounds)
        else:
            eng = None
            logs = server.run(rounds)
        if trace.enabled():
            for k, v in cohort.cache_sizes().items():
                trace.gauge_set(f"compile.{k}", float(v))
    out = {
        "task": tsk.name,
        "scenario": scn.name,
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
            [l.attack_success for l in logs], cfg.recovery_threshold),
        "final_reputation_malicious": float(
            np.mean(server.reputation.values[malicious])),
        "final_reputation_honest": float(np.mean(np.delete(
            server.reputation.values, malicious))),
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


# ---------------------------------------------------------------------- #
# Batched multi-run sweeps
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
        attack_pair) key (``attack_pair`` is the scenario's watched pair,
        None if it has none — kept for back-compat with pair-keyed
        callers).
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


class _SweepRun:
    """One (task, policy, seed, scenario, defense) run's server +
    in-flight round state."""

    def __init__(self, task, policy, seed, scenario, defense, server,
                 malicious, watch_mask, ty_target):
        self.task = task
        self.policy = policy
        self.seed = seed
        self.scenario = scenario
        self.defense = defense
        self.pair = scenario.watch         # back-compat attack_pair key
        self.server = server
        self.malicious = malicious
        self.watch_mask = watch_mask       # (U,) float32, source-unit rows
        self.ty_target = ty_target         # (U,) unit labels relabelled to
        #                                    the attack target (== ey if none)
        self.plan = None                   # (values, sched, sel, forced)
        self.stacked = None                # merged cohort params (sel order)
        self.acc_local = None
        self.acc_test = None
        self.acc_val = None                # detector validation accuracies
        self.g_acc = float("nan")
        self.g_loss = float("nan")
        self.src_acc = float("nan")
        self.atk_succ = float("nan")

    def summary(self) -> Dict:
        s = self.server
        return {
            "task": self.task.name,
            "policy": self.policy, "seed": self.seed,
            "scenario": self.scenario.name,
            "defense": self.defense.name,
            "attack_pair": self.pair,
            "acc": [l.global_acc for l in s.logs],
            "loss": [l.global_loss for l in s.logs],
            "source_acc": [l.source_acc for l in s.logs],
            "attack_success": [l.attack_success for l in s.logs],
            "malicious_selected": [l.n_malicious_selected for l in s.logs],
            "objective": [l.objective for l in s.logs],
            "rep_gap": [l.rep_gap for l in s.logs],
            "forced": [l.forced for l in s.logs],
            "n_clipped": [l.n_clipped for l in s.logs],
            "n_rejected": [l.n_rejected for l in s.logs],
            "n_flagged": [l.n_flagged for l in s.logs],
            "det_precision": [l.det_precision for l in s.logs],
            "det_recall": [l.det_recall for l in s.logs],
            "recovery_rounds": atk.recovery_rounds(
                [l.attack_success for l in s.logs],
                s.cfg.recovery_threshold),
            "final_reputation_malicious": float(
                np.mean(s.reputation.values[self.malicious])),
            "final_reputation_honest": float(np.mean(np.delete(
                s.reputation.values, self.malicious))),
            "malicious": self.malicious.tolist(),
        }


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
              stack_runs: bool = True,
              population: Optional[int] = None) -> SweepResult:
    """Run the full (tasks x policies x seeds x scenarios x defenses) grid
    batched.

    The task axis: ``tasks`` is a sequence of ``federated.task.FeelTask``
    specs (objects or registry names; None = the single ``cfg.task``
    default) — the model/data pair becomes one more sweep axis. Tasks
    cannot share parameter pytrees, so the cohort phases batch WITHIN each
    task while the control plane (schedule + Eq. 1 reputation, which never
    touches the model) still runs ONE vmapped kernel across every run of
    every task. Per-run metrics gain the ``task`` key and the
    task-defined ``loss`` curve (NaN for tasks without one). Data attacks
    are dataset-typed — cross tasks with data-free scenarios or
    task-compatible data attacks (module docstring).

    The defense axis: ``defenses`` is a sequence of
    ``core.defenses.DefensePolicy`` specs (objects or registry names;
    None = the single ``cfg.defense`` default). Defenses are
    deterministic server-side counter-measures, so every (scenario,
    defense) cell shares the scenario's partition, device layout and RNG
    streams — (scenario x defense x policy x seed) runs as ONE stacked
    sweep with shared partitions, and a defended run's undefended twin
    differs only through the defense's model/reputation effects.

    The threat-model axis: ``scenarios`` is a sequence of
    ``core.attacks.AttackScenario`` specs (scenario objects, registry
    names, or legacy ``(source, target)`` pairs) — HETEROGENEOUS threat
    models (label-flip variants, feature noise, token attacks,
    free-riders, model poisoning, colluding schedules, ...) run as one
    stacked sweep through the bucketed engine and batched control plane.
    When ``scenarios`` is None the legacy ``attack_pairs`` +
    ``no_attack`` / ``model_poison_scale`` / ``lie_boost`` knobs are
    shimmed into one scenario per pair (``attacks.legacy_scenario`` —
    same contract as ``run_experiment``); the legacy knobs must stay at
    their defaults when ``scenarios`` is given.

    Semantics: every run is exactly ``run_experiment(policy, task=tsk,
    scenario=scn, seed=seed, ...)`` — same datasets, partitions and RNG
    streams — but the sweep (1) generates each (task, seed) dataset once,
    (2) builds each (task, seed, data-attack) partition and its
    device-resident padded bucket layout once, shared across policies AND
    across scenarios whose poisoned data is identical (e.g. every pure
    model-poisoning scenario shares the clean ``mal_only`` partition),
    and (3) with ``stack_runs`` and the vectorized engine,
    trains/evaluates the per-round cohorts of a task's runs in one
    vmapped call per size bucket: a shared per-task ``pad_to`` makes the
    bucket levels identical across runs, so runs become one more slice of
    the stacked client axis (``cohort.cohort_train_multi``).

    ``control="batched"`` (default) also stacks the *control plane*: with
    ``stack_runs``, round t of every run is scheduled by ONE vmapped
    ``core.control.schedule_runs`` call over a sweep-wide ``ControlState``
    (and Eq. 1 reputations update in one ``finalize_runs``) instead of a
    per-run numpy loop, so the schedule phase stops scaling linearly in
    the number of runs. ``control="host"`` keeps the sequential numpy
    control oracle per run.

    ``stack_runs=False`` (or engine="loop") executes the runs sequentially
    while still sharing the dataset/partition caches — the oracle the
    batched path is tested against.

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
    # task's runs
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

    runs: List[_SweepRun] = []
    for tsk in tsks:
        cfg_t = dataclasses.replace(cfg, task=tsk.name)
        for scn in scns:
            for dfn in dfns:
                for seed in seeds:
                    for policy in policies:
                        key = (tsk.name, seed, scn.data_key())
                        clients, malicious, rng_state = part_cache[key]
                        _, test = data_cache[(tsk.name, seed)]
                        rng = np.random.default_rng(seed)
                        rng.bit_generator.state = rng_state
                        server = FeelServer(
                            cfg_t, clients, test, rng, policy=policy,
                            adaptive_omega=adaptive_omega, scenario=scn,
                            engine=engine, defense=dfn,
                            control=control, pad_to=pad_to[tsk.name],
                            n_buckets=n_buckets, task=tsk,
                            cohort_data=cohort_cache.get(key))
                        unit_labels = tsk.unit_labels(test)
                        watch = ((unit_labels == scn.watch[0])
                                 .astype(np.float32) if scn.watch else
                                 np.zeros(unit_labels.size, np.float32))
                        ty_target = (np.full_like(unit_labels,
                                                  scn.watch[1])
                                     if scn.watch else unit_labels)
                        runs.append(_SweepRun(tsk, policy, seed, scn, dfn,
                                              server, malicious, watch,
                                              jnp.asarray(ty_target)))

    n_rounds = rounds or cfg.rounds
    if cfg.mode == "async":
        # event-driven mode: every run gets its own event loop (waves are
        # per-run decisions, so rounds cannot interleave across runs), but
        # the whole (scenario x defense x policy) grid still shares the
        # dataset/partition/cohort caches built above
        for run in runs:
            AsyncFeelEngine(run.server).run(n_rounds)
    elif stack_runs and engine == "vectorized":
        # sweep-wide control state: ONE vmapped schedule / reputation
        # kernel call per round for ALL runs — of every task
        # (core/control.py; the control plane is model-free)
        sweep_ctrl = (ctl.ControlState.from_servers(
            [r.server for r in runs]) if control == "batched" else None)
        for t in range(n_rounds):
            _sweep_round_stacked(runs, t, sweep_ctrl)
    else:
        for run in runs:
            for t in range(n_rounds):
                run.server.run_round(t)
    if trace.enabled():
        for k, v in cohort.cache_sizes().items():
            trace.gauge_set(f"compile.{k}", float(v))

    rows = [
        {"task": run.task.name,
         "policy": run.policy, "seed": run.seed,
         "scenario": run.scenario.name, "defense": run.defense.name,
         "attack_pair": run.pair,
         "round": l.round, "acc": l.global_acc, "loss": l.global_loss,
         "source_acc": l.source_acc,
         "attack_success": l.attack_success,
         "malicious_selected": l.n_malicious_selected,
         "objective": l.objective, "rep_gap": l.rep_gap,
         "forced": l.forced, "n_clipped": l.n_clipped,
         "n_rejected": l.n_rejected, "n_flagged": l.n_flagged,
         "det_precision": l.det_precision, "det_recall": l.det_recall}
        for run in runs for l in run.server.logs]
    return SweepResult(rows=rows, runs=[r.summary() for r in runs])


_PAD = FeelServer._N_BUCKET
def _schedule_runs_stacked(runs: List[_SweepRun],
                           sweep_ctrl: ctl.ControlState, t: int) -> None:
    """Phase A, batched control plane: draw each run's channel (and
    ``random``-policy permutation) from its own host RNG — the oracle
    streams — then schedule round t of ALL runs in one vmapped
    ``control.schedule_runs`` call and scatter the per-run Schedules."""
    servers = [r.server for r in runs]
    sweep_ctrl.pull(servers)
    N = servers[0].cfg.n_population     # candidate width (== n_ues legacy)
    gains = np.empty((len(runs), N))
    rand_rank = np.empty((len(runs), N), int)
    omega = np.empty((len(runs), 2))
    for i, s in enumerate(servers):
        gains[i], rand_rank[i] = s.draw_control_inputs()
        omega[i] = s._omega(t)
    if sweep_ctrl.cfg.population is not None:
        # population cut: the schedule-preserving top-M prefilter
        # (identical selection by certificate, core/population.py)
        x, alpha, costs, values, forced, _ = \
            population.prefilter_schedule_runs(
                sweep_ctrl, gains, rand_rank, omega[:, 0], omega[:, 1])
    else:
        x, alpha, costs, values, forced = ctl.schedule_runs(
            sweep_ctrl, gains, rand_rank, omega[:, 0], omega[:, 1])
    for i, run in enumerate(runs):
        sched = Schedule(x=x[i], alpha=alpha[i], cost=costs[i],
                         value=values[i])
        run.plan = (values[i], sched, sched.selected, bool(forced[i]))


def _train_runs_stacked(runs: List[_SweepRun], t: int) -> None:
    """Phase B for ONE task's runs: one ``cohort_train_multi`` call per
    (shared client arrays, size bucket) group. Parameter pytrees are only
    stackable within a task, so the sweep round calls this once per task
    group; everything else batches across tasks or runs per run."""
    task = runs[0].task
    lr = runs[0].server.lr
    epochs = runs[0].server.cfg.local_epochs
    batch_size = runs[0].server.batch_size
    assert all(r.server.lr == lr and r.server.batch_size == batch_size
               and r.task == task for r in runs)

    # (R, ...) stacked run parameters; each group's per-row params are one
    # shape-stable gather from it
    params_all = jax.tree.map(lambda *xs: jnp.stack(xs),
                              *[r.server.params for r in runs])
    groups: Dict[int, Dict] = {}
    for i, run in enumerate(runs):
        sel = run.plan[2]
        waste_slots = 0
        for bkt, pos, rows in run.server._cohort_parts(sel, t, pad=False):
            g = groups.setdefault(id(bkt), {"bkt": bkt, "parts": []})
            g["parts"].append((i, pos, rows))
            # report the same metric the single-run path reports (per-part
            # padded slots); the cross-run group actually pads once for
            # the whole group, so this is a (slight) upper bound
            waste_slots += cohort.pad_count(pos.size, _PAD) * bkt["level"]
        run.server.pad_waste.append(
            waste_slots / max(float(
                run.server._ensure_cohort_data().sizes[sel].sum()), 1.0))

    stacks, acc_parts = [], []
    row_map: Dict[int, List] = {i: [] for i in range(len(runs))}
    g_off = 0            # row offset into the concatenated round stack
    for g in groups.values():
        bkt, parts = g["bkt"], g["parts"]
        rows_cat = [rows for _, _, rows in parts]
        ids_cat = [np.full(rows.size, i) for i, _, rows in parts]
        off = 0
        for i, pos, rows in parts:
            row_map[i].append((pos, g_off + off + np.arange(rows.size)))
            off += rows.size
        n_pad = cohort.pad_count(off, _PAD)
        rows_cat.append(np.full(n_pad - off, bkt["null"]))
        ids_cat.append(np.zeros(n_pad - off, int))   # null rows: any params
        idx = jnp.asarray(np.concatenate(rows_cat))
        p = jax.tree.map(
            lambda l, r=jnp.asarray(np.concatenate(ids_cat)):
                jnp.take(l, r, axis=0), params_all)
        data = {f: jnp.take(a, idx, axis=0)
                for f, a in bkt["data"].items()}
        stacked_g, acc_g = cohort.cohort_train_multi(
            task, p, data, jnp.take(bkt["mask"], idx, axis=0), lr, epochs,
            batch_size)
        stacks.append(stacked_g)
        acc_parts.append(acc_g)
        g_off += n_pad

    big = cohort.merge_stacks(stacks)        # (g_off, ...) round stack
    acc_all = count_accuracy(jnp.concatenate(acc_parts))  # one sync
    for i, run in enumerate(runs):
        order = np.concatenate([pos for pos, _ in row_map[i]])
        gidx = np.concatenate([g for _, g in row_map[i]])
        inv = np.argsort(order, kind="stable")
        stacked = jax.tree.map(
            lambda l, r=jnp.asarray(gidx[inv]): jnp.take(l, r, axis=0),
            big)
        run.stacked, run.acc_local = run.server._apply_attacks(
            run.plan[2], stacked, acc_all[gidx][inv], t)


def _sweep_round_stacked(runs: List[_SweepRun], t: int,
                         sweep_ctrl: Optional[ctl.ControlState]
                         = None) -> None:
    """One round of every run, batched: one vmapped control-plane call for
    all runs' schedules (host numpy per run when ``sweep_ctrl`` is None),
    then — per task — one ``cohort_train_multi`` per (shared client
    arrays, size bucket) group, one ``cohort_eval`` per (task, seed) for
    the uploaded models, per-run FedAvg, one ``cohort_eval`` per (task,
    seed) for the global/watched-unit metrics, and one batched Eq. 1
    reputation update spanning every task's runs.

    All device-side reshuffling uses gathers (``jnp.take``) whose compile
    cache is keyed on *index shapes*, never value-dependent slicing — the
    eager-op cache stays warm across rounds even though every round
    selects different cohorts (value-keyed ``l[a:b]`` slicing recompiled a
    mini-program per new offset pair and dominated sweep wall-clock).
    """
    # -- phase A: schedules — one vmapped call for all runs ------------- #
    if sweep_ctrl is not None:
        with trace.span("schedule") as sp:
            _schedule_runs_stacked(runs, sweep_ctrl, t)
            if trace.enabled():
                sp.set(t=t, runs=len(runs))
    else:
        for run in runs:
            run.plan = run.server._schedule_round(t)

    # -- phase B: train — per task, one call per (arrays, bucket) group - #
    for group in _by_task(runs):
        with trace.span("train") as sp:
            _train_runs_stacked(group, t)
            if trace.enabled():
                sp.set(task=group[0].task.name, runs=len(group))

    # -- phase C: evaluate uploads — one call per (task, seed) ---------- #
    with trace.span("eval"):
        for group in _by_task_seed(runs):
            stacks = [run.stacked for run in group]
            masks = [run.server._eval_masks(run.plan[2], run.plan[2].size)
                     for run in group]
            counts = [run.plan[2].size for run in group]
            accs = _eval_stacked(group[0].server, stacks, masks, counts)
            for run, a in zip(group, accs):
                run.acc_test = a

    # -- phase C2: defense validation pass — the detector runs' uploads
    # AND their start-of-round global models scored on the held-out split
    # (per-UE unit masks) in one extra vmapped eval per (task, seed),
    # through the same machinery as phase C
    with trace.span("eval.validation"):
        for group in _by_task_seed(runs):
            det_runs = [r for r in group
                        if r.server.defense.detector is not None]
            if not det_runs:
                continue
            stacks, masks, counts = [], [], []
            for run in det_runs:
                n = run.plan[2].size
                vm = run.server._val_eval_masks(run.plan[2], n)
                stacks += [run.stacked,
                           cohort.broadcast_params(run.server.params, n)]
                masks += [vm, vm]
                counts += [n, n]
            accs = _eval_stacked(det_runs[0].server, stacks, masks, counts)
            for run, v, g in zip(det_runs, accs[::2], accs[1::2]):
                run.acc_val = np.stack([v, g])

    # -- phase D: per-run FedAvg (weights span the run's buckets) ------- #
    for run in runs:
        sel = run.plan[2]
        stacked_p = cohort.pad_stacked(run.stacked,
                                       cohort.pad_count(sel.size, _PAD))
        run.server._aggregate_cohort(sel, stacked_p)

    # -- phase E: global / watched-unit / attack-success — one call per
    # (task, seed). A watched run contributes three rows to the vmapped
    # eval: full-test unit accuracy, watched-unit accuracy, and the attack
    # success rate (unit labels relabelled to the attack's target over the
    # same watch mask); a watch-less run contributes only the accuracy
    # row — no wasted forward passes on rows whose result would be NaN
    # anyway. The task's loss metric (LM held-out CE) is one extra scalar
    # eval per run (free for loss-less tasks).
    with trace.span("eval.global"):
        for group in _by_task_seed(runs):
            ty = group[0].server._ey
            ones = jnp.ones_like(ty, jnp.float32)
            counts = [3 if run.scenario.watch else 1 for run in group]
            stacks = [cohort.broadcast_params(run.server.params, c)
                      for run, c in zip(group, counts)]
            masks, ys = [], []
            for run, c in zip(group, counts):
                if c == 3:
                    wm = jnp.asarray(run.watch_mask)
                    masks.append(jnp.stack([ones, wm, wm]))
                    ys.append(jnp.stack([ty, ty, run.ty_target]))
                else:
                    masks.append(ones[None])
                    ys.append(ty[None])
            accs = _eval_stacked(group[0].server, stacks, masks, counts,
                                 ys=ys)
            for run, c, a in zip(group, counts, accs):
                run.g_acc = float(a[0])
                run.g_loss = run.server._global_loss()
                watched = c == 3 and bool(run.watch_mask.any())
                run.src_acc = float(a[1]) if watched else float("nan")
                run.atk_succ = float(a[2]) if watched else float("nan")

    # -- phase F: detector penalties + reputation / staleness (one batched
    # Eq. 1 call) + logs
    if sweep_ctrl is not None:
        # state was pulled in phase A and nothing touched it since; update
        # every run's reputation/ages in one kernel call, push back, then
        # log per run against the servers' refreshed state. Detector
        # penalties (host numpy from the phase-C2 accuracies) ride into
        # the same Eq. 1 kernel call.
        with trace.span("finalize"):
            ctl.finalize_runs(sweep_ctrl, [run.plan[2] for run in runs],
                              [run.acc_local for run in runs],
                              [run.acc_test for run in runs],
                              penalties=[run.server._detect(run.plan[2],
                                                            run.acc_val)
                                         for run in runs])
            sweep_ctrl.push([run.server for run in runs])
            for run in runs:
                values, sched, sel, forced = run.plan
                run.server._log_round(t, values, sched, sel, forced,
                                      run.g_acc, run.src_acc,
                                      run.atk_succ, run.g_loss)
                run.plan = run.stacked = None
                run.acc_local = run.acc_test = None
                run.acc_val = None
    else:
        for run in runs:
            values, sched, sel, forced = run.plan
            run.server._finalize_round(t, values, sched, sel, forced,
                                       run.acc_local, run.acc_test,
                                       run.g_acc, run.src_acc,
                                       run.atk_succ, run.acc_val,
                                       run.g_loss)
            run.plan = run.stacked = run.acc_local = run.acc_test = None
            run.acc_val = None


def _by_task(runs: List[_SweepRun]) -> List[List[_SweepRun]]:
    groups: Dict[str, List[_SweepRun]] = {}
    for run in runs:
        groups.setdefault(run.task.name, []).append(run)
    return list(groups.values())


def _by_task_seed(runs: List[_SweepRun]) -> List[List[_SweepRun]]:
    groups: Dict[Tuple[str, int], List[_SweepRun]] = {}
    for run in runs:
        groups.setdefault((run.task.name, run.seed), []).append(run)
    return list(groups.values())


def _eval_stacked(server, stacks, masks, counts, ys=None) -> List[np.ndarray]:
    """One cohort_eval over the concatenated per-run stacks; split back.

    All stacks must come from runs sharing ``server``'s (task, seed) —
    the evaluation inputs/targets are the server's. ``ys`` (optional) —
    per-run (rows, U) unit-label arrays for metrics that score against
    relabelled targets (attack success); None keeps the shared test
    targets for every row."""
    n_tot = sum(counts)
    n_pad = cohort.pad_count(n_tot, _PAD)
    stacked = cohort.pad_stacked(cohort.merge_stacks(stacks), n_pad)
    mask = cohort.pad_stacked(cohort.merge_stacks(masks), n_pad)
    if ys is None:
        acc = count_accuracy(
            cohort.cohort_eval(server.task, stacked, server._ex,
                               server._ey, mask))
    else:
        y_rows = cohort.pad_stacked(cohort.merge_stacks(ys), n_pad)
        acc = count_accuracy(
            cohort.cohort_eval_rows(server.task, stacked, server._ex,
                                    y_rows, mask))
    out, off = [], 0
    for c in counts:
        out.append(acc[off:off + c])
        off += c
    return out


def averaged(policy, attack_pair, n_runs=3, **kw) -> Dict:
    """Paper reports the average of independent runs per setting —
    executed as one batched ``run_sweep`` over the seeds."""
    res = run_sweep([policy], seeds=range(n_runs),
                    attack_pairs=[attack_pair], **kw)
    return {"acc": res.mean_curve("acc").tolist(),
            "malicious_selected":
                res.mean_curve("malicious_selected").tolist(),
            "rep_gap": float(np.mean([r["final_reputation_honest"]
                                      - r["final_reputation_malicious"]
                                      for r in res.runs]))}
