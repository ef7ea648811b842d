"""FEEL server (Alg. 1): per-round schedule -> local train -> evaluate ->
reputation update -> FedAvg aggregate.

The server sees only what the paper allows it to see: dataset *metadata*
(size, symbol histogram for the diversity index, staleness), self-reported
local accuracies, uploaded models evaluated on the public test set, and
channel state. It never touches raw client data.

The model/data pair is a pluggable ``FeelTask`` (federated/task.py): the
server orchestrates Alg. 1 over the task's jit-static train/eval steps and
quality metadata, so the paper's MNIST MLP and the federated LM task run
through the exact same scheduling, threat-model and defense planes.

Two execution engines implement Alg. 1 lines 9-14:

    "vectorized" (default) — the cohort engine (federated/cohort.py): the
        round's scheduled UEs are split into ``n_buckets`` size buckets
        (``data.partition.bucket_levels`` — each bucket padded only to its
        own quantized max_samples level, reclaiming the ~2x padding waste
        of a single global pad), each bucket trains in one jitted vmapped
        step, the per-bucket stacks are merged back into selection order,
        and evaluation + aggregation run once on the merged stack — a
        single ``fedavg_stacked`` call whose weights span all buckets.
        Per-round padding overhead is recorded in ``FeelServer.pad_waste``
        (padded train slots / real samples).
    "loop" — the original sequential per-client loop, kept as the
        correctness oracle (tests/test_cohort.py pins the engines to the
        same accuracy curve).

The vectorized engine streams the cohort instead where one model copy
per padded cohort row would not fit (``cohort.streams``: the model's
bytes x rows against the device's memory; the MNIST MLP never streams).
Each scheduled UE then trains, is scored for Eq. 1 and is folded into a
running FedAvg sum, in selection order, and the global model is replaced
once at the end of the round (``_run_cohort_streamed``). Robust defenses,
the validation detector and model-space attacks need the stacked uploads
and raise there.

The padded device-resident client arrays live in a ``CohortData`` that can
be shared by several servers running on the same (dataset, partition) —
the sweep driver (federated/simulation.py::run_sweep) builds it once per
(task, seed, data-attack) and hands it to every run on that partition.

Threat model: the server takes an ``core.attacks.AttackScenario``; its
model/report components apply to the merged cohort stack through ONE
masked ``tree_map`` (``_apply_attacks``) on the scenario's activity
schedule — the pre-refactor per-malicious-client dispatch loop survives
as ``_apply_attacks_loop``, pinned bit-equal (DESIGN.md §8).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FeelConfig
from repro.core import attacks as atk
from repro.core import defenses as dfs
from repro.core import (ReputationTracker, WirelessModel, adaptive_weights,
                        data_quality_value, diversity_index, dqs_schedule,
                        top_value_schedule)
from repro.core import control as ctl
from repro.core import population
from repro.core.scheduler import (Schedule, best_channel_schedule,
                                  max_count_schedule, random_schedule)
from repro.data.partition import (ClientData, pad_clients,
                                  pad_clients_bucketed, sample_arrays)
from repro.federated import cohort
from repro.federated.aggregation import (fedavg, fedavg_stacked,
                                         normalize_weights)
from repro.federated.task import FeelTask, as_task
from repro.models.common import count_accuracy
from repro.obs import trace


@dataclasses.dataclass
class RoundLog:
    round: int
    selected: np.ndarray
    global_acc: float
    n_malicious_selected: int
    objective: float
    values: np.ndarray
    reputations: np.ndarray
    # task-defined global loss metric (the LM task's held-out per-token
    # cross-entropy; NaN for tasks without one, e.g. the MNIST MLP)
    global_loss: float = float("nan")
    source_acc: float = float("nan")   # accuracy on the attacked class
    # attack success rate: fraction of watched source-class test samples
    # the global model classifies as the attack's TARGET class (NaN when
    # the scenario has no watched (source, target) pair)
    attack_success: float = float("nan")
    # honest-vs-malicious reputation separation after this round's Eq. 1
    # update (NaN when the run has no malicious UEs)
    rep_gap: float = float("nan")
    # True when the schedule was degenerate (no UE met the deadline) and the
    # server forced the highest-value UE. Problem (8) had no feasible point,
    # so ``objective`` is reported as 0.0 for forced rounds — the forced
    # UE's V_k must not be credited to the scheduler.
    forced: bool = False
    # defense-plane metrics (core/defenses.py, DESIGN.md §9): what the
    # round's DefensePolicy did — norm-clipped / aggregation-rejected
    # upload counts, validation-detector flags, and detection
    # precision/recall against the ground-truth malicious mask (metrics
    # only; the defense itself never sees the truth)
    n_clipped: int = 0
    n_rejected: int = 0
    n_flagged: int = 0
    det_precision: float = float("nan")
    det_recall: float = float("nan")


@dataclasses.dataclass
class CohortData:
    """Device-resident padded client layout for the vectorized engine.

    ``buckets[b]`` holds one size bucket's stacked per-sample array pytree
    (``data`` — the task's ``sample_arrays`` fields) and validity mask,
    laid out as [real client rows | clean twin rows | one all-zero "null
    client" row at index ``null``] — cohort-size padding gathers the null
    row for a strict training no-op. The twin rows hold the PRE-POISON
    data of clients whose partition baked in a data attack
    (``ClientData.clean``): a round-scheduled (intermittent / colluding)
    data attack gathers a malicious UE's twin row in its off rounds, so
    the schedule gates data attacks without re-partitioning or a second
    device layout. Built once per (dataset, partition) and shareable
    across servers (policies) — ``run_sweep`` exploits this to amortise
    padding + host-to-device transfer across a whole sweep.
    """
    buckets: List[Dict]       # data pytree/mask device arrays, level, null
    bucket_of: np.ndarray     # (K,) bucket index per client
    row_of: np.ndarray        # (K,) row within the client's bucket arrays
    clean_row_of: np.ndarray  # (K,) clean-twin row, -1 when none exists
    mask_dev: jax.Array       # (K+1, U) per-UE eval unit masks + null row
    sizes: np.ndarray         # (K,) true sample counts


def build_cohort_data(clients: List[ClientData], test_mask_arr: np.ndarray,
                      batch_size: int = 50, pad_to: Optional[int] = None,
                      n_buckets: int = 3) -> CohortData:
    """Bucket, pad and device-place the clients (see CohortData).

    test_mask_arr — (K, U) float {0,1} per-UE evaluation unit masks (the
    server restricts Eq. 1's acc_test to the symbols a UE claims to hold).
    """
    bucketed = pad_clients_bucketed(clients, n_buckets=n_buckets,
                                    multiple_of=batch_size, pad_to=pad_to)
    K = len(clients)
    bucket_of = np.full(K, -1)
    row_of = np.full(K, -1)
    clean_row_of = np.full(K, -1)
    zrow = lambda a: np.concatenate([a, np.zeros_like(a[:1])])
    buckets = []
    for b, (ids, pd) in enumerate(bucketed):
        # loop-engine parity contract: the loop's plain sgd epoch DROPS a
        # tail batch (nb = n // batch_size) while the masked engine would
        # train it, so a non-dividing batch_size must fail loudly
        assert not np.any(pd.sizes % batch_size), (
            "vectorized engine requires batch_size to divide every "
            "client dataset size (the loop oracle drops tail batches)")
        bucket_of[ids] = b
        row_of[ids] = np.arange(ids.size)
        arrays = {f: [a] for f, a in pd.arrays.items()}
        mask_parts = [pd.mask]
        # clean twins share the poisoned row's size (data attacks preserve
        # sample counts), so they land in the same bucket level
        twin_ids = [int(i) for i in ids if clients[i].clean is not None]
        if twin_ids:
            tw = pad_clients(
                [dataclasses.replace(clients[i], data=clients[i].clean,
                                     clean=None) for i in twin_ids],
                multiple_of=batch_size, pad_to=pd.max_samples)
            clean_row_of[twin_ids] = ids.size + np.arange(len(twin_ids))
            for f in arrays:
                arrays[f].append(tw.arrays[f])
            mask_parts.append(tw.mask)
        buckets.append({
            "data": {f: jnp.asarray(zrow(np.concatenate(parts)))
                     for f, parts in arrays.items()},
            "mask": jnp.asarray(zrow(np.concatenate(mask_parts))),
            "level": pd.max_samples, "null": ids.size + len(twin_ids)})
    return CohortData(
        buckets=buckets, bucket_of=bucket_of, row_of=row_of,
        clean_row_of=clean_row_of,
        mask_dev=jnp.asarray(zrow(test_mask_arr)),
        sizes=np.array([c.size for c in clients], float))


class FeelServer:
    """policy: 'dqs' | 'random' | 'best_channel' | 'max_count' | 'top_value'.
    'top_value' reproduces §V-B.1 (pure data-quality selection, no wireless).

    task: a ``federated.task.FeelTask`` (or registry name; None defers to
    ``cfg.task``) — the model/data pair the round trains. The task owns
    every model-specific step (init, masked local SGD, unit prediction,
    the loop oracle) and the quality metadata definition (histogram,
    Gini-Simpson diversity); the server only orchestrates Alg. 1 over it.
    ``lr``/``batch_size`` default to the task's protocol values when None.

    engine: 'vectorized' | 'loop' (see module docstring).
    control: 'batched' | 'host' — the control plane (values -> Eq. 9 costs
    -> Alg. 2 selection -> Eq. 1 reputation). 'batched' (default) runs it
    as the jitted vmapped kernel of core/control.py (one run: R=1);
    'host' is the sequential numpy oracle (tests/test_control.py pins
    the parity) — mirroring the engine='loop' pattern of the data plane.
    n_buckets: number of max_samples size buckets for the vectorized
    engine (1 = the old single global pad; 2-3 reclaim the padding waste).
    scenario: an ``core.attacks.AttackScenario`` (or registry name) — the
    threat model. Its data component must already be baked into
    ``clients`` by the partition; the server applies the model/report
    components on the scenario's activity schedule and tracks the
    watched (source, target) metrics. Supersedes the legacy
    ``model_poison``/``lie_boost`` knobs (kept for back-compat and
    normalized into an equivalent scenario).
    defense: a ``core.defenses.DefensePolicy`` (or registry name) — the
    server-side counter-measure plane (DESIGN.md §9). Its robust
    aggregator replaces/augments FedAvg in ``_aggregate_cohort`` (both
    engines); its validation detector adds one extra vmapped eval per
    round and feeds a trust penalty into Eq. 1 in ``_finalize_round``.
    None defers to ``cfg.defense`` (default ``"none"``).

    Outside ``run_round``, ``AsyncFeelEngine`` composes the round-phase
    methods, and the benchmark's drivers and faults wrap
    ``_schedule_round``, ``_schedule_round_batched``, ``_train_cohort``
    and ``_aggregate_uploads``.
    """

    _N_BUCKET = 8   # cohort sizes are padded to a multiple of this with
                    # zero-weight null clients (shape-stable compiles)

    def __init__(self, cfg: FeelConfig, clients: List[ClientData],
                 test, rng: np.random.Generator,
                 policy: str = "dqs", lr: Optional[float] = None,
                 adaptive_omega: bool = False, lie_boost: float = 0.0,
                 watch_class: Optional[int] = None, model_poison=None,
                 engine: str = "vectorized",
                 batch_size: Optional[int] = None,
                 pad_to: Optional[int] = None, n_buckets: int = 3,
                 cohort_data: Optional[CohortData] = None,
                 control: str = "batched",
                 scenario: Optional[atk.AttackScenario] = None,
                 defense=None, task: Optional[FeelTask] = None):
        assert engine in ("vectorized", "loop"), engine
        assert control in ("batched", "host"), control
        self.control = control
        self.cfg = cfg
        self.task = as_task(task if task is not None else cfg.task)
        self.clients = clients
        self.test = test
        self.rng = rng
        self.policy = policy
        self.lr = self.task.default_lr if lr is None else lr
        self.adaptive_omega = adaptive_omega
        # threat model: either an explicit AttackScenario (data attacks
        # are already baked into ``clients`` by the partition; the server
        # applies the model/report components on the schedule) or the
        # legacy knobs, normalized into an equivalent scenario
        if scenario is not None:
            assert (model_poison is None and not lie_boost
                    and watch_class is None), \
                "scenario supersedes the legacy model_poison/lie_boost/" \
                "watch_class knobs (set AttackScenario.watch instead)"
            self.scenario = atk.as_scenario(scenario)
        else:
            self.scenario = atk.AttackScenario(
                "legacy",
                model=(atk.ModelAttack(scale=model_poison.scale)
                       if model_poison is not None else None),
                report=atk.ReportAttack(lie_boost) if lie_boost else None)
        # metrics watch pair: explicit watch_class wins (legacy callers),
        # else the scenario's (source, target)
        watch = self.scenario.watch
        self.watch_class = (watch_class if watch_class is not None
                            else (watch[0] if watch else None))
        self.watch_target = watch[1] if watch else None
        self.engine = engine
        self.batch_size = (self.task.batch_size if batch_size is None
                           else batch_size)
        self.pad_to = pad_to        # stable cohort shape across seeds
        self.n_buckets = n_buckets

        # candidate width: N = cfg.n_population (== n_ues in the legacy
        # regime, > n_ues under a population cut, DESIGN.md §12) — every
        # per-UE control array spans the full candidate population while
        # cfg.n_ues stays the Eq. 9 bandwidth budget
        assert len(clients) == cfg.n_population, \
            (len(clients), cfg.n_population)
        self.wireless = WirelessModel(cfg, rng)
        self.reputation = ReputationTracker(cfg)
        self.params = self.task.init_params(
            jax.random.PRNGKey(int(rng.integers(1 << 31))))
        self.ages = np.ones(cfg.n_population)   # rounds since last selected
        self.cpu_hz = rng.uniform(cfg.cpu_hz_min, cfg.cpu_hz_max,
                                  cfg.n_population)
        self.sizes = np.array([c.size for c in clients], float)
        # malicious-set layout for the activity schedule: rank within the
        # malicious set (by ue_id) drives the colluding round-robin
        self._mal_mask = np.array([c.malicious for c in clients])
        mal_ids = np.flatnonzero(self._mal_mask)
        self._mal_rank = np.full(cfg.n_population, -1)
        self._mal_rank[mal_ids] = np.arange(mal_ids.size)
        # stale free-riders replay the global model from ``staleness``
        # rounds ago; keep exactly that much history (None otherwise)
        st = self.scenario.model.staleness if self.scenario.model else 0
        self._param_hist = (collections.deque(maxlen=st + 1) if st > 0
                            else None)
        # UEs report their quality metadata once (task-defined: label
        # histograms for MNIST, token histograms for the LM); poisoned data
        # is what the UE *believes*, so the report reflects the attack —
        # including for round-scheduled data attacks, whose one-time report
        # is the poisoned histogram (metadata is not re-reported per round).
        self.divs = np.array([self.task.gini(c.data) for c in clients])
        self.histograms = [self.task.histogram(c.data) for c in clients]
        # Interpretation decision (DESIGN.md): Eq. 1's acc_test is evaluated
        # on the test UNITS restricted to the symbols a UE claims to hold
        # (classes / vocabulary) — otherwise the reputation punishes
        # honest-but-skewed (non-IID) UEs exactly as hard as poisoners,
        # which contradicts the paper's Fig. 2.
        unit_labels = self.task.unit_labels(test)
        self._test_masks = [np.isin(unit_labels, np.flatnonzero(h > 0))
                            for h in self.histograms]
        self._test_mask_arr = np.stack(self._test_masks).astype(np.float32)
        self._ex = self.task.eval_inputs(test)
        self._ey = self.task.unit_targets(test)
        # defense plane (core/defenses.py, DESIGN.md §9): robust
        # aggregation replaces/augments FedAvg in _aggregate_cohort, the
        # validation detector scores every upload on a held-out split
        # (the first n_val test rows) and its anomaly feeds Eq. 1 as a
        # trust penalty in _finalize_round
        self.defense = dfs.as_defense(defense if defense is not None
                                      else cfg.defense)
        det = self.defense.detector
        if det is not None:
            # validation split: the units of the first n_val test rows,
            # restricted per UE to the symbols it claims to hold (the same
            # masking argument as Eq. 1's acc_test, DESIGN.md §2 — an
            # unmasked score cannot tell an honest non-IID UE from a noise
            # UE). The detector's novelty over Eq. 1 is using the ABSOLUTE
            # cohort-relative level of this score, not a report gap.
            self._n_val = min(det.n_val, len(test.y))
            val_rows = self.task.unit_rows(test) < self._n_val
            self._val_masks = [m & val_rows for m in self._test_masks]
            arr = self._test_mask_arr * val_rows.astype(np.float32)[None]
            self._val_mask_dev = jnp.asarray(
                np.concatenate([arr, np.zeros_like(arr[:1])]))
        self._def_stats = dfs.DefenseStats()   # refreshed every round
        # vectorized-engine client layout: injected (sweep-shared) or built
        # lazily on first use (see CohortData)
        self._cohort_data = cohort_data
        # batched-control state (R=1), built lazily
        self._ctrl: Optional[ctl.ControlState] = None
        # async-engine busy mask (federated/async_engine.py, DESIGN.md §13):
        # when set, these UEs have an upload in flight and must not be
        # re-scheduled — their channel gains are zeroed for the draw (an
        # arithmetic mask, NOT an RNG op: the host stream of record is
        # untouched), which makes Eq. 9 infeasible so packing skips them.
        # None in synchronous mode (every round's cohort fully lands).
        self.unavailable: Optional[np.ndarray] = None
        self.pad_waste: List[float] = []   # per-round padded/real sample ratio
        self.logs: List[RoundLog] = []
        # streamed path: per-(UE, clean twin) device batches and per-UE
        # Eq. 1 unit masks, placed on first use
        self._stream_batches: Dict[Tuple[int, bool], list] = {}
        self._stream_masks: Dict[int, jax.Array] = {}

    # ------------------------------------------------------------------ #
    def _omega(self, round_t: int) -> Tuple[float, float]:
        """(w_rep, w_div) for this round — annealed under adaptive omega."""
        if self.adaptive_omega:
            return adaptive_weights(round_t, self.cfg.rounds, self.cfg)
        return self.cfg.omega_rep, self.cfg.omega_div

    def _values(self, round_t: int) -> np.ndarray:
        cfg = self.cfg
        I = diversity_index(self.divs, self.sizes, self.ages, cfg.gamma)
        return data_quality_value(self.reputation.values, I, cfg,
                                  omega=self._omega(round_t))

    def _mask_unavailable(self, gains: np.ndarray) -> np.ndarray:
        """Zero the gains of busy UEs (async in-flight uploads): a zero
        gain makes Eq. 9 infeasible (cost K+1), so every channel-aware
        packing skips them. Channel-blind policies (top_value, the forced
        rewrite) are post-filtered by the async engine instead."""
        if self.unavailable is not None:
            gains = np.where(self.unavailable, 0.0, gains)
        return gains

    def _schedule(self, values: np.ndarray) -> Schedule:
        cfg = self.cfg
        gains = self._mask_unavailable(self.wireless.draw_channels().gains)
        t_train = self.wireless.train_time(self.sizes, self.cpu_hz)
        costs = self.wireless.cost(gains, t_train)
        if self.policy == "dqs":
            return dqs_schedule(values, costs, cfg)
        if self.policy == "random":
            return random_schedule(values, costs, cfg, self.rng)
        if self.policy == "best_channel":
            return best_channel_schedule(values, costs, cfg, gains)
        if self.policy == "max_count":
            return max_count_schedule(values, costs, cfg)
        if self.policy == "top_value":
            # selection ignores the channel, but the logged Schedule.cost
            # must report the real Eq. 9 costs (accounting bugfix)
            return top_value_schedule(values, costs, cfg, cfg.min_selected)
        raise KeyError(self.policy)

    # ------------------------------------------------------------------ #
    # Per-cohort execution engines: both return the round's uploads
    # WITHOUT aggregating — (uploads, weights, acc_local, acc_test,
    # acc_val) where ``uploads`` is a params list (loop) or the padded
    # merged stack (vectorized) and ``weights`` the aligned FedAvg sample
    # counts. ``run_round`` aggregates immediately (synchronous Alg. 1);
    # the async engine banks them and aggregates on its trigger with
    # staleness-discounted weights (federated/async_engine.py).
    # ------------------------------------------------------------------ #
    def _run_cohort_loop(self, sel: np.ndarray, t: int):
        cfg = self.cfg
        # round-scheduled data attacks: an inactive malicious UE trains on
        # its clean twin this round (the loop-engine mirror of the
        # vectorized engine's twin-row gather, see CohortData)
        active = self.scenario.schedule.active(t, self._mal_mask,
                                               self._mal_rank)
        reports = []
        for k in sel:
            c = self.clients[k]
            if c.clean is not None and not active[k]:
                c = dataclasses.replace(c, data=c.clean, clean=None)
            reports.append(self.task.local_train(
                c, self.params, cfg.local_epochs, self.lr,
                self.batch_size))
        acc_local = np.array([r.acc_local for r in reports])
        params_list = [r.params for r in reports]

        # attack application, per client — the loop engine IS the host
        # oracle the masked batched path is pinned against
        scn = self.scenario
        ref = self._attack_ref_params()
        mal = active[sel]
        if scn.model is not None:
            params_list = [scn.model.apply_loop(self.params, p, ref)
                           if m else p for p, m in zip(params_list, mal)]
        if scn.report is not None:
            acc_local = scn.report.apply(acc_local, mal)

        # server-side evaluation of every uploaded model (Alg. 1 line 14)
        # on the units of the symbols each UE claims to hold (see
        # __init__ note)
        acc_test = np.empty(len(reports))
        for i, (p, k) in enumerate(zip(params_list, sel)):
            acc_test[i] = self.task.eval_units_loop(p, self.test,
                                                    self._test_masks[k])

        # defense plane, host-oracle side: per-client validation pass
        # (upload AND start-of-round global model on each UE's masked val
        # split) + compressed-matrix robust aggregation (core/defenses.py)
        acc_val = None
        if self.defense.detector is not None:
            acc_val = np.zeros((2, len(params_list)))
            for i, (p, k) in enumerate(zip(params_list, sel)):
                m = self._val_masks[k]
                if m.any():
                    acc_val[0, i] = self.task.eval_units_loop(
                        p, self.test, m)
                    acc_val[1, i] = self.task.eval_units_loop(
                        self.params, self.test, m)
        weights = np.asarray([r.n_samples for r in reports], float)
        return params_list, weights, acc_local, acc_test, acc_val

    def _ensure_cohort_data(self) -> CohortData:
        # resident on device once; per-round cohort stacking is then a
        # device-side gather instead of a host copy + transfer. Only the
        # device copy is kept — a host copy would double the padded
        # dataset's footprint for the server's lifetime.
        if self._cohort_data is None:
            self._cohort_data = build_cohort_data(
                self.clients, self._test_mask_arr,
                batch_size=self.batch_size, pad_to=self.pad_to,
                n_buckets=self.n_buckets)
        return self._cohort_data

    def _cohort_parts(self, sel: np.ndarray, t: int):
        """Split round ``t``'s cohort per size bucket.

        Yields ``(bucket, positions_in_sel, row_ids)``. A malicious UE
        whose data attack is INACTIVE in round t (round-scheduled
        scenarios) maps to its clean twin row instead of its poisoned row
        (see CohortData) — for always-on schedules the mapping is the
        identity, bit-for-bit. The row ids are padded to a multiple of
        _N_BUCKET with the bucket's null client (mask all-zero -> training
        no-op, weight 0 downstream), so rounds with new cohort sizes reuse
        the compiled per-bucket step instead of re-tracing — the exact
        pathology this engine replaces.
        """
        cd = self._ensure_cohort_data()
        rows_of = cd.row_of
        if np.any(cd.clean_row_of >= 0):
            active = self.scenario.schedule.active(t, self._mal_mask,
                                                   self._mal_rank)
            use_clean = ~active & (cd.clean_row_of >= 0)
            rows_of = np.where(use_clean, cd.clean_row_of, cd.row_of)
        for b, bkt in enumerate(cd.buckets):
            pos = np.flatnonzero(cd.bucket_of[sel] == b)
            if pos.size == 0:
                continue
            rows = rows_of[sel[pos]]
            n_pad = cohort.pad_count(pos.size, self._N_BUCKET)
            rows = np.concatenate(
                [rows, np.full(n_pad - pos.size, bkt["null"], rows.dtype)])
            yield bkt, pos, rows

    def _active_malicious(self, sel: np.ndarray, t: int) -> np.ndarray:
        """(len(sel),) bool — scheduled UEs whose malicious behaviour is
        ACTIVE in round t (the scenario's activity schedule gates the
        model/report components; data attacks are baked into the data)."""
        return self.scenario.schedule.active(
            t, self._mal_mask, self._mal_rank)[sel]

    def _attack_ref_params(self):
        """Reference params for the model attack: the current global
        model, or — for stale free-riders — the global model from
        ``staleness`` rounds ago. Must be called exactly once per round
        (it advances the history)."""
        if self._param_hist is None:
            return self.params
        self._param_hist.append(self.params)     # start-of-round params
        return self._param_hist[0]

    def _apply_attacks(self, sel, stacked, acc_local, t):
        """Model poisoning + dishonest reporting on the merged stack:
        ONE masked ``tree_map`` over the malicious rows
        (``ModelAttack.apply_stacked``) — no per-malicious-client
        dispatch. The stack may carry pad rows past ``sel``; they pass
        through. ``acc_local`` None leaves the report to
        ``_apply_report`` (the vectorized engine reads the counts after
        the uploads' eval). ``_apply_attacks_loop`` keeps the replaced
        per-client ``.at[i].set`` loop as the parity oracle
        (tests/test_attacks.py pins them bit-for-bit equal)."""
        scn = self.scenario
        with trace.span("attack.apply") as sp:
            ref = self._attack_ref_params()
            mal = self._active_malicious(sel, t)
            if scn.model is not None and mal.any():
                rows = jax.tree.leaves(stacked)[0].shape[0]
                stacked = scn.model.apply_stacked(
                    stacked, self.params, np.pad(mal, (0, rows - mal.size)),
                    ref)
            acc_local = self._apply_report(sel, acc_local, t)
            if trace.enabled():
                sp.set(scenario=scn.name, n_active=int(mal.sum()))
        return stacked, acc_local

    def _apply_attacks_loop(self, sel, stacked, acc_local, t):
        """The pre-refactor O(n_malicious) dispatch loop — one
        ``.at[i].set`` tree_map per malicious client. Kept ONLY as the
        parity oracle for ``_apply_attacks``."""
        scn = self.scenario
        ref = self._attack_ref_params()
        mal = self._active_malicious(sel, t)
        if scn.model is not None and mal.any():
            for i in np.flatnonzero(mal):
                poisoned = scn.model.apply_loop(
                    self.params, cohort.unstack(stacked, int(i)), ref)
                stacked = jax.tree.map(
                    lambda l, p, i=int(i): l.at[i].set(p), stacked, poisoned)
        return stacked, self._apply_report(sel, acc_local, t)

    def _apply_report(self, sel, acc_local, t):
        """Dishonest reporting on the host's local accuracies (None passes
        through)."""
        scn = self.scenario
        if scn.report is None or acc_local is None:
            return acc_local
        return scn.report.apply(acc_local, self._active_malicious(sel, t))

    def _cohort_weights(self, sel: np.ndarray, stacked_p) -> np.ndarray:
        """FedAvg sample-count weights for a padded merged stack: real rows
        carry their dataset size, pad rows weight 0."""
        cd = self._ensure_cohort_data()
        weights = np.zeros(jax.tree.leaves(stacked_p)[0].shape[0])
        weights[:sel.size] = cd.sizes[sel]
        return weights

    def _aggregate_cohort(self, sel: np.ndarray, stacked_p,
                          weights: np.ndarray) -> None:
        """ONE fedavg_stacked call whose weights span all buckets — or,
        under a defense with a robust aggregator, the batched defended
        aggregation over the padded (K_pad, P) flattened-update layout
        (core/defenses.py, DESIGN.md §9; stats land in ``_def_stats``
        for the round's log). ``weights`` are the sample-count weights,
        or the async engine's staleness-discounted ones."""
        agg = self.defense.aggregator
        with trace.span("defense.aggregate") as sp:
            if trace.enabled():
                sp.set(defense=self.defense.name, n=int(sel.size))
            if agg is None:
                self.params = fedavg_stacked(stacked_p, weights)
                self._def_stats = dfs.DefenseStats()
            else:
                self.params, self._def_stats = dfs.aggregate_stacked(
                    agg, stacked_p, weights, self.params, sel.size,
                    self.cfg.n_malicious)

    def _run_cohort_vectorized(self, sel: np.ndarray, t: int):
        """The stacked round as a few programs and one host read: one
        gather of every bucket's rows, ``cohort_train`` per bucket, one
        ``assemble_cohort`` (merge into selection order, pad to a stable
        row count, gather the eval masks), ``cohort_eval``; the local
        and test counts then come back together."""
        cfg = self.cfg
        cd = self._ensure_cohort_data()
        n = sel.size
        n_pad = cohort.pad_count(n, self._N_BUCKET)
        parts = list(self._cohort_parts(sel, t))
        # cohort row i <- row take[i] of the buckets' concatenated stacks;
        # pad rows point past its end, and the merge fills them with zeros
        # (null rows: all-zero eval mask, weight 0)
        starts = np.cumsum([0] + [rows.size for _, _, rows in parts])
        take = np.full(n_pad, starts[-1])
        for (_, pos, _), start in zip(parts, starts):
            take[pos] = start + np.arange(pos.size)
        mask_rows = np.concatenate(
            [sel, np.full(n_pad - n, len(self.clients), sel.dtype)])
        rows, take, mask_rows = jax.device_put(
            ([r for _, _, r in parts], take, mask_rows))
        batches = cohort.gather_buckets(
            tuple((b["data"], b["mask"]) for b, _, _ in parts), tuple(rows))
        stacks, counts, pad_slots = [], [], 0
        for (bkt, pos, rows_b), (data, ms) in zip(parts, batches):
            with trace.span("train.bucket") as bsp:
                probe0 = trace.compiles()
                stacked_b, acc_b = cohort.cohort_train(
                    self.task, self.params, data, ms, self.lr,
                    cfg.local_epochs, self.batch_size)
                if trace.enabled():
                    bsp.set(level=int(bkt["level"]), rows=int(rows_b.size),
                            real=int(pos.size),
                            compiled=trace.compiles() > probe0)
                    trace.observe("train.bucket_occupancy",
                                  pos.size / rows_b.size)
            stacks.append(stacked_b)
            counts.append(acc_b)
            pad_slots += rows_b.size * bkt["level"]
        stacked_p, local_counts, eval_masks = cohort.assemble_cohort(
            tuple(stacks), tuple(counts), take, cd.mask_dev, mask_rows)
        self.pad_waste.append(
            float(pad_slots) / max(float(cd.sizes[sel].sum()), 1.0))
        if trace.enabled():
            trace.observe("train.pad_waste", self.pad_waste[-1])

        stacked_p, _ = self._apply_attacks(sel, stacked_p, None, t)

        with trace.span("eval") as esp:
            probe0 = trace.compiles()
            test_counts = cohort.cohort_eval(self.task, stacked_p, self._ex,
                                             self._ey, eval_masks)
            local_counts, test_counts = jax.device_get(
                (local_counts, test_counts))
            trace.counter_inc("data.host_reads")
            if trace.enabled():
                esp.set(rows=int(n_pad),
                        compiled=trace.compiles() > probe0)
        acc_local = self._apply_report(sel, count_accuracy(local_counts)[:n],
                                       t)
        acc_test = count_accuracy(test_counts)[:n]
        acc_val = self._eval_validation(stacked_p, sel)
        return (stacked_p, self._cohort_weights(sel, stacked_p),
                acc_local, acc_test, acc_val)

    def _val_eval_masks(self, sel: np.ndarray, n_pad: int) -> jax.Array:
        """(n_pad, T) per-UE class-masked validation-split eval masks."""
        idx = jnp.asarray(np.concatenate(
            [sel, np.full(n_pad - sel.size, len(self.clients), sel.dtype)]))
        return jnp.take(self._val_mask_dev, idx, axis=0)

    def _eval_validation(self, stacked_p, sel: np.ndarray
                         ) -> Optional[np.ndarray]:
        """Defense detector: the ONE extra vmapped eval — every uploaded
        model AND the start-of-round global model scored on the held-out
        validation split restricted to each UE's claimed classes (same
        ``cohort_eval`` machinery; (2, n): uploads row, global row)."""
        if self.defense.detector is None:
            return None
        with trace.span("eval.validation") as sp:
            n = sel.size
            n_pad = jax.tree.leaves(stacked_p)[0].shape[0]
            vm = self._val_eval_masks(sel, n_pad)
            both = cohort.merge_stacks(
                [stacked_p, cohort.broadcast_params(self.params, n_pad)])
            acc = count_accuracy(
                cohort.cohort_eval(self.task, both, self._ex, self._ey,
                                   jnp.concatenate([vm, vm])))
            trace.counter_inc("data.host_reads")
            if trace.enabled():
                sp.set(rows=int(2 * n_pad))
            return np.stack([acc[:n], acc[n_pad:n_pad + n]])

    # -- streamed cohort (models too large to stack) -------------------- #
    def _streams(self, n: int) -> bool:
        """The one place the round picks its path: the stacked engine
        unless a model copy per padded row would not fit."""
        nbytes = sum(l.nbytes for l in jax.tree.leaves(self.params))
        return cohort.streams(nbytes, cohort.pad_count(n, self._N_BUCKET))

    def _check_streamable(self) -> None:
        if self.defense.name != "none":
            raise ValueError(
                f"defense {self.defense.name!r} needs the stacked uploads; "
                "the streamed cohort keeps none (model too large to stack)")
        if self.scenario.model is not None:
            raise ValueError(
                f"scenario {self.scenario.name!r} attacks in model space, "
                "which needs the stacked uploads; the streamed cohort keeps "
                "none (model too large to stack)")
        if self.cfg.mode != "sync":
            raise ValueError("the streamed cohort aggregates as it trains; "
                             "mode='async' needs the stacked uploads")

    def _client_batches(self, k: int, clean: bool) -> list:
        """UE ``k``'s (data, mask) device batches of ``batch_size``
        samples (the last zero-padded, mask 0), its clean twin's where
        ``clean``."""
        key = (k, clean)
        if key not in self._stream_batches:
            c = self.clients[k]
            arrays = sample_arrays(c.clean if clean else c.data)
            n, b = c.size, self.batch_size
            out = []
            for i in range(0, n, b):
                m = np.zeros(b, np.float32)
                m[:min(b, n - i)] = 1.0
                d = {f: np.zeros((b,) + a.shape[1:], a.dtype)
                     for f, a in arrays.items()}
                for f, a in arrays.items():
                    d[f][:min(b, n - i)] = a[i:i + b]
                out.append(jax.device_put((d, m)))
            self._stream_batches[key] = out
        return self._stream_batches[key]

    def _client_mask(self, k: int) -> jax.Array:
        if k not in self._stream_masks:
            self._stream_masks[k] = jnp.asarray(self._test_mask_arr[k])
        return self._stream_masks[k]

    def _run_cohort_streamed(self, sel: np.ndarray, t: int):
        """The round's cohort one UE at a time, in selection order: local
        training from the global model (span ``train.client``), Eq. 1's
        score of the upload (``eval.upload``), and the weighted upload
        added into the FedAvg sum. Returns the sum in the uploads' place
        with weights None (``_aggregate_uploads`` installs it); the small
        outputs of every UE come back in one host read."""
        self._check_streamable()
        cfg = self.cfg
        active = self.scenario.schedule.active(t, self._mal_mask,
                                               self._mal_rank)
        w = np.asarray(normalize_weights(self.sizes[sel]))
        width = int(np.prod(jax.tree.leaves(
            sample_arrays(self.clients[0].data))[0].shape[1:]))
        total, small, tokens = None, [], 0
        for i, k in enumerate(sel.tolist()):
            c = self.clients[k]
            batches = self._client_batches(
                k, c.clean is not None and not bool(active[k]))
            with trace.span("train.client") as sp:
                up, counts, stats = cohort.client_train(
                    self.task, self.params, batches, self.lr,
                    cfg.local_epochs)
                if trace.enabled():
                    sp.set(ue=k, steps=len(batches) * cfg.local_epochs)
            with trace.span("eval"):
                with trace.span("eval.upload"):
                    score = cohort.upload_eval(self.task, up, self._ex,
                                               self._ey, self._client_mask(k))
            total = (cohort.scaled(up, w[i]) if total is None
                     else cohort.add_scaled(total, up, w[i]))
            del up
            small.append((counts, score, stats))
            tokens += c.size * width * cfg.local_epochs
        small = jax.device_get(small)
        acc_local = count_accuracy(np.stack([s[0] for s in small]))
        acc_test = count_accuracy(np.stack([s[1] for s in small]))
        mal = active[sel]
        if self.scenario.report is not None:
            acc_local = self.scenario.report.apply(acc_local, mal)
        if trace.enabled():
            trace.counter_inc("cohort.streamed", len(sel))
            trace.counter_inc("train.tokens", tokens)
            pairs = float(sum(s[2]["pairs"] for s in small))
            if pairs:
                trace.counter_inc("moe.held_pairs", pairs)
                trace.counter_inc("moe.held_load_max",
                                  float(max(s[2]["load_max"] for s in small)))
        return total, None, acc_local, acc_test, None

    # ------------------------------------------------------------------ #
    # Round phases. ``run_round`` composes them; so does the async engine
    # (federated/async_engine.py), on its own trigger.
    # ------------------------------------------------------------------ #
    def _schedule_round(self, t: int):
        """Alg. 1 lines 4-8: values -> schedule -> participant set.

        Returns (values, sched, sel, forced). ``forced`` marks a degenerate
        channel draw: no UE met the deadline, so the server forces the
        single highest-value UE to keep training alive — but problem (8)
        had no feasible point, so the round's *objective* is 0.0 (the
        forced UE's V_k is not credited to the scheduler).
        """
        with trace.span("schedule") as sp:
            if self.control == "batched":
                out = self._schedule_round_batched(t)
            else:
                out = self._schedule_round_host(t)
            if trace.enabled():
                values, sched, sel, forced = out
                sp.set(t=t, n_selected=int(sel.size), forced=bool(forced))
            return out

    def _schedule_round_host(self, t: int):
        """Sequential numpy oracle path of ``_schedule_round``."""
        values = self._values(t)
        sched = self._schedule(values)
        sel = sched.selected
        forced = False
        if sel.size == 0:
            # Rewrite the schedule so the logged selection vector describes
            # the actual participant set, not the empty one.
            k = int(np.argmax(values))
            sel = np.array([k])
            x = np.zeros(values.size, bool)
            x[k] = True
            alpha = np.zeros(values.size)
            alpha[k] = 1.0          # the forced UE gets the whole band
            sched = Schedule(x=x, alpha=alpha, cost=sched.cost,
                             value=sched.value)
            forced = True
        return values, sched, sel, forced

    # -- batched control plane (core/control.py) ----------------------- #
    def _control_state(self) -> ctl.ControlState:
        if self._ctrl is None:
            self._ctrl = ctl.ControlState.from_servers([self])
        return self._ctrl

    def _draw_control_inputs(self) -> Tuple[np.ndarray, np.ndarray]:
        """(gains, rand_rank) for one round, drawn from THIS server's RNG
        in the oracle's order: channel draw first, then — only for the
        ``random`` policy — the packing permutation. The batched kernel is
        a deterministic function of these host draws, which is what keeps
        every run's stream identical to its sequential twin."""
        gains = self._mask_unavailable(self.wireless.draw_channels().gains)
        if self.policy == "random":
            rand_rank = np.argsort(
                self.rng.permutation(self.cfg.n_population))
        else:
            rand_rank = np.arange(self.cfg.n_population)
        return gains, rand_rank

    def _schedule_round_batched(self, t: int):
        st = self._control_state()
        st.pull([self])
        gains, rand_rank = self._draw_control_inputs()
        w_rep, w_div = self._omega(t)
        if self.cfg.population is not None:
            # population cut: schedule through the top-M prefilter
            # (schedule-preserving by certificate — identical selection,
            # core/population.py / DESIGN.md §12)
            x, alpha, costs, values, forced, _ = \
                population.prefilter_schedule_runs(
                    st, gains[None], rand_rank[None],
                    np.array([w_rep]), np.array([w_div]))
        else:
            x, alpha, costs, values, forced = ctl.schedule_runs(
                st, gains[None], rand_rank[None],
                np.array([w_rep]), np.array([w_div]))
        sched = Schedule(x=x[0], alpha=alpha[0], cost=costs[0],
                         value=values[0])
        return values[0], sched, sched.selected, bool(forced[0])

    def _train_cohort(self, sel: np.ndarray, t: int):
        """(uploads, weights, acc_local, acc_test, acc_val) of the round's
        cohort — no aggregation (see the engines' section comment);
        ``acc_val`` is None unless the defense has a validation detector."""
        with trace.span("train") as sp:
            if trace.enabled():
                sp.set(t=t, engine=self.engine, n=int(sel.size))
            if self.engine == "vectorized":
                if self._streams(sel.size):
                    return self._run_cohort_streamed(sel, t)
                return self._run_cohort_vectorized(sel, t)
            return self._run_cohort_loop(sel, t)

    def _aggregate_uploads(self, sel: np.ndarray, uploads,
                           weights: np.ndarray) -> None:
        """Aggregate a cohort's uploads into ``self.params`` — the single
        write point for both engines and both execution modes. ``uploads``
        is whatever ``_train_cohort`` returned (params list / padded
        stack); ``weights`` the aligned FedAvg weights, possibly
        staleness-discounted by the async engine. The streamed path hands
        the finished FedAvg sum with weights None."""
        if weights is None:
            with trace.span("defense.aggregate"):
                self.params = uploads
                self._def_stats = dfs.DefenseStats()
            return
        if self.engine == "vectorized":
            self._aggregate_cohort(sel, uploads, weights)
            return
        agg = self.defense.aggregator
        with trace.span("defense.aggregate") as sp:
            if trace.enabled():
                sp.set(defense=self.defense.name, n=int(sel.size))
            if agg is None:
                self.params = fedavg(uploads, list(weights))
                self._def_stats = dfs.DefenseStats()
            else:
                self.params, self._def_stats = dfs.aggregate_host(
                    agg, uploads, np.asarray(weights, float), self.params,
                    self.cfg.n_malicious)

    def _detect(self, sel: np.ndarray, acc_val) -> Optional[np.ndarray]:
        """Validation-detector phase: anomaly scores -> Eq. 1 trust
        penalties (returned, aligned with ``sel``) + detection metrics
        against the ground-truth malicious mask (merged into
        ``_def_stats`` for the round's log — metrics only)."""
        det = self.defense.detector
        if det is None or acc_val is None or sel.size == 0:
            return None
        with trace.span("defense.detect") as sp:
            anomaly = det.anomaly(acc_val)
            flags = anomaly > 0
            st = self._def_stats
            st.n_flagged = int(flags.sum())
            st.det_precision, st.det_recall = dfs.detection_stats(
                flags, self._mal_mask[sel])
            if trace.enabled():
                sp.set(n_flagged=st.n_flagged)
            return det.weight * anomaly

    def _finalize_round(self, t: int, values, sched, sel, forced,
                        acc_local, acc_test, g_acc, src_acc,
                        atk_succ=float("nan"), acc_val=None,
                        g_loss=float("nan")) -> RoundLog:
        """Alg. 1 lines 15-16 + logging: detector penalty, reputation,
        staleness, RoundLog."""
        with trace.span("finalize"):
            penalty = self._detect(sel, acc_val)
            if self.control == "batched":
                st = self._control_state()
                st.pull([self])
                ctl.finalize_runs(st, [sel], [acc_local], [acc_test],
                                  penalties=[penalty])
                st.push([self])
            else:
                self.reputation.update(sel, acc_local, acc_test,
                                       penalty=penalty)
                # ages: selected reset, others grow (staleness of Eq. 2)
                self.ages += 1.0
                self.ages[sel] = 1.0
            ds = self._def_stats
            log = RoundLog(
                round=t, selected=sel, global_acc=g_acc,
                global_loss=g_loss,
                n_malicious_selected=sum(self.clients[k].malicious
                                         for k in sel),
                objective=0.0 if forced else sched.objective(),
                values=values.copy(),
                reputations=self.reputation.values.copy(),
                source_acc=src_acc, attack_success=atk_succ,
                rep_gap=atk.reputation_gap(self.reputation.values,
                                           self._mal_mask),
                forced=forced,
                n_clipped=ds.n_clipped, n_rejected=ds.n_rejected,
                n_flagged=ds.n_flagged, det_precision=ds.det_precision,
                det_recall=ds.det_recall)
            self.logs.append(log)
            return log

    def _global_metrics(self) -> Tuple[float, float, float, float]:
        """(global unit accuracy, global loss, watch accuracy, attack
        success rate) of the current params — task-defined (NaN loss for
        tasks without one). Attack success is the fraction of watched
        source units classified as the scenario's TARGET symbol (NaN
        without a watched pair)."""
        with trace.span("eval.global"):
            return self.task.global_metrics(self.params, self.test,
                                            self._ex, self._ey,
                                            self.watch_class,
                                            self.watch_target)

    def run_round(self, t: int) -> RoundLog:
        with trace.span("round") as sp:
            if trace.enabled():
                sp.set(t=t, policy=self.policy, engine=self.engine,
                       control=self.control)
            values, sched, sel, forced = self._schedule_round(t)
            uploads, weights, acc_local, acc_test, acc_val = \
                self._train_cohort(sel, t)
            self._aggregate_uploads(sel, uploads, weights)
            g_acc, g_loss, src_acc, atk_succ = self._global_metrics()
            return self._finalize_round(t, values, sched, sel, forced,
                                        acc_local, acc_test, g_acc,
                                        src_acc, atk_succ, acc_val,
                                        g_loss)

    def run(self, rounds: Optional[int] = None) -> List[RoundLog]:
        assert self.cfg.mode == "sync", \
            "mode='async' runs through federated.async_engine.AsyncFeelEngine"
        for t in range(rounds or self.cfg.rounds):
            self.run_round(t)
        return self.logs
