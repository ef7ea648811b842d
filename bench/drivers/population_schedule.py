"""Scheduling rounds over a 10^6-UE candidate pool: R stacked runs
through ``core.population.prefilter_schedule_runs`` (the top-M prefilter,
the program's default kernel layout) and ``scatter_finalize``.

Set-up draws the pool and ``pool_rounds`` rounds of inputs (channels,
permutations, the cohort's accuracies) from the seed, adopts the state
into a ``PopulationState`` and drives its first ``warm_rounds`` rounds,
which compiles the kernel. The window continues round after round from
there, cycling the pooled inputs; the state keeps evolving. The harness
times each round. ``check`` replays every round's Eq. 1 and staleness
update on the reference side from the initial state and the selections
the window made, compares the final reputations and ages, and schedules
``check_rounds`` rounds drawn from the seed, plus the window's last one,
with the float64 reference from the replayed state.
"""
from __future__ import annotations

import numpy as np

import counts as cnt
import harness
from gen import population as gen
from reference import control as ref


class Session:
    def __init__(self, ctx):
        from repro.core import control as pctl
        from repro.core import population as ppop
        from repro.core.scheduler import POLICY_IDS

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        s_pool, s_check = harness.seeds(ctx.seed, 2)
        self.pool = p = gen.pool(cfg, tr, np.random.default_rng(s_pool))
        ctx.mark("pool")
        self.fc = fc = harness.feel_config(cfg)
        self._ppop = ppop
        r = len(p.policies)
        state = pctl.ControlState(
            policy_id=np.array([POLICY_IDS[x] for x in p.policies],
                               np.int32),
            sizes=p.sizes, divs=p.divs, r_min=p.r_min,
            reputations=p.reputations.copy(), ages=np.ones_like(p.sizes),
            cfg=fc)
        self.state = ppop.PopulationState.from_control(state, 0)
        self.rep0 = p.reputations.copy()
        self.omega = (np.full(r, fc.omega_rep), np.full(r, fc.omega_div))
        self.n_pool = len(p.gains)
        self.sels = []           # per round: R selected-id arrays
        self.held = {}           # round -> program outputs kept for check
        self.t = 0
        warm = int(tr["warm_rounds"])
        rng = np.random.default_rng(s_check)
        self.sample = set(int(x) for x in rng.choice(
            np.arange(warm, warm + int(tr["check_window_rounds"])),
            size=int(tr["check_rounds"]), replace=False))
        for _ in range(warm):
            self.step()
        ctx.mark("warm-up")

    def step(self) -> int:
        t, i = self.t, self.t % self.n_pool
        p = self.pool
        with self.ctx.annotate("bench.prefilter"):
            x, _, costs, values, forced, _ = \
                self._ppop.prefilter_schedule_runs(
                    self.state.control_view(t), p.gains[i], p.rand_rank[i],
                    *self.omega)
        sels = [np.flatnonzero(row) for row in x]
        with self.ctx.annotate("bench.finalize"):
            self._ppop.scatter_finalize(
                self.state, t, sels,
                [a[:s.size] for a, s in zip(p.acc_local[i], sels)],
                [a[:s.size] for a, s in zip(p.acc_test[i], sels)])
        self.sels.append(sels)
        out = (x, costs, values, forced)
        if t in self.sample:
            self.held[t] = out
        self.held["last"] = (t, out)
        self.t += 1
        return 1

    def finish(self):
        self.final = (self.state.reputations.copy(),
                      self.state.last_sel.copy())
        self.state = None

    def check(self):
        """Replay Eq. 1 and the ages over every round; schedule the held
        rounds with the reference at their pre-round state."""
        p, feel = self.pool, self.ctx.config["feel"]
        lim = self.ctx.traffic["limits"]
        last_t, last_out = self.held.pop("last")
        held = dict(self.held)
        held[last_t] = last_out
        rep = self.rep0.copy()
        last_sel = np.full(rep.shape, -1, np.int64)
        sel_diff = cost_diff = forced_diff = 0
        value_gap = 0.0
        for t, sels in enumerate(self.sels):
            i = t % self.n_pool
            if t in held:
                ages = (t - last_sel).astype(float)
                for r, pol in enumerate(p.policies):
                    want = _schedule_row(p, feel, i, r, pol, rep[r],
                                         ages[r], np.float64)
                    if self.ctx.control == "control":
                        got = _schedule_row(p, feel, i, r, pol, rep[r],
                                            ages[r], np.float32)
                    else:
                        x, costs, values, forced = held[t]
                        got = (np.flatnonzero(x[r]), bool(forced[r]),
                               costs[r], values[r])
                    sel_diff += len(np.setxor1d(want[0], got[0]))
                    forced_diff += int(want[1] != got[1])
                    cost_diff += int(np.sum(want[2] != got[2]))
                    value_gap = max(value_gap, float(np.max(np.abs(
                        want[3] - np.asarray(got[3], float)))))
            for r, s in enumerate(sels):
                rep[r] = ref.reputation_update(
                    rep[r], s, p.acc_local[i][r][:s.size],
                    p.acc_test[i][r][:s.size], feel)
                last_sel[r, s] = t
        rep_gap = float(np.max(np.abs(rep - self.final[0])))
        age_diff = int(np.sum(last_sel != self.final[1]))
        return [("selection_diff", float(sel_diff), lim["selection_diff"]),
                ("cost_diff", float(cost_diff), lim["cost_diff"]),
                ("forced_diff", float(forced_diff), lim["forced_diff"]),
                ("value_gap", value_gap, lim["value_gap"]),
                ("reputation_gap", rep_gap, lim["reputation_gap"]),
                ("age_diff", float(age_diff), lim["age_diff"])]

    def counts(self):
        """Bytes the prefilter kernel must move per round, from shapes."""
        r, n = self.pool.sizes.shape
        return cnt.prefilter_round(r, n, self.ctx.n_steps)


def _schedule_row(p, feel, i, r, policy, rep, ages, dtype):
    """(selected ids, forced, costs, values) of run ``r`` in pooled
    round ``i`` by the reference, in ``dtype``."""
    v = ref.values(rep, p.divs[r], p.sizes[r], ages, feel["gamma"],
                   feel["omega_rep"], feel["omega_div"], dtype=dtype)
    rmin = ref.min_rate(feel, p.sizes[r], p.cpu_hz[r], dtype=dtype)
    c = ref.costs(p.gains[i][r], rmin, feel, dtype=dtype)
    s, f = ref.schedule(policy, v, c, feel["n_ues"], feel["min_selected"],
                        gains=p.gains[i][r], rand_rank=p.rand_rank[i][r])
    return s, f, c, v
