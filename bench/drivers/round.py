"""One §V experiment served round after round through
``FeelServer.run_round`` (vectorized engine, batched control plane).

Set-up makes the deployment from the seed (gen/mnist.py: the fixed
device profiles dealt to UE ids, the data, the channel stream, the
initial model), builds one server, and drives it through one whole
experiment of ``cycle_rounds`` rounds: that warms every shape the window
uses and is what ``check`` compares. The server is then put back to its
initial state and the window serves the same experiment again and again,
each pass from round 0, so that every round in the window is a round
users run and nothing in it compiles. ``check`` follows the first
``check_rounds`` rounds with the float64 reference and asserts that
every pass of the window repeated the set-up's pass exactly.
"""
from __future__ import annotations

import numpy as np

import counts as cnt
import harness
from gen import mnist
from reference import feel as ref


class Session:
    def __init__(self, ctx):
        import jax
        from repro.core.attacks import as_scenario
        from repro.data.partition import ClientData
        from repro.data.synthetic_mnist import Dataset
        from repro.federated.server import FeelServer
        from repro.federated.task import as_task

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.cycle = int(tr["cycle_rounds"])
        self.n_check = int(tr["check_rounds"])
        s_data, s_w, s_chan, s_srv = harness.seeds(ctx.seed, 4)
        self.data = mnist.feel_data(cfg, np.random.default_rng(s_data))
        ctx.mark("data")
        d = self.data
        mal = set(d.malicious.tolist())
        clients = [ClientData(ue_id=u, data=Dataset(c.x, c.y),
                              malicious=u in mal,
                              clean=(Dataset(d.clean[u].x, d.clean[u].y)
                                     if u in mal else None))
                   for u, c in enumerate(d.clients)]
        fc = harness.feel_config(cfg)
        self.server = srv = FeelServer(
            fc, clients, Dataset(d.test.x, d.test.y),
            np.random.default_rng(s_srv), policy=cfg["policy"],
            scenario=as_scenario(cfg["attack"]["scenario"]),
            task=as_task(cfg["task"]["name"]))
        # the deployment's inputs come from the seed, not from the server
        self.w0 = harness.init_mlp(s_w, cfg["task"]["layers"])
        self.distances, self.cpu_hz = d.distances, d.cpu_hz
        srv.params = self.w0
        srv.wireless.distances = self.distances
        srv.cpu_hz = self.cpu_hz
        self.s_chan = s_chan
        srv.rng.bit_generator.state = np.random.default_rng(
            s_chan).bit_generator.state
        self._initial = (srv.reputation.values.copy(), srv.ages.copy(),
                         srv.rng.bit_generator.state)
        ctx.mark("server")
        # warm-up: the first pass, kept for the check, with the first
        # rounds' Eq. 9 costs and round 0's uploads as the program made them
        self.first, self.first_params, self.costs = [], [], []
        self.cohort_acc = []    # (round, malicious, acc_local, acc_test)
        srv._train_cohort = self._keep_uploads(srv._train_cohort)
        srv._schedule_round = self._keep_costs(srv._schedule_round)
        for t in range(self.cycle):
            log = srv.run_round(t)
            self.first.append(_summary(log))
            if t < self.n_check:
                self.first_params.append(jax.device_get(srv.params))
        del srv._train_cohort, srv._schedule_round
        ctx.mark("warm-up")
        self.rounds_of_cycle = [s["sel"] for s in self.first]
        self.passes, self.t = [], 0
        self._restore()

    def _keep_costs(self, schedule_round):
        def keep(t):
            out = schedule_round(t)
            if t < self.n_check:
                self.costs.append(np.asarray(out[1].cost).copy())
            return out
        return keep

    def _keep_uploads(self, train_cohort):
        def keep(sel, t):
            out = train_cohort(sel, t)
            mal = np.isin(sel, self.data.malicious)
            self.cohort_acc.extend(zip([t] * len(sel), mal.tolist(),
                                       np.asarray(out[2]).tolist(),
                                       np.asarray(out[3]).tolist()))
            if t == 0:
                import jax
                n = len(sel)
                self.uploads0 = (np.asarray(sel), jax.tree.map(
                    lambda l: np.asarray(l)[:n], jax.device_get(out[0])))
            return out
        return keep

    def _restore(self):
        srv = self.server
        rep, ages, rng_state = self._initial
        srv.params = self.w0
        srv.reputation.values[:] = rep
        srv.ages[:] = ages
        srv.rng.bit_generator.state = rng_state
        srv.logs, srv.pad_waste = [], []
        self.t = 0

    def step(self) -> int:
        if self.t == self.cycle:
            self.passes.append([_summary(l) for l in self.server.logs])
            self._restore()
        self.server.run_round(self.t)
        self.t += 1
        return 1

    def finish(self):
        self.passes.append([_summary(l) for l in self.server.logs])
        self.server = None

    def check(self):
        cfg = self.ctx.config
        w0 = {k: np.asarray(v, float) for k, v in self.w0.items()}
        out = ref.follow(cfg, self.data, w0, self.distances, self.cpu_hz,
                         np.random.default_rng(self.s_chan), self.n_check)
        lim = self.ctx.traffic["limits"]
        prog = [dict(p, costs=c) for p, c in
                zip(self.first[:self.n_check], self.costs)]
        if self.ctx.control == "control":
            # the control plane's control: round 0's schedule by the
            # reference in float32 in the program's place
            rep, ages, divs, sizes = ref.initial_state(cfg, self.data)
            plan = ref.schedule_round(
                cfg["feel"], rep, divs, sizes, ages, self.cpu_hz,
                self.distances, np.random.default_rng(self.s_chan),
                dtype=np.float32)
            prog[0].update(values=plan["values"], costs=plan["costs"],
                           objective=plan["objective"])
        return compare_rounds(prog, self.first_params, out, w0, lim) + \
            compare_uploads(self.uploads0, out[0], w0, lim) + [
                ("repeat_mismatch", float(_repeat_mismatch(self.first,
                                                           self.passes)),
                 lim["repeat_mismatch"])]

    def counts(self):
        """Operations and bytes of the window's rounds, from shapes."""
        cfg = self.ctx.config
        d = self.data
        sizes = np.array([len(c) for c in d.clients])
        units = cnt.claimed_units(d)
        watched = int(np.sum(d.test.y == cfg["attack"]["flip"][0]))
        per_round = [cnt.feel_round(cfg, sizes[sel], units[sel], len(d.test),
                                    watched)
                     for sel in self.rounds_of_cycle]
        done = [per_round[i % self.cycle]
                for i in range(self.ctx.n_steps)]
        return cnt.total(done)


def _summary(log) -> dict:
    return {"sel": np.sort(np.asarray(log.selected)), "forced": log.forced,
            "objective": log.objective, "values": log.values.copy(),
            "rep": log.reputations.copy(), "acc": log.global_acc}


def _repeat_mismatch(first, passes) -> int:
    """Rounds of the window that did not repeat the set-up's pass."""
    bad = 0
    for p in passes:
        for a, b in zip(first, p):
            same = (np.array_equal(a["sel"], b["sel"])
                    and a["forced"] == b["forced"]
                    and a["objective"] == b["objective"]
                    and np.array_equal(a["values"], b["values"])
                    and np.array_equal(a["rep"], b["rep"])
                    and a["acc"] == b["acc"])
            bad += not same
    return bad


def _leaf_gap(got, want, w0) -> float:
    """Worst leaf's norm of (got - want) over the norm of want's change
    since ``w0``, or over the median leaf's change where larger."""
    moved = {k: float(np.linalg.norm(want[k] - w0[k])) for k in w0}
    med = float(np.median(list(moved.values())))
    return max(float(np.linalg.norm(np.asarray(got[k], float) - want[k]))
               / max(moved[k], med) for k in w0)


def compare_uploads(prog, ref0, w0, lim):
    """Round 0's uploads, one UE at a time, both sides trained from the
    same initial model: the median UE's model gap. A median over the
    cohort is steady where a worst case is not: rounding differences that
    push a ReLU input across zero part one UE's training from the
    reference by up to ~1e-2 whatever the precision (PERF.md)."""
    sel, stack = prog
    pos = {int(u): i for i, u in enumerate(ref0["sel"])}
    gaps = [_leaf_gap({k: v[j] for k, v in stack.items()},
                      ref0["uploads"][pos[int(u)]], w0)
            for j, u in enumerate(sel) if int(u) in pos]
    return [("upload_gap", float(np.median(gaps)), lim["upload_gap"])]


def _rel(got, want) -> float:
    """Largest gap of ``got`` from ``want`` over the largest |want|."""
    want = np.asarray(want, float)
    return float(np.max(np.abs(np.asarray(got, float) - want))
                 / max(float(np.max(np.abs(want))), 1e-300))


def compare_rounds(prog, prog_params, refs, w0, lim):
    """The compared numbers of ``check_rounds`` rounds, worst over rounds:
    UEs selected by one side only (and forced flags that differ), Eq. 9
    costs that differ, the objective's relative gap, the largest
    reputation gap, the global accuracy's gap, and the worst leaf's model
    gap: the norm of (program - reference) over the norm of the
    reference's change since the initial model, or over the median leaf's
    change where larger. Round 0's Eq. 3 values and objective depend on
    the initial state alone, not on training: ``value_gap_r0`` is their
    relative gap, the worse of the two."""
    sel = cost = obj = rep = acc = model = 0.0
    for p, q, r in zip(prog, prog_params, refs):
        sel += len(np.setxor1d(p["sel"], r["sel"])) + (p["forced"]
                                                       != r["forced"])
        cost += float(np.sum(np.asarray(p["costs"]) != r["costs"]))
        obj = max(obj, _rel(p["objective"], r["objective"]))
        rep = max(rep, float(np.max(np.abs(p["rep"] - r["rep"]))))
        acc = max(acc, abs(p["acc"] - r["acc"]))
        model = max(model, _leaf_gap(q, r["params"], w0))
    v0 = max(_rel(prog[0]["values"], refs[0]["values"]),
             _rel(prog[0]["objective"], refs[0]["objective"]))
    return [("selection_diff", sel, lim["selection_diff"]),
            ("cost_diff", cost, lim["cost_diff"]),
            ("value_gap_r0", v0, lim["value_gap_r0"]),
            ("objective_gap", obj, lim["objective_gap"]),
            ("reputation_gap", rep, lim["reputation_gap"]),
            ("accuracy_gap", acc, lim["accuracy_gap"]),
            ("model_gap", model, lim["model_gap"])]
