"""Federated fine-tuning of an LM configuration served round after round
through ``FeelServer.run_round`` (vectorized engine, batched control
plane; the model is too large to stack, so the cohort streams).

Set-up makes the deployment from the seed (gen/tokens_lm.py: the fixed
device profiles dealt to UE ids, the token windows, the channel stream,
the initial model), balances the routers' selection biases on the seed's
windows by DeepSeek-V3's own rule (``balance_bias``: a random model routes
most tokens to a few experts, which no trained one does), builds one
server, and drives it through one whole
experiment of ``cycle_rounds`` rounds: that warms every shape the window
uses and is what ``check`` compares. The server is then put back to its
initial state and the window serves the same experiment again and again,
each pass from round 0, so that nothing in it compiles.

``check`` compares, against the plain reference (reference/moonlight.py,
float32 at ``highest``): round 0's uploads, UE by UE, each trained by the
reference from the same initial model on the same windows (the worst
leaf's gap over the reference's change, worst UE), and the round-0
aggregate, kept on the host, against the reference's FedAvg of its
uploads (the same measure over every leaf: an aggregate left unchanged
reads 1). Round 0's uploads (2.27 GB each) cannot all be kept: the
warm-up keeps a checksum of each one's bits, and the check trains each
UE again through the program's own ``client_train`` with the same
inputs and asserts the same bits (``upload_redo_diff``) before
comparing. The float64 control plane is replayed over the whole pass
from the program's own Eq. 1 inputs (reference/control.py):
selections and Eq. 9 costs exact, Eq. 3 values and reputations relative.
Every pass of the window must repeat the set-up's exactly.

``ctx.control == "model"`` runs the program with its parameters held in
bfloat16, one step below the configuration's float32 model.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

import counts_lm as cnt
import harness
from gen import tokens_lm
from reference import control as ctl
from reference import feel as ref_feel
from reference import moonlight as ref


def init_params(shapes, seed: int):
    """The initial model on the device, from the seed, in one jitted call,
    in the program's parameter layout (``shapes``): norm scales 1, the
    embedding 0.02 x and the selection biases 0.01 x a [-3, 3] truncated
    normal, every other weight a truncated normal over sqrt(fan-in), the
    fan-in being its second-last axis."""
    import jax
    import jax.numpy as jnp

    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def one(key, path, s):
        name = str(getattr(path[-1], "key", ""))
        if "norm" in name:
            return jnp.ones(s.shape, jnp.float32)
        z = jax.random.truncated_normal(key, -3.0, 3.0, s.shape, jnp.float32)
        if name == "embed":
            return 0.02 * z
        if name == "e_bias":
            return 0.01 * z
        return z / np.float32(np.sqrt(s.shape[-2]))

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree_util.tree_unflatten(
            tree, [one(k, p, s) for k, (p, s) in zip(keys, paths)])

    return make(jax.random.key(seed))


def balance_bias(spec: dict, params, windows: np.ndarray, rates):
    """The selection biases set by DeepSeek-V3's balance rule
    (arXiv:2412.19437 §2.1.2): after each pass over ``windows`` each
    expert layer's bias moves by the step's rate towards even loads,
    b_i += rate x sign(mean load - load_i), over all routed experts. The
    passes run the reference's forward (``expert_loads``). Returns the
    parameters with the final biases, and the busiest expert's load over
    the mean load (worst layer) before and after."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(params, bias, windows, rate):
        ld = jax.lax.map(
            lambda w: ref.expert_loads(spec, _with_bias(params, bias), w),
            windows).sum(0)
        return bias + rate * jnp.sign(ld.mean(-1, keepdims=True) - ld), \
            jnp.max(ld.max(-1) / ld.mean(-1))

    windows, bias, skew = jnp.asarray(windows), _bias(params), []
    for rate in list(rates) + [0.0]:
        bias, s = step(params, bias, windows, np.float32(rate))
        skew.append(s)
    return _with_bias(params, bias), float(skew[0]), float(skew[-1])


def _bias(params):
    return params["blocks"]["layers"][0]["mlp"]["e_bias"]


def _with_bias(params, bias):
    layer = params["blocks"]["layers"][0]
    mlp = dict(layer["mlp"], e_bias=bias)
    layers = (dict(layer, mlp=mlp),) + tuple(params["blocks"]["layers"][1:])
    return dict(params, blocks=dict(params["blocks"], layers=layers))


def _checksum(params):
    import jax
    import jax.numpy as jnp

    def leaf(x):
        bits = jax.lax.bitcast_convert_type(
            x, jnp.uint16 if x.dtype.itemsize == 2 else jnp.uint32)
        bits = bits.reshape(-1).astype(jnp.uint32)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                          jnp.sum(bits * (idx * jnp.uint32(2654435761)
                                          + jnp.uint32(1)),
                                  dtype=jnp.uint32)])
    return jnp.stack([leaf(x) for x in jax.tree.leaves(params)])


def checksum(params):
    """Two wrapping uint32 sums per leaf of its bits (plain, and weighted
    by position): equal bits give equal sums on any device."""
    import jax
    global _CHECKSUM
    if _CHECKSUM is None:
        _CHECKSUM = jax.jit(_checksum)
    return _CHECKSUM(params)


_CHECKSUM = None


def _gap_norms(got, want, w0):
    import jax
    import jax.numpy as jnp
    n = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    return jnp.stack([jnp.stack([n(a.astype(jnp.float32) - b), n(b - c)])
                      for a, b, c in zip(*(jax.tree.leaves(t) for t in
                                           (got, want, w0)))])


def spec(cfg: dict) -> dict:
    """The reference's numbers from the configuration file."""
    return {"n_heads": cfg["num_attention_heads"],
            "qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"], "rope_theta": cfg["rope_theta"],
            "rms_norm_eps": cfg["rms_norm_eps"],
            "n_routed_experts": cfg["router_experts"],
            "num_experts_per_tok": cfg["num_experts_per_tok"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "aux_weight": cfg["aux_weight"],
            "first_expert": cfg["first_expert"]}


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.core.attacks import as_scenario
        from repro.data.partition import ClientData
        from repro.data.tokens import TokenDataset
        from repro.federated import cohort
        from repro.federated.server import FeelServer
        from repro.federated.task import as_task

        self.ctx = ctx
        cfg, tr = ctx.config, ctx.traffic
        self.cycle = int(tr["cycle_rounds"])
        s_data, s_w, s_chan, s_srv = harness.seeds(ctx.seed, 4)
        self.data = d = tokens_lm.lm_data(cfg, np.random.default_rng(s_data))
        ctx.mark("data")
        mal = set(d.malicious.tolist())
        clients = [ClientData(ue_id=u, data=TokenDataset(w, d.domains[u]),
                              malicious=u in mal,
                              clean=(TokenDataset(d.clean[u], d.domains[u])
                                     if u in mal else None))
                   for u, w in enumerate(d.clients)]
        test = TokenDataset(d.test, np.zeros(len(d.test), np.int32))
        task = as_task(cfg["task"]["name"])
        m = task.model
        assert (m.d_model, m.vocab_size, m.n_layers, m.moe.n_held,
                m.moe.n_routed) == (cfg["hidden_size"], cfg["vocab_size"],
                                    cfg["num_hidden_layers"],
                                    cfg["n_routed_experts"],
                                    cfg["router_experts"]), "config drift"
        task = dataclasses.replace(task, batch_size=cfg["task"]["batch_size"],
                                   remat=cfg["task"]["remat"],
                                   seq=cfg["data"]["seq"])
        self.task = task
        self.server = srv = FeelServer(
            harness.feel_config(cfg), clients, test,
            np.random.default_rng(s_srv), policy=cfg["policy"],
            scenario=as_scenario(cfg["attack"]["scenario"]), task=task,
            lr=cfg["task"]["lr"])
        # the deployment's inputs come from the seed, not from the server
        shapes = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
        srv.params = None
        bal = cfg["balance"]
        rates = np.concatenate([np.full(n, r, np.float32)
                                for r, n in bal["rates"]])
        self.w0, *self.skew = balance_bias(
            spec(cfg), init_params(shapes, s_w),
            np.concatenate(d.clients)[:bal["windows"]], rates)
        print("balance: the busiest expert's load over the mean, worst "
              "layer, %.4f before, %.4f after" % tuple(self.skew),
              file=sys.stderr)
        ctx.mark("balance")
        if ctx.control == "model":
            self.w0 = jax.tree.map(lambda l: l.astype(jnp.bfloat16), self.w0)
        srv.params = self.w0
        srv.wireless.distances = d.distances
        srv.cpu_hz = d.cpu_hz
        self.s_chan = s_chan
        srv.rng.bit_generator.state = np.random.default_rng(
            s_chan).bit_generator.state
        self._initial = (srv.reputation.values.copy(), srv.ages.copy(),
                         srv.rng.bit_generator.state)
        ctx.mark("server")
        # warm-up: the first pass, kept for the check, with each round's
        # Eq. 9 costs and Eq. 1 inputs, round 0's client_train calls and
        # their uploads' checksums, and round 0's aggregate (on the host)
        self.first, self.costs, self.eq1 = [], [], []
        self.calls0 = []
        train = self.client_train = cohort.client_train

        def keep_upload(task, params, batches, lr, epochs):
            assert params is self.w0
            out = train(task, params, batches, lr, epochs)
            self.calls0.append(((task, batches, lr, epochs),
                                np.asarray(checksum(out[0]))))
            return out

        srv._train_cohort = self._keep_inputs(srv._train_cohort)
        srv._schedule_round = self._keep_costs(srv._schedule_round)
        for t in range(self.cycle):
            if t == 0:
                cohort.client_train = keep_upload
            try:
                log = srv.run_round(t)
            finally:
                cohort.client_train = train
            if t == 0:
                self.agg0 = jax.device_get(srv.params)
            self.first.append(_summary(log))
        del srv._train_cohort, srv._schedule_round
        ctx.mark("warm-up")
        self.rounds_of_cycle = [s["sel"] for s in self.first]
        self.passes, self.t = [], 0
        self._restore()

    def _keep_costs(self, schedule_round):
        def keep(t):
            out = schedule_round(t)
            self.costs.append(np.asarray(out[1].cost).copy())
            return out
        return keep

    def _keep_inputs(self, train_cohort):
        def keep(sel, t):
            out = train_cohort(sel, t)
            self.eq1.append((np.asarray(sel).copy(), np.asarray(out[2]),
                             np.asarray(out[3])))
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
        lim = self.ctx.traffic["limits"]
        return (self._replay_control(lim) + self._compare_training(lim)
                + [("repeat_mismatch",
                    float(_repeat_mismatch(self.first, self.passes)),
                    lim["repeat_mismatch"])])

    def _replay_control(self, lim):
        """The float64 control plane over the pass, from the program's own
        Eq. 1 inputs and the same channel stream."""
        cfg, d = self.ctx.config, self.data
        feel = cfg["feel"]
        sizes = np.array([len(w) for w in d.clients], float)
        divs = np.array([_gini(w, cfg["vocab_size"]) for w in d.clients])
        rep, ages = np.ones(len(sizes)), np.ones(len(sizes))
        channel = np.random.default_rng(self.s_chan)
        sel_diff = cost_diff = 0.0
        v_gap = r_gap = 0.0
        for p, c, (sel, a_loc, a_test) in zip(self.first, self.costs,
                                              self.eq1):
            plan = ref_feel.schedule_round(feel, rep, divs, sizes, ages,
                                           d.cpu_hz, d.distances, channel)
            sel_diff += len(np.setxor1d(p["sel"], plan["sel"])) + (
                p["forced"] != plan["forced"])
            cost_diff += float(np.sum(c != plan["costs"]))
            v_gap = max(v_gap, _rel(p["values"], plan["values"]),
                        _rel(p["objective"], plan["objective"]))
            rep = ctl.reputation_update(rep, sel, a_loc, a_test, feel)
            ages = ages + 1.0
            ages[sel] = 1.0
            r_gap = max(r_gap, _rel(p["rep"], rep))
        return [("selection_diff", sel_diff, lim["selection_diff"]),
                ("cost_diff", cost_diff, lim["cost_diff"]),
                ("value_gap", v_gap, lim["value_gap"]),
                ("reputation_gap", r_gap, lim["reputation_gap"])]

    def _compare_training(self, lim):
        """Round 0's uploads and aggregate against the reference, one UE at
        a time so that it fits: the program's upload trained again (bits
        checked against the warm-up's), the reference's from the same
        model and windows, their gap on the device, and the reference's
        upload folded into its FedAvg on the host, which the program's
        aggregate is compared with there."""
        import jax
        import jax.numpy as jnp
        cfg, d = self.ctx.config, self.data
        feel, task = cfg["feel"], cfg["task"]
        r = ref.Reference(spec(cfg))
        w0 = jax.tree.map(lambda l: l.astype(jnp.float32), self.w0)
        gap_norms = jax.jit(_gap_norms)
        sel = self.eq1[0][0].tolist()
        worst, redo = 0.0, 0

        def uploads():
            """The reference's uploads in selection order, each compared
            with the program's as it comes."""
            nonlocal worst, redo
            for u, (args, bits) in zip(sel, self.calls0):
                with jax.default_matmul_precision(cfg["precision"]["matmul"]):
                    got = self.client_train(args[0], self.w0, *args[1:])[0]
                redo += not np.array_equal(np.asarray(checksum(got)), bits)
                want = r.sgd(w0, d.clients[u], task["lr"],
                             feel["local_epochs"], task["batch_size"])
                g = np.asarray(gap_norms(got, want, w0), float)
                del got
                med = float(np.median(g[:, 1]))
                worst = max(worst, float(np.max(
                    g[:, 0] / np.maximum(g[:, 1], med))))
                yield jax.device_get(want)

        sizes = [len(d.clients[u]) for u in sel]
        agg = ref.fedavg(uploads(), sizes)
        self.calls0 = None
        gap = aggregate_gap(self.agg0, agg, w0)
        return [("upload_redo_diff", float(redo), lim["upload_redo_diff"]),
                ("upload_gap", worst, lim["upload_gap"]),
                ("aggregate_gap", gap, lim["aggregate_gap"])]

    def counts(self):
        """Operations and bytes of the window's rounds, from shapes."""
        cfg = self.ctx.config
        windows = np.array([len(w) for w in self.data.clients])
        per_round = [cnt.lm_round(cfg, windows[sel], len(self.data.test))
                     for sel in self.rounds_of_cycle]
        done = [per_round[i % self.cycle] for i in range(self.ctx.n_steps)]
        out: dict = {}
        for p in done:
            for k, v in p.items():
                out[k] = out.get(k, 0.0) + v
        return out


def _gini(windows: np.ndarray, vocab: int) -> float:
    """Eq. 2's Gini-Simpson index over a UE's token frequencies."""
    p = np.bincount(windows.reshape(-1), minlength=vocab) / windows.size
    return float(1.0 - np.sum(p * p))


def _summary(log) -> dict:
    return {"sel": np.sort(np.asarray(log.selected)), "forced": log.forced,
            "objective": log.objective, "values": log.values.copy(),
            "rep": log.reputations.copy(), "acc": log.global_acc,
            "loss": log.global_loss}


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
                    and a["acc"] == b["acc"] and a["loss"] == b["loss"])
            bad += not same
    return bad


def _rel(got, want) -> float:
    want = np.asarray(want, float)
    return float(np.max(np.abs(np.asarray(got, float) - want))
                 / max(float(np.max(np.abs(want))), 1e-300))


def aggregate_gap(got, want, w0) -> float:
    """The worst leaf's gap between an aggregate and the reference's, over
    the larger of the reference's change of that leaf from ``w0`` and the
    median leaf's change; in float64 on the host, leaf by leaf. An
    aggregate equal to ``w0`` reads 1."""
    import jax
    gap, chg = [], []
    for a, b, c in zip(*(jax.tree.leaves(t) for t in (got, want, w0))):
        b = np.asarray(b, np.float64)
        gap.append(np.linalg.norm(np.asarray(a, np.float64) - b))
        chg.append(np.linalg.norm(b - np.asarray(jax.device_get(c),
                                                  np.float64)))
    gap, chg = np.array(gap), np.array(chg)
    return float(np.max(gap / np.maximum(chg, np.median(chg))))
