"""Mixture-of-experts MLP with sort-based dropless-with-capacity dispatch.

Tokens are argsorted by assigned expert, gathered into a dense ``(E, C, d)``
buffer (capacity ``C = top_k * T * cf / E``) and processed with grouped
einsums, so compiled FLOPs are proportional to *active* parameters — unlike a
dense all-experts formulation. Overflowing tokens are dropped (GShard-style).
Shared experts (Qwen2-MoE / DeepSeek-V3) are a single fused SwiGLU of width
``n_shared * d_ff_expert`` applied to every token.

The expert dimension E shards over the ``model`` mesh axis (and over
``data``x``model`` for the 256-expert DeepSeek config); GSPMD inserts the
dispatch all-to-alls.

``dropless`` configs (Moonlight-16B-A3B) take the expert-parallel
layer ``moe_held_apply`` instead: it routes every token over all
``n_routed`` experts (softmax, or sigmoid noaux_tc scoring) and computes,
dropless, only the part of the layer that the ``n_held`` experts this
chip holds add — tokens sorted by held expert and pushed through
``jax.lax.ragged_dot``.

Both layers return ``(y, stats)``: ``stats`` holds ``balance``, the
balance loss that enters the training loss, ``pairs``, the token-expert
pairs the held experts computed, and ``load_max``, the most any one held
expert computed (both 0 in the capacity layer). ``add_aux`` combines the
stats of two layers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, dtype_of, swiglu_apply, swiglu_init
from repro.sharding.ctx import constrain


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def moe_init(key, cfg, d_model=None):
    m = cfg.moe
    d = d_model or cfg.d_model
    f = m.d_ff_expert
    E, H = m.n_routed, m.n_held          # routed over, held here
    ks = jax.random.split(key, 5)
    dt = dtype_of(cfg)
    p = {
        "router": dense_init(ks[0], (d, E), jnp.float32),
        "wg": dense_init(ks[1], (H, d, f), dt),
        "wu": dense_init(ks[2], (H, d, f), dt),
        "wd": dense_init(ks[3], (H, f, d), dt, fan_in=f),
    }
    if m.n_shared:
        p["shared"] = swiglu_init(ks[4], d, m.n_shared * f, dt)
    if m.scoring == "sigmoid":
        # the selection bias, seeded and never trained by gradient
        # (DeepSeek-V3 moves it by a balance rule of its own)
        p["e_bias"] = 0.01 * jax.random.truncated_normal(
            jax.random.fold_in(ks[0], 1), -3.0, 3.0, (E,), jnp.float32)
    return p


def capacity(T: int, cfg) -> int:
    m = cfg.moe
    c = int(m.capacity_factor * T * m.top_k / m.n_routed)
    return max(_round_up(c, 8), 8)


def moe_apply(cfg, p, x):
    """x (..., d) -> (y (..., d), stats): the capacity layer, or
    ``moe_held_apply`` where the config is dropless."""
    m = cfg.moe
    if m.dropless:
        return moe_held_apply(cfg, p, x)
    orig_shape = x.shape
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    T = x2.shape[0]
    G = m.dispatch_groups
    if G > 1 and T % G == 0 and T // G >= m.top_k:
        y, aux = _moe_grouped(cfg, p, x2.reshape(G, T // G, d))
        return y.reshape(orig_shape), aux_stats(aux)
    E, K = m.n_routed, m.top_k
    C = capacity(T, cfg)

    logits = (x2.astype(jnp.float32) @ p["router"])               # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                           # (T,K)
    if m.normalize_gates:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch/GShard) ----
    me = probs.mean(0)                                            # (E,)
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce) * m.router_aux_weight

    # ---- sort-based dispatch ----
    flat_e = idx.reshape(-1)                                      # (T*K,)
    order = jnp.argsort(flat_e)                                   # stable
    se = flat_e[order]
    tok = order // K
    counts = jnp.zeros(E, jnp.int32).at[flat_e].add(1)
    offs = jnp.cumsum(counts) - counts                            # exclusive
    pos_in_e = jnp.arange(T * K) - offs[se]
    valid = pos_in_e < C
    dest = jnp.where(valid, se * C + pos_in_e, E * C)             # sentinel row

    gathered = constrain(x2[tok], "moe_gather")                   # (T*K, d)
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(gathered)
    # hillclimb lever (no-op without an active sharding ctx): pin the dispatch
    # buffer to the expert layout so GSPMD routes tokens with all-to-all
    # instead of replicating the scatter (see EXPERIMENTS.md §Perf)
    buf = constrain(buf[: E * C].reshape(E, C, d), "moe_disp")

    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"]))
         * jnp.einsum("ecd,edf->ecf", buf, p["wu"]))
    h = constrain(h, "moe_hidden")
    y = jnp.einsum("ecf,efd->ecd", h, p["wd"]).reshape(E * C, d)
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], 0)       # sentinel

    w = gate.reshape(-1)[order].astype(x.dtype)
    back = constrain(y[dest], "moe_gather")                       # (T*K, d)
    out = jnp.zeros((T, d), x.dtype).at[tok].add(back * w[:, None])

    if m.n_shared:
        out = out + swiglu_apply(p["shared"], x2)
    return out.reshape(orig_shape), aux_stats(aux)


def _moe_grouped(cfg, p, x3):
    """Group-local dispatch (§Perf iteration 3). x3 (G, T, d).

    Sort/scatter are per-group (G aligns with the data axis, so they never
    cross shards); the only cross-shard movement is resharding the dense
    (G, E, C, d) buffer to the expert layout before the grouped GEMM —
    an all-to-all, which is the textbook MoE dispatch pattern.
    """
    m = cfg.moe
    G, T, d = x3.shape
    E, K = m.n_routed, m.top_k
    C = capacity(T, cfg)

    logits = x3.astype(jnp.float32) @ p["router"]                 # (G,T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                           # (G,T,K)
    if m.normalize_gates:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    me = probs.mean((0, 1))
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / (G * T * K)
    aux = E * jnp.sum(me * ce) * m.router_aux_weight

    flat_e = idx.reshape(G, T * K)
    order = jnp.argsort(flat_e, axis=1)                           # per group
    se = jnp.take_along_axis(flat_e, order, 1)
    tok = order // K                                              # (G,T*K)
    counts = jax.vmap(
        lambda fe: jnp.zeros(E, jnp.int32).at[fe].add(1))(flat_e)
    offs = jnp.cumsum(counts, 1) - counts                         # (G,E)
    pos = jnp.arange(T * K)[None] - jnp.take_along_axis(offs, se, 1)
    valid = pos < C
    dest = jnp.where(valid, se * C + pos, E * C)                  # (G,T*K)

    gathered = constrain(jnp.take_along_axis(x3, tok[..., None], 1),
                         "moe_local")                             # (G,T*K,d)
    buf = jax.vmap(
        lambda dst, g: jnp.zeros((E * C + 1, d), x3.dtype).at[dst].set(g)
    )(dest, gathered)
    # scatter output stays in the group-local layout; the switch to the
    # expert layout below is then a standalone all-to-all reshard
    buf = constrain(buf, "moe_local")
    buf = buf[:, : E * C].reshape(G, E, C, d)
    # two single-axis reshards (XLA lowers each to one all-to-all; a combined
    # two-axis move degenerates to replication — see §Perf iteration log)
    buf = constrain(buf, "moe_disp4a")    # model: d -> E
    buf = constrain(buf, "moe_disp4")     # data: G -> E

    h = (jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["wg"]))
         * jnp.einsum("gecd,edf->gecf", buf, p["wu"]))
    h = constrain(h, "moe_hidden4")
    y = constrain(jnp.einsum("gecf,efd->gecd", h, p["wd"]), "moe_out4")
    # reshard back to the group-local layout before the local un-permute
    y = constrain(y, "moe_disp4a")
    y = constrain(y.reshape(G, E * C, d), "moe_local")
    y = jnp.concatenate([y, jnp.zeros((G, 1, d), y.dtype)], 1)    # sentinel

    back = constrain(jnp.take_along_axis(y, dest[..., None], 1),
                     "moe_local")                                 # (G,T*K,d)
    w = jnp.take_along_axis(gate.reshape(G, T * K), order, 1).astype(x3.dtype)
    out = jax.vmap(
        lambda t, b: jnp.zeros((T, d), x3.dtype).at[t].add(b)
    )(tok, back * w[..., None])

    if m.n_shared:
        out = out + swiglu_apply(p["shared"], x3)
    return out, aux


# ---------------------------------------------------------------------- #
# Expert-parallel layer: routing over all experts, dropless compute of the
# held ones (Moonlight / DeepSeek-V3, arXiv:2412.19437 §2.1.2)
# ---------------------------------------------------------------------- #
def route(m, x2, p):
    """x2 (T, d) -> (scores (T, E), experts (T, K), gates (T, K)).

    The router's product is taken at full float32 precision (DeepSeek-V3
    gates in float32). Softmax scoring takes the top-k scores; sigmoid
    scoring chooses the top-k on score + selection bias and gates with the
    chosen scores without the bias. The gates are renormalised where
    ``normalize_gates`` and scaled by ``routed_scaling``."""
    logits = jnp.matmul(x2.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    if m.scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        choose = s + jax.lax.stop_gradient(p["e_bias"])
    else:
        s = choose = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(choose, m.top_k)
    g = jnp.take_along_axis(s, idx, -1)
    if m.normalize_gates:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return s, idx, g * m.routed_scaling


def moe_held_apply(cfg, p, x, first: int = 0):
    """x (..., d) -> (y, stats): the shared experts plus the part of the
    routed experts' sum that experts [first, first + n_held) give, every
    token routed over all ``n_routed`` experts and none dropped.

    The token-expert pairs are sorted by held expert (pairs for experts
    held elsewhere last) and each held expert's contiguous rows go through
    its SwiGLU by ``ragged_dot``; rows past the held pairs are selected to
    zero, and the weighted rows are gathered back per token and summed
    over its k choices. ``stats``: ``balance``, the sequence-wise balance loss
    (weight ``router_aux_weight``; the last leading axis of ``x`` is the
    sequence); ``pairs``, the token-expert pairs the held experts
    computed; ``load_max``, the most any one held expert computed."""
    m = cfg.moe
    lead, d = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, d)
    T = x2.shape[0]
    E, K, H = m.n_routed, m.top_k, m.n_held
    s, idx, gate = route(m, x2, p)

    local = idx.reshape(-1) - first                                # (T*K,)
    held = (local >= 0) & (local < H)
    group = jnp.where(held, local, H)
    order = jnp.argsort(group, stable=True)
    loads = jnp.zeros(H + 1, jnp.int32).at[group].add(1)[:H]
    n_held = loads.sum()
    # rows past the held pairs belong to no group: the TPU's ragged kernel
    # leaves them (and those of its transposes in the backward pass)
    # unwritten, so every ragged product's rows, and the rows fed to it,
    # are selected to zero there — a select, which keeps whatever those
    # rows hold out of the values and the gradients alike
    valid = (jnp.arange(T * K) < n_held)[:, None]

    def ragged(lhs, rhs):
        return jnp.where(valid, jax.lax.ragged_dot(lhs, rhs, loads), 0)

    xs = jnp.where(valid, x2[order // K], 0)
    wg, wu, wd = (p[k].astype(xs.dtype) for k in ("wg", "wu", "wd"))
    h = jax.nn.silu(ragged(xs, wg)) * ragged(xs, wu)
    w = jnp.where(held, gate.reshape(-1), 0.0)[order]
    y = ragged(h, wd) * w[:, None].astype(xs.dtype)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(T * K))
    out = y[back].reshape(T, K, d).sum(1)
    if m.n_shared:
        out = out + swiglu_apply(p["shared"], x2)

    S = lead[-1] if len(lead) > 1 else T
    sb = s.reshape(-1, S, E)
    frac = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1).reshape(-1, S, E)
    f = frac.sum(1) * (E / (K * S))                                # (B, E)
    prob = (sb / sb.sum(-1, keepdims=True)).mean(1)                # (B, E)
    balance = m.router_aux_weight * jnp.mean(jnp.sum(f * prob, -1))
    stats = {"balance": balance, "pairs": n_held.astype(jnp.float32),
             "load_max": loads.max().astype(jnp.float32)}
    return out.reshape(x.shape).astype(x.dtype), stats


def aux_stats(balance=0.0):
    """A layer's stats with no held-expert work: the capacity layer's, and
    the starting point of a layer stack's."""
    z = jnp.zeros((), jnp.float32)
    return {"balance": z + balance, "pairs": z, "load_max": z}


def add_aux(a, b):
    """Two layers' stats combined: balance losses and pairs add, the
    larger ``load_max`` is kept."""
    return {"balance": a["balance"] + b["balance"],
            "pairs": a["pairs"] + b["pairs"],
            "load_max": jnp.maximum(a["load_max"], b["load_max"])}
