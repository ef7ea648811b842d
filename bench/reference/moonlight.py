"""Moonlight-16B-A3B's training step, plainly: the forward pass, loss and
gradients in straightforward ``jax.numpy`` at float32 under
``jax.default_matmul_precision("highest")``, plain SGD, and FedAvg over a
list. A copy of the program's reference
(``src/repro/models/reference_moonlight.py``) kept with the benchmark, so
that a change to the program cannot move what ``moonlight_round`` is
checked against; it imports nothing of the program.

It reads the program's parameter layout (``models/transformer.py::lm_init``)
and a ``spec`` dict of the configuration's numbers (``spec_keys``). Every
layer is pre-norm: ``h += attn(rms(h)); h += mlp(rms(h))``.

- Attention is MLA expanded, with no absorption: queries ``x @ wq`` (no
  query compression), the latent ``c = rms(x @ wdkv)``, keys
  ``[c @ wuk, rope(x @ wkr)]`` with the rope key shared by the heads,
  values ``c @ wuv``, causal softmax at ``(nope + rope)^-1/2``.
- The first layer's MLP is a SwiGLU; the others are the expert layer:
  sigmoid scores of the float32 router logits; the top-k chosen on score +
  selection bias; gates the chosen scores over their sum times the scaling
  factor. Every held expert runs on every token and is multiplied by its
  gate, zero where the token did not choose it; no sort, no capacity.
  The shared experts are one SwiGLU of their fused width.
- Loss: mean next-token cross-entropy plus, per expert layer, the
  sequence-wise balance loss ``w * mean_b sum_i f_i P_i`` with
  ``f_i = E / (K S) * #(tokens choosing i)`` and ``P_i`` the mean over the
  sequence of the scores normalised to sum 1 (arXiv:2412.19437 Eq. 17-20).

Departures from ``config.json`` (those of the cut model the benchmark
runs): only experts ``[first, first + held)`` are computed (the chip's
share; the router still spans all experts); the vocabulary is a slice;
the depth is the dense layer plus a period of expert layers; the
selection bias is an input held through training (DeepSeek-V3 updates it
by a balance rule outside the gradient; ``expert_loads`` gives the loads
that rule reads); RoPE rotates the two halves of the
rope dims rather than interleaved pairs (a fixed permutation of ``wq``'s
and ``wkr``'s rope columns, the same function class).
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

spec_keys = ("n_heads", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "rope_theta", "rms_norm_eps", "n_routed_experts",
             "num_experts_per_tok", "routed_scaling_factor",
             "norm_topk_prob", "aux_weight", "first_expert")

F32 = jnp.float32


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, ..., D): rotate the two halves of D by position."""
    S, D = x.shape[0], x.shape[-1]
    freq = 1.0 / (theta ** (np.arange(0, D, 2) / D))
    ang = jnp.asarray(np.arange(S)[:, None] * freq[None], F32)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


def swiglu(p, x):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def attention(spec, p, x):
    """x (S, d) -> (S, d)."""
    S = x.shape[0]
    H, dn, dr, dv = (spec["n_heads"], spec["qk_nope_head_dim"],
                     spec["qk_rope_head_dim"], spec["v_head_dim"])
    q = (x @ p["wq"]).reshape(S, H, dn + dr)
    c = rms(x @ p["wdkv"], p["kv_norm"], spec["rms_norm_eps"])
    k_nope = (c @ p["wuk"]).reshape(S, H, dn)
    k_rope = rope(x @ p["wkr"], spec["rope_theta"])              # (S, dr)
    v = (c @ p["wuv"]).reshape(S, H, dv)
    q_rope = rope(q[..., dn:], spec["rope_theta"])
    scores = (jnp.einsum("shd,thd->hst", q[..., :dn], k_nope)
              + jnp.einsum("shd,td->hst", q_rope, k_rope)) / np.sqrt(dn + dr)
    causal = np.tril(np.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1), v)
    return out.reshape(S, H * dv) @ p["wo"]


def choice(spec, p, x):
    """x (S, d) -> (scores (S, E), chosen (S, E)): sigmoid scores of the
    router's logits, and 1 where the token chose the expert (top-k on
    score + selection bias)."""
    E, K = spec["n_routed_experts"], spec["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ p["router"])                           # (S, E)
    _, top = jax.lax.top_k(s + p["e_bias"], K)
    return s, jax.nn.one_hot(top, E, dtype=F32).sum(1)


def experts(spec, p, x):
    """x (S, d) -> (the held experts' gated sum + shared experts, balance
    loss of this sequence)."""
    S = x.shape[0]
    E, K = spec["n_routed_experts"], spec["num_experts_per_tok"]
    s, chosen = choice(spec, p, x)
    g = s * chosen
    if spec["norm_topk_prob"]:
        g = g / g.sum(-1, keepdims=True)
    g = g * spec["routed_scaling_factor"]
    out = swiglu(p["shared"], x) if "shared" in p else jnp.zeros_like(x)
    first = spec["first_expert"]
    for j in range(p["wg"].shape[0]):
        e = {"wg": p["wg"][j], "wu": p["wu"][j], "wd": p["wd"][j]}
        out = out + g[:, first + j:first + j + 1] * swiglu(e, x)
    f = chosen.sum(0) * E / (K * S)
    prob = (s / s.sum(-1, keepdims=True)).mean(0)
    return out, spec["aux_weight"] * jnp.sum(f * prob)


def _attend(spec, p, h):
    return h + attention(spec, p["mixer"],
                         rms(h, p["norm1"], spec["rms_norm_eps"]))


def _layer(spec, p, h, moe: bool):
    h = _attend(spec, p, h)
    x = rms(h, p["norm2"], spec["rms_norm_eps"])
    if moe:
        y, bal = experts(spec, p["mlp"], x)
        return h + y, bal
    return h + swiglu(p["mlp"], x), jnp.zeros((), F32)


def _blocks(params):
    n = jax.tree.leaves(params["blocks"])[0].shape[0]
    return [jax.tree.map(lambda l: l[i], params["blocks"])["layers"][0]
            for i in range(n)]


def forward(spec, params, tokens):
    """tokens (S,) -> (logits (S, V), summed balance loss)."""
    h = params["embed"][tokens]
    bal = jnp.zeros((), F32)
    dense = jax.checkpoint(lambda p, h: _layer(spec, p, h, False))
    moe = jax.checkpoint(lambda p, h: _layer(spec, p, h, True))
    for p in params["head_layers"]:
        h, b = dense(p, h)
        bal = bal + b
    for p in _blocks(params):
        h, b = moe(p, h)
        bal = bal + b
    h = rms(h, params["final_norm"], spec["rms_norm_eps"])
    return h @ params["lm_head"], bal


def expert_loads(spec, params, tokens):
    """tokens (S,) -> (expert layers, E): how many tokens chose each
    expert of each expert layer, along ``forward``'s pass."""
    h = params["embed"][tokens]
    for p in params["head_layers"]:
        h, _ = _layer(spec, p, h, False)
    loads = []
    for p in _blocks(params):
        h = _attend(spec, p, h)
        x = rms(h, p["norm2"], spec["rms_norm_eps"])
        loads.append(choice(spec, p["mlp"], x)[1].sum(0))
        h = h + experts(spec, p["mlp"], x)[0]
    return jnp.stack(loads)


def loss(spec, params, windows):
    """windows (B, S): mean next-token cross-entropy over the batch plus
    the mean over windows of their balance losses."""
    ce, bal = [], []
    for w in windows:
        logits, b = forward(spec, params, w)
        lp = jax.nn.log_softmax(logits[:-1], -1)
        ce.append(-jnp.mean(jnp.take_along_axis(lp, w[1:, None], -1)))
        bal.append(b)
    return jnp.mean(jnp.stack(ce)) + jnp.mean(jnp.stack(bal))


class Reference:
    """The reference's jitted pieces for one ``spec`` (a dict)."""

    def __init__(self, spec: Dict):
        self.spec = {k: spec[k] for k in spec_keys}
        sp = tuple(sorted(self.spec.items()))
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, w: loss(dict(sp), p, w)))
        self._logits = jax.jit(lambda p, w: forward(dict(sp), p, w)[0])

    def logits(self, params, tokens):
        with jax.default_matmul_precision("highest"):
            return self._logits(params, jnp.asarray(tokens))

    def sgd(self, params, windows: np.ndarray, lr: float, epochs: int,
            batch: int):
        """Plain SGD over the windows in order, ``batch`` at a time (a
        tail that does not fill a batch is a smaller last batch)."""
        with jax.default_matmul_precision("highest"):
            for _ in range(epochs):
                for i in range(0, len(windows), batch):
                    _, g = self._grad(params, jnp.asarray(windows[i:i + batch]))
                    params = jax.tree.map(lambda p, d: p - F32(lr) * d,
                                          params, g)
        return params


def fedavg(uploads: Iterable, weights: Sequence[float]):
    """sum_k (w_k / sum w) * upload_k over a list (or any iterable) of
    pytrees, accumulated leaf by leaf in float64 on the host."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    total, tree = None, None
    for wk, u in zip(w, uploads):
        leaves, tree = jax.tree.flatten(u)
        if total is None:
            total = [wk * np.asarray(l, np.float64) for l in leaves]
        else:
            for t, l in zip(total, leaves):
                t += wk * np.asarray(l, np.float64)
    return jax.tree.unflatten(tree, total)
