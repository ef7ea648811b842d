"""Decoder-only language models (dense / MoE / SSM / hybrid / VLM) built from
``repro.models.blocks``: init, train forward, prefill, and single-token decode.

DeepSeek-V3 extras supported here: ``first_dense_layers`` unrolled before the
scanned MoE stack, and the depth-1 multi-token-prediction (MTP) head.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models.common import (correct_counts, cross_entropy, dtype_of,
                                 embed_init, ones, rms_norm, dense_init)
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------- #
# Init
# ---------------------------------------------------------------------- #
def lm_init(key, cfg):
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    d = cfg.d_model
    params = {
        "embed": embed_init(ks[0], (cfg.vocab_size, d), dt),
        "blocks": blk.stacked_blocks_init(ks[1], cfg),
        "final_norm": ones((d,), dt),
        "lm_head": dense_init(ks[2], (d, cfg.vocab_size), dt),
    }
    if cfg.first_dense_layers:
        kind = {"mixer": "attn", "mlp": "dense"}
        hks = jax.random.split(ks[3], cfg.first_dense_layers)
        params["head_layers"] = tuple(blk.layer_init(k, cfg, kind) for k in hks)
    if cfg.mtp:
        kind = {"mixer": "attn", "mlp": "dense"}
        params["mtp"] = {
            "proj": dense_init(ks[4], (2 * d, d), dt, fan_in=2 * d),
            "norm_h": ones((d,), dt),
            "norm_e": ones((d,), dt),
            "layer": blk.layer_init(ks[5], cfg, kind),
        }
    return params


def _head_kind():
    return {"mixer": "attn", "mlp": "dense"}


# ---------------------------------------------------------------------- #
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------- #
def lm_forward(cfg, params, tokens, *, window=None, remat=False,
               return_cache=False):
    """tokens (B,S) int32 -> (logits (B,S,V), aux, cache|None, h_last)."""
    h = params["embed"][tokens].astype(dtype_of(cfg))
    h = constrain(h, "act")
    aux = 0.0
    head_caches = []
    for p in params.get("head_layers", ()):
        h, a, c = blk.layer_apply(cfg, p, _head_kind(), h, window=window,
                                  return_cache=return_cache)
        aux += a
        head_caches.append(c)
    h, a, caches = blk.scan_blocks(cfg, params["blocks"], h, window=window,
                                   return_cache=return_cache, remat=remat)
    aux += a
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = constrain(hn @ params["lm_head"], "logits")
    cache = None
    if return_cache:
        cache = {"blocks": caches, "head_layers": tuple(head_caches)}
    return logits, aux, cache, h


def lm_loss(cfg, params, batch, *, remat=False):
    """Next-token CE (+ MoE aux + optional MTP)."""
    tokens = batch["tokens"]
    window = cfg.sliding_window
    logits, aux, _, h = lm_forward(cfg, params, tokens, window=window,
                                   remat=remat)
    loss = cross_entropy(logits[:, :-1], tokens[:, 1:])
    metrics = {"ce": loss}
    if cfg.mtp:
        mtp = params["mtp"]
        # depth-1 MTP: combine running hidden state with the embedding of the
        # *next* token, run one extra block, predict token t+2.
        nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        e = params["embed"][nxt].astype(h.dtype)
        z = jnp.concatenate([rms_norm(h, mtp["norm_h"], cfg.norm_eps),
                             rms_norm(e, mtp["norm_e"], cfg.norm_eps)], -1)
        z = z @ mtp["proj"]
        z, a2, _ = blk.layer_apply(cfg, mtp["layer"], _head_kind(), z,
                                   window=window)
        aux += a2
        mtp_logits = rms_norm(z, params["final_norm"], cfg.norm_eps) @ params["lm_head"]
        mtp_loss = cross_entropy(mtp_logits[:, :-2], tokens[:, 2:])
        loss = loss + 0.3 * mtp_loss
        metrics["mtp_ce"] = mtp_loss
    loss = loss + aux
    metrics["aux"] = aux
    return loss, metrics


# ---------------------------------------------------------------------- #
# Masked federated twins — the cohort engine's contract (models/mlp.py has
# the feature-model originals): client datasets are zero-padded to a
# uniform window count with a {0,1} per-window validity mask; a padded
# window must contribute *exactly* zero loss and gradient so the padded
# run reproduces the unpadded one. The window mask expands to per-TOKEN
# target weights (a target position counts iff both it and its input
# position are valid), so the same code path supports ragged windows.
# ---------------------------------------------------------------------- #
def _token_weights(tokens, m):
    """(B,) or (B, S) validity mask -> (B, S-1) next-token target weights."""
    m = jnp.asarray(m, jnp.float32)
    if m.ndim == 1:
        m = jnp.broadcast_to(m[:, None], tokens.shape)
    return m[:, 1:] * m[:, :-1]


def lm_loss_masked(cfg, params, batch, *, remat=False):
    """Masked next-token CE over the valid target positions of a batch.

    batch["tokens"] (B, S) int32; batch["m"] (B,) per-window or (B, S)
    per-token {0,1} validity. Padded positions carry weight 0: the loss is
    invariant to their token content and their gradient contribution is
    exactly zero (a fully padded batch is a strict parameter no-op). For a
    fully valid batch the masked mean reduces to the plain ``lm_loss``
    (weight sum == target count). MoE router aux is NOT masked — use
    aux-free (dense/ssm) configs for the federated task.
    """
    tokens = batch["tokens"]
    logits, aux, _, _ = lm_forward(cfg, params, tokens,
                                   window=cfg.sliding_window, remat=remat)
    w = _token_weights(tokens, batch["m"])
    loss = cross_entropy(logits[:, :-1], tokens[:, 1:], mask=w)
    return loss + aux, {"ce": loss, "aux": aux}


def lm_correct_counts(cfg, params, tokens, m):
    """Masked next-token (greedy top-1) (correct, valid) counts — the LM
    analogue of the MLP's masked local accuracy (Alg. 1 line 11), divided
    by ``models.common.count_accuracy``."""
    logits, _, _, _ = lm_forward(cfg, params, tokens,
                                 window=cfg.sliding_window)
    correct = (jnp.argmax(logits[:, :-1], -1)
               == tokens[:, 1:]).astype(jnp.float32)
    w = _token_weights(tokens, m)
    return correct_counts(correct, w)


@partial(jax.jit, static_argnums=(0, 4))
def lm_sgd_epoch(cfg, params, tokens, lr, batch_size: int = 8):
    """One epoch of mini-batch SGD over a client's token windows (the
    federated loop oracle's path; mirrors ``mlp_sgd_epoch`` — a tail batch
    that does not fill ``batch_size`` is dropped)."""
    n = tokens.shape[0]
    nb = max(n // batch_size, 1)

    def body(params, i):
        tb = jax.lax.dynamic_slice_in_dim(tokens, i * batch_size, batch_size)
        g = jax.grad(lambda p: lm_loss(cfg, p, {"tokens": tb})[0])(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, 0.0

    params, _ = jax.lax.scan(body, params, jnp.arange(nb))
    return params


@partial(jax.jit, static_argnums=(0, 5))
def lm_sgd_epoch_masked(cfg, params, tokens, m, lr, batch_size: int = 8):
    """Masked twin of ``lm_sgd_epoch`` over a padded window set.

    tokens (S, seq), m (S,) with S a multiple of batch_size; batches that
    fall entirely in the padding leave params untouched. Same row-major
    reshape batch grid as ``mlp_sgd_epoch_masked``.
    """
    n = tokens.shape[0]
    assert n % batch_size == 0, (
        f"padded window count {n} must be a multiple of batch_size "
        f"{batch_size} (pad_clients(multiple_of=batch_size) guarantees this)")
    nb = n // batch_size
    tb = tokens.reshape(nb, batch_size, -1)
    mb = m.reshape(nb, batch_size)

    def body(params, batch):
        bt, bm = batch
        g = jax.grad(lambda p: lm_loss_masked(
            cfg, p, {"tokens": bt, "m": bm})[0])(params)
        params = jax.tree.map(lambda p, gg: p - lr * gg, params, g)
        return params, 0.0

    params, _ = jax.lax.scan(body, params, (tb, mb))
    return params


# ---------------------------------------------------------------------- #
# Serving
# ---------------------------------------------------------------------- #
def decode_cache_len(cfg, seq_len: int):
    """(cache_len, is_ring). Ring caches are used for sliding-window archs and
    for the explicit long-context variant of full-attention archs."""
    win = cfg.sliding_window
    if seq_len > 32_768 and cfg.long_context_window and cfg.attn_layer_period == 0:
        win = (min(win, cfg.long_context_window) if win
               else cfg.long_context_window)
    if win and win < seq_len:
        return win, True
    return seq_len, False


def lm_cache_init(cfg, batch: int, seq_len: int):
    cache_len, ring = decode_cache_len(cfg, seq_len)
    cache = {
        "blocks": blk.stacked_cache_init(cfg, batch, cache_len),
        "head_layers": tuple(
            blk.layer_cache_init(cfg, _head_kind(), batch, cache_len)
            for _ in range(cfg.first_dense_layers)),
        "index": jnp.zeros((), jnp.int32),
    }
    if ring:
        cache["slot_pos"] = jnp.full((cache_len,), -1, jnp.int32)
    return cache


def lm_prefill(cfg, params, tokens, target_len: Optional[int] = None):
    """Prefill: returns (last-position logits, decode-ready cache)."""
    S = tokens.shape[1]
    logits, _, cache, _ = lm_forward(cfg, params, tokens,
                                     window=cfg.sliding_window,
                                     return_cache=True)
    cache = {"blocks": cache["blocks"], "head_layers": cache["head_layers"],
             "index": jnp.asarray(S, jnp.int32)}
    if target_len is not None and target_len > S:
        cache = grow_cache(cache, target_len - S)
    return logits[:, -1], cache


def grow_cache(cache, extra: int):
    """Pad linear attention caches by ``extra`` positions (prefill->decode)."""
    def pad(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in ("k", "v", "ckv", "kr"):
            pads = [(0, 0)] * x.ndim
            pads[-3 if name in ("k", "v") else -2] = (0, extra)
            return jnp.pad(x, pads)
        return x
    return jax.tree_util.tree_map_with_path(pad, cache)


def lm_decode_step(cfg, params, cache, token):
    """token (B,1) int32 -> (logits (B,V), new cache)."""
    index = cache["index"]
    slot_pos = cache.get("slot_pos")
    window = cfg.sliding_window if slot_pos is None else None
    h = params["embed"][token].astype(dtype_of(cfg))
    h = constrain(h, "dec")
    new_head = []
    for p, c in zip(params.get("head_layers", ()), cache["head_layers"]):
        h, nc = blk.layer_decode(cfg, p, _head_kind(), h, c, index,
                                 slot_pos=slot_pos, window=window)
        new_head.append(nc)
    h, new_blocks = blk.scan_blocks_decode(cfg, params["blocks"], h,
                                           cache["blocks"], index,
                                           slot_pos=slot_pos, window=window)
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = hn[:, 0] @ params["lm_head"]
    new_cache = {"blocks": new_blocks, "head_layers": tuple(new_head),
                 "index": index + 1}
    if slot_pos is not None:
        C = slot_pos.shape[0]
        new_cache["slot_pos"] = slot_pos.at[index % C].set(index)
    return logits, new_cache
