"""Transformer super-blocks: init / apply for the repeating layer pattern of
each architecture, plus the scan-over-blocks drivers.

A *super-block* is ``cfg.block_len`` consecutive layers (1 for homogeneous
stacks; 8 for Jamba's [7 x mamba + 1 x attn] interleave; 2 when MoE alternates
with dense MLPs). Parameters and decode caches are stacked over
``cfg.n_blocks`` and driven by ``lax.scan`` so compiled HLO stays proportional
to one super-block.
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import ssm as ssm_mod
from repro.models import moe as moe_mod
from repro.models.common import dtype_of, ones, rms_norm, swiglu_apply, swiglu_init
from repro.sharding.ctx import constrain


# ---------------------------------------------------------------------- #
# Init
# ---------------------------------------------------------------------- #
def layer_init(key, cfg, kind: dict, cross_attention: bool = False):
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    d = cfg.d_model
    p: dict = {"norm1": ones((d,), dt)}
    if kind["mixer"] == "attn":
        p["mixer"] = (mla_mod.mla_init(ks[0], cfg) if cfg.mla is not None
                      else attn.attn_init(ks[0], cfg))
    else:
        p["mixer"] = ssm_mod.ssm_init(ks[0], cfg)
    if cross_attention:
        p["norm_x"] = ones((d,), dt)
        p["cross"] = attn.attn_init(ks[3], cfg)
    if kind["mlp"] != "none":
        p["norm2"] = ones((d,), dt)
        p["mlp"] = (moe_mod.moe_init(ks[1], cfg) if kind["mlp"] == "moe"
                    else swiglu_init(ks[1], d, cfg.d_ff, dt))
    return p


def block_init(key, cfg, cross_attention: bool = False):
    pat = cfg.block_pattern()
    ks = jax.random.split(key, len(pat))
    return {"layers": tuple(layer_init(k, cfg, kind, cross_attention)
                            for k, kind in zip(ks, pat))}


def stacked_blocks_init(key, cfg, n_blocks: Optional[int] = None,
                        cross_attention: bool = False):
    n = n_blocks if n_blocks is not None else cfg.n_blocks
    ks = jax.random.split(key, n)
    blocks = [block_init(k, cfg, cross_attention) for k in ks]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


# ---------------------------------------------------------------------- #
# Cache init (decode)
# ---------------------------------------------------------------------- #
def layer_cache_init(cfg, kind: dict, batch: int, cache_len: int,
                     cross_len: int = 0):
    dt = dtype_of(cfg)
    c: dict = {}
    if kind["mixer"] == "attn":
        if cfg.mla is not None:
            m = cfg.mla
            c["ckv"] = jnp.zeros((batch, cache_len, m.kv_lora_rank), dt)
            c["kr"] = jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dt)
        else:
            hd = cfg.head_dim
            c["k"] = jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dt)
            c["v"] = jnp.zeros((batch, cache_len, cfg.n_kv_heads, hd), dt)
        if cross_len:
            hd = cfg.head_dim
            c["xk"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), dt)
            c["xv"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), dt)
    else:
        s = cfg.ssm
        d_in = s.expand * cfg.d_model
        nh = d_in // s.head_dim
        ch = d_in + 2 * s.n_groups * s.d_state
        c["conv"] = jnp.zeros((batch, s.conv_kernel - 1, ch), dt)
        c["state"] = jnp.zeros((batch, nh, s.d_state, s.head_dim), jnp.float32)
    return c


def block_cache_init(cfg, batch: int, cache_len: int, cross_len: int = 0):
    return {"layers": tuple(layer_cache_init(cfg, kind, batch, cache_len,
                                             cross_len)
                            for kind in cfg.block_pattern())}


def stacked_cache_init(cfg, batch: int, cache_len: int, n_blocks=None,
                       cross_len: int = 0):
    n = n_blocks if n_blocks is not None else cfg.n_blocks
    one = block_cache_init(cfg, batch, cache_len, cross_len)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), one)


# ---------------------------------------------------------------------- #
# Apply: full-sequence (train / prefill)
# ---------------------------------------------------------------------- #
def layer_apply(cfg, p, kind, h, *, window=None, enc_out=None,
                return_cache=False):
    """Pre-norm layer. Returns (h, aux, cache); ``aux`` is the expert
    layer's stats (models/moe.py), zeros where the layer has none."""
    aux = moe_mod.aux_stats()
    cache: dict = {}
    hin = rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind["mixer"] == "attn":
        if cfg.mla is not None:
            y, (ckv, kr) = mla_mod.mla_apply(cfg, p["mixer"], hin, window=window)
            if return_cache:
                cache.update(ckv=ckv, kr=kr)
        else:
            y, (k, v) = attn.attn_apply(cfg, p["mixer"], hin, window=window)
            if return_cache:
                cache.update(k=k, v=v)
    else:
        y, (conv_state, state) = ssm_mod.ssm_apply(cfg, p["mixer"], hin)
        if return_cache:
            cache.update(conv=conv_state, state=state)
    h = h + y
    if enc_out is not None and "cross" in p:
        hx = rms_norm(h, p["norm_x"], cfg.norm_eps)
        xkv = attn.encoder_kv(cfg, p["cross"], enc_out)
        h = h + attn.cross_attn_apply(cfg, p["cross"], hx, xkv)
        if return_cache and kind["mixer"] == "attn":
            cache.update(xk=xkv[0], xv=xkv[1])
    if kind["mlp"] != "none":
        h2 = rms_norm(h, p["norm2"], cfg.norm_eps)
        if kind["mlp"] == "moe":
            y2, aux = moe_mod.moe_apply(cfg, p["mlp"], h2)
        else:
            y2 = swiglu_apply(p["mlp"], h2)
        h = h + y2
    return constrain(h, "act"), aux, cache


def block_apply(cfg, bp, h, *, window=None, enc_out=None, return_cache=False):
    aux_total = moe_mod.aux_stats()
    caches = []
    for p, kind in zip(bp["layers"], cfg.block_pattern()):
        h, aux, cache = layer_apply(cfg, p, kind, h, window=window,
                                    enc_out=enc_out, return_cache=return_cache)
        aux_total = moe_mod.add_aux(aux_total, aux)
        caches.append(cache)
    return h, aux_total, {"layers": tuple(caches)}


def scan_blocks(cfg, stacked, h, *, window=None, enc_out=None,
                return_cache=False, remat=False):
    """Scan full-sequence blocks. Returns (h, aux, stacked_cache|None)."""
    def body(carry, bp):
        h, aux = carry
        h2, a, cache = block_apply(cfg, bp, h, window=window, enc_out=enc_out,
                                   return_cache=return_cache)
        return (h2, moe_mod.add_aux(aux, a)), (cache if return_cache
                                                else 0.0)

    if remat:
        body = jax.checkpoint(body)
    (h, aux), caches = jax.lax.scan(body, (h, moe_mod.aux_stats()), stacked,
                                    unroll=cfg.scan_unroll)
    return h, aux, (caches if return_cache else None)


# ---------------------------------------------------------------------- #
# Apply: single-token decode
# ---------------------------------------------------------------------- #
def layer_decode(cfg, p, kind, h, cache, index, *, slot_pos=None,
                 window=None):
    new_cache = dict(cache)
    hin = rms_norm(h, p["norm1"], cfg.norm_eps)
    if kind["mixer"] == "attn":
        if cfg.mla is not None:
            y, ckv, kr, _ = mla_mod.mla_decode(cfg, p["mixer"], hin,
                                               cache["ckv"], cache["kr"],
                                               index, slot_pos=slot_pos,
                                               window=window)
            new_cache.update(ckv=ckv, kr=kr)
        else:
            y, k, v, _ = attn.attn_decode(cfg, p["mixer"], hin,
                                          cache["k"], cache["v"], index,
                                          slot_pos=slot_pos, window=window)
            new_cache.update(k=k, v=v)
    else:
        y, conv, state = ssm_mod.ssm_decode(cfg, p["mixer"], hin,
                                            cache["conv"], cache["state"])
        new_cache.update(conv=conv, state=state)
    h = h + y
    if "cross" in p and "xk" in cache:
        hx = rms_norm(h, p["norm_x"], cfg.norm_eps)
        h = h + attn.cross_attn_apply(cfg, p["cross"], hx,
                                      (cache["xk"], cache["xv"]))
    if kind["mlp"] != "none":
        h2 = rms_norm(h, p["norm2"], cfg.norm_eps)
        if kind["mlp"] == "moe":
            y2, _ = moe_mod.moe_apply(cfg, p["mlp"], h2)
        else:
            y2 = swiglu_apply(p["mlp"], h2)
        h = h + y2
    return constrain(h, "dec"), new_cache


def block_decode(cfg, bp, h, bcache, index, *, slot_pos=None, window=None):
    new = []
    for p, kind, cache in zip(bp["layers"], cfg.block_pattern(),
                              bcache["layers"]):
        h, c = layer_decode(cfg, p, kind, h, cache, index, slot_pos=slot_pos,
                            window=window)
        new.append(c)
    return h, {"layers": tuple(new)}


def scan_blocks_decode(cfg, stacked, h, caches, index, *, slot_pos=None,
                       window=None):
    def body(h, xs):
        bp, bcache = xs
        h, newc = block_decode(cfg, bp, h, bcache, index, slot_pos=slot_pos,
                               window=window)
        return h, newc
    h, new_caches = jax.lax.scan(body, h, (stacked, caches),
                                 unroll=cfg.scan_unroll)
    return h, new_caches
