"""Encoder-decoder backbone (Seamless-M4T medium transformer backbone,
arXiv:2308.11596). Per the assignment carve-out the modality frontend is a
stub: the encoder consumes precomputed frame embeddings ``(B, S_src, d)``
(mel-spectrogram + conv feature extractor output), projected by one linear
layer. The decoder is a standard causal transformer with per-layer
cross-attention.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import blocks as blk
from repro.models.common import (cross_entropy, dense_init, dtype_of,
                                 embed_init, ones, rms_norm)
from repro.sharding.ctx import constrain

_KIND = {"mixer": "attn", "mlp": "dense"}


def encdec_init(key, cfg):
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    d = cfg.d_model
    assert cfg.encoder_layers > 0 and cfg.is_encoder_decoder
    return {
        "src_proj": dense_init(ks[0], (d, d), dt),
        "enc_blocks": blk.stacked_blocks_init(ks[1], cfg,
                                              n_blocks=cfg.encoder_layers),
        "enc_norm": ones((d,), dt),
        "embed": embed_init(ks[2], (cfg.vocab_size, d), dt),
        "dec_blocks": blk.stacked_blocks_init(ks[3], cfg,
                                              cross_attention=True),
        "final_norm": ones((d,), dt),
        "lm_head": dense_init(ks[4], (d, cfg.vocab_size), dt),
    }


def _encode(cfg, params, src, remat=False):
    """src (B,S_src,d) frame embeddings -> encoder output (bidirectional)."""
    h = constrain(src.astype(dtype_of(cfg)) @ params["src_proj"], "act")

    def body(carry, bp):
        h = carry
        p = bp["layers"][0]
        hin = rms_norm(h, p["norm1"], cfg.norm_eps)
        q, k, v = attn._project_qkv(cfg, p["mixer"], hin)
        S = hin.shape[1]
        pos = jnp.arange(S)[None]
        from repro.models.common import apply_rope
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
        mask = jnp.ones((1, 1, 1, S, S), bool)          # bidirectional
        y = attn.sdpa(q, k, v, mask) @ p["mixer"]["wo"]
        h = h + y
        h2 = rms_norm(h, p["norm2"], cfg.norm_eps)
        from repro.models.common import swiglu_apply
        h = h + swiglu_apply(p["mlp"], h2)
        return constrain(h, "act"), 0.0

    if remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["enc_blocks"],
                        unroll=cfg.scan_unroll)
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def encdec_forward(cfg, params, src, tokens, *, remat=False,
                   return_cache=False):
    """Teacher-forced forward. src (B,S_src,d); tokens (B,S_tgt)."""
    enc_out = _encode(cfg, params, src, remat=remat)
    h = constrain(params["embed"][tokens].astype(dtype_of(cfg)), "act")
    h, aux, caches = blk.scan_blocks(cfg, params["dec_blocks"], h,
                                     enc_out=enc_out,
                                     return_cache=return_cache, remat=remat)
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = constrain(hn @ params["lm_head"], "logits")
    return logits, aux, caches, enc_out


def encdec_loss(cfg, params, batch, *, remat=False):
    logits, aux, _, _ = encdec_forward(cfg, params, batch["src"],
                                       batch["tokens"], remat=remat)
    loss = (cross_entropy(logits[:, :-1], batch["tokens"][:, 1:])
            + aux["balance"])
    return loss, {"ce": loss}


def encdec_cache_init(cfg, batch: int, seq_len: int, src_len: int):
    return {
        "blocks": blk.stacked_cache_init(cfg, batch, seq_len,
                                         cross_len=src_len),
        "index": jnp.zeros((), jnp.int32),
    }


def encdec_prefill(cfg, params, src, bos_tokens, target_len: int):
    """Encode source + run decoder prefill on bos_tokens, return cache."""
    logits, _, caches, enc_out = encdec_forward(cfg, params, src, bos_tokens,
                                                return_cache=True)
    from repro.models.transformer import grow_cache
    S = bos_tokens.shape[1]
    cache = {"blocks": caches, "index": jnp.asarray(S, jnp.int32)}
    if target_len > S:
        cache = grow_cache(cache, target_len - S)
    return logits[:, -1], cache


def encdec_decode_step(cfg, params, cache, token):
    """One decoder token; cross K/V live in the cache (computed at prefill)."""
    index = cache["index"]
    h = constrain(params["embed"][token].astype(dtype_of(cfg)), "dec")
    h, new_blocks = blk.scan_blocks_decode(cfg, params["dec_blocks"], h,
                                           cache["blocks"], index)
    hn = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = hn[:, 0] @ params["lm_head"]
    return logits, {"blocks": new_blocks, "index": index + 1}
