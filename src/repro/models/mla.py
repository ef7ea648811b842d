"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Faithful low-rank structure: compressed KV latent ``c_kv`` (kv_lora_rank) +
decoupled shared RoPE key (qk_rope_head_dim); queries through the low-rank
``q_lora_rank`` path, or one direct projection where it is None. Prefill/train expands the latent;
decode uses the *absorbed* formulation (W_uk folded into the query, W_uv applied
after attention) so the cache holds only (kv_lora_rank + rope_dim) per token —
the actual MLA memory win, visible in dry-run cache bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import apply_rope, dense_init, dtype_of, ones, rms_norm


def mla_init(key, cfg):
    m, d = cfg.mla, cfg.d_model
    H = cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    dt = dtype_of(cfg)
    if m.q_lora_rank is None:            # direct query projection
        q = {"wq": dense_init(ks[0], (d, H * qk_hd), dt)}
    else:
        q = {"wdq": dense_init(ks[0], (d, m.q_lora_rank), dt),
             "q_norm": ones((m.q_lora_rank,), dt),
             "wuq": dense_init(ks[1], (m.q_lora_rank, H * qk_hd), dt)}
    return {
        **q,
        "wdkv": dense_init(ks[2], (d, m.kv_lora_rank), dt),
        "kv_norm": ones((m.kv_lora_rank,), dt),
        "wkr": dense_init(ks[3], (d, m.qk_rope_head_dim), dt),
        "wuk": dense_init(ks[4], (m.kv_lora_rank, H * m.qk_nope_head_dim), dt),
        "wuv": dense_init(ks[5], (m.kv_lora_rank, H * m.v_head_dim), dt),
        "wo": dense_init(ks[6], (H * m.v_head_dim, d), dt,
                         fan_in=H * m.v_head_dim),
    }


def _queries(cfg, p, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    if "wq" in p:
        q = x @ p["wq"]
    else:
        q = rms_norm(x @ p["wdq"], p["q_norm"], cfg.norm_eps) @ p["wuq"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(cfg, p, x, *, window=None, positions=None):
    """Full-sequence (train / prefill). Returns (y, cache=(c_kv, k_rope))."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.arange(S)[None]
    q_nope, q_rope = _queries(cfg, p, x, positions)

    ckv = rms_norm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)     # (B,S,r)
    k_rope = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0]                   # (B,S,dr)
    k_nope = (ckv @ p["wuk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (ckv @ p["wuv"]).reshape(B, S, H, m.v_head_dim)

    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
              + jnp.einsum("bshd,btd->bhst", q_rope, k_rope))
    logits = logits.astype(jnp.float32) * scale
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask &= (i - j) < window
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, -1)
    return out @ p["wo"], (ckv, k_rope)


def mla_decode(cfg, p, x, cache_ckv, cache_kr, index, *, slot_pos=None,
               window=None):
    """Absorbed single-token decode over the compressed cache.

    cache_ckv (B,C,r), cache_kr (B,C,dr). Returns (y, ckv, kr, slot_pos).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    pos = jnp.full((B, 1), index)
    q_nope, q_rope = _queries(cfg, p, x, pos)                      # (B,1,H,*)

    ckv_new = rms_norm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)  # (B,1,r)
    kr_new = apply_rope((x @ p["wkr"])[:, :, None, :], pos,
                        cfg.rope_theta)[:, :, 0]                   # (B,1,dr)
    C = cache_ckv.shape[1]
    slot = index % C if slot_pos is not None else index
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(cache_ckv, ckv_new, slot, 1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(cache_kr, kr_new, slot, 1)
    if slot_pos is not None:
        slot_pos = slot_pos.at[slot].set(index)
        valid = slot_pos >= 0
    else:
        j = jnp.arange(C)
        valid = j <= index
        if window is not None:
            valid &= j > index - window

    # absorb W_uk into q: q_lat (B,1,H,r)
    wuk = p["wuk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wuk)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    logits = (jnp.einsum("bshr,btr->bhst", q_lat, cache_ckv)
              + jnp.einsum("bshd,btd->bhst", q_rope, cache_kr))
    logits = logits.astype(jnp.float32) * scale
    logits = jnp.where(valid[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, cache_ckv)       # (B,1,H,r)
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhd->bshd", out_lat, wuv).reshape(B, 1, -1)
    return out @ p["wo"], cache_ckv, cache_kr, slot_pos
