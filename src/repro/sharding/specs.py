"""Partition rules: params / optimizer state / batches / decode caches.

Baseline layout (single pod 16x16, axes ("data", "model")):
  * Megatron-style tensor parallelism over ``model``: attention head
    projections and MLP hidden dims are column/row sharded.
  * Batch (and MoE dispatch) over ``data``; multi-pod adds a leading ``pod``
    axis that extends the batch sharding.
  * MoE experts: ``(data x model)``-sharded when E divides the full mesh
    (DeepSeek's 256), else expert dim over ``model`` with the expert FFN dim
    over ``data`` (Jamba's 16 x 24576, Moonlight/Qwen's 6x/15x 1408) — this is
    what fits the 398B/671B configs in 16 GB/chip.
  * Optimizer moments: ZeRO-style — the first unsharded, divisible dim is
    additionally sharded over ``data``.
  * Decode caches: batch over ``data`` when divisible, sequence over
    ``model`` (GQA kv-head counts are below 16, so head-sharding the cache is
    not viable); batch=1 long-context shards sequence over the whole mesh.

All rules return PartitionSpecs; GSPMD pads non-divisible dims (e.g. Qwen's 60
experts, vocab 50280) — correctness is unaffected, the dry-run prices it.

``data_axes`` + ``named`` are also the sharding primitives of the
population plane (``core/population.py``, DESIGN.md §12): the (R, N)
control arrays shard their N-candidate trailing axis over the mesh's
data axes with ``named(mesh, PartitionSpec(None, data_axes(mesh)))``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig

# parameter-name rule tables (trailing dims, before the scan-stack prefix)
_COL = {"wq", "wk", "wv", "wg", "wu", "in_proj", "wuq", "wuk", "wuv", "wdq",
        "proj", "src_proj", "embed", "lm_head", "conv_w"}
_ROW = {"wo", "wd", "out_proj"}
_VEC_MODEL = {"bq", "bk", "bv", "conv_b", "A_log", "D", "dt_bias"}
_REPL = {"router", "wkr", "wdkv", "norm1", "norm2", "norm_x", "final_norm",
         "enc_norm", "q_norm", "k_norm", "kv_norm", "norm_h", "norm_e",
         "e_bias"}


def _path_names(path) -> list:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(int(p.idx))
    return out


def _is_stacked(names) -> bool:
    return any(n in ("blocks", "enc_blocks", "dec_blocks") for n in names
               if isinstance(n, str))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _expert_spec(name: str, shape, mesh: Mesh) -> P:
    """(E, d, f) / (E, f, d) expert tensors."""
    E = shape[0]
    total = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    dax = data_axes(mesh)
    if E % total == 0:
        return P((*dax, "model"), None, None)
    if name in ("wg", "wu"):
        return P("model", None, dax)
    return P("model", dax, None)          # wd: (E, f, d)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    n = 1
    for a in (entry if isinstance(entry, tuple) else (entry,)):
        n *= mesh.shape[a]
    return n


def _fix(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec entries that do not evenly divide the dim (NamedSharding on
    inputs requires exact divisibility); if a 2D+ weight loses its only
    sharded dim, fall back to sharding the first divisible dim over model."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    fixed = [s if shape[i] % _axis_size(mesh, s) == 0 else None
             for i, s in enumerate(parts)]
    if any(fixed) or not any(parts):
        return P(*fixed)
    for i, dim in enumerate(shape):              # fallback: row-shard
        if dim % mesh.shape["model"] == 0 and dim >= mesh.shape["model"]:
            fixed[i] = "model"
            break
    return P(*fixed)


def param_rule(path, leaf, cfg: ModelConfig, mesh: Mesh) -> P:
    names = _path_names(path)
    name = next((n for n in reversed(names) if isinstance(n, str)), "")
    stacked = _is_stacked(names)
    shape = leaf.shape
    core = shape[1:] if stacked else shape
    nd = len(core)

    if name in ("wg", "wu", "wd") and nd == 3:       # routed experts
        spec = _expert_spec(name, core, mesh)
    elif name == "norm" and nd == 1:                 # ssm gated norm (d_in,)
        spec = P("model")
    elif name in _VEC_MODEL:
        spec = P("model") if nd == 1 else P(None, "model")
    elif name in _ROW:
        spec = P("model", *([None] * (nd - 1)))
    elif name in _COL:
        spec = P(*([None] * (nd - 1)), "model")
    elif name in _REPL or nd == 0:
        spec = P(*([None] * nd))
    else:
        spec = P(*([None] * nd))
    if stacked:
        spec = P(None, *spec)
    return _fix(spec, shape, mesh)


def param_specs(cfg: ModelConfig, params, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: param_rule(path, leaf, cfg, mesh), params)


# ---------------------------------------------------------------------- #
# Optimizer state: ZeRO the first unsharded divisible dim over data
# ---------------------------------------------------------------------- #
def _zero_shard(spec: P, shape, mesh: Mesh) -> P:
    dax = data_axes(mesh)
    n = int(np.prod([mesh.shape[a] for a in dax]))
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for s in parts:
        for a in (s if isinstance(s, tuple) else (s,)):
            used.add(a)
    if used & set(dax):               # expert tensors already span data
        return P(*parts)
    for i, (s, dim) in enumerate(zip(parts, shape)):
        if s is None and dim % n == 0 and dim >= n:
            parts[i] = dax if len(dax) > 1 else dax[0]
            break
    return P(*parts)


def opt_state_specs(opt_name: str, params, pspecs, mesh: Mesh):
    def like(p, spec):
        return _zero_shard(spec, p.shape, mesh)

    if opt_name in ("sgd",):
        return {}
    if opt_name in ("momentum",):
        return {"m": jax.tree.map(like, params, pspecs)}
    if opt_name in ("adam", "adamw"):
        m = jax.tree.map(like, params, pspecs)
        return {"m": m, "v": m}
    if opt_name == "adafactor":
        def fact(p, spec):
            parts = list(spec) + [None] * (p.ndim - len(spec))
            if p.ndim >= 2:
                return {"vr": P(*parts[:-1]), "vc": P(*parts[:-2], parts[-1])}
            return {"v": P(*parts)}
        return {"s": jax.tree.map(fact, params, pspecs)}
    raise KeyError(opt_name)


# ---------------------------------------------------------------------- #
# Batch / cache specs
# ---------------------------------------------------------------------- #
def batch_specs(cfg: ModelConfig, shape: InputShape, mesh: Mesh):
    dax = data_axes(mesh)
    bax = dax if len(dax) > 1 else dax[0]
    if shape.kind in ("train", "prefill"):
        specs = {"tokens": P(bax, None)}
        if cfg.is_encoder_decoder:
            specs["src"] = P(bax, None, None)
        return specs
    # decode: cache + token
    nd = int(np.prod([mesh.shape[a] for a in dax]))
    batch_shardable = shape.global_batch % nd == 0 and shape.global_batch >= nd
    b = bax if batch_shardable else None
    seq = "model" if batch_shardable else ("model", *dax)

    def cache_spec(path, leaf):
        names = _path_names(path)
        name = next((n for n in reversed(names) if isinstance(n, str)), "")
        stacked = _is_stacked(names) or "layers" in names or "head_layers" in names
        core = leaf.shape[1:] if _is_stacked(names) else leaf.shape
        pre = (None,) if _is_stacked(names) else ()
        if name in ("k", "v"):        # (B, C, Hkv, hd)
            return P(*pre, b, seq, None, None)
        if name in ("xk", "xv"):      # cross-attn (B, S_src, Hkv, hd)
            return P(*pre, b, None, None, None)
        if name in ("ckv", "kr"):     # MLA (B, C, r)
            return P(*pre, b, seq, None)
        if name == "conv":            # (B, K-1, ch)
            return P(*pre, b, None, "model")
        if name == "state":           # (B, H, N, P)
            return P(*pre, b, "model", None, None)
        if name in ("index", "slot_pos"):
            return P() if leaf.ndim == 0 else P(None)
        return P(*([None] * leaf.ndim))

    def checked(path, leaf):
        return _fix(cache_spec(path, leaf), leaf.shape, mesh)

    cache = jax.tree_util.tree_map_with_path(checked,
                                             _cache_shape_tree(cfg, shape))
    return {"cache": cache, "token": P(b, None)}


def _cache_shape_tree(cfg, shape):
    from repro.models import api
    return jax.eval_shape(
        lambda: api.cache_init(cfg, shape.global_batch, shape.seq_len))


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
