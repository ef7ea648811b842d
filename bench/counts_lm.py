"""Operations and bytes of a federated LM round, computed from shapes
(the ``feel_moonlight`` configuration: an MLA + expert-layer decoder).

Only what the algorithm needs is counted: matrix products of the forward
pass (2 operations a multiply-add), and in training twice that again for
the backward pass's weight and input gradients; recomputation under
rematerialisation is not counted, nor is elementwise work. Attention's
two products are counted over the whole window, as the program computes
them. A held expert's products are counted for the token-expert pairs it
would compute under even routing: top-k over the router's experts
spreads ``tokens x k x held / router_experts`` pairs over the held
experts. The driver's set-up balances the selection biases on two
windows; the training windows still route unevenly, as
``expert_imbalance.lm`` and the ``moe.held_pairs`` counter show.
Bytes are what must cross HBM at least once: each forward-pass call
reads the parameters once, each training step reads and writes them once,
and every token id is read once.
"""
from __future__ import annotations

from typing import Dict, Sequence

F32 = 4


def forward_macs_per_token(cfg: dict) -> Dict[str, float]:
    """Multiply-adds per token of the cut model's forward pass, by part."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, S = cfg["kv_lora_rank"], cfg["data"]["seq"]
    E, K, held = (cfg["router_experts"], cfg["num_experts_per_tok"],
                  cfg["n_routed_experts"])
    f, F = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    attn_proj = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) \
        + H * dv * d
    attn_scores = S * H * (dn + dr + dv)
    expert = 3 * d * f
    return {
        "attention": (n_dense + n_moe) * (attn_proj + attn_scores),
        "dense_mlp": n_dense * 3 * d * F,
        "router": n_moe * d * E,
        "shared_experts": n_moe * cfg["n_shared_experts"] * expert,
        "held_experts": n_moe * K * held / E * expert,
        "head": d * cfg["vocab_size"],
    }


def forward_flops_per_token(cfg: dict) -> float:
    return 2.0 * sum(forward_macs_per_token(cfg).values())


def lm_round(cfg: dict, windows_sel: Sequence[int], n_test: int
             ) -> Dict[str, float]:
    """One round's device work: each selected UE's local steps (one window
    a step, ``local_epochs`` times), its local metric over its windows
    (one forward call a window), its upload scored on the test windows,
    and the global model's prediction and loss on them. ``expert_slots``
    counts (step, expert layer, held expert) triples: the mean held
    expert's pairs per step is ``held_pairs / expert_slots``."""
    seq, epochs = cfg["data"]["seq"], cfg["feel"]["local_epochs"]
    fwd = forward_flops_per_token(cfg)
    pbytes = F32 * cfg["params"]
    steps = float(sum(windows_sel)) * epochs
    metric_calls = float(sum(windows_sel))
    evals = len(windows_sel) + 2                 # uploads + global twice
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return {
        "train_steps": steps,
        "train_flops": 3.0 * fwd * seq * steps,
        "train_bytes": (2.0 * pbytes + 4 * seq) * steps,
        "eval_flops": fwd * seq * (metric_calls + evals * n_test),
        "eval_bytes": pbytes * (metric_calls + evals)
        + 4.0 * seq * (metric_calls + evals * n_test),
        "expert_slots": steps * n_moe * cfg["n_routed_experts"],
    }
