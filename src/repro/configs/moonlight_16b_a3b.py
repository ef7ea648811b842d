"""moonlight-16b-a3b — Moonlight-16B-A3B [hf:moonshotai/Moonlight-16B-A3B,
config.json; model_type deepseek_v3].

27 layers at d_model 2048: the first dense (SwiGLU 11,264), then 26 MoE
layers of 64 routed experts (width 1,408, 6 per token) and 2 shared ones.
Routing is DeepSeek-V3's noaux_tc with one group: sigmoid scores, top-6 on
score + a selection bias, the 6 gates renormalised and scaled by 2.446, no
token dropped; ``seq_aux: true`` adds the sequence-wise balance loss.
Attention is MLA with 16 heads and no query compression (``q_lora_rank:
null``): kv_lora_rank 512, nope/rope/v head dims 128/64/128, RoPE theta
50,000. RMSNorm eps 1e-5, vocabulary 163,840, untied embeddings, 8,192
positions.

``ONE_CHIP`` is what one chip holds of the deployment the FEEL cell
states: each layer expert-parallel over 8 chips (experts 0-7 of 64 here,
the router still over all 64), the vocabulary sliced to its first eighth
(ids 0-20,479), and the dense layer plus one period of 4 MoE layers, the
other layers lying on further pipeline stages. No width is cut:
568.5 M float32 parameters, 2.27 GB.
"""
import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11_264,                     # the leading dense layer's SwiGLU
    vocab_size=163_840,
    head_dim=128,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(n_routed=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  scoring="sigmoid", routed_scaling=2.446,
                  normalize_gates=True, dropless=True,
                  # the balance loss's weight is not published; DeepSeek-V3
                  # used 1e-4 (arXiv:2412.19437 §4.2)
                  router_aux_weight=1e-4),
    first_dense_layers=1,
    long_context_window=8192,        # long_500k SWA variant (DESIGN.md)
    rope_theta=50_000.0,
    norm_eps=1e-5,
    citation="[hf:moonshotai/Moonlight-16B-A3B]",
)

CHIPS_PER_LAYER = 8

ONE_CHIP = dataclasses.replace(
    CONFIG,
    name="moonlight-16b-a3b-chip",
    n_layers=5,                      # the dense layer + 4 MoE layers
    vocab_size=CONFIG.vocab_size // CHIPS_PER_LAYER,
    moe=dataclasses.replace(
        CONFIG.moe, held_experts=CONFIG.moe.n_routed // CHIPS_PER_LAYER),
    dtype="float32",
)
