"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``. Families:
  dense   — pre-norm decoder-only transformer with GQA attention
  moe     — dense attention + mixture-of-experts MLPs (shared + routed)
  ssm     — attention-free Mamba2/SSD stack
  hybrid  — Jamba-style interleave of Mamba2 and attention layers + MoE
  audio   — encoder-decoder backbone consuming precomputed frame embeddings
  vlm     — early-fusion decoder (VQ image tokens share the text vocab)
  mlp     — the paper's MNIST MLP (federated learning experiments)
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts sub-config (GShard-style capacity, sort-based dispatch)."""
    n_routed: int                 # routed experts
    top_k: int
    d_ff_expert: int              # hidden width of each routed expert
    n_shared: int = 0             # always-active shared experts
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    normalize_gates: bool = True  # renormalize top-k gate probs (DeepSeek style)
    # >1: group-local dispatch — tokens are grouped (aligned with the data
    # axis), sort/scatter happen within a group, and only the expert einsum
    # crosses shards (all-to-all). 0/1 = single global dispatch (SPMD-hostile
    # scatter; kept as the recorded baseline). See EXPERIMENTS.md §Perf.
    dispatch_groups: int = 0
    # Routing rule. "softmax": softmax scores, top-k, the gates
    # renormalised where ``normalize_gates``. "sigmoid": DeepSeek-V3's
    # noaux_tc rule — sigmoid scores, top-k chosen on score + a per-expert
    # selection bias (never trained by gradient: DeepSeek-V3 moves it by a
    # balance rule of its own, which local training leaves out), the chosen
    # scores renormalised (``normalize_gates``) and scaled by
    # ``routed_scaling``.
    scoring: str = "softmax"
    routed_scaling: float = 1.0
    # Compute path. False: capacity dropping (the sort-and-scatter layer;
    # softmax scoring only). True: no token dropped — the expert-parallel
    # layer, which routes over all ``n_routed`` experts, computes the part
    # of the layer that experts [0, held_experts) add (0 = all of them)
    # and weighs the sequence-wise balance loss by ``router_aux_weight``.
    dropless: bool = False
    held_experts: int = 0

    def __post_init__(self):
        if not self.dropless and (self.scoring != "softmax"
                                  or self.held_experts):
            raise ValueError("sigmoid scoring and held experts need the "
                             "dropless layer (dropless=True)")

    @property
    def n_held(self) -> int:
        return self.held_experts or self.n_routed


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD sub-config."""
    d_state: int = 128            # N
    head_dim: int = 64            # P
    expand: int = 2               # d_inner = expand * d_model
    n_groups: int = 1             # G (B/C groups)
    conv_kernel: int = 4
    chunk: int = 256              # SSD chunk length Q
    dt_min: float = 0.001
    dt_max: float = 0.1
    # dtype of the materialised intra-chunk decay/score tensors (hillclimb
    # lever: bf16 halves the dominant HBM traffic; state stays fp32)
    compute_dtype: str = "float32"


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention sub-config [arXiv:2412.19437].
    ``q_lora_rank=None`` projects queries directly (Moonlight, V2-Lite)."""
    q_lora_rank: Optional[int] = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm | mlp
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                     # dense-MLP hidden width (0 for pure-SSM)
    vocab_size: int
    citation: str = ""

    head_dim: int = 0             # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    qkv_bias: bool = False
    qk_norm: bool = False         # Chameleon-style query/key RMSNorm
    norm_eps: float = 1e-5

    # Sliding-window attention. ``sliding_window`` applies to ALL shapes
    # (StarCoder2 native). ``long_context_window`` is the explicit variant used
    # only for the long_500k shape on otherwise-full-attention archs; None
    # means the arch either handles long context natively (ssm/hybrid) or
    # skips the shape (enc-dec).
    sliding_window: Optional[int] = None
    long_context_window: Optional[int] = None

    # MoE
    moe: Optional[MoEConfig] = None
    moe_layer_period: int = 1     # apply MoE every p-th layer (Jamba: 2)
    first_dense_layers: int = 0   # DeepSeek: first k layers use dense MLP

    # SSM / hybrid
    ssm: Optional[SSMConfig] = None
    attn_layer_period: int = 0    # hybrid: one attention layer per p layers
    attn_layer_offset: int = 4    # position of the attention layer in a block

    # Encoder-decoder (audio)
    encoder_layers: int = 0
    is_encoder_decoder: bool = False
    frontend: str = "none"        # none | audio | vlm  (stubs per carve-out)

    # DeepSeek extras
    mla: Optional[MLAConfig] = None
    mtp: bool = False             # depth-1 multi-token-prediction head

    dtype: str = "bfloat16"
    # Scan super-block length; derived in __post_init__ if 0.
    block_len: int = 0
    # lax.scan unroll factor for the layer scan (dry-run cost extraction uses
    # fully-unrolled short variants; production configs keep 1).
    scan_unroll: int = 1

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.block_len == 0:
            p = 1
            if self.attn_layer_period:
                p = max(p, self.attn_layer_period)
            if self.moe is not None:
                p = max(p, self.moe_layer_period)
            object.__setattr__(self, "block_len", p)

    # ------------------------------------------------------------------ #
    # Layer-pattern helpers
    # ------------------------------------------------------------------ #
    @property
    def scanned_layers(self) -> int:
        return self.n_layers - self.first_dense_layers

    @property
    def n_blocks(self) -> int:
        assert self.scanned_layers % self.block_len == 0, (
            f"{self.name}: {self.scanned_layers} layers not divisible by "
            f"block_len {self.block_len}")
        return self.scanned_layers // self.block_len

    def layer_kind(self, idx_in_block: int) -> dict:
        """Describe sub-layer ``idx_in_block`` of a scan super-block."""
        if self.family == "ssm":
            return {"mixer": "ssm", "mlp": "none"}
        mixer = "attn"
        if self.attn_layer_period:
            mixer = ("attn" if idx_in_block % self.attn_layer_period
                     == self.attn_layer_offset % self.attn_layer_period
                     else "ssm")
        mlp = "dense"
        if self.moe is not None and (idx_in_block % self.moe_layer_period
                                     == self.moe_layer_period - 1):
            mlp = "moe"
        if self.family == "ssm":
            mlp = "none"
        return {"mixer": mixer, "mlp": mlp}

    def block_pattern(self) -> Tuple[dict, ...]:
        return tuple(self.layer_kind(i) for i in range(self.block_len))

    # ------------------------------------------------------------------ #
    # Analytic parameter counts (for MODEL_FLOPS = 6 N D roofline term)
    # ------------------------------------------------------------------ #
    def param_count(self, active_only: bool = False) -> int:
        d, hd = self.d_model, self.head_dim
        n = 0
        # embeddings + head (untied)
        n += 2 * self.vocab_size * d
        for b in range(self.n_blocks):
            for k in self.block_pattern():
                n += self._mixer_params(k["mixer"])
                n += self._mlp_params(k["mlp"], active_only)
                n += 2 * d  # two rms-norm scales
        for _ in range(self.first_dense_layers):
            n += self._mixer_params("attn")
            n += self._dense_mlp_params(self.d_ff)
            n += 2 * d
        if self.is_encoder_decoder:
            for _ in range(self.encoder_layers):
                n += self._mixer_params("attn") + self._dense_mlp_params(self.d_ff)
                n += 2 * d
            # cross attention per decoder layer
            n += self.n_layers * (self._mixer_params("attn") + d)
        n += d  # final norm
        if self.mtp:
            n += (self._mixer_params("attn") + self._dense_mlp_params(self.d_ff)
                  + 2 * d * d + 3 * d)     # combine-proj (2d x d) + norms
        return n

    def _mixer_params(self, kind: str) -> int:
        d, hd = self.d_model, self.head_dim
        if kind == "attn":
            if self.mla is not None:
                m = self.mla
                qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
                n = (d * self.n_heads * qk_hd if m.q_lora_rank is None else
                     d * m.q_lora_rank + m.q_lora_rank * self.n_heads * qk_hd)
                n += d * (m.kv_lora_rank + m.qk_rope_head_dim)
                n += m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
                n += self.n_heads * m.v_head_dim * d
                return n
            q = d * self.n_heads * hd
            kv = 2 * d * self.n_kv_heads * hd
            o = self.n_heads * hd * d
            return q + kv + o
        if kind == "ssm":
            s = self.ssm
            d_in = s.expand * d
            nh = d_in // s.head_dim
            proj_in = d * (2 * d_in + 2 * s.n_groups * s.d_state + nh)
            conv = (d_in + 2 * s.n_groups * s.d_state) * s.conv_kernel
            out = d_in * d
            extra = nh * 3 + d_in  # A_log, D, dt_bias, gated-norm scale
            return proj_in + conv + out + extra
        return 0

    def _dense_mlp_params(self, d_ff: int) -> int:
        return 3 * self.d_model * d_ff  # SwiGLU: gate, up, down

    def _mlp_params(self, kind: str, active_only: bool) -> int:
        if kind == "none":
            return 0
        if kind == "dense":
            return self._dense_mlp_params(self.d_ff)
        m = self.moe
        per = self._dense_mlp_params(m.d_ff_expert)
        router = self.d_model * m.n_routed
        n_exp = (m.top_k if active_only else m.n_held) + m.n_shared
        return n_exp * per + router


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode


TRAIN_4K = InputShape("train_4k", 4_096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32_768, 128, "decode")
LONG_500K = InputShape("long_500k", 524_288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


@dataclass(frozen=True)
class TrainConfig:
    """Optimizer / training-loop hyper-parameters."""
    optimizer: str = "adamw"      # sgd | momentum | adam | adamw | adafactor
    lr: float = 3e-4
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    remat: bool = True


@dataclass(frozen=True)
class FeelConfig:
    """Federated-edge-learning round configuration (the paper's Table I)."""
    n_ues: int = 50               # K
    n_malicious: int = 5
    # Candidate population size N (DESIGN.md §12). ``n_ues`` stays the
    # bandwidth budget K — the Eq. 9 fraction denominator, the Alg. 2
    # knapsack capacity — while the scheduler ranks over all N candidates.
    # None pins the legacy N == K regime (every pre-population caller).
    population: Optional[int] = None
    rounds: int = 15              # t_max
    local_epochs: int = 3         # epsilon (paper leaves it unspecified)
    deadline_s: float = 300.0     # T
    bandwidth_hz: float = 1e6     # B
    model_size_bits: float = 100e3 * 8   # s = 100 Ko
    tx_power_dbm: float = -23.0   # P_k
    noise_dbm_hz: float = -174.0  # N0
    pathloss_exp: float = 3.76    # alpha (not given in paper; 3GPP UMa value)
    cell_side_m: float = 500.0
    min_selected: int = 5         # N in Algorithm 1
    # data-quality weights
    omega_rep: float = 0.5        # omega_1
    omega_div: float = 0.5        # omega_2
    gamma: Tuple[float, float, float] = (1 / 3, 1 / 3, 1 / 3)
    eta: float = 1.0              # reputation rate (paper: eta = 1)
    # beta_i are unspecified in the paper; weighted toward the server-side
    # test gap, the stronger poisoning signal (see EXPERIMENTS.md)
    beta1: float = 0.2            # weight of (acc_local - avg_acc)
    beta2: float = 0.8            # weight of (acc_local - acc_test)
    # threat-model metrics (core/attacks.py): the attacked class counts as
    # recovered once the source->target attack success rate stays below
    # this threshold (feeds ``recovery_rounds``)
    recovery_threshold: float = 0.5
    # default defense policy (core/defenses.py registry name) — the server
    # resolves it when no explicit ``defense=`` is given, so a config can
    # pin a defended baseline; sweeps vary defenses per run via
    # ``run_sweep(defenses=[...])`` while sharing one config
    defense: str = "none"
    # default task (federated/task.py registry name): the model/data pair
    # the federated round trains — "mnist_mlp" (the paper's §V protocol)
    # or "lm_tiny" (federated LM fine-tuning). Sweeps vary tasks per run
    # via ``run_sweep(tasks=[...])``, which sets this field per run.
    task: str = "mnist_mlp"
    # --- execution mode (federated/async_engine.py, DESIGN.md §13) ---
    # "sync" runs Alg. 1 as lockstep rounds; "async" runs the
    # event-driven engine: each scheduled UE's upload arrives at a
    # simulated per-UE time from the Eq. 6/7 latency model, the server
    # aggregates on a buffer/deadline trigger with staleness-discounted
    # weights, and the next wave is dispatched right after each
    # aggregation (cohort selection overlaps in-flight training).
    mode: str = "sync"
    # aggregate once this many uploads are buffered; None waits for every
    # in-flight upload (the synchronous lockstep limit)
    async_buffer: Optional[int] = None
    # also aggregate at dispatch_time + deadline sim-seconds with whatever
    # has arrived (None = no deadline trigger)
    async_deadline: Optional[float] = None
    # staleness-discount base (core/control.py::staleness_discount): an
    # upload computed on a model ``a`` aggregations old weighs
    # sizes * async_staleness**a. a = 0 gives exactly 1.0 — the FedAvg
    # weight, bit-for-bit — which is what makes the synchronous engine
    # the zero-latency oracle.
    async_staleness: float = 0.5
    # scales every simulated upload latency; 0.0 is the zero-latency
    # oracle limit where mode="async" must reproduce mode="sync" exactly
    async_latency_scale: float = 1.0
    # AR(1)/Gauss-Markov small-scale fading correlation rho across
    # consecutive channel draws (core/wireless.py): 0.0 keeps the legacy
    # memoryless Rayleigh draw bit-for-bit; rho in (0, 1) gives each UE
    # persistent block-fading state with stationary |h|^2 ~ Exp(1).
    channel_corr: float = 0.0
    # client compute model (Eq. 6). zeta/f are unspecified in the paper;
    # calibrated so t_train spans [~1s, ~375s] against T=300s — large datasets
    # on slow UEs can blow the deadline, which is exactly the paper's
    # motivation for joint selection + bandwidth allocation.
    cycles_per_bit: float = 2e3   # zeta_k
    cpu_hz_min: float = 5e7       # f_k drawn uniformly in [min, max]
    cpu_hz_max: float = 5e8
    sample_bits: float = 28 * 28 * 8

    # ------------------------------------------------------------------ #
    # Derived linear-scale wireless constants. Both control planes — the
    # host-numpy oracle (core/wireless.py) and the batched JAX kernel
    # (core/control.py) — must feed Eq. 4/9 the exact same float64
    # scalars, so the dBm -> watt conversion lives here, once
    # (``dbm_to_watt`` below; wireless.py re-exports it).
    # ------------------------------------------------------------------ #
    @property
    def n_population(self) -> int:
        """Candidate population size N (defaults to the budget K)."""
        n = self.population if self.population is not None else self.n_ues
        assert n >= self.n_ues, (
            f"population {n} smaller than the bandwidth budget K="
            f"{self.n_ues}")
        return n

    @property
    def p_watt(self) -> float:
        """Uplink transmit power P_k in watts."""
        return dbm_to_watt(self.tx_power_dbm)

    @property
    def n0_watt_hz(self) -> float:
        """Noise power spectral density N0 in W/Hz."""
        return dbm_to_watt(self.noise_dbm_hz)


def dbm_to_watt(dbm: float) -> float:
    return 10.0 ** (dbm / 10.0) / 1000.0
