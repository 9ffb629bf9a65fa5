"""Configuration dataclasses for the K-GT-Minimax framework.

Everything that defines a run — model architecture, minimax objective, the
K-GT-Minimax algorithm hyperparameters, mesh/sharding layout, and input shape —
is a frozen dataclass here.  Arch files under ``repro/configs/`` instantiate
``ModelConfig`` with the exact assigned specs; ``repro/configs/shapes.py`` holds
the four assigned input shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

# Block kinds a decoder stack may be composed of.
BLOCK_ATTN = "attn"            # full causal self-attention + MLP
BLOCK_SLIDING = "sliding"      # sliding-window causal attention + MLP
BLOCK_MOE = "moe"              # attention + MoE MLP
BLOCK_SSM = "ssm"              # Mamba2 SSD block (attention-free)
BLOCK_RGLRU = "rglru"          # RG-LRU recurrent block (Griffin/Hawk style)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0
    top_k: int = 2
    # d_ff of EACH expert (assigned configs give the per-expert width).
    expert_d_ff: int = 0
    router_aux_coef: float = 0.01
    router_jitter: float = 0.0
    capacity_factor: float = 1.25
    dispatch: str = "dense"  # "dense" (one-hot capacity) | "sorted" (ragged_dot)
    # Experts this chip holds of ``num_experts`` (the first ones; 0 = all):
    # the layer routes over every expert and computes only its own experts'
    # part of the result (expert parallelism's share, "sorted" dispatch).
    experts_held: int = 0

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block configuration."""
    d_state: int = 128
    d_head: int = 64           # P in the SSD paper
    expand: int = 2            # d_inner = expand * d_model
    chunk: int = 64            # SSD chunk length
    d_conv: int = 4            # depthwise conv width


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    """RG-LRU (RecurrentGemma) configuration."""
    lru_width: int = 0         # 0 -> d_model
    conv_width: int = 4
    block_pattern: Tuple[str, ...] = ("rglru", "rglru", "attn_local")
    local_window: int = 2048


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                      # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                   # 0 -> d_model // num_heads
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    # Block pattern; if empty, derived from arch_type (all-attn / all-moe / ...).
    block_pattern: Tuple[str, ...] = ()
    moe: MoEConfig = MoEConfig()
    ssm: SSMConfig = SSMConfig()
    rglru: RGLRUConfig = RGLRUConfig()
    # Sliding-window size used when a "sliding" block is selected (also the
    # beyond-paper long-context variant for dense archs).
    sliding_window: int = 4096
    # When > 0, full-attention blocks (attn/moe) switch to this sliding window
    # — the long_500k variant for otherwise-quadratic archs (see DESIGN.md §5).
    long_context_window: int = 0
    # Modality frontend stub: number of prefix embedding tokens supplied by
    # input_specs() (vlm: vision patches; 0 = none).
    num_prefix_tokens: int = 0
    # Audio: number of parallel codebook streams (musicgen).
    num_codebooks: int = 0
    # Granite's scalar multipliers; the defaults emit no operation.
    embedding_multiplier: float = 1.0   # embeddings scaled by it
    attention_multiplier: float = 0.0   # score scale; 0 -> head_dim ** -0.5
    residual_multiplier: float = 1.0    # each block's attention / MLP branch
    logits_scaling: float = 1.0         # logits divided by it
    # Source citation for the assigned config.
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    def blocks(self) -> Tuple[str, ...]:
        """Per-layer block kinds, length == num_layers."""
        if self.block_pattern:
            pat = self.block_pattern
        elif self.arch_type == "moe":
            pat = (BLOCK_MOE,)
        elif self.arch_type == "ssm":
            pat = (BLOCK_SSM,)
        elif self.arch_type == "hybrid":
            pat = self.rglru.block_pattern
        else:
            pat = (BLOCK_ATTN,)
        reps = -(-self.num_layers // len(pat))
        return (pat * reps)[: self.num_layers]

    def param_count(self) -> int:
        """Approximate parameter count (embedding + blocks + final norm +
        head); an MoE layer counts the experts held here."""
        d, hd = self.d_model, self.resolved_head_dim
        n_q, n_kv = self.num_heads, self.num_kv_heads
        total = self.vocab_size * d + d  # embed, final norm
        if not self.tie_embeddings:
            total += self.vocab_size * d
        for kind in self.blocks():
            if kind in (BLOCK_ATTN, BLOCK_SLIDING, BLOCK_MOE):
                attn = d * (n_q * hd) + 2 * d * (n_kv * hd) + (n_q * hd) * d
                if self.qkv_bias:
                    attn += (n_q + 2 * n_kv) * hd
                total += attn
                if kind == BLOCK_MOE:
                    m = self.moe
                    total += d * m.num_experts  # router over every expert
                    total += m.held * 3 * d * m.expert_d_ff
                else:
                    total += 3 * d * self.d_ff  # gate/up/down
                total += 2 * d  # norms
            elif kind == BLOCK_SSM:
                s = self.ssm
                d_in = s.expand * d
                nheads = d_in // s.d_head
                total += d * (2 * d_in + 2 * s.d_state + nheads)  # in_proj-ish
                total += d_in * d  # out_proj
                total += d_in * s.d_conv + 2 * nheads + d  # conv, A, D, norm
            elif kind == "attn_local":
                attn = d * (n_q * hd) + 2 * d * (n_kv * hd) + (n_q * hd) * d
                total += attn + 3 * d * self.d_ff + 2 * d
            elif kind == BLOCK_RGLRU:
                w = self.rglru.lru_width or d
                total += d * w * 2 + w * d  # in (x,gate) + out
                total += 3 * w  # recurrent/input gates diag-ish + Λ
                total += 3 * d * self.d_ff + 2 * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: the top_k experts, of which a
        share of ``held / num_experts`` is expected among the held ones)."""
        if self.arch_type != "moe":
            return self.param_count()
        m = self.moe
        n_moe = sum(1 for k in self.blocks() if k == BLOCK_MOE)
        idle = m.held - m.top_k * m.held / m.num_experts
        unused = n_moe * idle * 3 * self.d_model * m.expert_d_ff
        return self.param_count() - round(unused)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


# ---------------------------------------------------------------------------
# Minimax objective
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MinimaxConfig:
    objective: str = "dro"     # quadratic | dro | adversarial
    # DRO: number of loss groups (= d_y); strong-concavity modulus mu.
    num_groups: int = 8
    mu: float = 1.0
    # adversarial: perturbation scale / dims handled by objective impl.
    adv_scale: float = 0.1


# ---------------------------------------------------------------------------
# K-GT-Minimax algorithm hyperparameters (Algorithm 1)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AlgorithmConfig:
    algorithm: str = "kgt_minimax"  # kgt_minimax | dsgda | local_sgda | gt_gda
    num_clients: int = 4
    local_steps: int = 2            # K
    eta_cx: float = 1e-3            # local stepsize for x
    eta_cy: float = 1e-2            # local stepsize for y
    eta_sx: float = 1.0             # communication stepsize for x
    eta_sy: float = 1.0             # communication stepsize for y
    topology: str = "ring"          # ring | torus | full | exp | star
    # Gossip implementation: "dense" (faithful W-einsum), "ring" (ppermute),
    # "fused_dense"/"fused_ring" (pack Delta+params into one collective per
    # leaf), "pallas_packed" (ravel the whole state into one (n, D) buffer and
    # run the fused gossip/correction/mixing epilogue in a single pass —
    # see repro.core.packing + repro.kernels.gossip), or "sparse_packed"
    # (same fused packed epilogue, but W is padded-CSR neighbor lists and
    # gossip is a neighbor-row gather — O(n·max_deg·D), never an (n, n)
    # array; the scaling path for num_clients ≳ 512, see
    # repro.core.sparse_topology + repro.kernels.neighbor_gossip).
    mixing_impl: str = "dense"
    # Backend for the pallas_packed/sparse_packed epilogue: "auto" (Pallas
    # kernel on TPU, packed-xla oracle elsewhere), "pallas", "interpret",
    # or "xla".
    gossip_backend: str = "auto"
    gossip_dtype: str = "float32"   # beyond-paper: "bfloat16" halves gossip bytes
    # Error-feedback compressed gossip (Sun & Wei's communication-efficient
    # federated minimax line): quantize the transmitted round delta with a
    # deterministic quantizer ("bf16" | "int8") and carry the quantization
    # residual as per-client EF state (KGTState.ef_x/ef_y).  None = exact.
    # Valid only for the packed lowerings (mixing_impl "pallas_packed" /
    # "fused_round") — the per-leaf impls have no packed buffer to quantize.
    gossip_compress: Optional[str] = None
    # Inner optimizer applied to local steps ("sgd" is the faithful Algorithm 1).
    inner_opt: str = "sgd"
    # Correction-state dtype: bfloat16 halves tracking-state memory (the
    # internvl2 memory lever in EXPERIMENTS.md §Perf); float32 is faithful.
    correction_dtype: str = "float32"
    # Time-varying gossip: cycle through these topologies round-robin
    # (e.g. ("ring", "exp")); empty = static cfg.topology.  Covered by the
    # changing-topology analysis of [KLB+20] the paper builds on.
    topology_cycle: Tuple[str, ...] = ()
    # --- stochastic topologies + partial participation (beyond-paper churn
    # axes, repro.core.stochastic_topology).  The family is a static program
    # property; the rates are traced scalars.  "static" + participation_rate
    # 1.0 = the paper's fixed-W/full-participation setting.
    topology_family: str = "static"   # static | erdos_renyi | pairwise | dropout
    edge_prob: float = 0.5            # erdos_renyi: P[link present] per round
    client_drop_prob: float = 0.3     # dropout family: P[client drops links]
    participation_rate: float = 1.0   # < 1: per-round Bernoulli client mask
    topology_seed: int = 0            # seeds the W/mask sampling streams
    # --- Byzantine adversary axis (repro.core.adversary).  num_byzantine
    # clients (the first f client slots) corrupt their *outgoing* Δ each
    # round per `attack`; honest clients are untouched.  Defending requires
    # a robust mixing_impl ("coord_median"/"trimmed_mean" and their
    # sparse_* forms) — plain gossip averages the poison in.  `robust_trim`
    # is the number of extreme values trimmed per side by trimmed_mean.
    num_byzantine: int = 0
    attack: str = "honest"            # honest | sign_flip | large_norm | random_noise
    attack_scale: float = 1.0         # attack magnitude multiplier
    robust_trim: int = 1              # trimmed_mean: values trimmed per side


# ---------------------------------------------------------------------------
# Mesh / sharding
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    num_clients: int = 4       # clients axis of the logical mesh
    fsdp: int = 4
    model: int = 16
    # Parameter sharding mode within a client: "fsdp2d" shards params over
    # (fsdp, model); "replicated" keeps them client-replicated (small models).
    param_mode: str = "fsdp2d"
    moe_expert_parallel: bool = False
    # shard attention heads over 'model' via all-to-all instead of
    # all-gathering the seq-sharded residual (Megatron-SP style switch)
    attn_heads_sharding: bool = False
    # residual sharding: "batch_seq" (fsdp, model) or "batch" (fsdp only)
    residual_mode: str = "batch_seq"
    remat: bool = True

    @property
    def devices_needed(self) -> int:
        return self.num_clients * self.fsdp * self.model


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainConfig:
    rounds: int = 100
    seed: int = 0
    dtype: str = "bfloat16"         # activations/compute dtype
    param_dtype: str = "float32"
    schedule: str = "constant"      # constant | cosine | wsd
    warmup_rounds: int = 10
    decay_start_frac: float = 0.8   # WSD stable->decay point
    log_every: int = 10
    checkpoint_every: int = 0       # 0 = off
    checkpoint_dir: str = "/tmp/repro_ckpt"


@dataclasses.dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: InputShape
    minimax: MinimaxConfig = MinimaxConfig()
    algo: AlgorithmConfig = AlgorithmConfig()
    mesh: MeshConfig = MeshConfig()
    train: TrainConfig = TrainConfig()
