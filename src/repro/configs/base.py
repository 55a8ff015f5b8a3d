"""Config system for the repro framework.

Plain frozen dataclasses (hashable -> usable as jit static args).
Every assigned architecture file in this package exposes ``CONFIG`` built
from these dataclasses; ``repro.configs.registry`` maps arch-id -> config.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Gating Dropout (the paper's contribution)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GatingDropoutConfig:
    """Gating Dropout (Liu et al., ICML 2022).

    mode:
      "off"              -- plain MoE baseline.
      "gate_drop"        -- with prob. `rate` route all tokens to the local
                            expert group, skipping the all-to-all.
      "gate_expert_drop" -- with prob. `rate` skip the MoE sub-layer entirely
                            (residual passthrough; LayerDrop-style).
    local_combine:
      "prob" -- dropped steps weight the local expert output by the
                renormalized local softmax (gate still gets gradient).
      "one"  -- weight 1.0 (strict "ignore the gating network").
    """
    mode: str = "off"                  # off | gate_drop | gate_expert_drop
    rate: float = 0.0                  # paper: 0.3 gate_drop, 0.2 gate_expert_drop
    local_combine: str = "prob"        # prob | one
    # Execution strategy: "traced_cond" (lax.cond in one executable) or
    # "host_cond" (two executables, drop-on one has NO all-to-all; paper-faithful).
    strategy: str = "traced_cond"

    def __post_init__(self):
        assert self.mode in ("off", "gate_drop", "gate_expert_drop"), self.mode
        assert self.local_combine in ("prob", "one")
        assert self.strategy in ("traced_cond", "host_cond")
        assert 0.0 <= self.rate <= 1.0

    @property
    def enabled(self) -> bool:
        return self.mode != "off" and self.rate > 0.0


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 1                      # paper default k=1 (Switch)
    d_ff_expert: int = 0                # 0 -> use model d_ff
    n_shared_experts: int = 0           # DeepSeek-style always-on experts
    router_type: str = "softmax"        # softmax | sigmoid | hash
    capacity_factor: float = 1.0        # train (paper); eval uses eval_capacity_factor
    eval_capacity_factor: float = 2.0
    jitter_eps: float = 0.01            # input jitter (Fedus et al.) on by default
    balance_coef: float = 0.01          # aux balance loss coefficient
    router_z_coef: float = 0.0          # optional router z-loss
    moe_layer_period: int = 1           # 1 = every layer; 2 = every other (paper)
    first_dense_layers: int = 0         # deepseek-v3: first 3 layers dense
    ep_on_model: bool = False           # beyond-paper: expert parallelism over
                                        # data x model (a2a bytes / tp; no TP
                                        # inside experts). Needs E % (dp*tp)==0.
    # Execution backend (core/backend.py registry, DESIGN.md §6):
    #   auto | oracle | sharded | pallas | pallas_fused (megakernel, §11)
    backend: str = "auto"
    gating_dropout: GatingDropoutConfig = field(default_factory=GatingDropoutConfig)

    def __post_init__(self):
        assert self.backend in ("auto", "oracle", "sharded", "pallas",
                                "pallas_fused"), self.backend

    def d_ff(self, model_d_ff: int) -> int:
        return self.d_ff_expert or model_d_ff

    def is_moe_layer(self, layer_idx: int) -> bool:
        if layer_idx < self.first_dense_layers:
            return False
        return (layer_idx - self.first_dense_layers) % self.moe_layer_period == 0


# ---------------------------------------------------------------------------
# Attention variants
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 Multi-head Latent Attention."""
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD."""
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2                     # d_inner = expand * d_model
    chunk: int = 64                     # SSD chunk length
    conv_kernel: int = 4
    n_groups: int = 1                   # B/C groups (GVA-style)

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class VLMConfig:
    """Llama-3.2-Vision style gated cross-attention onto stub image embeds."""
    cross_attn_period: int = 5          # cross-attn layer every N layers
    n_image_tokens: int = 1601          # ViT stub output length (tokens)
    d_image: int = 1280                 # stub encoder width (projected to d_model)


@dataclass(frozen=True)
class EncDecConfig:
    n_encoder_layers: int = 12
    encoder_seq: int = 1500             # whisper: 1500 frames post-conv
    frontend: str = "stub"              # conv frontend stubbed: input_specs gives embeds
    encoder_causal: bool = False


@dataclass(frozen=True)
class HybridConfig:
    """Hymba: parallel attention + SSM heads within each layer."""
    n_meta_tokens: int = 128
    # fraction of layers using global attention (rest SWA); hymba uses 3 global
    global_attn_layers: Tuple[int, ...] = (0, 15, 31)


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    arch_id: str = "tiny"
    family: str = "dense"               # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 1024
    head_dim: int = 0                   # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    max_seq: int = 8192
    sliding_window: int = 0             # 0 = full attention
    norm: str = "rmsnorm"               # rmsnorm | layernorm
    act: str = "silu"                   # silu (gated) | gelu (non-gated)
    gated_mlp: bool = True
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    vlm: Optional[VLMConfig] = None
    encdec: Optional[EncDecConfig] = None
    hybrid: Optional[HybridConfig] = None
    mtp: bool = False                   # DeepSeek-V3 multi-token-prediction head
    dropout: float = 0.0
    dtype: str = "bfloat16"             # activation dtype
    param_dtype: str = "float32"
    remat: bool = True                  # checkpoint each layer
    fsdp: bool = False                  # shard weights over data axis too
    seq_parallel: bool = False          # shard layer-boundary activations
                                        # (sequence dim) over the model axis
    scan_layers: bool = True            # lax.scan over layer segments (fast
                                        # compile); False unrolls (exact
                                        # cost_analysis for the dry-run)
    banded_swa: bool = False            # sliding-window attention with block
                                        # skipping: O(L*W) instead of masked
                                        # O(L^2) (beyond-paper perf option)
    source: str = ""                    # citation for the config

    # ---- derived ----
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    def n_params(self) -> int:
        """Analytic parameter count (embeddings + blocks), for roofline."""
        d, dff, V = self.d_model, self.d_ff, self.vocab
        hd = self.head_dim_
        emb = V * d * (1 if self.tie_embeddings else 2)
        total = emb
        for i in range(self.n_layers):
            if self.mla is not None:
                m = self.mla
                attn = (d * m.q_lora_rank
                        + m.q_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                        + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
                        + self.n_heads * m.v_head_dim * d)
            else:
                attn = d * hd * (self.n_heads + 2 * self.n_kv_heads) + self.n_heads * hd * d
            if self.ssm is not None and self.family == "ssm":
                s = self.ssm
                di = s.d_inner(d)
                attn = d * (2 * di + 2 * s.n_groups * s.d_state + di // s.head_dim) + di * d
            mlp_mult = 3 if self.gated_mlp else 2
            if self.moe is not None and self.moe.is_moe_layer(i):
                dffe = self.moe.d_ff(dff)
                ffn = (self.moe.n_experts + self.moe.n_shared_experts) * mlp_mult * d * dffe
                ffn += self.moe.n_experts * d  # router
            else:
                ffn = mlp_mult * d * dff
            total += attn + ffn
        if self.encdec is not None:
            # encoder layers (honouring MoE period) + decoder cross-attn
            for i in range(self.encdec.n_encoder_layers):
                attn = 4 * d * d
                if self.moe is not None and self.moe.is_moe_layer(i):
                    dffe = self.moe.d_ff(dff)
                    ffn = (self.moe.n_experts + self.moe.n_shared_experts) * mlp_mult * d * dffe
                    ffn += self.moe.n_experts * d
                else:
                    ffn = mlp_mult * d * dff
                total += attn + ffn
            total += self.n_layers * 4 * d * d  # decoder cross attention
        return total

    def n_active_params(self) -> int:
        """Active params per token (MoE top-k only), for MODEL_FLOPS."""
        if self.moe is None:
            return self.n_params()
        d, dff = self.d_model, self.d_ff
        mlp_mult = 3 if self.gated_mlp else 2
        dffe = self.moe.d_ff(dff)
        per_layer_all = (self.moe.n_experts) * mlp_mult * d * dffe
        per_layer_act = (self.moe.top_k + self.moe.n_shared_experts) * mlp_mult * d * dffe
        n_moe_layers = sum(1 for i in range(self.n_layers) if self.moe.is_moe_layer(i))
        return self.n_params() - n_moe_layers * (per_layer_all - per_layer_act)


# ---------------------------------------------------------------------------
# Paged KV cache (serving, DESIGN.md §13)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PagedKVConfig:
    """Block-table-addressed decode cache (vLLM-style page pool).

    ``page_size`` logical positions per physical page (keep it a multiple
    of 8 — the paged flash kernel's KV block is one page). ``n_pages`` is
    the usable arena size (one extra scratch page is always appended);
    0 derives it from the slot pool it replaces: ``n_slots_equiv *
    ceil(seq_len / page_size)`` — equal paged-leaf KV bytes to an
    ``n_slots_equiv``-row slot pool. ``prefix_caching`` shares full
    prompt-prefix pages across requests via a token-hash page cache;
    ``reserve_pages`` is the admission headroom (a request is admitted
    only when its prompt pages + this reserve are free or evictable)."""
    page_size: int = 16
    n_pages: int = 0
    n_slots_equiv: int = 8
    prefix_caching: bool = True
    reserve_pages: int = 1

    def __post_init__(self):
        assert self.page_size >= 1
        assert self.reserve_pages >= 0


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                            # train | prefill | decode


INPUT_SHAPES = {
    "train_4k":    InputShape("train_4k",    4_096,   256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  InputShape("decode_32k",  32_768,  128, "decode"),
    "long_500k":   InputShape("long_500k",   524_288, 1,   "decode"),
}


@dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 5000
    schedule: str = "inverse_sqrt"       # inverse_sqrt | cosine | constant
    b1: float = 0.9
    b2: float = 0.99                     # paper: beta2 = 0.99
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    seed: int = 0
    steps: int = 1000
    microbatches: int = 1                # grad accumulation: activation mem /k
    moment_dtype: str = "float32"        # bfloat16 for the huge archs
    loss: str = "xent"                   # xent | xent+dae (paper Web-50)
    dae_coef: float = 1.0
    # surface the in-graph router/comm MetricsFrame (DESIGN.md §15) in
    # every step's metric dict. Off drops ONLY telemetry outputs: the
    # loss/update math is bitwise identical either way (tests/test_obs.py)
    metrics_frame: bool = True


def reduced(cfg: ModelConfig, **overrides) -> ModelConfig:
    """A smoke-test-sized variant of the same family (<=2 layers, d<=512, <=4 experts)."""
    kw = dict(
        n_layers=2,
        d_model=min(cfg.d_model, 256),
        d_ff=min(cfg.d_ff, 512) or 512,
        vocab=min(cfg.vocab, 512),
        max_seq=512,
        remat=False,
        fsdp=False,
        param_dtype="float32",
        dtype="float32",
    )
    n_heads = min(cfg.n_heads, 4)
    kw["n_heads"] = n_heads
    kw["n_kv_heads"] = max(1, min(cfg.n_kv_heads, n_heads))
    while n_heads % kw["n_kv_heads"] != 0:
        kw["n_kv_heads"] -= 1
    kw["head_dim"] = kw["d_model"] // n_heads
    if cfg.sliding_window:
        kw["sliding_window"] = 128
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=4, top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=min(cfg.moe.d_ff(cfg.d_ff), 256),
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            first_dense_layers=min(cfg.moe.first_dense_layers, 1))
    if cfg.mla is not None:
        kw["mla"] = MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                              qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)
        kw["head_dim"] = 0
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk=16)
    if cfg.vlm is not None:
        kw["vlm"] = VLMConfig(cross_attn_period=2, n_image_tokens=16, d_image=64)
    if cfg.encdec is not None:
        kw["encdec"] = dataclasses.replace(cfg.encdec, n_encoder_layers=2, encoder_seq=32)
    if cfg.hybrid is not None:
        kw["hybrid"] = HybridConfig(n_meta_tokens=4, global_attn_layers=(0,))
        kw["ssm"] = SSMConfig(d_state=16, head_dim=32, expand=2, chunk=16)
    kw.update(overrides)
    return dataclasses.replace(cfg, **kw)
