"""Architecture + shape + approximate-multiplier configuration dataclasses."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["AmmConfig", "ArchConfig", "ShapeConfig", "SHAPES", "reduced"]


@dataclasses.dataclass(frozen=True)
class AmmConfig:
    """Approximate-matmul (the paper's technique) as a model-level feature.

    mode:
      "off"      — exact bf16/f32 matmuls (baseline hardware)
      "noise"    — WL-bit fixed-point quantization + calibrated white-noise
                   error injection (paper §II.B, scales to 671B)
      "bitexact" — the true Broken-Booth datapath, lowered to dense
                   contractions (kernels.bbm_matmul_scaled: exact-dot +
                   low-bit correction, O(B*N) live memory, bit-identical
                   to the scalar oracle kernels.ref.amm_dense_ref).
                   Non-Booth families (bam/kulkarni/etm) still take the
                   scalar closed forms: reduced configs only for those.
    """
    mode: str = "off"
    mul: str = "bbm0"          # multiplier family (core.multipliers registry)
    wl: int = 16
    param: int = 13            # VBL (or K for kulkarni)
    apply_to: str = "mlp"      # which matmul families are approximated:
                               #   "mlp"  — the gated MLPs (weight-side,
                               #            plane-cacheable)
                               #   "attn" — the attention score/value
                               #            products Q@K^T and P@V
                               #            (activation x activation;
                               #            mode="bitexact" Booth families
                               #            only — docs/attention.md)
                               #   "all"  — both
    use_pallas: bool = False   # mode="noise": fused quant_matmul Pallas
                               # kernel (quantize->MXU->in-kernel noise->
                               # descale; interpret-mode off TPU)

    def __post_init__(self):
        if self.apply_to not in ("mlp", "attn", "all"):
            raise ValueError(f"apply_to must be 'mlp', 'attn' or 'all', "
                             f"got {self.apply_to!r}")


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0           # 0 -> d_model // n_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    first_k_dense: int = 0
    routed_scaling_factor: float = 1.0  # routed-expert weights, after the
                                        # normalization over the top-k
    router_bias: bool = False   # DeepSeek-V3 noaux_tc: a correction bias
                                # added to the scores to choose experts only
    experts_held: int = 0       # expert share: routed experts this device
    expert_offset: int = 0      # holds (0: all), from expert_offset on
    # --- MLA (deepseek) ---
    use_mla: bool = False
    q_lora_rank: int = 0        # 0: a direct query projection (no q-LoRA)
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # --- MTP (deepseek) ---
    mtp_depth: int = 0
    # --- SSM (mamba2 / zamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 128
    ssm_conv: int = 4
    ssm_groups: int = 1
    # --- hybrid (zamba2) ---
    shared_attn_every: int = 0      # shared transformer block period
    # --- encoder-decoder (whisper) ---
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    encoder_len: int = 1500         # precomputed frame embeddings (stub)
    # --- modality frontend stub ---
    frontend: str = "none"          # none | audio | vision
    # --- paper technique ---
    amm: AmmConfig = dataclasses.field(default_factory=AmmConfig)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def n_local_experts(self) -> int:
        """Routed experts whose weights this device holds."""
        return self.experts_held or self.n_experts

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Archs eligible for the long_500k shape (SSM/hybrid)."""
        return self.family in ("ssm", "hybrid")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def reduced(cfg: ArchConfig, *, layers: int = 2, d_model: int = 64,
            vocab: int = 512) -> ArchConfig:
    """Tiny same-family config for CPU smoke tests."""
    scale = d_model / cfg.d_model
    def sc(x, lo=1):
        return max(lo, int(round(x * scale)))
    heads = max(2, min(cfg.n_heads, 4))
    kv = max(1, min(cfg.n_kv_heads, heads))
    return dataclasses.replace(
        cfg,
        n_layers=layers, d_model=d_model,
        n_heads=heads, n_kv_heads=kv, head_dim=d_model // heads,
        d_ff=4 * d_model if cfg.d_ff else 0,
        vocab=vocab,
        n_experts=min(cfg.n_experts, 8), top_k=min(cfg.top_k, 2),
        moe_d_ff=2 * d_model if cfg.moe_d_ff else 0,
        first_k_dense=min(cfg.first_k_dense, 1),
        q_lora_rank=sc(cfg.q_lora_rank, 8) if cfg.q_lora_rank else 0,
        kv_lora_rank=sc(cfg.kv_lora_rank, 8) if cfg.kv_lora_rank else 0,
        qk_nope_dim=16 if cfg.qk_nope_dim else 0,
        qk_rope_dim=8 if cfg.qk_rope_dim else 0,
        v_head_dim=16 if cfg.v_head_dim else 0,
        ssm_state=min(cfg.ssm_state, 16), ssm_headdim=16, ssm_chunk=16,
        shared_attn_every=2 if cfg.shared_attn_every else 0,
        n_encoder_layers=min(cfg.n_encoder_layers, 2),
        encoder_len=32 if cfg.is_encoder_decoder else cfg.encoder_len,
        mtp_depth=cfg.mtp_depth,
    )
