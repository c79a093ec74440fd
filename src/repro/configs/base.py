"""Model/config registry for the RANL framework.

Every assigned architecture from the public pool gets one module in this
package defining a :class:`ModelConfig` with the exact published dimensions
(citation recorded in ``source``).  ``smoke_variant`` derives the reduced
configuration used by CPU smoke tests (2 layers, d_model <= 512, <= 4
experts) so the same code path is exercised end-to-end without TPU-scale
allocation.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int              # 0 for attention-free archs
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0           # 0 -> d_model // num_heads
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6      # RMSNorm epsilon
    # --- MoE ---
    num_experts: int = 0        # experts the router scores over
    experts_per_token: int = 0
    experts_held: int = 0       # experts whose weights are held (0 -> all)
    moe_d_ff: int = 0           # routed/shared expert width (0 -> d_ff)
    num_shared_experts: int = 0
    first_dense_layers: int = 0  # leading layers with a dense FFN
    router_score: str = "softmax"  # softmax | sigmoid
    routed_scale: float = 1.0   # scale of the renormalised top-k weights
    router_aux_weight: float = 0.01
    # --- latent attention (MLA): kv_lora_rank > 0 turns it on ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_conv_width: int = 4
    # --- RWKV ---
    attn_free: bool = False
    rwkv_head_dim: int = 64
    # --- modality frontends (stubs per the carve-out) ---
    modality: str = "text"      # text | vision | audio
    num_codebooks: int = 1      # audio: EnCodec codebooks summed at the embed
    vision_embed_dim: int = 1024
    vision_tokens: int = 576    # anyres base-tile token budget (stubbed)
    # --- long-context serving ---
    sliding_window: int = 8192  # window used by the long_500k decode variant
    # --- misc ---
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return self.rwkv_head_dim

    @property
    def held_experts(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def uses_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def num_rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    @property
    def uses_attention(self) -> bool:
        return not self.attn_free

    @property
    def uses_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid") and self.ssm_state > 0

    def _attention_params(self) -> int:
        d, H = self.d_model, self.num_heads
        if self.uses_mla:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            qk = self.qk_nope_head_dim + rope
            return (d * H * qk + d * (r + rope) + r
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)
        hd = self.resolved_head_dim
        return 2 * d * H * hd + 2 * d * self.num_kv_heads * hd

    def param_count(self, routed_experts: int | None = None) -> int:
        """Analytic parameter count (embedding + blocks + head).  Each MoE
        layer counts ``routed_experts`` routed experts (default: the held
        ones) beside its router, shared experts and selection bias."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        per_layer = 0
        if self.uses_attention and not self.attn_free:
            per_layer += self._attention_params() + 2 * d  # + norms
        if self.attn_free:  # rwkv time-mix
            h = self.num_rwkv_heads * self.rwkv_head_dim
            per_layer += 5 * d * h + h * d + 2 * d
        if self.uses_ssm:
            di = d
            per_layer += d * 2 * di + di * (2 * self.ssm_state + 1) + di * d
        if self.attn_free:  # rwkv channel mix
            ffn = dense_ffn = 2 * d * int(ff)
        else:
            dense_ffn = ffn = 3 * d * ff
        if self.num_experts:
            E, fe = self.num_experts, self.expert_d_ff
            k = self.held_experts if routed_experts is None \
                else routed_experts
            ffn = (k * 3 * d * fe + d * E
                   + 3 * d * fe * self.num_shared_experts)
            if self.router_score == "sigmoid":
                ffn += E                        # selection bias
        nd = self.first_dense_layers
        total = (self.num_layers * per_layer + nd * dense_ffn
                 + (self.num_layers - nd) * ffn)
        total += v * d  # embedding
        if not self.tie_embeddings:
            total += d * v
        if self.modality == "vision":
            total += self.vision_embed_dim * d
        if self.modality == "audio":
            total += (self.num_codebooks - 1) * v * d
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if not self.num_experts:
            return self.param_count()
        return self.param_count(routed_experts=self.experts_per_token)


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


INPUT_SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


_REGISTRY: dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate config {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    from . import ALL_ARCHS  # noqa: F401  (forces registration)
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs() -> list[str]:
    from . import ALL_ARCHS  # noqa: F401
    return sorted(_REGISTRY)


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests."""
    heads = max(1, min(4, cfg.num_heads)) if cfg.num_heads else 0
    kv = 0
    if cfg.num_kv_heads:
        kv = max(1, min(2, cfg.num_kv_heads))
        if heads % kv:
            kv = 1
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=2,
        d_model=256,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=64 if cfg.num_heads else 0,
        d_ff=512,
        vocab_size=512,
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        experts_held=min(cfg.experts_held, 4),
        moe_d_ff=min(cfg.moe_d_ff, 128),
        num_shared_experts=min(cfg.num_shared_experts, 2),
        first_dense_layers=min(cfg.first_dense_layers, 1),
        kv_lora_rank=min(cfg.kv_lora_rank, 64),
        qk_nope_head_dim=min(cfg.qk_nope_head_dim, 32),
        qk_rope_head_dim=min(cfg.qk_rope_head_dim, 16),
        v_head_dim=min(cfg.v_head_dim, 32),
        ssm_state=min(cfg.ssm_state, 8),
        rwkv_head_dim=64,
        vision_embed_dim=96,
        vision_tokens=8,
        sliding_window=16,
        dtype="float32",
    )
