"""Model / run configuration schema.

Every assigned architecture is a :class:`ModelConfig`; ``reduce()`` derives
the CPU-smoke-test variant of the same family (small dims, same topology).
``registry.py`` maps ``--arch`` ids to configs.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int  # per-expert FFN hidden dim
    n_shared: int = 0  # always-on shared experts (deepseek)
    layer0_dense: bool = False  # deepseek: first layer is a dense FFN
    capacity_factor: float = 1.25
    router_norm_topk: bool = False  # normalise top-k weights to sum 1
    # experts this chip holds (expert parallelism): the router scores all
    # n_experts, the layer computes the part of experts 0..n_held-1,
    # dropless, through the grouped path; 0 = all
    n_held: int = 0
    # DeepSeek-V2's expert-level balance loss per sequence (arXiv:2405.04434)
    # in place of the switch-style loss on the top-1 choice
    seq_aux: bool = False
    aux_weight: float = 0.01  # the balance loss's weight in the total loss
    # softmax | sigmoid: sigmoid scores choose the top-k by score plus a
    # correction bias that no gradient moves (DeepSeek-V3, Nemotron-H)
    router: str = "softmax"
    routed_scale: float = 1.0  # the routed experts' weights times this
    d_shared: int = 0  # the shared experts' width; 0 = n_shared * d_expert
    # the correction bias's step: b_i += rate * sign(mean(load) - load_i)
    # after each training step, from the step's tokens per expert
    bias_rate: float = 0.0

    @property
    def shared_width(self) -> int:
        return self.d_shared or self.n_shared * self.d_expert


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention without query compression:
    keys and values come from one ``kv_lora_rank`` latent per position, and
    one rope key of ``qk_rope_head_dim`` is shared by all heads."""

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling: the published ``rope_scaling`` keys."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int
    head_dim: int = 64
    expand: int = 2
    conv_kernel: int = 4
    n_groups: int = 1  # B and C state groups; the gated norm's groups too
    chunk: int = 64
    heads: int = 0  # 0 = expand * d_model // head_dim

    def n_heads(self, d_model: int) -> int:
        return self.heads or (self.expand * d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """zamba2-style: mamba trunk + one *shared* attention block applied every
    ``period`` layers (weights reused at every application point)."""

    period: int = 6
    shared_d_ff: int = 0  # FFN width inside the shared block (0 = none)


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    n_enc_layers: int
    frontend_downsample: int = 4  # stubbed conv frontend: frames = seq // this


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None  # default d_model // n_heads
    act: str = "swiglu"  # swiglu | geglu | gelu | relu2 (down(relu(up x)^2))
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    rope_fraction: float = 1.0  # glm4 rotates half the head dim
    tied_embeddings: bool = True
    embed_scale: bool = False  # gemma multiplies embeddings by sqrt(d)
    norm_eps: float = 1e-6
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    mla: Optional[MLAConfig] = None
    yarn: Optional[YarnConfig] = None
    mrope: bool = False
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)
    # one mixer per layer, in order (Nemotron-H's hybrid_override_pattern):
    # M Mamba-2, E expert layer, * attention; "" = the family's structure
    pattern: str = ""
    # numerics / execution
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"  # none | full  (activation checkpointing per layer)
    use_pallas: bool = False  # TPU kernels (interpret-validated on CPU)
    moe_ragged: bool = False  # ragged grouped-matmul MoE path (vs capacity)
    loss_chunk: int = 0  # chunked cross-entropy (never materialise full
    # (B,S,V) logits); 0 = off
    kv_quant: bool = False  # int8 KV cache (per-position-head scales): ~2x
    # cache memory + bandwidth at decode
    attn_chunk: int = 0  # query-chunked attention: (S,S) logits never
    # materialise (XLA-level flash analogue; the Pallas kernel is the
    # TPU-native path); 0 = off

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    def reduce(self) -> "ModelConfig":
        """Smoke-test variant: same family/topology, tiny dims."""
        kw = dataclasses.asdict(self)
        hd = 16
        n_heads = max(2, min(4, self.n_heads))
        n_kv = 1 if self.n_kv_heads == 1 else min(2, n_heads)
        n_layers = max(2, min(4, self.n_layers))
        if self.pattern:  # the shortest prefix that holds every kind
            n_layers = max(self.pattern.index(c)
                           for c in set(self.pattern)) + 1
        kw.update(
            n_layers=n_layers,
            pattern=self.pattern[:n_layers],
            d_model=n_heads * 32,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            d_ff=128,
            vocab=256,
            head_dim=hd if self.head_dim else None,
            param_dtype="float32",
            compute_dtype="float32",
            remat="none",
        )
        if self.moe:
            kw["moe"] = dataclasses.replace(
                self.moe, n_experts=4, top_k=2, d_expert=64,
                n_shared=min(1, self.moe.n_shared),
                d_shared=self.moe.d_shared and 128,
                # dropless at smoke scale so forward ≡ prefill+decode
                capacity_factor=4.0,
                n_held=2 if self.moe.n_held else 0)
        if self.mla:
            kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                  qk_rope_head_dim=8, v_head_dim=16)
        if self.ssm:
            kw["ssm"] = SSMConfig(d_state=16, head_dim=16,
                                  expand=2, conv_kernel=4,
                                  n_groups=min(2, self.ssm.n_groups),
                                  chunk=16)
        if self.hybrid:
            kw["hybrid"] = HybridConfig(period=2,
                                        shared_d_ff=self.hybrid.shared_d_ff
                                        and 128)
        if self.encdec:
            kw["encdec"] = EncDecConfig(n_enc_layers=2, frontend_downsample=4)
        if self.mrope:
            kw["mrope_sections"] = (4, 6, 6)
        for k in ("moe", "ssm", "hybrid", "encdec", "mla", "yarn"):
            if isinstance(kw[k], dict):
                cls = {"moe": MoEConfig, "ssm": SSMConfig,
                       "hybrid": HybridConfig, "encdec": EncDecConfig,
                       "mla": MLAConfig, "yarn": YarnConfig}[k]
                kw[k] = cls(**kw[k])
        return ModelConfig(**kw)
