"""deepseek-v2-lite — 27L d=2048 16H, multi-head latent attention
(kv_lora_rank 512, no query compression; qk 128+64 rope, v 128) with YaRN
rope (factor 40 over 4096), 64 routed experts top-6 (softmax, greedy, no
renormalisation) + 2 shared of width 1408, layer 0 dense (10944),
vocab=102400 untied.  [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite/config.json]

Every width is the published one.  The expert layer is one chip's share of
an 8-way expert-parallel deployment: it holds experts 0-7 and routes over
all 64.  The balance loss is the paper's expert-level loss per sequence,
alpha1 = 0.001 (the value given for V2-Lite)."""

from .base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,          # the dense layer-0 FFN width
    vocab=102400,
    tied_embeddings=False,
    rope_theta=10_000.0,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  layer0_dense=True, n_held=8, seq_aux=True,
                  aux_weight=0.001),
)
