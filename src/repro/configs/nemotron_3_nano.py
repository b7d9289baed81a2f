"""nemotron-3-nano-30b-a3b — 52 single-mixer blocks, d=2688, in the pattern
below (M Mamba-2, E expert layer, * attention): Mamba-2 with 64 heads of 64
(d_inner 4096), 8 state groups of 128, conv 4; 128 routed relu^2 experts of
1856, top 6 by sigmoid score plus a correction bias, renormalised and
scaled by 2.5, one shared relu^2 expert of 3712; GQA 32 query / 2 KV heads
of 128 with no position encoding; RMSNorm eps 1e-5, vocab 131072 untied.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/config.json, model_type
nemotron_h]

The balance loss is DeepSeek-V3's sequence-wise loss on the normalised
sigmoid scores at 1e-4, and the correction bias moves by 1e-3 a step (the
Nemotron 3 Nano report, from memory)."""

from .base import ModelConfig, MoEConfig, SSMConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b",
    family="hybrid",
    n_layers=len(PATTERN),
    pattern=PATTERN,
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab=131072,
    act="relu2",
    rope_fraction=0.0,
    tied_embeddings=False,
    norm_eps=1e-5,
    moe_ragged=True,  # the dropless grouped path (sigmoid router, relu^2)
    ssm=SSMConfig(d_state=128, head_dim=64, heads=64, n_groups=8,
                  conv_kernel=4, chunk=128),
    moe=MoEConfig(n_experts=128, top_k=6, d_expert=1856, n_shared=1,
                  d_shared=3712, router_norm_topk=True, router="sigmoid",
                  routed_scale=2.5, seq_aux=True, aux_weight=1e-4,
                  bias_rate=1e-3),
)
