"""Operations and bytes from a configuration's shapes (dense GQA decoders).

Keys are those of the model's published ``config.json``.  A multiply-add
counts as two operations.  Nothing here reads the program: the benchmark
holds its own arithmetic so that no change to the program can move it.
"""

from __future__ import annotations

BF16 = 2


def dims(cfg: dict) -> dict:
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"D": D, "H": H, "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or D // H),
            "F": int(cfg["intermediate_size"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"]),
            "tied": bool(cfg.get("tie_word_embeddings", False)),
            "bias": bool(cfg.get("qkv_bias", True))}


def layer_params(cfg: dict) -> int:
    """One decoder layer: q/k/v/o projections (q/k/v biased), the gated MLP
    and two RMSNorm scales."""
    d = dims(cfg)
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    attn = D * H * hd + 2 * D * K * hd + H * hd * D
    if d["bias"]:
        attn += H * hd + 2 * K * hd
    return attn + 3 * D * F + 2 * D


def param_count(cfg: dict) -> int:
    """Every parameter: the layers, the embedding, the final norm and an
    untied output head."""
    d = dims(cfg)
    head = 0 if d["tied"] else d["V"] * d["D"]
    return d["L"] * layer_params(cfg) + d["V"] * d["D"] + d["D"] + head


def matmul_params(cfg: dict) -> int:
    """Parameters a token multiplies with: the layers' projections and the
    output head (the embedding is a lookup)."""
    d = dims(cfg)
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return d["L"] * per_layer + d["V"] * D


def token_flops(cfg: dict, ctx: int, logits: bool = True) -> int:
    """One token's forward pass at context length ``ctx`` (the positions it
    attends to, itself included): projections, attention scores and the
    weighted sum, and the output head when ``logits``."""
    d = dims(cfg)
    mm = matmul_params(cfg) - (0 if logits else d["V"] * d["D"])
    attn = 4 * d["L"] * d["H"] * d["hd"] * int(ctx)
    return 2 * mm + attn


def kv_bytes_per_position(cfg: dict) -> int:
    """Keys and values of one position over all layers, in bfloat16."""
    d = dims(cfg)
    return 2 * d["L"] * d["K"] * d["hd"] * BF16


def decode_step_cost(cfg: dict, ctx_lens) -> tuple:
    """(operations, bytes) a decode step needs at least for rows whose
    context lengths are ``ctx_lens``: each row's token through the model,
    the weights read once at bfloat16, each row's valid keys and values read
    and its new ones written."""
    ctx_lens = [int(c) for c in ctx_lens]
    flops = sum(token_flops(cfg, c) for c in ctx_lens)
    kv = kv_bytes_per_position(cfg)
    nbytes = (matmul_params(cfg) * BF16
              + sum(c * kv for c in ctx_lens))
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over peak FLOP/s and
    bytes over peak bandwidth, and which of the two it was."""
    tc = flops / float(peak["bf16_flops_per_s"])
    tm = nbytes / float(peak["hbm_bytes_per_s"])
    return (tc, "compute") if tc >= tm else (tm, "memory")
