"""Parameters and operations of a Nemotron-H training step, from the
configuration's shapes (Mamba-2 mixers, held relu^2 experts, attention).

Keys are those of the model's published ``config.json``, with
``n_routed_experts`` the experts held and ``n_routed_experts_published`` the
router's width; the layers are the first ``num_hidden_layers`` letters of
``hybrid_override_pattern`` (M Mamba-2, E expert layer, * attention).  A
multiply-add counts as two operations, and only matrix products count: the
SSD's at ``chunk_size`` as the chunked form computes them, not the conv,
norms or gates.  Nothing here reads the program: the benchmark holds its own
arithmetic so that no change to the program can move it.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    n = int(cfg["n_routed_experts"])
    pattern = cfg["hybrid_override_pattern"][:int(cfg["num_hidden_layers"])]
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {"D": int(cfg["hidden_size"]), "V": int(cfg["vocab_size"]),
            "Lm": pattern.count("M"), "Le": pattern.count("E"),
            "La": pattern.count("*"), "H": H, "P": P, "G": G, "N": N,
            "di": H * P, "conv": H * P + 2 * G * N,
            "k": int(cfg["conv_kernel"]), "chunk": int(cfg["chunk_size"]),
            "Ha": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg["head_dim"]), "n": n,
            "E": int(cfg.get("n_routed_experts_published", n)),
            "Fe": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["moe_shared_expert_intermediate_size"])}


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 layer: its norm, in_proj to z, xBC and dt, the conv and
    its bias, dt_bias, A_log, D, the gated norm and out_proj."""
    d = dims(cfg)
    D, H, di, conv = d["D"], d["H"], d["di"], d["conv"]
    return (D + D * (di + conv + H) + d["k"] * conv + conv + 3 * H + di
            + di * D)


def expert_params(cfg: dict) -> int:
    """One expert layer: its norm, the router and its correction bias, the
    held experts and the shared expert (relu^2: up and down)."""
    d = dims(cfg)
    D = d["D"]
    return (D + D * d["E"] + d["E"] + d["n"] * 2 * D * d["Fe"]
            + 2 * D * d["Fs"])


def attention_params(cfg: dict) -> int:
    d = dims(cfg)
    q, kv = d["Ha"] * d["hd"], d["Hkv"] * d["hd"]
    return d["D"] + d["D"] * (q + 2 * kv) + q * d["D"]


def param_count(cfg: dict) -> int:
    """Every parameter the chip holds: layers, embedding, final norm and
    the untied head."""
    d = dims(cfg)
    return (d["Lm"] * mamba_params(cfg) + d["Le"] * expert_params(cfg)
            + d["La"] * attention_params(cfg) + 2 * d["V"] * d["D"] + d["D"])


def ssd_flops(cfg: dict) -> int:
    """One token through one Mamba-2 layer's SSD at ``chunk_size`` L: its
    row of ``C . B`` over the chunk in each group, of the weights times x
    in each head, its part of the chunk's state and its read of the state
    entering the chunk."""
    d = dims(cfg)
    L = d["chunk"]
    return 2 * (d["G"] * L * d["N"] + d["H"] * L * d["P"]
                + 2 * d["H"] * d["N"] * d["P"])


def token_flops(cfg: dict) -> int:
    """One token's forward operations outside causal attention's core and
    the routed experts: the Mamba projections and SSD, the attention
    projections, the routers, the shared experts and the output head."""
    d = dims(cfg)
    D = d["D"]
    mamba = 2 * (D * (d["di"] + d["conv"] + d["H"]) + d["di"] * D)
    q, kv = d["Ha"] * d["hd"], d["Hkv"] * d["hd"]
    attn = 2 * (D * (q + 2 * kv) + q * D)
    expert = 2 * (D * d["E"] + 2 * D * d["Fs"])
    return (d["Lm"] * (mamba + ssd_flops(cfg)) + d["La"] * attn
            + d["Le"] * expert + 2 * D * d["V"])


def attention_flops(cfg: dict, seq: int) -> int:
    """Causal attention of one sequence of ``seq`` tokens, every attention
    layer: each token's scores and weighted values over the positions up to
    itself."""
    d = dims(cfg)
    per_pos = 2 * d["Ha"] * 2 * d["hd"]
    return d["La"] * per_pos * seq * (seq + 1) // 2


def pair_flops(cfg: dict) -> int:
    """One token through one routed expert (relu^2, up and down)."""
    d = dims(cfg)
    return 2 * 2 * d["D"] * d["Fe"]


def step_flops(cfg: dict, batch: int, seq: int, held_pairs: int) -> int:
    """A training step's model operations: the forward over ``batch``
    sequences of ``seq`` tokens, with ``held_pairs`` token-expert pairs on
    the held experts (all layers), and the backward at twice the forward.
    Recomputation does not count."""
    fwd = (batch * seq * token_flops(cfg) + batch * attention_flops(cfg, seq)
           + int(held_pairs) * pair_flops(cfg))
    return 3 * fwd
