"""Parameters and operations of a DeepSeek-V2 training step, from the
configuration's shapes (latent attention, held experts, shared experts).

Keys are those of the model's published ``config.json``, with
``n_routed_experts`` the experts held and ``n_routed_experts_published`` the
router's width.  A multiply-add counts as two operations, and only matrix
products count.  Nothing here reads the program: the benchmark holds its
own arithmetic so that no change to the program can move it.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    n = int(cfg["n_routed_experts"])
    L, Ld = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    return {"D": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "r": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]), "dv": int(cfg["v_head_dim"]),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]), "n": n,
            "E": int(cfg.get("n_routed_experts_published", n)),
            "Ns": int(cfg["n_shared_experts"]), "L": L, "Ld": Ld,
            "Lm": L - Ld, "V": int(cfg["vocab_size"])}


def mla_params(cfg: dict) -> int:
    """One layer's latent attention: q, the latent and rope key, the
    latent's norm, the per-head decompression and the output."""
    d = dims(cfg)
    D, H, r, nope, rope, dv = (d[k] for k in "D H r nope rope dv".split())
    return (D * H * (nope + rope) + D * (r + rope) + r
            + r * H * (nope + dv) + H * dv * D)


def param_count(cfg: dict) -> int:
    """Every parameter the chip holds: layers, embedding, final norm and
    the untied head."""
    d = dims(cfg)
    D, Fe = d["D"], d["Fe"]
    layer = mla_params(cfg) + 2 * D
    dense = layer + 3 * D * d["F"]
    expert = (layer + D * d["E"] + d["n"] * 3 * D * Fe
              + 3 * D * d["Ns"] * Fe)
    return d["Ld"] * dense + d["Lm"] * expert + 2 * d["V"] * D + D


def token_flops(cfg: dict) -> int:
    """One token's forward operations outside causal attention and the
    routed experts: the attention projections, the dense MLP or the shared
    experts, the router and the output head."""
    d = dims(cfg)
    D = d["D"]
    mla = mla_params(cfg) - d["r"]          # the norm's scale multiplies
    return 2 * (d["L"] * mla + d["Ld"] * 3 * D * d["F"]
                + d["Lm"] * (3 * D * d["Ns"] * d["Fe"] + D * d["E"])
                + D * d["V"])


def attention_flops(cfg: dict, seq: int) -> int:
    """Causal attention of one sequence of ``seq`` tokens, every layer: each
    token's scores and weighted values over the positions up to itself."""
    d = dims(cfg)
    per_pos = 2 * d["H"] * (d["nope"] + d["rope"] + d["dv"])
    return d["L"] * per_pos * seq * (seq + 1) // 2


def pair_flops(cfg: dict) -> int:
    """One token through one routed expert (SiLU-gated MLP)."""
    d = dims(cfg)
    return 2 * 3 * d["D"] * d["Fe"]


def step_flops(cfg: dict, batch: int, seq: int, held_pairs: int) -> int:
    """A training step's model operations: the forward over ``batch``
    sequences of ``seq`` tokens, with ``held_pairs`` token-expert pairs on
    the held experts (all layers), and the backward at twice the forward.
    Recomputation does not count."""
    fwd = (batch * seq * token_flops(cfg) + batch * attention_flops(cfg, seq)
           + int(held_pairs) * pair_flops(cfg))
    return 3 * fwd
