"""The program's view of a Qwen2 configuration and of the benchmark's weights.

``program_model`` builds the program's ``Model`` from the published keys;
``program_params`` re-nests the benchmark's weights (``references/qwen2.py``)
into the program's parameter tree without copying them.
"""

from __future__ import annotations


def program_model(cfg: dict):
    from repro.configs.base import ModelConfig
    from repro.models import Model
    rs = cfg.get("rope_scaling") or {}
    mrope = rs.get("type") == "mrope"
    kw = dict(name=cfg["name"], family="vlm" if mrope else "dense",
              n_layers=int(cfg["num_hidden_layers"]),
              d_model=int(cfg["hidden_size"]),
              n_heads=int(cfg["num_attention_heads"]),
              n_kv_heads=int(cfg["num_key_value_heads"]),
              d_ff=int(cfg["intermediate_size"]),
              vocab=int(cfg["vocab_size"]), qkv_bias=True,
              tied_embeddings=bool(cfg["tie_word_embeddings"]),
              rope_theta=float(cfg["rope_theta"]),
              norm_eps=float(cfg["rms_norm_eps"]),
              param_dtype=cfg["served"]["param_dtype"],
              compute_dtype=cfg["served"]["compute_dtype"])
    if cfg.get("head_dim"):
        kw["head_dim"] = int(cfg["head_dim"])
    if mrope:
        kw.update(mrope=True, mrope_sections=tuple(rs["mrope_section"]))
    return Model(ModelConfig(**kw))


def program_params(w: dict) -> dict:
    L = w["layers"]
    seg = {"norm1": {"scale": L["ln1"]}, "norm2": {"scale": L["ln2"]},
           "attn": {"wq": L["q_w"], "wk": L["k_w"], "wv": L["v_w"],
                    "wo": L["o_w"], "bq": L["q_b"], "bk": L["k_b"],
                    "bv": L["v_b"]},
           "mlp": {"gate": L["gate"], "up": L["up"], "down": L["down"]}}
    emb = {"embed": w["embed"]}
    if "lm_head" in w:
        emb["lm_head"] = w["lm_head"]
    return {"embedding": emb, "final_norm": {"scale": w["norm"]},
            "segments": [seg]}
