"""The program's view of a DeepSeek-V2 configuration and of the benchmark's
weights.

``program_model`` builds the program's ``Model`` from the published keys and
the file's ``train`` settings; ``program_params`` re-nests weights (or
gradients) in the reference's layout (``references/deepseek_v2.py``) into
the program's parameter tree, and ``reference_weights`` does the reverse.
The program rotates the two halves of each rope part where the published
model rotates interleaved pairs, so the rope columns of ``q`` and ``kv_a``
are permuted on the way in (evens, then odds) and back on the way out.
"""

from __future__ import annotations

import numpy as np


def program_model(cfg: dict):
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, \
        YarnConfig
    from repro.models import Model
    rs, t = cfg["rope_scaling"], cfg["train"]
    n = int(cfg["n_routed_experts"])
    E = int(cfg.get("n_routed_experts_published", n))
    return Model(ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        tied_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        mla=MLAConfig(kv_lora_rank=int(cfg["kv_lora_rank"]),
                      qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
                      qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
                      v_head_dim=int(cfg["v_head_dim"])),
        yarn=YarnConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=int(
                rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        moe=MoEConfig(
            n_experts=E, top_k=int(cfg["num_experts_per_tok"]),
            d_expert=int(cfg["moe_intermediate_size"]),
            n_shared=int(cfg["n_shared_experts"]),
            layer0_dense=int(cfg["first_k_dense_replace"]) == 1,
            router_norm_topk=bool(cfg["norm_topk_prob"]), n_held=n,
            seq_aux=bool(cfg["seq_aux"]),
            aux_weight=float(cfg["aux_loss_alpha"])),
        param_dtype=t["param_dtype"], compute_dtype=t["compute_dtype"],
        remat=t["remat"], attn_chunk=int(t["attn_chunk"]),
        loss_chunk=int(t["loss_chunk"])))


def _rope_perm(a, at: int, width: int, heads: int, inverse: bool = False):
    """Permute columns ``[at, at + width)`` of each of ``heads`` column
    groups of ``a``'s last axis: evens first, then odds (or back, with
    ``inverse``).  ``a`` may be a jax or numpy array."""
    perm = np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])
    if inverse:
        perm = np.argsort(perm)
    g = a.shape[-1] // heads
    cols = np.concatenate([np.arange(at), at + perm,
                           np.arange(at + width, g)])
    return a[..., (np.arange(heads)[:, None] * g + cols).reshape(-1)]


def program_params(w: dict, cfg: dict) -> dict:
    """Reference-layout weights (or gradients) to the program's tree."""
    H, nope, rope, r = (int(cfg[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "kv_lora_rank"))

    def attn(a):
        return {"wq": _rope_perm(a["q"], nope, rope, H),
                "wkva": _rope_perm(a["kv_a"], r, rope, 1),
                "kv_norm": {"scale": a["kv_norm"]}, "wkvb": a["kv_b"],
                "wo": a["o"]}

    def seg(s):
        out = {"norm1": {"scale": s["ln1"]}, "norm2": {"scale": s["ln2"]},
               "attn": attn(s["attn"])}
        if "mlp" in s:
            out["mlp"] = dict(s["mlp"])
        else:
            out["moe"] = {"router": s["router"], "experts": dict(s["experts"]),
                          "shared": dict(s["shared"])}
        return out

    return {"embedding": {"embed": w["embed"], "lm_head": w["lm_head"]},
            "final_norm": {"scale": w["norm"]},
            "segments": [seg(w["dense"]), seg(w["moe"])]}


def reference_weights(params: dict, cfg: dict) -> dict:
    """The program's tree to reference-layout weights."""
    H, nope, rope, r = (int(cfg[k]) for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "kv_lora_rank"))

    def attn(a):
        return {"q": _rope_perm(a["wq"], nope, rope, H, inverse=True),
                "kv_a": _rope_perm(a["wkva"], r, rope, 1, inverse=True),
                "kv_norm": a["kv_norm"]["scale"], "kv_b": a["wkvb"],
                "o": a["wo"]}

    def seg(s):
        out = {"ln1": s["norm1"]["scale"], "ln2": s["norm2"]["scale"],
               "attn": attn(s["attn"])}
        if "mlp" in s:
            out["mlp"] = dict(s["mlp"])
        else:
            out.update(router=s["moe"]["router"],
                       experts=dict(s["moe"]["experts"]),
                       shared=dict(s["moe"]["shared"]))
        return out

    dense, moe = params["segments"]
    return {"embed": params["embedding"]["embed"],
            "lm_head": params["embedding"]["lm_head"],
            "norm": params["final_norm"]["scale"],
            "dense": seg(dense), "moe": seg(moe)}
