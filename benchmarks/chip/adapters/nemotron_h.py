"""The program's view of a Nemotron-H configuration and of the benchmark's
weights.

``program_model`` builds the program's ``Model`` from the published keys and
the file's ``train`` settings: one mixer per layer from the first
``num_hidden_layers`` letters of ``hybrid_override_pattern``.
``program_params`` re-nests weights (or gradients) in the reference's layout
(``references/nemotron_h.py``: a list of layers) into the program's
parameter tree (segments of consecutive layers of one kind, stacked), and
``reference_weights`` does the reverse.
"""

from __future__ import annotations

# where each reference leaf of a layer sits in the program's block
LEAVES = {
    "mamba": {"ln": ("norm1", "scale"), "in_proj": ("mamba", "in_proj"),
              "conv_w": ("mamba", "conv_w"), "conv_b": ("mamba", "conv_b"),
              "dt_bias": ("mamba", "dt_bias"), "A_log": ("mamba", "A_log"),
              "D": ("mamba", "D_skip"),
              "norm": ("mamba", "gate_norm", "scale"),
              "out_proj": ("mamba", "out_proj")},
    "moe": {"ln": ("norm1", "scale"), "router": ("moe", "router"),
            "router_bias": ("moe", "router_bias"),
            "experts": ("moe", "experts"), "shared": ("moe", "shared")},
    "attn_only": {"ln": ("norm1", "scale"), "q": ("attn", "wq"),
                  "k": ("attn", "wk"), "v": ("attn", "wv"),
                  "o": ("attn", "wo")},
}


def program_model(cfg: dict):
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    from repro.models import Model
    t = cfg["train"]
    L = int(cfg["num_hidden_layers"])
    n = int(cfg["n_routed_experts"])
    return Model(ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=L,
        pattern=cfg["hybrid_override_pattern"][:L],
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        act=cfg["mlp_hidden_act"], rope_fraction=0.0,
        tied_embeddings=bool(cfg["tie_word_embeddings"]),
        norm_eps=float(cfg["norm_eps"]),
        ssm=SSMConfig(d_state=int(cfg["ssm_state_size"]),
                      head_dim=int(cfg["mamba_head_dim"]),
                      heads=int(cfg["mamba_num_heads"]),
                      expand=int(cfg["expand"]),
                      conv_kernel=int(cfg["conv_kernel"]),
                      n_groups=int(cfg["n_groups"]),
                      chunk=int(cfg["chunk_size"])),
        moe=MoEConfig(
            n_experts=int(cfg.get("n_routed_experts_published", n)),
            top_k=int(cfg["num_experts_per_tok"]),
            d_expert=int(cfg["moe_intermediate_size"]),
            n_shared=int(cfg["n_shared_experts"]),
            d_shared=int(cfg["moe_shared_expert_intermediate_size"]),
            router_norm_topk=bool(cfg["norm_topk_prob"]), n_held=n,
            router="sigmoid",
            routed_scale=float(cfg["routed_scaling_factor"]),
            seq_aux=True, aux_weight=float(cfg["aux_loss_alpha"]),
            bias_rate=float(cfg["bias_update_rate"])),
        moe_ragged=True,
        param_dtype=t["param_dtype"], compute_dtype=t["compute_dtype"],
        remat=t["remat"], attn_chunk=int(t["attn_chunk"]),
        loss_chunk=int(t["loss_chunk"])))


def _segments(cfg: dict):
    from repro.models.transformer import structure
    return structure(program_model(cfg).cfg)


def program_params(w: dict, cfg: dict) -> dict:
    """Reference-layout weights (or gradients) to the program's tree."""
    import jax
    import jax.numpy as jnp
    segments, at = [], 0
    for kind, count in _segments(cfg):
        layers = w["layers"][at:at + count]
        at += count
        block: dict = {}
        for name, path in LEAVES[kind].items():
            node = block
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = jax.tree.map(lambda *a: jnp.stack(a),
                                          *(layer[name] for layer in layers))
        segments.append(block)
    return {"embedding": {"embed": w["embed"], "lm_head": w["lm_head"]},
            "final_norm": {"scale": w["norm"]}, "segments": segments}


def reference_weights(params: dict, cfg: dict) -> dict:
    """The program's tree to reference-layout weights."""
    import jax
    layers = []
    for (kind, count), block in zip(_segments(cfg), params["segments"]):
        for i in range(count):
            layer = {}
            for name, path in LEAVES[kind].items():
                node = block
                for key in path:
                    node = node[key]
                layer[name] = jax.tree.map(lambda a: a[i], node)
            layers.append(layer)
    return {"embed": params["embedding"]["embed"],
            "lm_head": params["embedding"]["lm_head"],
            "norm": params["final_norm"]["scale"], "layers": layers}
