"""Parameter sharding rules: param-tree paths → PartitionSpecs.

Leaf names are the contract (see models/layers.py): the table below assigns
*logical* axes to each leaf's trailing dims; leading dims (layer-stacking by
``lax.scan``) are unsharded.  Logical axes are resolved against a
:class:`repro.parallel.axes.ShardingRules` and mesh-axis sizes that do not
divide a dim fall back to replication — one definition for every mesh, the
GPP property again.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .axes import ShardingRules

__all__ = ["param_specs", "param_shardings", "LEAF_RULES"]

# leaf name → logical axes of the TRAILING dims
LEAF_RULES: dict[str, tuple] = {
    # embeddings
    "embed": ("vocab", "d"),
    "lm_head": ("d", "vocab"),
    "dec_pos": (None, "d"),
    # attention
    "wq": ("d", "heads"),
    "wk": ("d", "heads"),
    "wv": ("d", "heads"),
    "wo": ("heads", "d"),
    "bq": ("heads",),
    "bk": ("heads",),
    "bv": ("heads",),
    # latent attention
    "wkva": ("d", None),
    "wkvb": (None, "heads"),
    # mlp
    "gate": ("d", "ff"),
    "up": ("d", "ff"),
    "down": ("ff", "d"),
    "up_b": ("ff",),
    "down_b": ("d",),
    # moe
    "router": ("d", None),
    # mamba
    "in_proj": ("d", "ff"),
    "out_proj": ("ff", "d"),
    "conv_w": (None, "ff"),
    "conv_b": ("ff",),
    "dt_bias": (None,),
    "A_log": (None,),
    "D_skip": (None,),
    # norms
    "scale": ("d",),
    "bias": ("d",),
}

# leaves under an "experts" subtree get the expert axis prepended
_EXPERT_PARENT = "experts"


def _path_names(path) -> list[str]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:  # pragma: no cover
            names.append(str(k))
    return names


def _spec_for(path, leaf, rules: ShardingRules, mesh) -> P:
    names = _path_names(path)
    leaf_name = names[-1] if names else ""
    logical = LEAF_RULES.get(leaf_name)
    if logical is None:
        return P()  # unknown leaves replicate (safe default)
    if _EXPERT_PARENT in names[:-1]:
        logical = ("expert",) + logical
    ndim = getattr(leaf, "ndim", len(getattr(leaf, "shape", ())))
    shape = leaf.shape
    n_lead = ndim - len(logical)
    if n_lead < 0:  # leaf smaller than rule (e.g. squeezed) → replicate
        return P()
    axes: list = [None] * n_lead
    used: set = set()  # a mesh axis shards at most one dim (EP beats TP
    # inside expert stacks: the expert axis comes first in the rule tuple)
    for dim, ax in zip(shape[n_lead:], logical):
        m = rules.of(ax) if ax else None
        m = _filter_axes(m, mesh)
        if m is not None:
            maxes = m if isinstance(m, tuple) else (m,)
            if any(a in used for a in maxes):
                m = None
        if m is None:
            axes.append(None)
            continue
        maxes = m if isinstance(m, tuple) else (m,)
        size = 1
        for a in maxes:
            size *= mesh.shape[a]
        if dim % size == 0:
            axes.append(m)
            used.update(maxes)
        else:
            axes.append(None)
    return P(*axes)


def _filter_axes(m, mesh):
    """Drop mesh axes absent from this mesh (e.g. 'pod' on single-pod)."""
    if m is None:
        return None
    axes = m if isinstance(m, tuple) else (m,)
    present = tuple(a for a in axes if a in mesh.shape)
    if not present:
        return None
    return present if isinstance(m, tuple) else present[0]


def param_specs(params: Any, mesh, rules: ShardingRules = ShardingRules()):
    """Pytree of PartitionSpec mirroring ``params`` (works on
    ShapeDtypeStructs too)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _spec_for(path, leaf, rules, mesh), params)


def param_shardings(params: Any, mesh,
                    rules: ShardingRules = ShardingRules()):
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), param_specs(params, mesh, rules),
        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# KV-cache / batch sharding (serving)
# --------------------------------------------------------------------------

# cache leaf name → logical axes of the trailing dims.  With batch=1
# (long-context) the batch axis won't divide and falls back to replication,
# and ``kv_seq`` (set to a mesh axis in the serve rules) carries the shard —
# flash-decoding style sequence sharding of the cache.
CACHE_RULES: dict[str, tuple] = {
    "k": ("batch", "kv_seq", "heads", None),
    "v": ("batch", "kv_seq", "heads", None),
    "k_scale": ("batch", "kv_seq", "heads"),
    "v_scale": ("batch", "kv_seq", "heads"),
    "ckv": ("batch", "kv_seq", None),
    "kpe": ("batch", "kv_seq", None),
    "index": ("batch",),
    "conv": ("batch", None, "ff"),
    "h": ("batch", "heads", None, None),
    "enc_out": ("batch", None, "d"),
    "step": ("batch",),
}


def cache_specs(cache: Any, mesh, rules: ShardingRules = ShardingRules()):
    def spec(path, leaf):
        names = _path_names(path)
        logical = CACHE_RULES.get(names[-1] if names else "")
        if logical is None:
            return P()
        ndim = leaf.ndim
        n_lead = ndim - len(logical)
        if n_lead < 0:
            return P()
        axes: list = [None] * n_lead
        used: set = set()  # a mesh axis may shard at most one dim
        for dim, ax in zip(leaf.shape[n_lead:], logical):
            m = _filter_axes(rules.of(ax) if ax else None, mesh)
            if m is not None:
                maxes = m if isinstance(m, tuple) else (m,)
                if any(a in used for a in maxes):
                    m = None
            if m is None:
                axes.append(None)
                continue
            size = 1
            for a in (m if isinstance(m, tuple) else (m,)):
                size *= mesh.shape[a]
            if dim % size == 0:
                axes.append(m)
                used.update(m if isinstance(m, tuple) else (m,))
            else:
                axes.append(None)
        return P(*axes)

    return jax.tree_util.tree_map_with_path(spec, cache)


def batch_specs(batch: Any, mesh, rules: ShardingRules = ShardingRules()):
    """Token batches: leading dim = batch, rest unsharded."""
    def spec(leaf):
        m = _filter_axes(rules.batch, mesh)
        if m is None or leaf.ndim == 0:
            return P()
        size = 1
        for a in (m if isinstance(m, tuple) else (m,)):
            size *= mesh.shape[a]
        if leaf.shape[0] % size:
            return P()
        return P(m, *([None] * (leaf.ndim - 1)))

    return jax.tree_util.tree_map(spec, batch)


def to_shardings(spec_tree: Any, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
