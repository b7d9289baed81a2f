"""Sharding-rule properties: for every (arch × rules × mesh shape), the
derived parameter/cache/batch specs are structurally valid — each mesh axis
used at most once per spec, every sharded dim divisible by its axis product.
This is the invariant that keeps sharded lowering clear of a
DuplicateSpecError or an indivisible shard."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models import Model
from repro.parallel import sharding as shlib
from repro.parallel.axes import ShardingRules


class _FakeMesh:
    """Mesh stand-in: only .shape is consulted by the spec derivation."""

    def __init__(self, shape: dict):
        self.shape = shape


MESHES = [
    _FakeMesh({"data": 16, "model": 16}),
    _FakeMesh({"pod": 2, "data": 16, "model": 16}),
    _FakeMesh({"data": 4, "model": 2}),
    _FakeMesh({"stage": 4}),  # none of the param axes exist → all replicated
]

RULES = [
    ShardingRules(),
    ShardingRules(seq="model"),
    ShardingRules(d="data"),  # weights sharded over data (ZeRO-3)
    ShardingRules(heads=None, ff=None, d=("data", "model"),
                  batch=("pod", "data", "model")),  # flattened pure DP
    ShardingRules(kv_seq="model"),  # serve
]


def _check_specs(tree, specs, mesh):
    for leaf, spec in zip(jax.tree_util.tree_leaves(tree),
                          jax.tree_util.tree_leaves(
                              specs, is_leaf=lambda x: isinstance(x, P))):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim, (leaf.shape, spec)
        used = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = 1
            for a in axes:
                assert a in mesh.shape, f"axis {a} not in mesh"
                assert a not in used, f"axis {a} used twice in {spec}"
                used.append(a)
                size *= mesh.shape[a]
            assert dim % size == 0, (
                f"dim {dim} not divisible by {size} in {spec}")


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh_i", range(len(MESHES)))
@pytest.mark.parametrize("rules_i", range(len(RULES)))
def test_param_specs_always_valid(arch, mesh_i, rules_i, key):
    cfg = get_config(arch)  # FULL config — the real dims matter here
    model = Model(cfg)
    params_sds = jax.eval_shape(model.init,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    mesh, rules = MESHES[mesh_i], RULES[rules_i]
    specs = shlib.param_specs(params_sds, mesh, rules)
    _check_specs(params_sds, specs, mesh)


@pytest.mark.parametrize("arch", ["yi-34b", "mamba2-2.7b", "zamba2-1.2b",
                                  "whisper-tiny"])
@pytest.mark.parametrize("batch,seqlen", [(128, 1024), (1, 4096)])
def test_cache_specs_always_valid(arch, batch, seqlen):
    cfg = get_config(arch)
    model = Model(cfg)
    cache_sds = jax.eval_shape(lambda: model.init_cache(batch, seqlen))
    for mesh in MESHES:
        for rules in RULES:
            specs = shlib.cache_specs(cache_sds, mesh, rules)
            _check_specs(cache_sds, specs, mesh)


def test_batch_specs_fallback_on_indivisible():
    mesh = _FakeMesh({"data": 16, "model": 16})
    batch = {"tokens": jax.ShapeDtypeStruct((10, 64), jnp.int32)}  # 10 % 16
    specs = shlib.batch_specs(batch, mesh, ShardingRules())
    assert specs["tokens"] == P()
