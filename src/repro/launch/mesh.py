"""Production mesh construction.

The production target is a TPU v5e pod of 16×16 = 256 chips (axes
``data × model``) and the 2-pod variant (``pod × data × model`` = 512).
The cluster pattern of the paper (§7) maps onto the ``pod`` axis: pods are
the workstations, ICI is the in-pod interconnect, DCN the 1GbE.

``make_production_mesh`` is a *function* so importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
use and everything else sees the single real CPU device.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "serve_rules", "train_rules"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, examples, elastic re-mesh)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def train_rules(seq_shard: bool = False, fsdp: bool = False,
                tp: bool = True):
    """seq_shard: Megatron-style sequence parallelism on activations;
    fsdp: ZeRO-3 weight sharding over the data axis (weights gather at
    use); tp=False: no tensor parallelism (heads/ff replicated) — the
    right call when per-chip compute is tiny and TP collectives dominate
    (see §Perf, mamba2 cell)."""
    from repro.parallel.axes import ShardingRules
    return ShardingRules(
        seq="model" if seq_shard else None,
        d="data" if fsdp else None,
        heads="model" if tp else None,
        ff="model" if tp else None,
    )


def serve_rules(*, kv_seq_shard: bool = True):
    """Decode: shard the KV-cache sequence over 'model' (flash-decoding
    style) — essential for long_500k where batch=1 cannot shard."""
    from repro.parallel.axes import ShardingRules
    return ShardingRules(kv_seq="model" if kv_seq_shard else None)
