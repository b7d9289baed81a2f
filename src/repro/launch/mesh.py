"""Production mesh construction.

The production target is a TPU v5e pod of 16×16 = 256 chips (axes
``data × model``) and the 2-pod variant (``pod × data × model`` = 512).
The cluster pattern of the paper (§7) maps onto the ``pod`` axis: pods are
the workstations, ICI is the in-pod interconnect, DCN the 1GbE.

``make_production_mesh`` is a *function* so importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "train_rules"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, examples, elastic re-mesh)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def train_rules(seq_shard: bool = False):
    """The default rules (heads and ff over 'model'); seq_shard adds
    Megatron-style sequence parallelism on activations."""
    from repro.parallel.axes import ShardingRules
    return ShardingRules(seq="model" if seq_shard else None)
