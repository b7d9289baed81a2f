"""Each serving test cell's programs, compiled at their real shapes for a
described TPU v5e: the decode step and the prefill chunk of
``LocalDecodeBackend`` at the mix's slots and cache length, and the reference
at its longest padded sequence.  The compiler refuses what would not fit the
chip's memory."""

import functools

import jax
import jax.numpy as jnp
import pytest

from conftest import bench, mix_file
from harness import common

CELLS = ["qwen2-0.5b.chat", "qwen2-vl-2b.decode"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_programs_compile_for_v5e(one_chip, cell_name):
    from repro.serve import LocalDecodeBackend
    b = bench()
    cell = common.cell(b, cell_name)
    cfg = common.config_file(b, cell["config"])
    mix = mix_file(cell["traffic"])
    ref = common.load_module("references", cfg["family"])
    adapter = common.load_module("adapters", cfg["family"])
    model = adapter.program_model(cfg)
    S, M, pc = (int(mix[k]) for k in ("n_slots", "max_len", "prefill_chunk"))
    w = jax.eval_shape(functools.partial(ref.init_weights, cfg),
                       jax.random.PRNGKey(0))
    params = _shapes(adapter.program_params(w), one_chip)
    cache = _shapes(jax.eval_shape(lambda: model.init_cache(S, M)), one_chip)
    # the backend's programs depend on the slots, not on the cache length
    b = LocalDecodeBackend(model, None, n_slots=S, max_len=1,
                           prefill_chunk=pc)
    vec = lambda n, dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    hbm = 16e9
    dec = b._decode.lower(params, cache, vec(S, jnp.int32),
                          vec(S, bool)).compile()
    pre = b._prefill.lower(params, cache, vec(pc, jnp.int32), vec(pc, bool),
                           jax.ShapeDtypeStruct((), jnp.int32,
                                                sharding=one_chip)).compile()
    for c in (dec, pre):
        m = c.memory_analysis()
        assert m.argument_size_in_bytes < hbm
    longest = int(mix["prompt_len"]["max"]) + int(mix["output_len"]["max"])
    Sp = ref.padded(longest)
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool))))
    seq = jax.ShapeDtypeStruct((Sp,), jnp.int32, sharding=one_chip)
    refc = ref._stats.lower(key, _shapes(w, one_chip), seq, seq,
                            "f32").compile()
    assert refc.memory_analysis().temp_size_in_bytes < hbm
