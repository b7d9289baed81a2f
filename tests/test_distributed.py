"""Multi-device behaviour, via subprocesses (jax device count is fixed at
first init, so the main pytest process must stay single-device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # excluded from the fast CI lane

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        f"import sys; sys.path.insert(0, {_SRC!r})\n"
        + textwrap.dedent(code))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout)
    assert r.returncode == 0, f"subprocess failed:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pipeline_parallel_exact():
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.parallel.pipeline import pipeline_forward, split_stages
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    L, D = 8, 16
    Ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.2
    def block_fn(lp, h):
        out, _ = jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), h, lp)
        return out
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D))
    seq = block_fn(Ws, x)
    got = pipeline_forward(block_fn, split_stages(Ws, 4), x, mesh=mesh,
                           n_stages=4, n_micro=4)
    print(float(jnp.max(jnp.abs(got - seq))))
    """)
    assert float(out.strip()) == 0.0


def test_int8_ring_allreduce_and_error_feedback():
    out = _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.parallel.collectives import ring_allreduce_int8
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("dp",))
    g = jax.random.normal(jax.random.PRNGKey(2), (8, 1000)) * 0.01
    def red0(gl):
        r, e = ring_allreduce_int8(gl[0], "dp", 8)
        return r[None], e[None]
    def red(gl, el):
        r, e = ring_allreduce_int8(gl[0], "dp", 8, error=el[0])
        return r[None], e[None]
    red0j = jax.jit(jax.shard_map(red0, mesh=mesh, in_specs=(P("dp"),),
                                  out_specs=(P("dp"), P("dp"))))
    redj = jax.jit(jax.shard_map(red, mesh=mesh,
                                 in_specs=(P("dp"), P("dp")),
                                 out_specs=(P("dp"), P("dp"))))
    exact = jnp.sum(g, axis=0)
    r1, err = red0j(g)
    rel1 = float(jnp.max(jnp.abs(r1[0] - exact)) / jnp.max(jnp.abs(exact)))
    # feed the SAME gradient again with error feedback: residue is re-
    # injected, so the time-averaged estimate improves
    r2, err = redj(g, err)
    avg = (r1[0] + r2[0]) / 2
    rel2 = float(jnp.max(jnp.abs(avg - exact)) / jnp.max(jnp.abs(exact)))
    print(rel1, rel2)
    """)
    rel1, rel2 = map(float, out.split())
    assert rel1 < 0.05  # int8 quantisation error is small
    assert rel2 < rel1  # error feedback reduces the time-averaged error


def test_compiled_farm_uses_devices():
    """The farm pattern with axis sharding really partitions the batch."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.core import DataParallelCollect, build
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    net = DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: x * x,
        collector=lambda a, x: a + x, init=jnp.asarray(0.0),
        workers=8, axis="data", jit_combine=True)
    cn = build(net, mesh=mesh)
    batch = cn.make_batch(64)
    lowered = cn.lower(batch)
    txt = lowered.compile().as_text()
    out = cn.run(instances=64)
    print(float(out["collect"]), txt.count("all-reduce") > 0)
    """)
    val, has_ar = out.split()
    assert float(val) == sum(i * i for i in range(64))
    assert has_ar == "True"  # the Collect fold psums across shards


def test_jaxmesh_hosts_compute_on_their_own_devices():
    """Four jaxmesh hosts on four devices: a chunk committed to its
    producer's device enters the consumer's stage jit (whose placement is
    folded in) and is computed on the consumer's device, bit-identical to
    the farm on one device."""
    out = _run("""
    import jax, numpy as np
    from repro.cluster import ClusterDeployment, partition
    from repro.cluster.transport import JaxMesh
    from repro.core import DataParallelCollect, build
    import jax.numpy as jnp
    net = DataParallelCollect(
        create=lambda i: jnp.asarray(float(i)),
        function=lambda x: jnp.sin(x) * x,
        collector=lambda acc, x: acc + [float(x)], init=[],
        workers=4, name="farm")
    plan = partition(net, assignment={"emit": 0, "ofa": 1, "group": 2,
                                      "afo": 3, "collect": 3})
    sent = {}

    class Probe(JaxMesh):
        def _send_transform(self, chan, value):
            for leaf in jax.tree_util.tree_leaves(value):
                sent.setdefault(plan.assignment[chan[0]], set()).update(
                    d.id for d in leaf.devices())
            return super()._send_transform(chan, value)

    with ClusterDeployment(net, plan=plan, transport=Probe(devices=jax.devices()[:4]),
                           microbatch_size=4) as dep:
        got = dep.run(instances=16)["collect"]
    want = build(net).run(instances=16)["collect"]
    print(got == want, sorted((h, sorted(d)) for h, d in sent.items()))
    """, devices=4)
    same, placed = out.strip().split(" ", 1)
    assert same == "True"
    assert placed == "[(0, [0]), (1, [1]), (2, [2])]"


def test_sharded_train_step_compiles_small_mesh():
    """Reduced config on a (2,2) mesh: the sharded train step lowers,
    compiles and communicates (its HLO holds a collective)."""
    out = _run("""
    import jax, jax.numpy as jnp, dataclasses
    from repro.configs import get_config
    from repro.models import Model
    from repro.parallel import sharding as shlib
    from repro.parallel.axes import shard_ctx, ShardingRules
    from repro.train.optimizer import AdamW
    from repro.train.train_loop import make_train_step
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    cfg = dataclasses.replace(get_config("qwen2-0.5b", reduced=True),
                              compute_dtype="float32")
    model = Model(cfg)
    params_sds = jax.eval_shape(model.init,
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
    rules = ShardingRules()
    p_spec = shlib.param_specs(params_sds, mesh, rules)
    p_sh = shlib.to_shardings(p_spec, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    b_sh = shlib.to_shardings(shlib.batch_specs(batch, mesh, rules), mesh)
    opt = AdamW()
    opt_sds = jax.eval_shape(opt.init, params_sds)
    o_sh = shlib.to_shardings({"m": p_spec, "v": p_spec,
                               "step": jax.sharding.PartitionSpec()}, mesh)
    with shard_ctx(mesh, rules):
        step = make_train_step(model, opt)
        compiled = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, None)).lower(
            params_sds, opt_sds, batch).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    print(any(k in text for k in ("all-reduce", "all-gather",
                                  "reduce-scatter")),
          ma.temp_size_in_bytes > 0)
    """, devices=4)
    assert out.split() == ["True", "True"]


def test_elastic_remesh_checkpoint():
    """A checkpoint written under one mesh restores onto another (the
    elastic-scaling path: pod loss → shrink and continue)."""
    out = _run("""
    import tempfile, numpy as np
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import Model
    from repro.parallel import sharding as shlib
    from repro.train import AdamW, Checkpointer
    from repro.launch.mesh import make_mesh, train_rules
    cfg = get_config("qwen2-0.5b", reduced=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rules = train_rules()
    mesh_a = make_mesh((4, 2), ("data", "model"))
    sh_a = shlib.to_shardings(shlib.param_specs(params, mesh_a, rules),
                              mesh_a)
    placed = jax.tree_util.tree_map(jax.device_put, params, sh_a)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(5, {"params": placed})
        # restore onto a SHRUNK mesh (node loss: 8 -> 4 devices)
        mesh_b = make_mesh((2, 2), ("data", "model"))
        sh_b = shlib.to_shardings(shlib.param_specs(params, mesh_b, rules),
                                  mesh_b)
        step, restored = ck.restore({"params": params},
                                    shardings={"params": sh_b})
        ok = all(np.allclose(np.asarray(a), np.asarray(b)) for a, b in zip(
            jax.tree_util.tree_leaves(params),
            jax.tree_util.tree_leaves(restored["params"])))
        devs = {d2 for l in jax.tree_util.tree_leaves(restored["params"])
                for d2 in l.devices()}
        print(step == 5, ok, len(devs) == 4)
    """)
    assert out.split() == ["True", "True", "True"]


def test_mesh_numerical_invariance():
    """The same train step on a (2,2) mesh and on one device produces the
    same loss/gradients — distribution never changes semantics."""
    out = _run("""
    import jax, jax.numpy as jnp, dataclasses
    from repro.configs import get_config
    from repro.models import Model
    from repro.data import SyntheticLM
    from repro.parallel import sharding as shlib
    from repro.parallel.axes import shard_ctx, ShardingRules
    from repro.launch.mesh import make_mesh, train_rules
    cfg = dataclasses.replace(get_config("qwen2-0.5b", reduced=True),
                              compute_dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    src = SyntheticLM(batch=8, seq=16, vocab=cfg.vocab)
    batch = src.create(0)
    loss_plain, _ = jax.jit(model.loss_fn)(params, batch)
    mesh = make_mesh((2, 2), ("data", "model"))
    rules = train_rules()
    sh = shlib.to_shardings(shlib.param_specs(params, mesh, rules), mesh)
    bsh = shlib.to_shardings(shlib.batch_specs(batch, mesh, rules), mesh)
    with shard_ctx(mesh, rules):
        loss_mesh, _ = jax.jit(model.loss_fn, in_shardings=(sh, bsh))(
            jax.tree_util.tree_map(jax.device_put, params, sh),
            jax.tree_util.tree_map(jax.device_put, batch, bsh))
    print(abs(float(loss_plain) - float(loss_mesh)))
    """, devices=4)
    assert float(out.strip()) < 1e-4


def test_multipod_mesh_axes():
    out = _run("""
    from repro.launch.mesh import make_production_mesh
    m = make_production_mesh(multi_pod=True)
    print(m.axis_names, m.devices.size)
    m1 = make_production_mesh()
    print(m1.axis_names, m1.devices.size)
    """, devices=512)
    lines = out.strip().splitlines()
    assert "('pod', 'data', 'model') 512" in lines[0]
    assert "('data', 'model') 256" in lines[1]
