"""Quickstart: Monte-Carlo π as a GPP farm (paper §3, Listings 1–4).

The user writes three sequential methods (create / getWithin / collector) —
the library provides the parallel architecture, formal verification, the
sequential oracle, and integrated logging.

    PYTHONPATH=src python examples/quickstart.py

The same declarative network also deploys across hosts unchanged (the
paper's cluster capstone): see ``examples/mandelbrot.py --hosts 2`` and
``python -m repro.launch.cluster`` for the cluster runtime with pluggable
channel transports.
"""

import jax
import jax.numpy as jnp

from repro.core import DataParallelCollect, build, csp, run_sequential
from repro.launch.cache import enable_compile_cache

INSTANCES = 256
ITERATIONS = 10_000
WORKERS = 4


# -- the user's sequential methods (paper Listing 5/6) ----------------------

def create(i):
    """piData.createInstance: the i-th work item (its RNG seed)."""
    return jnp.asarray(i, jnp.uint32)


def get_within(seed):
    """piData.getWithin: count points inside the unit quadrant."""
    pts = jax.random.uniform(jax.random.PRNGKey(seed), (ITERATIONS, 2))
    return jnp.sum((pts ** 2).sum(-1) <= 1.0).astype(jnp.int32)


def collector(acc, within):
    """piResults.collector: accumulate the within counts."""
    return acc + within


def finalise(total_within):
    """piResults.finalise: π from the hit ratio."""
    return 4.0 * total_within / (INSTANCES * ITERATIONS)


def main():
    enable_compile_cache()
    # the declarative network (paper Listing 2 — one pattern invocation)
    net = DataParallelCollect(
        create=create, function=get_within, collector=collector,
        init=jnp.asarray(0, jnp.int32), finalise=finalise,
        workers=WORKERS, jit_combine=True)

    # 1. formal verification of the explicit process network (FDR4-lite)
    explicit = DataParallelCollect(
        create=create, function=get_within, collector=collector,
        workers=2, explicit=True)
    r = csp.check(explicit, instances=3)
    print(f"[csp] states={r.n_states} deadlock_free={r.deadlock_free} "
          f"deterministic={r.deterministic} "
          f"terminates={r.all_paths_terminate}")

    # 2. sequential oracle (paper Listing 4 — same methods, plain loop)
    pi_seq = run_sequential(net, INSTANCES)["collect"]
    print(f"[seq] pi = {float(pi_seq):.5f}")

    # 3. compiled SPMD network
    cn = build(net)
    pi_par = cn.run(instances=INSTANCES)["collect"]
    print(f"[par] pi = {float(pi_par):.5f}  (identical: "
          f"{float(pi_seq) == float(pi_par)})")

    # 4. streaming microbatch execution (process-oriented throughput mode)
    pi_strm = cn.run_streaming(instances=INSTANCES,
                               microbatch_size=32)["collect"]
    print(f"[stream] pi = {float(pi_strm):.5f}  (identical: "
          f"{float(pi_seq) == float(pi_strm)})  "
          f"[{cn.stream_stats.summary()}]")

    # 5. integrated logging (paper §8) + visualisation (paper §13)
    cn.run(instances=INSTANCES, logged=True)
    from repro.core import netlog
    print(netlog.report(cn))


if __name__ == "__main__":
    main()
