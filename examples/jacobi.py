"""Jacobi solver on the MultiCoreEngine (paper §6.2, Listing 15).

A stream of equation systems flows Emit → MultiCoreEngine → Collect; the
engine iterates the partitioned update until the error margin is met (the
root's sequential error/update phase between BSP supersteps).

    PYTHONPATH=src python examples/jacobi.py [--n 256] [--nodes 4]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Collect, Emit, MultiCoreEngine, Network, build,
                        rows, verify)
from repro.launch.cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--systems", type=int, default=2)
    ap.add_argument("--tol", type=float, default=1e-7)
    args = ap.parse_args()
    enable_compile_cache()
    n = args.n

    rng = np.random.default_rng(0)
    systems, truths = [], []
    for _ in range(args.systems):
        A = rng.normal(size=(n, n)).astype(np.float32) \
            + n * np.eye(n, dtype=np.float32)  # diagonally dominant
        x_true = rng.normal(size=n).astype(np.float32)
        systems.append({"A": jnp.asarray(A), "b": jnp.asarray(A @ x_true),
                        "x": jnp.zeros(n, jnp.float32)})
        truths.append(x_true)

    # -- the user's sequential methods (paper Listing 15 names) -----------
    def partitionMethod(state, lo, size):
        return {"A": rows(state["A"], lo, size),
                "b": rows(state["b"], lo, size),
                "x": state["x"], "lo": lo, "size": size}

    def calculationMethod(part):
        idx = part["lo"] + jnp.arange(part["size"])
        diag = jax.vmap(lambda r, j: r[j])(part["A"], idx)
        return (part["b"] - part["A"] @ part["x"]
                + diag * rows(part["x"], part["lo"], part["size"])) / diag

    def updateMethod(state, new_x):
        return {**state, "x": new_x}

    def errorMethod(state, new_x):
        return jnp.max(jnp.abs(new_x - state["x"]))

    net = Network("jacobi")
    net.add(
        Emit(lambda i: systems[i], name="emit"),
        MultiCoreEngine(nodes=args.nodes, n_rows=n,
                        partitionMethod=partitionMethod,
                        calculationMethod=calculationMethod,
                        updateMethod=updateMethod, errorMethod=errorMethod,
                        tol=args.tol, name="mcEngine"),
        Collect(lambda acc, st: acc + [np.asarray(st["x"])], init=[],
                name="collector"),
    )
    verify(net)
    out = build(net).run(instances=args.systems)["collector"]
    for i, (x, x_true) in enumerate(zip(out, truths)):
        err = float(np.max(np.abs(x - x_true)))
        print(f"system {i}: max|x - x_true| = {err:.2e} "
              f"({'OK' if err < 1e-3 else 'FAIL'})")


if __name__ == "__main__":
    main()
