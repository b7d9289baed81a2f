"""Measured per-process cost profiles — the input to cost-balanced cuts.

The paper's cluster capstone (§7) splits the network across workstations by
hand and the bottleneck host sets the pace; ``auto_assignment`` balances
process *counts*, which is the same failure dressed up.  This module measures
what each stage actually costs so :func:`repro.cluster.partition.cost_assignment`
can cut by *time*:

* :func:`calibrate` runs a short seeded calibration pass of the network —
  one tiny batch through a :class:`repro.core.stream.StreamExecutor` with
  fusion off and donation off, capturing each stage jit's real arguments —
  then times every captured stage jit (best-of-``repeats`` with
  ``block_until_ready``) and records its output size.  jax's
  ``cost_analysis`` flops/bytes ride along as a *prior* (used to estimate
  stages the calibration never executed); the measured wall time is ground
  truth.
* :func:`calibrate_bandwidth` times one transport round-trip per kind so a
  plan can price cut-channel traffic in seconds, not bytes.

Everything lands in a :class:`CostProfile` — cached per
``(process, shape, dtype)`` so re-calibrating an unchanged stage is free —
which ``benchmarks/perf_report.py`` renders and
``cost_assignment`` consumes.
"""

from __future__ import annotations

import dataclasses
import json
import time as _time
from typing import Optional

import numpy as np

from repro.core.dataflow import NetworkError

__all__ = ["ProcessCost", "CostProfile", "calibrate", "calibrate_bandwidth"]


@dataclasses.dataclass
class ProcessCost:
    """Measured (or estimated) cost of one process at one input signature."""

    name: str
    shape: tuple = ()
    dtype: str = ""
    wall_s: float = 0.0       # best-of-repeats measured chunk time
    out_bytes: int = 0        # bytes one output chunk puts on the wire
    flops: float = 0.0        # HLO cost_analysis prior (0 = unavailable)
    bytes_accessed: float = 0.0
    source: str = "measured"  # "measured" | "estimated" | "default"

    def signature(self) -> tuple:
        return (tuple(self.shape), self.dtype)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["shape"] = list(self.shape)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ProcessCost":
        d = dict(d)
        d["shape"] = tuple(d.get("shape", ()))
        return cls(**d)


@dataclasses.dataclass
class CostProfile:
    """Per-process measured costs + per-transport calibrated bandwidths.

    ``costs`` maps process name -> :class:`ProcessCost`; ``bandwidths`` maps
    transport kind -> bytes/s.  ``default_wall_s`` prices the structural
    stages calibration never jits (Emit, spreaders, MERGE) — small but
    non-zero, so a host of pure wiring is never free.  ``flops_per_s`` is
    the achieved rate across measured stages, used to *estimate* a stage
    that only has a ``cost_analysis`` prior.
    """

    costs: dict = dataclasses.field(default_factory=dict)
    bandwidths: dict = dataclasses.field(default_factory=dict)
    microbatch_size: int = 8
    seed: int = 0
    default_wall_s: float = 1e-6
    flops_per_s: float = 0.0

    def time_of(self, name: str) -> float:
        """Seconds one chunk spends in ``name`` — measured when we have it,
        flops/rate estimate when only the prior exists, default otherwise."""
        c = self.costs.get(name)
        if c is None:
            return self.default_wall_s
        if c.wall_s > 0:
            return c.wall_s
        if c.flops > 0 and self.flops_per_s > 0:
            return c.flops / self.flops_per_s
        return self.default_wall_s

    def out_bytes_of(self, name: str) -> int:
        c = self.costs.get(name)
        return c.out_bytes if c is not None else 0

    def transfer_s(self, nbytes: int, transport: Optional[str] = None) -> float:
        """Seconds ``nbytes`` spend crossing a cut channel.  Falls back to
        the fastest calibrated transport, then to free (no bandwidth data
        means transfer cost cannot be priced honestly)."""
        if nbytes <= 0:
            return 0.0
        bw = self.bandwidths.get(transport, 0.0)
        if bw <= 0 and self.bandwidths:
            bw = max(self.bandwidths.values())
        return nbytes / bw if bw > 0 else 0.0

    def describe(self) -> str:
        lines = [f"== cost profile (mb={self.microbatch_size}, "
                 f"seed={self.seed}) =="]
        for name in sorted(self.costs):
            c = self.costs[name]
            f = f"{c.flops:.3e}" if c.flops else "-"
            lines.append(
                f"{name:<24} {c.wall_s * 1e6:10.1f}us  "
                f"out={c.out_bytes:>8}B  flops={f}  [{c.source}]")
        for kind in sorted(self.bandwidths):
            lines.append(f"bandwidth[{kind:<9}] "
                         f"{self.bandwidths[kind] / 1e6:10.1f} MB/s")
        return "\n".join(lines)

    def to_json(self) -> dict:
        return {
            "costs": {n: c.to_json() for n, c in self.costs.items()},
            "bandwidths": dict(self.bandwidths),
            "microbatch_size": self.microbatch_size,
            "seed": self.seed,
            "default_wall_s": self.default_wall_s,
            "flops_per_s": self.flops_per_s,
        }

    @classmethod
    def from_json(cls, d: dict) -> "CostProfile":
        return cls(
            costs={n: ProcessCost.from_json(c)
                   for n, c in d.get("costs", {}).items()},
            bandwidths=dict(d.get("bandwidths", {})),
            microbatch_size=int(d.get("microbatch_size", 8)),
            seed=int(d.get("seed", 0)),
            default_wall_s=float(d.get("default_wall_s", 1e-6)),
            flops_per_s=float(d.get("flops_per_s", 0.0)),
        )

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "CostProfile":
        with open(path) as f:
            return cls.from_json(json.load(f))


def _leaf_signature(xs) -> tuple:
    """(shape, dtype) of the first array leaf of the stage's inputs — the
    cache key deciding whether an old measurement still applies."""
    import jax
    for x in xs:
        for leaf in jax.tree_util.tree_leaves(x):
            if hasattr(leaf, "shape"):
                return (tuple(leaf.shape), str(getattr(leaf, "dtype", "")))
    return ((), "")


def _tree_nbytes(value) -> int:
    import jax
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(value))


def calibrate(net, *, instances: Optional[int] = None,
              microbatch_size: int = 4, repeats: int = 3, seed: int = 0,
              transports=(), profile: Optional[CostProfile] = None,
              payload_bytes: int = 1 << 16) -> CostProfile:
    """Short seeded calibration run → :class:`CostProfile`.

    One tiny batch (``instances`` items, default one microbatch per lane)
    streams through the net with fusion and donation off; every stage jit's
    first real arguments are captured, then each stage is re-timed
    best-of-``repeats``.  ``transports`` names the kinds to bandwidth-time.
    Pass ``profile`` to re-calibrate incrementally: stages whose input
    signature is unchanged keep their old measurement.
    """
    from repro.core.builder import build
    from repro.core.stream import StreamExecutor

    class _CalibratingExecutor(StreamExecutor):
        """Capture each stage jit's first real arguments as they stream."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self._can_donate = False  # donation would eat captured buffers
            self.captured: dict = {}

        def _stage_jit(self, name, donate):
            real = super()._stage_jit(name, False)

            def probe(*xs, _name=name, _real=real):
                self.captured.setdefault(_name, xs)
                return _real(*xs)

            return probe

    cn = build(net)
    ex = _CalibratingExecutor(cn, microbatch_size=microbatch_size,
                              fuse=False)
    if instances is None:
        # enough chunks that every lane/branch sees at least one
        instances = microbatch_size * max(2, ex.lanes)
    np.random.seed(seed)
    batch = cn.make_batch(instances)
    ex.run(batch)
    if not ex.captured:
        raise NetworkError(
            f"calibration run of {net.name!r} executed no stage jits")

    out = profile if profile is not None else CostProfile()
    out.microbatch_size = microbatch_size
    out.seed = seed
    import jax

    total_wall = total_flops = 0.0
    for name, xs in ex.captured.items():
        sig = _leaf_signature(xs)
        old = out.costs.get(name)
        if old is not None and old.signature() == sig and old.wall_s > 0:
            total_wall += old.wall_s
            total_flops += old.flops
            continue  # cache hit: same (process, shape, dtype)
        fn = ex._jits[(name, False)]
        jax.block_until_ready(fn(*xs))  # warm (compile outside the clock)
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(*xs))
            best = min(best, _time.perf_counter() - t0)
        result = fn(*xs)
        flops = bytes_accessed = 0.0
        try:  # HLO prior — best effort
            ca = fn.lower(*xs).compile().cost_analysis()
            flops = float(ca.get("flops") or 0.0)
            bytes_accessed = float(ca.get("bytes accessed") or 0.0)
        except Exception:
            pass
        out.costs[name] = ProcessCost(
            name=name, shape=sig[0], dtype=sig[1], wall_s=best,
            out_bytes=_tree_nbytes(result), flops=flops,
            bytes_accessed=bytes_accessed, source="measured")
        total_wall += best
        total_flops += flops
    if total_wall > 0 and total_flops > 0:
        out.flops_per_s = total_flops / total_wall
    # structural stages cost "one dispatch", not zero: an order of magnitude
    # under the cheapest measured stage
    cheapest = min((c.wall_s for c in out.costs.values() if c.wall_s > 0),
                   default=1e-5)
    out.default_wall_s = max(cheapest / 10.0, 1e-7)
    for kind in transports:
        out.bandwidths[kind] = calibrate_bandwidth(
            kind, payload_bytes=payload_bytes)
    return out


def calibrate_bandwidth(kind: str = "inprocess", *,
                        payload_bytes: int = 1 << 16,
                        repeats: int = 16) -> float:
    """Bytes/s of one transport kind: time ``repeats`` same-process
    send+recv round trips of a ``payload_bytes`` float32 array over a
    private channel.  Includes pack/unpack (pickling, shm slot copies) —
    the cost a cut channel actually pays, not the theoretical link rate."""
    from repro.cluster.transport import make_transport

    t = make_transport(kind)
    chan = ("__calib_src__", "__calib_dst__")
    t.setup([chan], {chan: 4})
    try:
        arr = np.zeros(max(1, payload_bytes // 4), dtype=np.float32)
        t.send(chan, 0, arr)  # warm the path (feeder threads, shm attach)
        t.recv(chan, 0)
        t0 = _time.perf_counter()
        for i in range(1, repeats + 1):
            t.send(chan, i, arr)
            t.recv(chan, i)
        elapsed = _time.perf_counter() - t0
    finally:
        t.close()
    return (repeats * arr.nbytes) / max(elapsed, 1e-9)
