"""NetworkBuilder — the gppBuilder analogue.

Two execution semantics for the *same* declarative network, mirroring the
paper's key property P4 (the same user methods run sequentially and in
parallel):

* :func:`run_sequential` — host-level denotational semantics (the paper's
  Listing-4 oracle): plain Python, item by item, no JAX tracing required.
* :func:`build` → :class:`CompiledNetwork` — the network is verified
  (``verify``), then traced into a single SPMD program.  Connector semantics
  become sharding constraints / collectives; the farm's workers become a
  vmapped (and mesh-sharded) batch dimension.

Logged execution (paper §8): ``CompiledNetwork.run(..., logged=True)``
executes stage-by-stage (per-stage jit with host timing) instead of one fused
program — exactly GPP's "two versions of every process" trade (observability
for peak speed) — and attributes per-stage FLOPs/bytes from each stage's own
compiled artifact.
"""

from __future__ import annotations

import copy
import dataclasses
import functools
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dataflow import Distribution, Kind, Network, NetworkError, ProcessDef
from .verify import verify

__all__ = ["run_sequential", "build", "CompiledNetwork", "StageLog",
           "make_emit_batch"]


# ==========================================================================
# Sequential oracle (denotational list semantics)
# ==========================================================================

def run_sequential(net: Network, instances: int, *, deepcopy_casts: bool = True):
    """Execute the network on the host, item by item, in declaration order.

    Returns ``{collect_name: finalised_value}``.  This is the correctness
    oracle: the compiled network must produce identical results.
    """
    verify(net)
    order = net.toposort()
    # each value on a wire is a list of (orig_index, item) pairs
    wires: dict[tuple[str, str], list] = {}
    results: dict[str, Any] = {}

    def _inputs(name: str) -> list[list]:
        return [wires[(p, name)] for p in net.predecessors(name)]

    for name in order:
        p = net.procs[name]
        succs = net.successors(name)
        if p.kind is Kind.EMIT:
            if p.modifier:  # EmitWithLocal: thread local state
                local = p.modifier[0]()
                stream = []
                for i in range(instances):
                    item, local = p.fn(i, local)
                    stream.append((i, item))
            else:
                stream = [(i, p.fn(i)) for i in range(instances)]
            out_streams = _spread_fan(stream, len(succs))
            for j, s in enumerate(succs):
                wires[(name, s)] = out_streams[j]
        elif p.kind is Kind.SPREADER:
            (stream,) = _inputs(name)
            if p.distribution is Distribution.FAN:
                outs = _spread_fan(stream, len(succs))
            else:  # casts: every successor gets a (deep) copy of the stream
                outs = [
                    [(i, copy.deepcopy(v) if deepcopy_casts else v)
                     for (i, v) in stream]
                    for _ in succs
                ]
            for j, s in enumerate(succs):
                wires[(name, s)] = outs[j]
        elif p.kind in (Kind.WORKER, Kind.ENGINE):
            (stream,) = _inputs(name)
            fn = p.fn if p.kind is Kind.WORKER else p.engine.as_worker_fn()
            out = [(i, fn(v, *p.modifier)) for (i, v) in stream]
            for s in succs:  # worker has exactly one successor (verified)
                wires[(name, s)] = out
        elif p.kind is Kind.REDUCER:
            streams = _inputs(name)
            if p.distribution is Distribution.COMBINE:
                flat = sorted((pair for s in streams for pair in s),
                              key=lambda t: t[0])
                acc = flat[0][1]
                for _, v in flat[1:]:
                    acc = p.fn(acc, v)
                out = [(0, acc)]
            else:  # MERGE: re-interleave by original index (fairSelect order)
                out = sorted((pair for s in streams for pair in s),
                             key=lambda t: t[0])
            for s in succs:
                wires[(name, s)] = out
        elif p.kind is Kind.COLLECT:
            streams = _inputs(name)
            flat = sorted((pair for s in streams for pair in s),
                          key=lambda t: t[0])
            acc = copy.deepcopy(p.init)
            for _, v in flat:
                acc = p.fn(acc, v)
            results[name] = p.finalise(acc) if p.finalise else acc
    return results


def _spread_fan(stream: list, n_succ: int) -> list[list]:
    """Round-robin split preserving original indices (OneFanList semantics)."""
    if n_succ <= 1:
        return [list(stream)]
    return [stream[j::n_succ] for j in range(n_succ)]


# ==========================================================================
# Compiled SPMD mode
# ==========================================================================

@dataclasses.dataclass
class StageLog:
    """One logged stage record (paper §8 analogue)."""

    stage: str
    kind: str
    wall_s: float
    flops: float | None = None
    bytes_accessed: float | None = None

    def row(self) -> str:
        f = f"{self.flops:.3e}" if self.flops is not None else "-"
        b = f"{self.bytes_accessed:.3e}" if self.bytes_accessed is not None else "-"
        return f"{self.stage:<24} {self.kind:<9} {self.wall_s*1e3:10.3f}ms  flops={f} bytes={b}"


class CompiledNetwork:
    """A verified network bound to an optional mesh, executable as one jitted
    SPMD program (``run``) or stage-by-stage with logging (``run(logged=True)``).
    """

    def __init__(self, net: Network, mesh: Optional[jax.sharding.Mesh] = None,
                 donate_batch: bool = False):
        self.net = net
        self.mesh = mesh
        self.report = verify(net)
        self.order = net.toposort()
        self._collect_host: dict[str, ProcessDef] = {}
        self._step = None
        self._donate = donate_batch
        self.logs: list[StageLog] = []
        self.stream_stats = None  # set by run_streaming
        self._streams: dict = {}  # StreamExecutor cache (stage jits persist)

    # -- sharding helpers --------------------------------------------------
    def _constraint(self, x, axis, *, replicate: bool = False):
        if self.mesh is None:
            return x
        P = jax.sharding.PartitionSpec
        if replicate or axis is None:
            spec = P()
        else:
            spec = P(axis)

        def _one(leaf):
            if not hasattr(leaf, "ndim") or leaf.ndim == 0:
                return leaf
            s = jax.sharding.NamedSharding(self.mesh, spec)
            return jax.lax.with_sharding_constraint(leaf, s)

        return jax.tree_util.tree_map(_one, x)

    # -- shared stage compilation ------------------------------------------
    def stage_fn(self, name: str) -> Optional[Callable]:
        """The pure traceable callable for one computational stage.

        This is the single stage-compilation path shared by all three
        execution modes: fused ``_trace`` inlines it into one program, logged
        execution wraps it in a timed per-stage jit, and the streaming
        microbatch executor (:mod:`repro.core.stream`) gives it a per-chunk
        jit with buffer donation.  Structural stages (Emit, spreaders, MERGE
        reducers) return None: they are wiring, realised by each mode.
        """
        p = self.net.procs[name]
        if p.kind is Kind.WORKER:
            if p.batched:
                return lambda x: p.fn(x, *p.modifier)
            return jax.vmap(lambda v: p.fn(v, *p.modifier))
        if p.kind is Kind.ENGINE:
            return lambda x: jax.lax.map(
                lambda it: p.engine.apply(it, mesh=self.mesh), x)
        if p.kind is Kind.REDUCER and p.distribution is Distribution.COMBINE:
            def _comb(*vals):
                acc = vals[0]
                for v in vals[1:]:
                    acc = p.fn(acc, v)
                return _fold_batch(p.fn, acc)
            return _comb
        if p.kind is Kind.COLLECT and p.jit_combine:
            return lambda x: _fold_batch(p.fn, x, init=p.init)
        return None

    def collect_carry_fn(self, name: str) -> Callable:
        """Streaming variant of the Collect fold: ``(acc, chunk) -> acc``.

        Folds a microbatch into the running accumulator in item order, so a
        chain of carry folds over chunks is the *same* linear left fold as
        the fused ``stage_fn`` over the whole batch — bit-identical results.
        """
        p = self.net.procs[name]
        return lambda acc, x: _fold_batch(p.fn, x, init=acc)

    def combine_carry_fn(self, name: str) -> Callable:
        """Streaming variant of the COMBINE reducer: ``(acc, *chunks) -> acc``.

        Same shape as ``collect_carry_fn``: elementwise across branches, then
        a linear fold continued from the carried accumulator, preserving the
        fused mode's exact float association across chunk boundaries.
        """
        p = self.net.procs[name]

        def _carry(acc, *vals):
            x = vals[0]
            for v in vals[1:]:
                x = p.fn(x, v)
            return _fold_batch(p.fn, x, init=acc)

        return _carry

    # -- tracing the DAG ---------------------------------------------------
    def _trace(self, batch, *, logged: bool = False):
        """Evaluate the network on a batched input pytree.

        Returns (results_dict, host_streams_dict) where host_streams carries
        batched outputs destined for host-side (non-jittable) collectors.
        """
        net = self.net
        wires: dict[tuple[str, str], Any] = {}
        results: dict[str, Any] = {}
        host_streams: dict[str, Any] = {}

        def _in(name: str) -> list:
            return [wires[(p, name)] for p in net.predecessors(name)]

        for name in self.order:
            p = net.procs[name]
            succs = net.successors(name)
            if p.kind is Kind.EMIT:
                out = batch
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.SPREADER:
                (x,) = _in(name)
                if p.distribution is Distribution.FAN:
                    if len(succs) == 1:
                        outs = [self._constraint(x, p.axis)]
                    else:
                        outs = _fan_split(x, len(succs))
                        outs = [self._constraint(o, p.axis) for o in outs]
                else:  # casts → replicate to each successor
                    outs = [self._constraint(x, None, replicate=True)
                            for _ in succs]
                for j, s in enumerate(succs):
                    wires[(name, s)] = outs[j]
            elif p.kind in (Kind.WORKER, Kind.ENGINE):
                # engines consume the stream one item at a time (lax.map =
                # sequential scan; engine bodies hold their own iteration
                # loops / shard_maps)
                (x,) = _in(name)
                with jax.named_scope(name):
                    out = self.stage_fn(name)(x)
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.REDUCER:
                xs = _in(name)
                if p.distribution is Distribution.COMBINE:
                    # fold across branches, then across the batch axis
                    out = self.stage_fn(name)(*xs)
                else:  # MERGE
                    out = xs[0] if len(xs) == 1 else _fan_merge(xs)
                    if p.axis is not None:
                        out = self._constraint(out, None, replicate=True)
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.COLLECT:
                xs = _in(name)
                x = xs[0] if len(xs) == 1 else _fan_merge(xs)
                if p.jit_combine:
                    results[name] = self.stage_fn(name)(x)
                else:
                    host_streams[name] = x  # fold host-side after the step
        return results, host_streams

    # -- public API ----------------------------------------------------------
    def step_fn(self) -> Callable:
        """The single fused jitted step: batch -> (results, host_streams)."""
        if self._step is None:
            donate = (0,) if self._donate else ()
            self._step = jax.jit(lambda b: self._trace(b),
                                 donate_argnums=donate)
        return self._step

    def lower(self, batch_spec):
        """Lower (no execution) for cost analysis."""
        return jax.jit(lambda b: self._trace(b)).lower(batch_spec)

    def make_batch(self, instances: int):
        """Build the batched Emit output on the host (stacking create(i))."""
        return make_emit_batch(self.net, instances)

    def run(self, batch=None, *, instances: Optional[int] = None,
            logged: bool = False):
        """Execute.  Provide either a pre-batched pytree or ``instances``."""
        if batch is None:
            if instances is None:
                raise NetworkError("run() needs batch= or instances=")
            batch = self.make_batch(instances)
        if logged:
            results, host_streams = self._run_logged(batch)
        else:
            results, host_streams = self.step_fn()(batch)
        return self._finalise(results, host_streams)

    def run_streaming(self, batch=None, *, instances: Optional[int] = None,
                      microbatch_size: int = 8,
                      max_in_flight: Optional[int] = None,
                      lanes: Optional[int] = None, fuse: bool = True):
        """Execute as a pipeline of microbatches (paper's process-oriented
        streaming, ``repro.core.stream``): items are split into
        ``microbatch_size`` chunks, each stage is a per-stage jitted step with
        buffer donation, chunks are dispatched asynchronously and only the
        Collect synchronises.  ``max_in_flight`` bounds the number of
        unretired chunks (defaults to the network's minimum positive channel
        capacity); ``lanes`` sets the work-stealing lane count for OneFanAny.

        Every Collect (and COMBINE reducer) folds chunks through a carried
        accumulator in the same linear order as the whole-batch fold, so
        results are bit-identical to logged mode always, and to fused
        ``run`` / ``run_sequential`` up to XLA's whole-program reassociation
        (observable only for COMBINE over non-exact floats; exact on every
        paper network).  Scheduling telemetry lands in ``self.stream_stats``.

        ``fuse`` (default on) compiles each maximal linear Worker/Engine run
        into ONE per-chunk jit (:func:`repro.core.stream.fused_chains`) —
        same op sequence, one dispatch per chain; the fused chains appear in
        ``stream_stats.fused``.
        """
        from .stream import StreamExecutor
        if batch is None:
            if instances is None:
                raise NetworkError("run_streaming() needs batch= or instances=")
            batch = self.make_batch(instances)
        key = (microbatch_size, max_in_flight, lanes, fuse)
        ex = self._streams.get(key)
        if ex is None:
            ex = self._streams[key] = StreamExecutor(
                self, microbatch_size=microbatch_size,
                max_in_flight=max_in_flight, lanes=lanes, fuse=fuse)
        out = ex.run(batch)
        self.stream_stats = ex.stats
        return out

    def _finalise(self, results, host_streams):
        out: dict[str, Any] = {}
        for name, p in ((c.name, c) for c in self.net.collects()):
            if p.jit_combine:
                val = results[name]
            else:
                val = fold_host_stream(p.fn, copy.deepcopy(p.init),
                                       host_stream(host_streams[name]))
            out[name] = p.finalise(val) if p.finalise else val
        return out

    # -- logged (per-stage) execution: paper §8 ------------------------------
    def _run_logged(self, batch):
        """Stage-by-stage execution with wall timing + per-stage HLO cost.

        Deliberately un-fused (the paper's logged processes forgo
        @CompileStatic); use for bottleneck hunting, not for peak numbers.
        """
        self.logs = []
        net = self.net
        wires: dict[tuple[str, str], Any] = {}
        results: dict[str, Any] = {}
        host_streams: dict[str, Any] = {}

        def timed(stage: str, kind: str, fn: Callable, *args):
            jfn = jax.jit(fn)
            t0 = time.monotonic()
            out = jfn(*args)
            out = jax.block_until_ready(out)
            wall = time.monotonic() - t0
            flops = bytes_ = None
            try:
                ca = jfn.lower(*args).compile().cost_analysis()
                flops = ca.get("flops")
                bytes_ = ca.get("bytes accessed")
            except Exception:  # cost analysis is best-effort
                pass
            self.logs.append(StageLog(stage, kind, wall, flops, bytes_))
            return out

        def _in(name: str) -> list:
            return [wires[(p, name)] for p in net.predecessors(name)]

        for name in self.order:
            p = net.procs[name]
            succs = net.successors(name)
            if p.kind is Kind.EMIT:
                for s in succs:
                    wires[(name, s)] = batch
            elif p.kind is Kind.SPREADER:
                (x,) = _in(name)
                if p.distribution is Distribution.FAN and len(succs) > 1:
                    outs = _fan_split(x, len(succs))
                else:
                    outs = [x for _ in succs]
                for j, s in enumerate(succs):
                    wires[(name, s)] = self._constraint(
                        outs[j], p.axis,
                        replicate=p.distribution is not Distribution.FAN)
            elif p.kind in (Kind.WORKER, Kind.ENGINE):
                (x,) = _in(name)
                out = timed(name, p.kind.value, self.stage_fn(name), x)
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.REDUCER:
                xs = _in(name)
                if p.distribution is Distribution.COMBINE:
                    out = timed(name, "reducer", self.stage_fn(name), *xs)
                else:
                    out = xs[0] if len(xs) == 1 else _fan_merge(xs)
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.COLLECT:
                xs = _in(name)
                x = xs[0] if len(xs) == 1 else _fan_merge(xs)
                if p.jit_combine:
                    results[name] = timed(name, "collect",
                                          self.stage_fn(name), x)
                else:
                    host_streams[name] = x
        return results, host_streams

    def log_report(self) -> str:
        lines = [f"== netlog: {self.net.name} =="]
        total = sum(l.wall_s for l in self.logs) or 1e-12
        for l in self.logs:
            lines.append(l.row() + f"  ({100*l.wall_s/total:5.1f}%)")
        bottleneck = max(self.logs, key=lambda l: l.wall_s, default=None)
        if bottleneck:
            lines.append(f"-- bottleneck: {bottleneck.stage} "
                         f"({bottleneck.wall_s*1e3:.3f}ms)")
        return "\n".join(lines)


# -- batch/stream manipulation helpers -------------------------------------

def make_emit_batch(net: Network, instances: int, *, emit=None):
    """Materialise the single Emit's output as a stacked batch pytree.

    Module-level so callers that never build a ``CompiledNetwork`` for the
    whole graph (the cluster runtime batches on the Emit-owning host only)
    share the exact item order of the fused path.  ``emit`` overrides the
    Emit to batch when the net holds more than one (cluster partitions also
    carry boundary-ingress Emit shims).
    """
    if emit is None:
        emits = net.emits()
        if len(emits) != 1:
            raise NetworkError("make_batch requires exactly one Emit")
        emit = emits[0]
    e = emit
    if e.modifier:
        local = e.modifier[0]()
        items = []
        for i in range(instances):
            item, local = e.fn(i, local)
            items.append(item)
    else:
        items = [e.fn(i) for i in range(instances)]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *items)


def _fan_split(x, k: int):
    """Round-robin split of the leading axis into k streams (OneFanList)."""

    def _split(leaf, j):
        if leaf.shape[0] % k != 0:
            raise NetworkError(
                f"compiled FAN to {k} heterogeneous branches requires batch "
                f"divisible by {k}, got {leaf.shape[0]}")
        return leaf[j::k]

    return [jax.tree_util.tree_map(lambda l: _split(l, j), x) for j in range(k)]


def _fan_merge(xs):
    """Inverse of _fan_split: interleave k equal streams back in order."""
    k = len(xs)

    def _merge(*leaves):
        stacked = jnp.stack(leaves, axis=1)  # (n/k, k, ...)
        return stacked.reshape((-1,) + stacked.shape[2:])

    return jax.tree_util.tree_map(_merge, *xs)


def start_host_copy(tree) -> None:
    """Start copying every device leaf of ``tree`` to the host, without
    waiting: a later ``np.asarray`` of the leaf takes the copy as it is."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()


def host_stream(stream):
    """A batched stream brought to the host in one copy per leaf (numpy)."""
    start_host_copy(stream)
    return jax.tree_util.tree_map(np.asarray, stream)


def fold_host_stream(fn, acc, stream):
    """A host Collect's fold (``jit_combine=False``): ``fn`` over the items
    of a :func:`host_stream` in item order.  Items are numpy views or
    scalars sliced on the host, so the loop launches no device program."""
    leaves, treedef = jax.tree_util.tree_flatten(stream)
    for i in range(leaves[0].shape[0] if leaves else 0):
        acc = fn(acc, treedef.unflatten([leaf[i] for leaf in leaves]))
    return acc


def _fold_batch(combine: Callable, x, init=None):
    """Associative fold of ``combine`` over the leading batch axis.

    Additions compile to a plain reduction (→ psum across shards); generic
    combines use a lax.scan fold.
    """
    leaves = jax.tree_util.tree_leaves(x)
    if not leaves or leaves[0].ndim == 0 or leaves[0].shape[0] == 1:
        item = jax.tree_util.tree_map(
            lambda l: l[0] if (hasattr(l, "ndim") and l.ndim > 0) else l, x)
        return combine(init, item) if init is not None else item
    n = leaves[0].shape[0]
    first = jax.tree_util.tree_map(lambda l: l[0], x)
    rest = jax.tree_util.tree_map(lambda l: l[1:], x)
    acc0 = combine(init, first) if init is not None else first

    def body(acc, item):
        return combine(acc, item), None

    acc, _ = jax.lax.scan(body, acc0, rest)
    return acc


def build(net: Network, mesh: Optional[jax.sharding.Mesh] = None,
          **kw) -> CompiledNetwork:
    """Verify + bind the network (the gppBuilder entry point)."""
    return CompiledNetwork(net, mesh=mesh, **kw)
