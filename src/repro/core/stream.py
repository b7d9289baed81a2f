"""Streaming microbatch executor — the process-oriented half of the paper.

The fused builder (:mod:`repro.core.builder`) materialises the whole item
batch and runs the network as one SPMD program; the paper's GPP runtime
instead *streams* items through Emit → Worker/Engine → Collect concurrently.
This module recovers that throughput model on top of JAX's async dispatch:

* the item batch is split into ``microbatch_size`` chunks
  (:func:`microbatch_plan` — the last chunk may be smaller);
* every computational stage is a per-stage jitted step (the builder's shared
  ``stage_fn`` compilation path) with buffer donation when the input chunk
  has no other reader;
* chunks are dispatched through the stage DAG without blocking — JAX queues
  the per-stage programs and overlaps host scheduling with device compute;
  ``jax.block_until_ready`` happens only when a chunk *retires* at Collect;
* the number of un-retired chunks in flight is bounded (backpressure): the
  depth defaults to the network's minimum positive CSP channel capacity
  (:meth:`Network.min_capacity`), so a tight channel throttles the whole
  pipeline exactly as a buffered CSP chain would;
* ``OneFanAny`` becomes work-stealing chunk assignment: each chunk goes to
  the least-loaded lane (with explicit per-worker branches, the whole chunk
  is routed down that branch), and the schedule is recorded in
  :class:`StreamStats`.

Correctness is anchored two ways.  Numerically, every Collect and COMBINE
reducer folds chunks with a carried accumulator in item order — the same
linear left fold as the whole-batch program, so results are bit-identical
to logged (per-stage) execution always and to fused ``run`` up to XLA's
whole-program reassociation (observable only for COMBINE over non-exact
floats).  Formally, :func:`streaming_abstract_model` builds the
CSP model of this schedule (chunks as items, lanes as concurrent stage
chains) and :func:`repro.core.csp.trace_equivalent` checks it against
:func:`synchronous_abstract_model` — the paper's §6.1.1 ``[T=`` refinement
story applied to our own runtime.

The microbatch *plan* is also the shared schedule for the mesh pipeline
(:func:`repro.parallel.pipeline.pipeline_forward`), gradient accumulation
(:func:`repro.train.train_loop.make_train_step`) and chunked prefill
(:class:`repro.serve.scheduler.FarmScheduler`) via :func:`stack_microbatches`
/ :func:`microbatch_plan`.
"""

from __future__ import annotations

import copy
import dataclasses
from collections import deque
from typing import Any, Optional

import jax

from . import trace as _trace
from .builder import CompiledNetwork, _fan_merge, _fan_split
from .dataflow import Distribution, Kind, Network, NetworkError
from .processes import (AnyFanOne, Collect, Emit, OneFanAny, Worker)

__all__ = [
    "microbatch_plan",
    "slice_microbatch",
    "stack_microbatches",
    "SlotEvent",
    "SlotPlan",
    "fused_chains",
    "plan_depth_lanes",
    "coalesced_capacity",
    "EmitChunks",
    "StreamStats",
    "StreamExecutor",
    "streaming_abstract_model",
    "synchronous_abstract_model",
]

_SKIP = object()  # sentinel: no chunk flowed down this branch


class EmitChunks(dict):
    """Chunk values keyed by Emit process name (cluster partitions feed
    several boundary-ingress Emits per chunk).  A dedicated type: a plain
    dict is a legal *pytree batch* and must reach every Emit whole."""


# ==========================================================================
# Microbatch planning (shared with pipeline / train / serve)
# ==========================================================================

def microbatch_plan(n_items: int, microbatch_size: int) -> list[tuple[int, int]]:
    """``[(lo, hi), ...]`` half-open chunk bounds covering ``[0, n_items)``.

    The last chunk may be smaller than ``microbatch_size``; callers that need
    uniform chunks (e.g. the GPipe schedule) use :func:`stack_microbatches`.
    """
    if microbatch_size <= 0:
        raise NetworkError(f"microbatch_size must be > 0, got {microbatch_size}")
    if n_items < 0:
        raise NetworkError(f"n_items must be >= 0, got {n_items}")
    return [(lo, min(lo + microbatch_size, n_items))
            for lo in range(0, n_items, microbatch_size)]


def slice_microbatch(batch, lo: int, hi: int):
    """Slice ``[lo, hi)`` off the leading axis of every leaf."""
    return jax.tree_util.tree_map(lambda l: l[lo:hi], batch)


def stack_microbatches(batch, n_micro: int):
    """``(B, ...)`` leaves → ``(n_micro, B // n_micro, ...)``.

    The uniform-chunk reshape of the same microbatch schedule, used where the
    chunk axis must be scanned (pipeline stages, gradient accumulation).
    """

    def _one(leaf):
        b = leaf.shape[0]
        if n_micro <= 0 or b % n_micro:
            raise NetworkError(
                f"batch axis {b} not divisible into {n_micro} microbatches")
        return leaf.reshape(n_micro, b // n_micro, *leaf.shape[1:])

    return jax.tree_util.tree_map(_one, batch)


# ==========================================================================
# Slot-batch plans (continuous batching: requests join/leave between chunks)
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class SlotEvent:
    """One admission-queue transition: request ``rid`` joined or left slot
    ``slot`` between decode chunks ``step - 1`` and ``step``."""

    step: int
    kind: str   # "join" | "leave"
    slot: int
    rid: int


class SlotPlan:
    """Which request owns which row of a slot-batched decode step.

    The serving engine's counterpart of :func:`microbatch_plan`: where a
    batch plan schedules a *fixed* item set into chunks, a slot plan
    schedules an *open-ended* request stream into a fixed row set — requests
    ``claim`` the lowest free slot when they join between decode chunks
    (the OneFanAny any-channel at request level) and ``release`` it when
    they finish, and every transition lands in :attr:`events` so an
    admission trace can be replayed or audited."""

    def __init__(self, n_slots: int):
        if n_slots <= 0:
            raise NetworkError(f"SlotPlan: n_slots must be > 0, got {n_slots}")
        self.n_slots = n_slots
        self.step = 0                       # decode chunks ticked so far
        self.events: list[SlotEvent] = []
        self._owner: list[Optional[int]] = [None] * n_slots

    @property
    def n_free(self) -> int:
        return sum(o is None for o in self._owner)

    def owner(self, slot: int) -> Optional[int]:
        return self._owner[slot]

    def claim(self, rid: int) -> int:
        """Seat ``rid`` in the lowest free slot; raises when the batch is
        full (admission must wait for a leave)."""
        for s, owner in enumerate(self._owner):
            if owner is None:
                self._owner[s] = rid
                self.events.append(SlotEvent(self.step, "join", s, rid))
                return s
        raise NetworkError(f"SlotPlan: no free slot for request {rid}")

    def release(self, slot: int) -> int:
        """Free ``slot``; returns the rid that held it."""
        rid = self._owner[slot]
        if rid is None:
            raise NetworkError(f"SlotPlan: slot {slot} is already free")
        self._owner[slot] = None
        self.events.append(SlotEvent(self.step, "leave", slot, rid))
        return rid

    def active(self) -> list[tuple[int, int]]:
        """``[(slot, rid), ...]`` for the occupied rows, slot order."""
        return [(s, r) for s, r in enumerate(self._owner) if r is not None]

    def mask(self):
        """(n_slots,) bool advance mask for the batched decode step."""
        import numpy as np
        return np.array([o is not None for o in self._owner], bool)

    def tick(self) -> None:
        """One decode chunk retired; joins/leaves now belong to the gap
        before the next chunk."""
        self.step += 1


# ==========================================================================
# Chain fusion planning (shared by the executor and the CSP abstraction)
# ==========================================================================

def fused_chains(net: Network) -> list[tuple[str, ...]]:
    """Maximal linear runs of functional stages that may compile as one jit.

    A run ``a -> b -> ...`` fuses when every member is a Worker/Engine, every
    link is the sole successor of its source and the sole predecessor of its
    destination, and no connector (fan/cast/reducer) sits inside the run —
    i.e. the stages form a straight pipe with no observable interleaving
    point between them.  Fusing such a run into one per-chunk jit preserves
    results exactly (same op sequence, one trace) while cutting per-chunk
    dispatch overhead to one call per chain instead of one per stage.

    Only runs of length >= 2 are returned; each is a tuple of stage names in
    dataflow order.
    """
    chains: list[tuple[str, ...]] = []
    in_chain: set[str] = set()
    for name in net.toposort():
        if name in in_chain:
            continue
        if net.procs[name].kind not in (Kind.WORKER, Kind.ENGINE):
            continue
        chain = [name]
        node = name
        while True:
            succs = net.successors(node)
            if len(succs) != 1:
                break
            nxt = succs[0]
            if (net.procs[nxt].kind not in (Kind.WORKER, Kind.ENGINE)
                    or len(net.predecessors(nxt)) != 1):
                break
            chain.append(nxt)
            node = nxt
        if len(chain) > 1:
            chains.append(tuple(chain))
            in_chain.update(chain)
    return chains


def plan_depth_lanes(net: Network, max_in_flight: Optional[int],
                     lanes: Optional[int]) -> tuple[int, int]:
    """The (in-flight depth, lane count) a StreamExecutor will run with.

    Depth defaults to the network's minimum positive CSP channel capacity
    (rendezvous networks get 2); lanes default to the widest OneFanAny (or
    the depth when no fan is present).  Exposed so deployment planning (e.g.
    cut-channel capacity derivation in :mod:`repro.cluster`) can size
    transport FIFOs to the executor's actual appetite without building one.
    """
    if max_in_flight is not None:
        depth = max_in_flight
    else:
        depth = net.min_capacity() or 2
    if depth < 1:
        raise NetworkError(f"max_in_flight must be >= 1, got {depth}")
    if lanes is not None and lanes < 1:
        raise NetworkError(f"lanes must be >= 1, got {lanes}")
    fan_widths = [
        len(net.successors(n)) for n, p in net.procs.items()
        if (p.kind is Kind.SPREADER and p.distribution is Distribution.FAN
            and p.fan_any)]
    n_lanes = lanes if lanes is not None else max(fan_widths + [depth])
    return depth, n_lanes


def coalesced_capacity(depth: int, lanes: int, record_bytes: int,
                       coalesce_bytes: int, floor: int = 2) -> int:
    """FIFO slot count for a cut channel whose transport coalesces records.

    With a ``coalesce_bytes`` budget, one queue slot carries
    ``budget // record_bytes`` records, so the consumer's in-flight appetite
    (``max(depth, lanes)`` records) fits in proportionally fewer slots —
    never below the rendezvous floor of 2.  ``floor`` is the transport's
    uncoalesced default capacity: when records are larger than the budget
    each ships alone (one record per slot), and the channel gets exactly
    the uncoalesced sizing ``max(floor, depth, lanes)`` — shrinking a
    large-record channel's FIFO below what the per-record path would
    allocate only adds backpressure stalls."""
    per_slot = max(1, coalesce_bytes // max(1, record_bytes))
    if per_slot == 1:
        return max(floor, depth, lanes)  # degraded: uncoalesced sizing
    appetite = max(depth, lanes, 2)
    return max(2, -(-appetite // per_slot))


# ==========================================================================
# The executor
# ==========================================================================

@dataclasses.dataclass
class StreamStats:
    """Telemetry of one streaming run."""

    n_items: int = 0
    microbatch_size: int = 0
    n_chunks: int = 0
    depth: int = 0  # bounded in-flight chunks (backpressure)
    lanes: int = 1
    schedule: list = dataclasses.field(default_factory=list)  # (chunk, lane)
    stalls: int = 0  # times the dispatcher blocked on backpressure
    # live progress, incremented at retirement (the only synchronisation
    # point).  Unlike ``n_items``/``n_chunks`` — plan totals preset when the
    # run starts — these count what actually finished, so a stalled or
    # partially-replayed run samples the truth, not the plan.
    chunks_done: int = 0
    items_done: int = 0
    # per-stage buffer-donation outcomes: {stage: [chunks_requested,
    # chunks_honoured]} — honoured means the input buffer was actually
    # consumed (is_deleted) by the stage jit, i.e. the memory was reused
    donation: dict = dataclasses.field(default_factory=dict)
    donation_enabled: bool = False  # False on backends without donation (CPU)
    # fused-chain composition: one tuple of stage names per linear run that
    # compiled into a single per-chunk jit (empty when nothing fused)
    fused: list = dataclasses.field(default_factory=list)
    # chunk-replay bookkeeping (cluster recovery): how many times this run
    # was resumed after an interrupted stream, and from which chunk
    replays: int = 0
    resumed_at: Optional[int] = None

    def donation_summary(self) -> str:
        if not self.donation_enabled:
            return "donation: disabled (backend has no buffer donation)"
        per = " ".join(f"{s}={h}/{r}" for s, (r, h) in
                       sorted(self.donation.items()))
        return f"donation: {per or '(no functional stages)'}"

    def fused_summary(self) -> str:
        if not self.fused:
            return "fused: (no chains)"
        per = " ".join("+".join(chain) for chain in self.fused)
        return f"fused: {per}"

    def summary(self) -> str:
        req = sum(r for r, _ in self.donation.values())
        hon = sum(h for _, h in self.donation.values())
        replay = (f", replays={self.replays}@chunk{self.resumed_at}"
                  if self.replays else "")
        return (f"stream: {self.n_chunks} chunks × ≤{self.microbatch_size} "
                f"items, depth={self.depth}, lanes={self.lanes}, "
                f"stalls={self.stalls}, donated={hon}/{req}, "
                f"fused_chains={len(self.fused)}{replay}")


@dataclasses.dataclass
class _ReplayState:
    """What survives an interrupted streaming run — the chunk-replay
    bookkeeping behind cluster recovery.  Captured when the interruption
    happened *before* the chunk had any effect (an ingress recv failure:
    chunks ``< next_ci`` are fully folded into the accumulators, chunk
    ``next_ci`` onwards never entered the DAG), so resuming the same plan at
    ``next_ci`` with these accumulators replays exactly the lost chunks."""

    next_ci: int          # first chunk that was NOT folded
    plan: list            # full bounds of the interrupted run
    jit_accs: dict        # per-Collect jitted fold accumulators
    host_accs: dict       # per-Collect host-side fold accumulators
    combine_carry: dict   # per-COMBINE carried accumulators
    stats: "StreamStats"  # telemetry continues across the resume


class StreamExecutor:
    """Run a :class:`CompiledNetwork` as a pipeline of microbatches."""

    # exception types whose mid-run capture is safe to resume from: raised
    # by _chunk_inputs BEFORE the chunk had any effect (the cluster
    # PartitionExecutor sets this to its transport error type)
    _resumable_errors: tuple = ()

    def __init__(self, compiled: CompiledNetwork, *, microbatch_size: int,
                 max_in_flight: Optional[int] = None,
                 lanes: Optional[int] = None, fuse: bool = True,
                 recorder: Optional[_trace.TraceRecorder] = None):
        self.cn = compiled
        self.net = compiled.net
        self.order = compiled.order
        self.mb = microbatch_size
        # observability: every executor records through a TraceRecorder —
        # the process-default (disabled unless trace.enable()) or an
        # explicitly owned one (cluster hosts get one each, so spans carry
        # correct host attribution even for thread-backed hosts)
        self.rec = recorder if recorder is not None else _trace.current()
        # depth: bounded in-flight chunks; lanes: work-stealing lane count
        # (explicit OneFanAny branches define it, otherwise as many lanes as
        # chunks can be in flight)
        self.depth, self.lanes = plan_depth_lanes(
            self.net, max_in_flight, lanes)
        self._outstanding = [0] * self.lanes
        self._combine_carry: dict = {}  # per-run COMBINE accumulators
        self.replay_state: Optional[_ReplayState] = None  # interrupted run
        # durability: when a snapshotter (train.checkpoint.Checkpointer) is
        # attached, the drive loop persists the fold accumulators every
        # `snapshot_every` chunks so an interrupted batch replays from the
        # last snapshot instead of chunk 0 (and a fresh controller can adopt
        # the on-disk state).  `snapshot_tag` is (batch_id, epoch), stamped
        # by the cluster host loop; `on_snapshot` is a pre-write hook (the
        # fault sim injects mid-snapshot-write kills through it)
        self.snapshotter = None
        self.snapshot_every: int = 0
        self.snapshot_tag: tuple = (0, 1)
        self.on_snapshot = None
        self._snap_seq = 0
        self._jits: dict = {}  # persists across runs: stages compile once
        self.jit_builds = 0  # cache misses — a warm executor stays at 0
        self.on_jit_build = None  # optional hook(name) for compile counting
        self.trace_counts: dict = {}  # stage -> actual jax trace count
        # intra-partition chain fusion: a straight Worker/Engine run compiles
        # into ONE per-chunk jit (composed via the shared stage_fn path), so
        # dispatch costs one call per chain instead of one per stage
        self._chains = fused_chains(self.net) if fuse else []
        self._chain_of_head = {c[0]: c for c in self._chains}
        self._chain_members = {n for c in self._chains for n in c[1:]}
        # CPU has no buffer donation — requesting it only buys a UserWarning
        # per stage per chunk
        self._can_donate = jax.default_backend() != "cpu"
        # mesh execution: fold each stage's input placement INTO its jit (as
        # the jit's input sharding) instead of an eager per-chunk device_put
        # between stages — the placement and the compute are one dispatch.
        # Maps stage name -> PartitionSpec of its input.
        self._in_spec: dict = {}
        if self.cn.mesh is not None:
            P = jax.sharding.PartitionSpec
            for c in self.net.channels:
                src, dst = self.net.procs[c.src], self.net.procs[c.dst]
                if (dst.kind in (Kind.WORKER, Kind.ENGINE)
                        and src.kind is Kind.SPREADER):
                    if src.distribution is Distribution.FAN:
                        spec = P(src.axis) if src.axis is not None else P()
                    else:  # casts replicate
                        spec = P()
                    self._in_spec[c.dst] = spec
        self.stats = StreamStats(microbatch_size=self.mb, depth=self.depth,
                                 lanes=self.lanes,
                                 donation_enabled=self._can_donate,
                                 fused=list(self._chains))

    def _is_fan_any(self, name: str) -> bool:
        p = self.net.procs[name]
        return (p.kind is Kind.SPREADER
                and p.distribution is Distribution.FAN and p.fan_any)

    def _record_build(self, name) -> None:
        self.jit_builds += 1
        if self.on_jit_build is not None:
            self.on_jit_build(name)

    def _stage_label(self, name: str) -> str:
        """Telemetry key for a stage: fused chains report as one unit."""
        chain = self._chain_of_head.get(name)
        return "+".join(chain) if chain else name

    # -- per-stage jit cache (shared stage_fn compilation path) ------------
    def _stage_fn(self, name: str):
        """The traceable callable for ``name`` — for a fused-chain head, the
        composition of every member's ``stage_fn`` (same shared compilation
        path, one trace)."""
        chain = self._chain_of_head.get(name)
        if chain is None:
            return self.cn.stage_fn(name)
        fns = [self.cn.stage_fn(m) for m in chain]

        def fused(x, _fns=tuple(fns)):
            for f in _fns:
                x = f(x)
            return x

        return fused

    def _stage_jit(self, name: str, donate: bool):
        key = (name, donate)
        if key not in self._jits:
            self._record_build(name)
            spec = self._in_spec.get(name)
            # the input placement is folded into the jit as its input
            # sharding: jit moves an input committed to another device (a
            # chunk from another host's chip), which a sharding constraint
            # inside the program may not
            kw = ({} if spec is None else {"in_shardings": (
                jax.sharding.NamedSharding(self.cn.mesh, spec),)})
            # the counter body executes only while jax TRACES (cache miss /
            # new shape): a warm deployment must never tick it again
            self._jits[key] = jax.jit(
                self._counted(self._stage_fn(name), self._stage_label(name)),
                donate_argnums=(0,) if donate else (), **kw)
        return self._jits[key]

    def _counted(self, fn, label):
        """Wrap ``fn`` so the counter ticks whenever jax TRACES it — cache
        misses AND shape-driven retraces both show up, so "0 new traces" is
        a truthful definition of a warm executor."""
        def counted(*args, _fn=fn, _label=label):
            self.trace_counts[_label] = self.trace_counts.get(_label, 0) + 1
            # this body runs only while jax traces, so the span brackets
            # exactly the trace/compile work (builds AND shape retraces)
            with self.rec.span("jit_trace", "compile", stage=_label):
                return _fn(*args)
        return counted

    def _carry_jit(self, name: str):
        if ("carry", name) not in self._jits:
            self._record_build(("carry", name))
            self._jits[("carry", name)] = jax.jit(self._counted(
                self.cn.collect_carry_fn(name), f"carry:{name}"))
        return self._jits[("carry", name)]

    def _combine_carry_jit(self, name: str):
        if ("comb", name) not in self._jits:
            self._record_build(("comb", name))
            self._jits[("comb", name)] = jax.jit(self._counted(
                self.cn.combine_carry_fn(name), f"comb:{name}"))
        return self._jits[("comb", name)]

    def new_traces(self) -> int:
        """Total stage-jit traces so far (builds + retraces); the warm-batch
        invariant is that this number stops moving."""
        return sum(self.trace_counts.values())

    def _wire(self, x, axis, dst: str, *, replicate: bool = False):
        """Constrain a value flowing to ``dst``: a no-op when ``dst``'s stage
        jit folds the constraint itself (``_in_spec``), else the eager put."""
        if dst in self._in_spec:
            return x
        return self._constrain(x, axis, replicate=replicate)

    def _constrain(self, x, axis, *, replicate: bool = False):
        """Eager analogue of the builder's sharding constraint (device_put —
        with_sharding_constraint needs a trace context).  Used only for wires
        whose reader has no stage jit to fold the constraint into."""
        mesh = self.cn.mesh
        if mesh is None:
            return x
        P = jax.sharding.PartitionSpec
        spec = P() if (replicate or axis is None) else P(axis)

        def _one(leaf):
            if not hasattr(leaf, "ndim") or leaf.ndim == 0:
                return leaf
            return jax.device_put(leaf, jax.sharding.NamedSharding(mesh, spec))

        return jax.tree_util.tree_map(_one, x)

    # -- work stealing ------------------------------------------------------
    def _steal_lane(self, chunk_idx: int) -> int:
        """OneFanAny chunk assignment: the least-loaded lane takes the chunk
        (any-channel semantics at microbatch granularity)."""
        lane = min(range(self.lanes), key=self._outstanding.__getitem__)
        self._outstanding[lane] += 1
        self.stats.schedule.append((chunk_idx, lane))
        return lane

    def _check_fan_divisibility(self, plan) -> None:
        """Fail fast (before any dispatch) when a heterogeneous FAN cannot
        split some chunk evenly — and name the knob the caller must turn."""
        for name in self.order:
            p = self.net.procs[name]
            succs = self.net.successors(name)
            if (p.kind is Kind.SPREADER
                    and p.distribution is Distribution.FAN
                    and len(succs) > 1 and not p.fan_any
                    and not self._homogeneous_fan(name)):
                k = len(succs)
                bad = sorted({hi - lo for lo, hi in plan if (hi - lo) % k})
                if bad:
                    raise NetworkError(
                        f"streaming over heterogeneous FAN {name!r} "
                        f"({k} branches) needs every microbatch divisible "
                        f"by {k}; microbatch_size={self.mb} yields chunk "
                        f"sizes {bad} — pick a microbatch_size (and item "
                        f"count) divisible by {k}")

    def _branch_signature(self, start: str):
        """The tag sequence of the functional chain from ``start`` down to
        the join node, or None when the branch itself branches (give up)."""
        sig: list = []
        node = start
        while True:
            p = self.net.procs[node]
            if p.kind not in (Kind.WORKER, Kind.ENGINE):
                sig.append(("join", node))
                return tuple(sig)
            # untagged workers count as unique (conservative: heterogeneous)
            sig.append(p.tag if p.tag is not None else node)
            succs = self.net.successors(node)
            if len(succs) != 1:
                return None
            node = succs[0]

    def _homogeneous_fan(self, name: str) -> bool:
        """True when every branch of a FAN runs the *same* stage-tag chain to
        the same join — the paper's CSPm Def 7 condition (workers of one
        stage share one ``f``), so whole chunks may route to any single
        branch without changing results."""
        sigs = {self._branch_signature(s) for s in self.net.successors(name)}
        return None not in sigs and len(sigs) == 1

    # -- one chunk through the DAG ------------------------------------------
    def _dispatch_chunk(self, ci: int, chunk, final: bool):
        """Push one microbatch through every stage (async — no blocking).

        ``chunk`` is the Emit's microbatch; a partitioned network (cluster
        runtime) passes an :class:`EmitChunks` map instead, so
        boundary-ingress Emits each carry their own transported chunk.

        Returns (collect_streams, host_streams, lanes_used): the values bound
        for each Collect (pre-fold), the host-side collect streams, and the
        work-stealing lanes this chunk occupies.
        """
        net = self.net
        wires: dict[tuple[str, str], Any] = {}
        collect_streams: dict[str, Any] = {}
        host_streams: dict[str, Any] = {}
        lanes_used: list[int] = []

        def _pop_in(name: str) -> list:
            return [wires.pop((q, name)) for q in net.predecessors(name)]

        for name in self.order:
            p = net.procs[name]
            succs = net.successors(name)
            if p.kind is Kind.EMIT:
                out = chunk[name] if isinstance(chunk, EmitChunks) else chunk
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.SPREADER:
                (x,) = _pop_in(name)
                if x is _SKIP:
                    for s in succs:
                        wires[(name, s)] = _SKIP
                elif p.distribution is Distribution.FAN:
                    if len(succs) == 1:
                        wires[(name, succs[0])] = self._wire(
                            x, p.axis, succs[0])
                    elif p.fan_any or self._homogeneous_fan(name):
                        # whole chunk to one branch: work-stealing lane for
                        # OneFanAny, round-robin for a homogeneous OneFanList
                        lane = (self._steal_lane(ci) if p.fan_any
                                else ci % len(succs))
                        if p.fan_any:
                            lanes_used.append(lane)
                        take = lane % len(succs)
                        for j, s in enumerate(succs):
                            wires[(name, s)] = (
                                self._wire(x, p.axis, s) if j == take
                                else _SKIP)
                    else:  # heterogeneous branches: item-level round-robin —
                        # every chunk must split evenly or assignment drifts
                        # from the sequential oracle's
                        outs = _fan_split(x, len(succs))
                        for j, s in enumerate(succs):
                            wires[(name, s)] = self._wire(outs[j], p.axis, s)
                else:  # casts: every successor reads the same (immutable)
                    # value — one replicated copy shared by all non-folded
                    # readers (folded stages place it inside their own jit)
                    rep = None
                    for s in succs:
                        if s in self._in_spec:
                            wires[(name, s)] = x
                        else:
                            if rep is None:
                                rep = self._constrain(x, None, replicate=True)
                            wires[(name, s)] = rep
            elif p.kind in (Kind.WORKER, Kind.ENGINE):
                if name in self._chain_members:
                    continue  # runs inside its chain head's fused jit
                chain = self._chain_of_head.get(name)
                label = self._stage_label(name)
                # a fused chain's output feeds the TAIL's successors
                out_of, succs = ((chain[-1], net.successors(chain[-1]))
                                 if chain else (name, succs))
                (x,) = _pop_in(name)
                if x is _SKIP:
                    out = _SKIP
                else:
                    # donate the input buffer iff nothing else still reads
                    # it — neither a pending wire nor a stream already
                    # handed to a Collect
                    donate = self._can_donate and not any(
                        v is x for v in (*wires.values(),
                                         *collect_streams.values(),
                                         *host_streams.values()))
                    out = self._stage_jit(name, donate)(x)
                    # conformance vocabulary: chunk ci traversed this stage
                    # (fused chains report "a+b" — every member applied)
                    self.rec.instant("stage", "csp", stage=label, ci=ci)
                    if donate:
                        rec = self.stats.donation.setdefault(label, [0, 0])
                        rec[0] += 1
                        leaves = [l for l in jax.tree_util.tree_leaves(x)
                                  if hasattr(l, "is_deleted")]
                        if leaves and all(l.is_deleted() for l in leaves):
                            rec[1] += 1
                    else:
                        self.stats.donation.setdefault(label, [0, 0])
                for s in succs:
                    wires[(out_of, s)] = out
            elif p.kind is Kind.REDUCER:
                xs = [v for v in _pop_in(name) if v is not _SKIP]
                if p.distribution is Distribution.COMBINE:
                    # carry the fold across chunks (same float association as
                    # the fused whole-batch fold); downstream sees the final
                    # accumulator once, on the last chunk — exactly fused
                    carry = self._combine_carry.get(name)
                    if carry is None:
                        acc = self._stage_jit(name, False)(*xs)
                    else:
                        acc = self._combine_carry_jit(name)(carry, *xs)
                    if final:
                        self._combine_carry.pop(name, None)
                        out = acc
                    else:
                        self._combine_carry[name] = acc
                        out = _SKIP
                else:  # MERGE (all-skip when e.g. every lane sat out a chunk)
                    if not xs:
                        out = _SKIP
                    else:
                        out = xs[0] if len(xs) == 1 else _fan_merge(xs)
                for s in succs:
                    wires[(name, s)] = out
            elif p.kind is Kind.COLLECT:
                xs = [v for v in _pop_in(name) if v is not _SKIP]
                if not xs:  # upstream COMBINE still accumulating
                    continue
                x = xs[0] if len(xs) == 1 else _fan_merge(xs)
                if p.jit_combine:
                    collect_streams[name] = x
                else:
                    host_streams[name] = x
        return collect_streams, host_streams, lanes_used

    # -- retirement (the only synchronisation point) -------------------------
    def _retire(self, entry, host_accs) -> None:
        ci, chunk_items, lanes_used, host_streams, watermark = entry
        with self.rec.span("retire", "stream", ci=ci):
            # Collect is the CSP sink: block on this chunk's folded
            # accumulators (snapshots — later chunks' folds keep streaming
            # behind them)
            for acc in watermark.values():
                jax.block_until_ready(acc)
            for name, stream in host_streams.items():
                p = self.net.procs[name]
                stream = jax.block_until_ready(stream)
                self.rec.instant("collect", "csp", collect=name, ci=ci)
                leaves = jax.tree_util.tree_leaves(stream)
                n = leaves[0].shape[0] if leaves else 0
                acc = host_accs[name]
                for i in range(n):
                    item = jax.tree_util.tree_map(lambda a: a[i], stream)
                    acc = p.fn(acc, item)
                host_accs[name] = acc
        self.stats.chunks_done += 1
        self.stats.items_done += chunk_items
        for lane in lanes_used:
            self._outstanding[lane] -= 1

    def run(self, batch):
        """Stream ``batch`` through the network; returns the Collect dict."""
        leaves = jax.tree_util.tree_leaves(batch)
        if not leaves:
            raise NetworkError("run: empty batch")
        n = leaves[0].shape[0]
        return self._run_plan(microbatch_plan(n, self.mb), batch)

    # -- hooks the cluster PartitionExecutor overrides -----------------------
    def _chunk_inputs(self, ci: int, lo: int, hi: int, batch):
        """The value(s) the Emit(s) produce for chunk ``ci``."""
        return slice_microbatch(batch, lo, hi)

    def _forward_egress(self, ci: int, host_streams: dict) -> None:
        """Ship boundary-collect values (cluster cut channels); base: none."""

    def _local_collects(self) -> list:
        """The Collects whose folds this executor owns (cluster partitions
        exclude boundary shims)."""
        return list(self.net.collects())

    def _run_plan(self, plan, batch, *, start_ci: int = 0):
        """Fresh run over ``plan[start_ci:]`` (``start_ci`` > 0 is a cluster
        replay of a stream tail: chunk numbering stays aligned with the full
        batch so transported chunk ids match the surviving peers')."""
        self._check_fan_divisibility(plan)
        n = plan[-1][1] if plan else 0
        self.replay_state = None
        self.stats = StreamStats(n_items=n, microbatch_size=self.mb,
                                 n_chunks=len(plan), depth=self.depth,
                                 lanes=self.lanes,
                                 donation_enabled=self._can_donate,
                                 fused=list(self._chains))
        self._outstanding = [0] * self.lanes
        self._combine_carry = {}
        jit_accs: dict[str, Any] = {}
        host_accs = {p.name: copy.deepcopy(p.init)
                     for p in self._local_collects() if not p.jit_combine}
        return self._drive(plan, batch, start_ci, jit_accs, host_accs)

    def reset_run_state(self) -> None:
        """Forget any interrupted run (a controller is starting a fresh
        batch or a replay-from-scratch): resume state and COMBINE carries
        all go.  Subclasses clear whatever per-run buffers they add."""
        self.replay_state = None
        self._combine_carry = {}

    def resume_plan(self, batch=None):
        """Resume the interrupted run captured in :attr:`replay_state`:
        chunks already folded stay folded, only the lost tail streams."""
        st = self.replay_state
        if st is None:
            raise NetworkError("resume_plan: no interrupted run to resume")
        self.replay_state = None
        self._combine_carry = st.combine_carry
        self.stats = st.stats
        self.stats.replays += 1
        if self.stats.resumed_at is None:
            self.stats.resumed_at = st.next_ci
        self._outstanding = [0] * self.lanes
        return self._drive(st.plan, batch, st.next_ci, st.jit_accs,
                           st.host_accs)

    # -- durability: fold-state snapshot / restore ---------------------------
    def snapshot_state(self, plan, next_ci: int, jit_accs: dict,
                       host_accs: dict) -> dict:
        """A host-portable (picklable) image of the fold state covering
        chunks ``[0, next_ci)`` — the on-disk twin of :class:`_ReplayState`.
        Valid only at a retire-consistent boundary (no chunks in flight)."""
        from ..cluster.durable import to_host
        batch_id, epoch = self.snapshot_tag
        return {"batch_id": batch_id, "epoch": epoch,
                "next_ci": next_ci, "bounds": list(plan),
                "jit_accs": to_host(jit_accs),
                "host_accs": to_host(host_accs),
                "combine_carry": to_host(self._combine_carry),
                "stats": copy.deepcopy(self.stats)}

    def _save_snapshot(self, plan, next_ci, jit_accs, host_accs) -> None:
        with self.rec.span("snapshot", "durable", ci=next_ci,
                           seq=self._snap_seq + 1):
            state = self.snapshot_state(plan, next_ci, jit_accs, host_accs)
            if self.on_snapshot is not None:
                self.on_snapshot(next_ci)  # fault-injection point: die here
            self._snap_seq += 1
            from ..cluster.durable import _to_blob
            self.snapshotter.save(self._snap_seq, _to_blob(state))

    def resume_from_state(self, state: dict, batch=None):
        """Stream the tail of an interrupted run from an on-disk snapshot:
        fold accumulators restored as of ``state["next_ci"]``, remaining
        chunks re-driven with full-batch chunk numbering intact."""
        with self.rec.span("snapshot_restore", "durable",
                           ci=state["next_ci"]):
            self.replay_state = None
            self._combine_carry = dict(state["combine_carry"])
            self.stats = state["stats"]
            self.stats.replays += 1
            if self.stats.resumed_at is None:
                self.stats.resumed_at = state["next_ci"]
            self._outstanding = [0] * self.lanes
            jit_accs = dict(state["jit_accs"])
            host_accs = dict(state["host_accs"])
        return self._drive(state["bounds"], batch, state["next_ci"],
                           jit_accs, host_accs)

    def _drive(self, plan, batch, start_ci, jit_accs, host_accs):
        rec = self.rec
        in_flight: deque = deque()
        for ci in range(start_ci, len(plan)):
            lo, hi = plan[ci]
            if (self.snapshot_every and self.snapshotter is not None
                    and ci > start_ci and ci % self.snapshot_every == 0):
                # drain in-flight first so host_accs covers chunks < ci —
                # the same consistency point _ReplayState capture relies on
                while in_flight:
                    self._retire(in_flight.popleft(), host_accs)
                self._save_snapshot(plan, ci, jit_accs, host_accs)
            if len(in_flight) >= self.depth:  # backpressure BEFORE dispatch:
                self.stats.stalls += 1       # ≤ `depth` chunks unretired
                with rec.span("stall", "stream", ci=ci):
                    self._retire(in_flight.popleft(), host_accs)
            try:
                chunk = self._chunk_inputs(ci, lo, hi, batch)
            except Exception as e:
                # the chunk never entered the DAG; whatever is in flight is
                # complete — retire it so the accumulators are consistent,
                # then (for resumable failures: a peer died mid-stream) save
                # the fold state so a controller can replay just the tail
                while in_flight:
                    self._retire(in_flight.popleft(), host_accs)
                if isinstance(e, self._resumable_errors):
                    self.replay_state = _ReplayState(
                        ci, list(plan), jit_accs, host_accs,
                        dict(self._combine_carry), self.stats)
                raise
            with rec.span("dispatch", "stream", ci=ci):
                streams, host_streams, lanes_used = self._dispatch_chunk(
                    ci, chunk, final=ci == len(plan) - 1)
                self._forward_egress(ci, host_streams)
                for name, x in streams.items():
                    rec.instant("collect", "csp", collect=name, ci=ci)
                    if name not in jit_accs:  # first chunk: fold with init
                        jit_accs[name] = self._stage_jit(name, False)(x)
                    else:  # later chunks: carry fold — linear item order
                        jit_accs[name] = self._carry_jit(name)(
                            jit_accs[name], x)
            watermark = {name: jit_accs[name] for name in streams}
            # COMBINE accumulators throttle too (collect may see nothing yet)
            for cname, acc in self._combine_carry.items():
                watermark[f"combine:{cname}"] = acc
            in_flight.append((ci, hi - lo, lanes_used, host_streams,
                              watermark))
            rec.counter("in_flight", len(in_flight), "stream")
        while in_flight:
            self._retire(in_flight.popleft(), host_accs)

        out: dict[str, Any] = {}
        for p in self._local_collects():
            if p.jit_combine:
                val = jax.block_until_ready(jit_accs[p.name])
            else:
                val = host_accs[p.name]
            out[p.name] = p.finalise(val) if p.finalise else val
        return out


# ==========================================================================
# CSP abstract models of the two schedules (paper §6.1.1 turned on ourselves)
# ==========================================================================

def _functional_tags(net: Network, fused: bool = False) -> list:
    """The symbolic stage chain every item traverses, in topological order.

    With ``fused=True`` consecutive stages that the executor fuses
    (:func:`fused_chains`) collapse into one *tuple* tag — the CSP worker
    applies each component in order (``repro.core.csp`` nests tuple tags),
    so a fused stage is, observably, exactly the composition of its members.
    """
    def _tag(n):
        return net.procs[n].tag or n

    if not fused:
        return [_tag(n) for n in net.toposort()
                if net.procs[n].kind in (Kind.WORKER, Kind.ENGINE)]
    head_of = {c[0]: c for c in fused_chains(net)}
    members = {n for c in head_of.values() for n in c[1:]}
    tags: list = []
    for n in net.toposort():
        if net.procs[n].kind not in (Kind.WORKER, Kind.ENGINE) or n in members:
            continue
        chain = head_of.get(n)
        tags.append(tuple(_tag(m) for m in chain) if chain else _tag(n))
    return tags


def synchronous_abstract_model(net: Network, name: str = "sync") -> Network:
    """CSP model of the fused / sequential schedule: one chain of stages —
    every chunk passes stage k before any chunk enters stage k+1 needn't
    hold, but there is a single lane, so chunks stay strictly ordered."""
    tags = _functional_tags(net)
    m = Network(f"{net.name}/{name}")
    m.add(Emit(lambda i: i, name="emit"))
    for k, tag in enumerate(tags):
        m.add(Worker(lambda x: x, name=f"s{k}", tag=tag))
    m.add(Collect(lambda a, x: a, name="collect"))
    return m


def streaming_abstract_model(net: Network, lanes: int = 2,
                             name: str = "stream",
                             fused: bool = False) -> Network:
    """CSP model of the streaming schedule: chunks are items, OneFanAny
    assigns each to any free lane (work stealing), each lane is the full
    stage chain, AnyFanOne merges lanes into the Collect.

    ``trace_equivalent(streaming_abstract_model(net), \
synchronous_abstract_model(net))`` is the refinement obligation the executor
    must meet: same guaranteed termination, same collected outcome on every
    interleaving.

    ``fused=True`` models the executor's chain-fused schedule: each fused
    run becomes ONE lane worker carrying the tuple of its members' tags, and
    the CSP worker applies the tags in order — so the fused schedule's
    outcomes are the same nested compositions as the synchronous model's,
    and ``trace_equivalent`` still holds (the fusion is observationally
    invisible, which is exactly the license to perform it)."""
    tags = _functional_tags(net, fused=fused)
    m = Network(f"{net.name}/{name}[{lanes}]{'/fused' if fused else ''}")
    m.add(Emit(lambda i: i, name="emit"),
          OneFanAny(destinations=lanes, name="ofa"))
    m.procs["afo"] = AnyFanOne(sources=lanes, name="afo")
    for lane in range(lanes):
        prev = "ofa"
        for k, tag in enumerate(tags):
            wn = f"l{lane}s{k}"
            m.procs[wn] = Worker(lambda x: x, name=wn, tag=tag)
            m.connect(prev, wn)
            prev = wn
        m.connect(prev, "afo")
    m._tail = "afo"
    m.add(Collect(lambda a, x: a, name="collect"))
    return m
