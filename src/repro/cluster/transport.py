"""Pluggable channel transports: how a cut channel moves chunks between hosts.

A :class:`ChannelTransport` realises the cut channels of a
:class:`repro.cluster.partition.PartitionPlan` as bounded FIFO pipes.  The
bound is the channel's CSP ``capacity`` (``ChannelDef.capacity``; rendezvous
channels get ``DEFAULT_CAPACITY``), and ``send`` *blocks* when the pipe is
full — PR 1's in-executor backpressure extended across the host boundary:
a slow consumer host throttles its producer host through the transport
itself, exactly as a buffered CSP channel chain would.

Three implementations:

* :class:`InProcess` — ``queue.Queue``-backed loopback; hosts are threads in
  this interpreter.  Always available; the reference semantics.
* :class:`MultiProcessPipe` — ``multiprocessing`` queues between *real OS
  processes* (spawn start method: each host is a fresh interpreter with its
  own JAX runtime), so CI exercises genuine host boundaries on CPU.  Values
  cross as numpy pytrees (:func:`encode` / :func:`decode`).
* :class:`JaxMesh` — hosts are submeshes of one JAX mesh; a send places the
  chunk onto the consumer host's submesh (``device_put`` → ICI/DCN transfer
  on real hardware), and when the consumer's first stage is jitted the
  placement is *folded into that stage jit* as a ``with_sharding_constraint``
  (the ROADMAP's "fold per-chunk device_put sharding into the stage jits"),
  so transfer and compute compile into one program.
* :class:`SharedMemoryRing` — process hosts like ``pipe``, but each channel
  owns a ring of preallocated ``multiprocessing.shared_memory`` slots
  (``ChannelDef.capacity`` of them), so array payloads cross the host
  boundary as raw buffer writes — no pickling of chunk data, and slot
  exhaustion IS the backpressure.

All transports carry a per-chunk SKIP marker so upstream COMBINE reducers
(which emit nothing until their final chunk) stay chunk-aligned across the
cut, and an EOS marker as a defensive stream terminator.

Elasticity (the control plane, :mod:`repro.cluster.control`): every record
on the wire is stamped ``(epoch, ci, payload)``.  The deployment epoch is
bumped by the controller on every recovery, so a consumer silently discards
records left over from a pre-recovery stream (stale epoch) and replayed
duplicates (``ci`` below the chunk it needs) instead of tripping the
out-of-order check — which is exactly what lets a restarted producer replay
a stream from chunk 0 against a surviving consumer that already folded a
prefix.  :meth:`ChannelTransport.drain` empties the FIFOs between epochs,
optionally *requeueing* still-valid undelivered chunks (re-tagged to the new
epoch) so a restarted host replays only the chunks that never reached the
transport.

Coalescing fast path (``coalesce_bytes > 0``): small records buffer per
channel until a byte budget fills, then ship as ONE queue put / ring slot
(:class:`_Coalesced` on the wire; one ``("cbatch", ...)`` header for shm).
The receiver explodes a batch into a read-ahead buffer and feeds each
sub-record through the same epoch/duplicate/order protocol as a plain
record, so exactly-once replay is untouched.  Flush points keep the elastic
machinery honest: EOS flushes before it ships, an epoch bump flushes under
the OLD epoch (buffered records belong to the abandoned stream and must
arrive stale — never renumbered), the executor flushes at end of stream and
on failure (so drained FIFOs see everything a producer believes it sent),
and :meth:`ChannelTransport.drain` sweeps any still-unflushed local buffers
— the controller's own and each thread host endpoint's — after the FIFO
contents.  ``SharedMemoryRing(double_buffer=True)`` allocates 2× slots per
ring (same logical CSP capacity) so a producer can pack the next slot while
the consumer is still unpacking the previous one.

Thread transports (:class:`InProcess` / :class:`JaxMesh`) hand each host
its own :class:`_ThreadEndpoint`: the FIFOs and the epoch are live views of
the parent's, but the coalescing state — unflushed send buffers and the
exploded-batch read-ahead — is per host, so concurrent host threads never
race one another's buffers and a host resetting for a replay-from-scratch
clears only its OWN ingress read-ahead, never a stall-resuming peer's.
"""

from __future__ import annotations

import queue
import threading
import time as _time
from contextlib import nullcontext

import numpy as np

from repro.core.dataflow import NetworkError

__all__ = [
    "DEFAULT_CAPACITY",
    "SKIP",
    "EOS",
    "TransportError",
    "ChannelTransport",
    "InProcess",
    "MultiProcessPipe",
    "SharedMemoryRing",
    "JaxMesh",
    "make_transport",
    "encode",
    "decode",
    "pack_raw",
    "unpack_raw",
]

DEFAULT_CAPACITY = 2  # rendezvous channels buffer like the stream executor
SKIP = "__gpp_skip__"  # chunk produced nothing (COMBINE still accumulating)
EOS = "__gpp_eos__"    # defensive end-of-stream marker

_RECV_TIMEOUT_S = 120.0  # a hung peer surfaces as a TransportError, not a hang
_DRAIN_POLL_S = 0.02  # drain declares a FIFO empty after 2 misses of this
_BRICK_PROBE_S = 0.25  # reader-lock probe: held longer than this = corpse


class TransportError(NetworkError):
    """A cut channel failed (peer died, timeout, protocol violation)."""


def _spawn_context():
    """The ``spawn`` context process hosts (``pipe``/``shm``) start from.

    Each spawned host initialises a JAX runtime of its own, and a TPU
    belongs to one process at a time.  A host process started while this
    process holds the chip cannot open it: on a v5e host it fails at
    backend start on libtpu's lock file, and where JAX may pick its backend
    it falls back to the CPU, whose work would then be reported as the
    deployment's.  Process hosts are therefore CPU hosts, refused here
    whenever this process's backend is not the CPU."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise NetworkError(
            f"process-host transports ('pipe', 'shm') spawn one JAX runtime "
            f"per host, and this process holds the {backend} device, which "
            "no host process can open; deploy with transport='inprocess' "
            "or 'jaxmesh' (hosts are threads on this process's devices), or "
            "run with JAX_PLATFORMS=cpu for CPU hosts")
    import multiprocessing
    # spawn: never fork a live JAX runtime (XLA thread pools do not survive
    # fork); children rebuild the network from a factory
    return multiprocessing.get_context("spawn")


def encode(value):
    """Pytree of arrays -> picklable numpy pytree (identity for markers)."""
    if isinstance(value, str):
        return value
    import jax
    return jax.tree_util.tree_map(np.asarray, value)


def decode(value):
    """Inverse of :func:`encode`; numpy feeds jax ops directly."""
    return value


class _RawLeaf:
    """Header + buffer encoding of one contiguous numpy leaf.

    Not a registered pytree node, so ``tree_map`` treats it as a leaf; the
    exact ``dtype.str`` (which carries byte order — ``'<f4'`` vs ``'>f4'``)
    and the full shape (``()`` for 0-d arrays) survive the round trip, which
    plain ``tobytes()`` alone would lose.
    """

    __slots__ = ("dtype", "shape", "buf")

    def __init__(self, dtype: str, shape: tuple, buf: bytes):
        self.dtype = dtype
        self.shape = shape
        self.buf = buf

    # __slots__ classes need explicit pickle support
    def __getstate__(self):
        return (self.dtype, self.shape, self.buf)

    def __setstate__(self, state):
        self.dtype, self.shape, self.buf = state


def _rawable(a: np.ndarray) -> bool:
    """Plain (non-object, non-structured) dtypes round-trip through raw
    bytes; anything exotic falls back to pickling the array itself."""
    return not a.dtype.hasobject and a.dtype.names is None


def _as_contig(leaf) -> np.ndarray:
    """C-contiguous numpy view of ``leaf`` — preserving 0-d shape, which
    ``np.ascontiguousarray`` alone would silently promote to ``(1,)``."""
    a = np.asarray(leaf)
    if a.ndim and not a.flags["C_CONTIGUOUS"]:
        a = np.ascontiguousarray(a)
    return a


def pack_raw(value):
    """Numpy pytree -> pytree of :class:`_RawLeaf` headers (markers pass
    through).  The raw-bytes fallback of :meth:`MultiProcessPipe._pack`:
    contiguous leaves ship as (dtype, shape, buffer) instead of pickled
    array objects."""
    if isinstance(value, str):
        return value
    import jax

    def _one(leaf):
        a = _as_contig(leaf)
        if not _rawable(a):
            return a  # pickle fallback (object/structured dtypes)
        return _RawLeaf(a.dtype.str, a.shape, a.tobytes())

    return jax.tree_util.tree_map(_one, value)


def unpack_raw(value):
    """Inverse of :func:`pack_raw`: rebuild each leaf with its recorded
    dtype (byte order included) and shape — 0-d arrays come back 0-d."""
    if isinstance(value, str):
        return value
    import jax

    def _one(leaf):
        if not isinstance(leaf, _RawLeaf):
            return leaf
        # bytearray: one copy, but WRITABLE — frombuffer over the bytes
        # object would hand consumers a read-only array, unlike the pickle
        # path this replaces (and unlike the shm slot path, which copies)
        return np.frombuffer(bytearray(leaf.buf),
                             dtype=np.dtype(leaf.dtype)).reshape(leaf.shape)

    return jax.tree_util.tree_map(_one, value)


class _Coalesced:
    """Wire wrapper for records coalesced into one queue put.

    ``records`` is ``[(ci, packed_payload), ...]`` in send order; the whole
    batch carries ONE epoch stamp (records never straddle an epoch bump —
    the bump flushes first).  Not a pytree; queue transports pickle it as a
    unit.
    """

    __slots__ = ("records",)

    def __init__(self, records: list):
        self.records = records

    def __getstate__(self):
        return self.records

    def __setstate__(self, state):
        self.records = state


def _payload_nbytes(value) -> int:
    """Approximate wire size of one record for coalesce-budget accounting:
    raw buffers and array leaves by byte length, markers/exotica by a small
    constant (the budget is a batching heuristic, not an exact quota)."""
    if isinstance(value, str):
        return 64
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(value):
        if isinstance(leaf, _RawLeaf):
            total += len(leaf.buf)
        else:
            total += int(getattr(leaf, "nbytes", 64))
    return total


class ChannelTransport:
    """One bounded FIFO per cut channel; chunk-granular send/recv.

    ``chan`` keys are ``(src, dst)`` process-name pairs from the plan's cut
    list.  ``send`` blocks on a full pipe (backpressure); ``recv`` blocks on
    an empty one and raises :class:`TransportError` after a timeout.

    Every record is stamped with the deployment ``epoch`` (see the module
    docstring): ``recv`` discards stale-epoch records and replayed
    duplicates, so post-recovery streams compose with pre-recovery leftovers
    without protocol violations.
    """

    name = "abstract"
    process_hosts = False  # True: hosts are spawned OS processes
    _epoch = 1  # backing store of the epoch property (controller-bumped)
    # how long a blocked send/recv waits before declaring the peer hung —
    # a class attribute so the fault-injection simulator (and tests) can
    # shrink it without patching the module constant
    recv_timeout_s = _RECV_TIMEOUT_S
    # coalescing fast path: > 0 buffers small records per channel until this
    # many bytes are pending, then ships them as ONE queue put / ring slot.
    # 0 (the default) keeps the legacy one-record-per-put wire format.
    coalesce_bytes = 0

    @property
    def epoch(self) -> int:
        """Deployment epoch records are stamped with."""
        return self._epoch

    @epoch.setter
    def epoch(self, value: int) -> None:
        # an epoch bump is a flush barrier: records buffered before it
        # belong to the abandoned stream and must arrive STALE (never
        # renumbered) — best effort, since a full FIFO of a doomed epoch is
        # not worth blocking recovery over (the replay re-sends drops)
        if value != self._epoch and getattr(self, "_send_pending", None):
            self.flush_sends(best_effort=True)
        self._epoch = value

    # -- coalescing buffers (lazy: endpoints that skip __init__ still work) --
    # Thread transports set a real threading.Lock here: their buffers can be
    # touched by a host thread (send / flush) and the controller thread
    # (epoch-bump flush, drain sweep) at once.  Per-process endpoints own
    # their buffers outright and stay lock-free.
    _coalesce_lock = None

    def _buf_lock(self):
        lk = self._coalesce_lock
        return lk if lk is not None else nullcontext()

    def _pending_map(self) -> dict:
        """``chan -> [records, nbytes]`` unflushed coalesce buffers.  Only
        mutate under :meth:`_buf_lock`: an unguarded flush-pop can race a
        concurrent append, landing a record in an already-detached buffer
        that never flushes."""
        p = getattr(self, "_send_pending", None)
        if p is None:
            p = self._send_pending = {}
        return p

    def _exploded_map(self) -> dict:
        """``chan -> [(ci, payload), ...]`` read-ahead buffer of an exploded
        coalesced batch (records pulled off the FIFO, not yet delivered)."""
        p = getattr(self, "_recv_exploded", None)
        if p is None:
            p = self._recv_exploded = {}
        return p

    def _take_pending(self, chan):
        """Atomically detach ``chan``'s coalesce buffer (None when empty)."""
        if not getattr(self, "_send_pending", None):
            return None
        with self._buf_lock():
            return self._send_pending.pop(chan, None)

    def _sweep_pending(self, chan) -> list:
        """Pop ``chan``'s unflushed coalesce records in send order — ours
        and every registered per-host endpoint's (thread transports): those
        producers believe the records were sent."""
        out = []
        for owner in (self, *getattr(self, "_endpoints", {}).values()):
            buf = owner._take_pending(chan)
            if buf:
                out.extend(buf[0])
        return out

    def flush_sends(self, chan=None, *, best_effort: bool = False) -> None:
        """Ship whatever the coalescing fast path still buffers — one
        batched record per channel (``chan`` limits it; None = all).  No-op
        with nothing pending.  ``best_effort`` drops what a full FIFO cannot
        take quickly instead of raising (stale-epoch flushes: the replay
        machinery re-sends anything dropped).  Buffers detach under the
        lock and ship outside it — a blocking put must not hold other
        threads' sends hostage."""
        pend = getattr(self, "_send_pending", None)
        if not pend:
            return
        with self._buf_lock():
            chans = [chan] if chan is not None else list(pend)
            bufs = [(c, pend.pop(c)) for c in chans if c in pend]
        for c, buf in bufs:
            if buf and buf[0]:
                self._flush_one(c, buf, best_effort=best_effort)

    def _flush_one(self, chan, buf, *, best_effort: bool = False) -> None:
        raise NotImplementedError

    def _send_transform(self, chan, value) -> object:
        """Pre-send payload hook (JaxMesh's consumer-submesh placement);
        per-host thread endpoints delegate to their parent's."""
        return value

    def clear_read_buffers(self) -> None:
        """Drop THIS endpoint's read-ahead state from a previous stream.
        An executor calls this when it RESETS its run state (fresh batch /
        replay from scratch); a stall-resume keeps the buffers — they hold
        exactly the records already pulled off the FIFO but not yet folded.
        Endpoints are per host on every transport, so the reset is host
        local: it can never destroy a stall-resuming peer's read-ahead."""
        m = getattr(self, "_recv_exploded", None)
        if m:
            m.clear()

    def setup(self, cut_channels, capacities: dict) -> None:
        raise NotImplementedError

    def reconfigure(self, cut_channels, capacities: dict) -> None:
        """Re-point the transport at a new cut (rebalance): keep the FIFO of
        every channel still in the cut, create the missing ones, release the
        removed ones.  Default: a full re-setup."""
        self.setup(cut_channels, capacities)

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def endpoint(self, host: int):
        """The (possibly serialisable) handle a host runner uses."""
        return self

    def send(self, chan, ci: int, value) -> None:
        raise NotImplementedError

    def recv(self, chan, ci: int):
        raise NotImplementedError

    def drain(self, channels=None, *, keep=frozenset()) -> dict:
        """Empty channel FIFOs (a recovery step).  ``channels`` limits the
        sweep (None = all).  For channels in ``keep`` the undelivered *data*
        records are decoded and returned in FIFO order so the controller can
        :meth:`requeue` them under the new epoch; everything else — EOS
        markers, records of dead peers, stale streams — is discarded (shm
        slots recycled).  Returns ``{chan: (records, n_discarded)}`` with
        ``records = [(ci, value), ...]``."""
        return {}

    def requeue(self, chan, records) -> int:
        """Re-send drained records on ``chan`` at the CURRENT epoch, oldest
        first, at most one FIFO's worth (never blocks on a full pipe: the
        producer replays whatever does not fit).  Returns the number
        requeued — a contiguous prefix of ``records``."""
        n = 0
        for ci, value in records[:self._requeue_limit(chan)]:
            self.send(chan, ci, value)
            n += 1
        if n and self.coalesce_bytes > 0:
            # requeued records must be ON the FIFO when the replay floor is
            # computed — a partial coalesce buffer here would break the
            # contiguous-prefix contract
            self.flush_sends(chan)
        return n

    def _requeue_limit(self, chan) -> int:
        return 0

    def inject_eos(self, chan) -> bool:
        """Controller-side out-of-band EOS (a dead producer cannot send its
        own): non-blocking, returns False when the FIFO is full (retry on
        the next quiesce tick)."""
        return False

    def bricked_channels(self, channels=None) -> set:
        """Channels whose FIFO inherited a *dead reader lock*: a host
        SIGKILLed while blocked inside ``recv`` dies holding the queue's
        reader lock, so every later ``get`` — a restarted worker, the
        controller's drain — times out empty forever.  The controller probes
        a dead host's ingress channels during :meth:`recover` and routes
        around (or rebuilds) whatever this reports.  ``channels`` limits the
        probe (None = all).  Default: nothing bricks (thread hosts cannot be
        SIGKILLed mid-``get``)."""
        return set()

    def rebuild_channel(self, chan) -> bool:
        """Replace a bricked channel's FIFO with a fresh one at the same
        capacity, abandoning the old queue and whatever the corpse left in
        it (the epoch bump makes those records stale anyway).  Returns True
        when the transport could rebuild — the *controller* is responsible
        for restarting any live host still holding an endpoint onto the old
        FIFO (spawned processes snapshot the queue map at spawn time).
        Default: cannot rebuild (fall back to ``mode="rebalance"``)."""
        return False

    def forget_channel(self, chan) -> None:
        """Discard a channel's FIFO entirely so a later ``reconfigure`` /
        ``setup`` recreates it from scratch.  The rebalance fallback uses
        this for bricked FIFOs: ``reconfigure`` keeps the FIFO of every
        channel still in the new cut, so without forgetting, a bricked
        channel whose (src, dst) pair survives the rebalance would hand the
        relocated consumer the same dead queue.  Default: nothing to do."""

    def channel_depths(self) -> dict:
        """``{(src, dst): records waiting right now}`` — the live queue-depth
        probe behind :class:`repro.core.trace.MetricsSnapshot`.  Best effort
        (mp ``qsize`` is approximate; -1 where the platform cannot say) and
        zero-cost unless polled.  Default: no visibility."""
        return {}

    def channel_capacities(self) -> dict:
        """``{(src, dst): FIFO bound}`` for the channels this transport
        carries — depth/capacity is the occupancy a scaling policy watches
        (1.0 = the cut channel is exerting backpressure)."""
        return {}

    def close(self) -> None:
        pass


class _QueueTransport(ChannelTransport):
    """Shared logic for queue-per-channel transports."""

    def __init__(self):
        self._queues: dict = {}
        self._caps: dict = {}  # chan -> capacity, kept for rebuilds

    def _capacity(self, capacities, chan) -> int:
        cap = capacities.get(chan, 0)
        return cap if cap > 0 else DEFAULT_CAPACITY

    def _new_queue(self, chan, capacities):
        raise NotImplementedError

    def _release_queue(self, q) -> None:
        pass

    def setup(self, cut_channels, capacities) -> None:
        self._caps.update(capacities)
        for chan in cut_channels:
            self._queues[chan] = self._new_queue(chan, capacities)

    def reconfigure(self, cut_channels, capacities) -> None:
        self._caps.update(capacities)
        old = self._queues
        self._queues = {}
        for chan in cut_channels:
            kept = old.pop(chan, None)
            self._queues[chan] = (kept if kept is not None
                                  else self._new_queue(chan, capacities))
        for q in old.values():  # channels no longer in the cut
            self._release_queue(q)

    def bricked_channels(self, channels=None) -> set:
        """Probe each FIFO's reader lock (mp queues only — ``queue.Queue``
        readers are threads, which cannot die holding it): a lock that stays
        held for :data:`_BRICK_PROBE_S` with its reader host dead is the
        corpse's.  Only probe channels whose legitimate reader is known dead
        (the controller passes a dead host's ingress): a *live* reader
        blocked in ``recv`` also holds the lock while waiting."""
        out = set()
        for chan in (list(self._queues) if channels is None else channels):
            q = self._queues.get(chan)
            rlock = getattr(q, "_rlock", None)
            if rlock is None:
                continue
            if rlock.acquire(True, _BRICK_PROBE_S):
                rlock.release()
            else:
                out.add(chan)
        return out

    def rebuild_channel(self, chan) -> bool:
        if chan not in self._queues:
            return False
        self.forget_channel(chan)
        self._queues[chan] = self._new_queue(chan, self._caps)
        return True

    def forget_channel(self, chan) -> None:
        old = self._queues.pop(chan, None)
        if old is None:
            return
        try:  # abandon the bricked FIFO; never join its feeder (it may
            self._release_queue(old)  # be wedged mid-flush with the corpse)
        except Exception:
            pass

    def send(self, chan, ci: int, value) -> None:
        if self.coalesce_bytes > 0:
            if isinstance(value, str) and value == EOS:
                # EOS terminates the stream: flush everything buffered before
                # it, then ship the marker ALONE so drains and out-of-band
                # consumers keep seeing it unwrapped
                self.flush_sends(chan)
                self._put_record(chan, ci, self._pack(value))
                return
            packed = self._pack(value)
            full = None
            with self._buf_lock():
                buf = self._pending_map().setdefault(chan, [[], 0])
                buf[0].append((ci, packed))
                buf[1] += _payload_nbytes(packed)
                if buf[1] >= self.coalesce_bytes:
                    full = self._send_pending.pop(chan)
            if full is not None:  # ship outside the lock (the put may block)
                self._flush_one(chan, full)
            return
        self._put_record(chan, ci, self._pack(value))

    def _put_record(self, chan, ci: int, packed, *,
                    best_effort: bool = False) -> None:
        try:
            self._queues[chan].put((self.epoch, ci, packed),
                                   timeout=0.1 if best_effort
                                   else self.recv_timeout_s)
        except queue.Full:
            if best_effort:
                return  # stale-epoch flush: replay re-sends the drop
            raise TransportError(
                f"{self.name}: channel {chan} full for "
                f"{self.recv_timeout_s}s (consumer host stalled?)") from None

    def _flush_one(self, chan, buf, *, best_effort: bool = False) -> None:
        records = buf[0]
        if len(records) == 1:  # no batching win — ship the plain record
            self._put_record(chan, records[0][0], records[0][1],
                             best_effort=best_effort)
        else:
            self._put_record(chan, records[0][0], _Coalesced(records),
                             best_effort=best_effort)

    def recv(self, chan, ci: int):
        deadline = _time.monotonic() + (self.recv_timeout_s if ci >= 0
                                        else 1.0)
        exploded = self._exploded_map()
        while True:
            buf = exploded.get(chan)
            while buf:  # read-ahead from an exploded coalesced batch
                got_ci, value = buf.pop(0)
                if not buf:
                    exploded.pop(chan, None)
                if isinstance(value, str) and value == EOS:
                    return EOS
                if ci < 0:
                    return value
                if got_ci < ci:
                    continue  # replayed duplicate of an already-folded chunk
                if got_ci > ci:
                    raise TransportError(
                        f"{self.name}: channel {chan} out of order: "
                        f"expected chunk {ci}, got {got_ci}")
                return value
            try:
                ep, got_ci, value = self._queues[chan].get(
                    timeout=max(deadline - _time.monotonic(), 0.01))
            except queue.Empty:
                raise TransportError(
                    f"{self.name}: channel {chan} empty for "
                    f"{self.recv_timeout_s}s (producer host died?)") from None
            if isinstance(value, _Coalesced):
                # ONE epoch check for the whole batch (records never
                # straddle a bump), then explode into the read-ahead buffer;
                # each sub-record still passes the dup/order filter above
                if ci >= 0 and ep < self.epoch:
                    continue  # pre-recovery leftover batch
                if ci >= 0 and ep > self.epoch:
                    raise TransportError(
                        f"{self.name}: channel {chan} carries epoch {ep} "
                        f"but this endpoint is at {self.epoch} (controller "
                        "out of sync)")
                exploded.setdefault(chan, []).extend(
                    (rci, rv if isinstance(rv, str) else self._unpack(rv))
                    for rci, rv in value.records)
                continue
            if ci < 0:  # draining: any record at any epoch
                if isinstance(value, str) and value == EOS:
                    return EOS
                return self._unpack(value)
            if ep < self.epoch:
                continue  # pre-recovery leftover: silently discarded
            if ep > self.epoch:
                raise TransportError(
                    f"{self.name}: channel {chan} carries epoch {ep} but "
                    f"this endpoint is at {self.epoch} (controller out of "
                    "sync)")
            if isinstance(value, str) and value == EOS:
                return EOS  # stream terminator outranks the order check (a
                # peer failing mid-stream sends EOS out of band)
            if got_ci < ci:
                continue  # replayed duplicate of an already-folded chunk
            if got_ci > ci:
                raise TransportError(
                    f"{self.name}: channel {chan} out of order: expected "
                    f"chunk {ci}, got {got_ci}")
            return self._unpack(value)

    def drain(self, channels=None, *, keep=frozenset()) -> dict:
        out = {}
        for chan in (self._queues if channels is None else channels):
            q = self._queues[chan]
            records, empties, failures = [], 0, 0
            while empties < 2 and failures < 10_000:
                try:
                    item = q.get(timeout=_DRAIN_POLL_S)
                    if isinstance(item[2], _Coalesced):  # flatten the batch
                        records.extend((item[0], rci, rv)
                                       for rci, rv in item[2].records)
                    else:
                        records.append(item)
                    empties = 0
                except queue.Empty:
                    empties += 1
                except Exception:  # a peer killed mid-put can corrupt a
                    failures += 1  # pickled record — count it lost, move on
            # sweep the unflushed coalesce buffers last — the controller's
            # own AND every thread host endpoint's: those producers believe
            # the records were sent
            records.extend((self.epoch, rci, rv)
                           for rci, rv in self._sweep_pending(chan))
            kept, dropped = [], 0
            for ep, ci, value in records:
                if (chan in keep and ci >= 0
                        and not (isinstance(value, str) and value == EOS)):
                    kept.append((ci, value if isinstance(value, str)
                                 else self._unpack(value)))
                else:
                    dropped += 1
            out[chan] = (kept, dropped + failures)
        return out

    def _requeue_limit(self, chan) -> int:
        return self._queues[chan].maxsize or DEFAULT_CAPACITY

    def channel_depths(self) -> dict:
        out = {}
        for chan, q in self._queues.items():
            try:
                out[chan] = q.qsize()
            except (NotImplementedError, OSError):
                out[chan] = -1  # platform without sem_getvalue (macOS mp)
        return out

    def channel_capacities(self) -> dict:
        return {chan: (getattr(q, "maxsize", 0)
                       or getattr(q, "_maxsize", 0) or DEFAULT_CAPACITY)
                for chan, q in self._queues.items()}

    def inject_eos(self, chan) -> bool:
        try:
            self._queues[chan].put((self.epoch, -1, EOS), timeout=0.1)
            return True
        except queue.Full:
            return False

    def _pack(self, value):
        return value

    def _unpack(self, value):
        return value


class InProcess(_QueueTransport):
    """Loopback transport: hosts are threads, channels are ``queue.Queue``s
    bounded by the CSP capacity.  The always-available reference.

    :meth:`endpoint` hands each host its own :class:`_ThreadEndpoint` —
    shared FIFOs and epoch, host-local coalesce buffers and read-ahead —
    so concurrent host threads never touch one another's buffered records."""

    name = "inprocess"

    def __init__(self):
        super().__init__()
        # controller-side flushes and drain sweeps race host-thread sends:
        # the coalesce buffers need a real lock here (per-process endpoints
        # are single-threaded and stay lock-free)
        self._coalesce_lock = threading.Lock()
        self._endpoints: dict = {}  # host -> _ThreadEndpoint (stable)

    def _new_queue(self, chan, capacities):
        return queue.Queue(maxsize=self._capacity(capacities, chan))

    def endpoint(self, host: int):
        # one stable endpoint per host: a restarted thread host reuses it
        # (its fresh executor clears the read-ahead; stale send buffers
        # flush as stale-epoch records on the next bump)
        ep = self._endpoints.get(host)
        if ep is None:
            ep = self._endpoints[host] = _ThreadEndpoint(self, host)
        return ep

    def set_epoch(self, epoch: int) -> None:
        # the epoch bump is a flush barrier for EVERY host's buffers, not
        # just the controller's own: records buffered before the bump
        # belong to the abandoned stream and must arrive stamped with the
        # OLD epoch (never renumbered)
        if epoch != self._epoch:
            for ep in list(self._endpoints.values()):
                ep.flush_sends(best_effort=True)
        super().set_epoch(epoch)


class _ThreadEndpoint(_QueueTransport):
    """Per-host handle of a thread transport (InProcess / JaxMesh).

    The FIFOs, epoch and knobs are live views of the parent's (a rebuilt
    channel is visible immediately — thread hosts, unlike spawned
    processes, never snapshot the queue map), but the coalescing state —
    unflushed send buffers and the exploded-batch read-ahead — is THIS
    host's alone.  Sharing it (the old endpoint()-returns-``self``
    behaviour) let one host's ``clear_read_buffers`` destroy a
    stall-resuming peer's read-ahead, and let a flush-pop interleave with a
    concurrent append so a record landed in an already-detached buffer and
    never flushed."""

    def __init__(self, parent, host: int):
        self._parent = parent
        self.host = host
        self.name = parent.name
        self._send_pending: dict = {}
        self._recv_exploded: dict = {}
        self._coalesce_lock = threading.Lock()

    @property
    def _queues(self):
        return self._parent._queues

    @property
    def epoch(self) -> int:
        return self._parent.epoch

    @epoch.setter
    def epoch(self, value: int) -> None:
        # the epoch is deployment-wide state: route through the parent so
        # every host's stale buffers flush under the old stamp
        self._parent.set_epoch(value)

    @property
    def recv_timeout_s(self) -> float:
        return self._parent.recv_timeout_s

    @recv_timeout_s.setter
    def recv_timeout_s(self, value: float) -> None:
        self._parent.recv_timeout_s = value

    @property
    def coalesce_bytes(self) -> int:
        return self._parent.coalesce_bytes

    @coalesce_bytes.setter
    def coalesce_bytes(self, value: int) -> None:
        self._parent.coalesce_bytes = value

    def send(self, chan, ci: int, value) -> None:
        if not isinstance(value, str):
            value = self._parent._send_transform(chan, value)
        super().send(chan, ci, value)

    def _pack(self, value):
        return self._parent._pack(value)

    def _unpack(self, value):
        return self._parent._unpack(value)


class MultiProcessPipe(_QueueTransport):
    """Real host boundaries: one OS process per host (``spawn`` — a fresh
    interpreter and JAX runtime each), channels are bounded
    ``multiprocessing`` queues, values cross as pickled numpy pytrees."""

    name = "pipe"
    process_hosts = True

    def __init__(self, ctx=None):
        super().__init__()
        self.ctx = _spawn_context() if ctx is None else ctx

    def _new_queue(self, chan, capacities):
        return self.ctx.Queue(maxsize=self._capacity(capacities, chan))

    def _release_queue(self, q) -> None:
        q.close()

    def _requeue_limit(self, chan) -> int:
        return self._queues[chan]._maxsize or DEFAULT_CAPACITY

    def endpoint(self, host: int):
        # mp.Queues are inheritable through Process args; ship only the dict
        ep = _PipeEndpoint(self._queues)
        ep.recv_timeout_s = self.recv_timeout_s  # keep any override
        ep.coalesce_bytes = self.coalesce_bytes
        return ep

    def _pack(self, value):
        # contiguous numpy leaves cross as raw header+buffer records — the
        # queue then pickles plain bytes, never array objects
        return pack_raw(encode(value))

    def _unpack(self, value):
        return decode(unpack_raw(value))

    def close(self) -> None:
        for q in self._queues.values():
            q.close()
            q.join_thread()


class _PipeEndpoint(_QueueTransport):
    """Child-process handle of a MultiProcessPipe (picklable via Process
    args inheritance)."""

    name = "pipe"

    def __init__(self, queues):
        super().__init__()
        self._queues = queues

    def _pack(self, value):
        return pack_raw(encode(value))

    def _unpack(self, value):
        return decode(unpack_raw(value))


class _ShmLeaf:
    """Placement record of one leaf inside a shared-memory slot."""

    __slots__ = ("dtype", "shape", "offset")

    def __init__(self, dtype: str, shape: tuple, offset: int):
        self.dtype = dtype
        self.shape = shape
        self.offset = offset

    def __getstate__(self):
        return (self.dtype, self.shape, self.offset)

    def __setstate__(self, state):
        self.dtype, self.shape, self.offset = state


def _attach_shm(name: str):
    """Attach a peer-created segment.  Spawned hosts share the parent's
    resource-tracker process and its registry is a *set*, so the attach's
    re-registration is idempotent and the single unregister happens when the
    owning transport ``unlink``\\ s in :meth:`SharedMemoryRing.close` —
    never unregister here, or concurrent hosts race to double-remove the
    name and the tracker logs KeyErrors."""
    from multiprocessing import shared_memory
    return shared_memory.SharedMemory(name=name)


class _ShmRing:
    """One channel's ring: slot names + the two queues that cycle them.

    Picklable through ``Process`` args (mp queues inherit); attached
    ``SharedMemory`` objects are cached per process, never pickled.
    """

    def __init__(self, slot_names: list, slot_bytes: int, free_q, data_q,
                 capacity: int = None):
        self.slot_names = slot_names
        self.slot_bytes = slot_bytes
        self.free_q = free_q  # indices of writable slots (backpressure)
        self.data_q = data_q  # (ci, header) FIFO, bounded by capacity
        # the LOGICAL CSP bound — double-buffered rings hold 2× slots but
        # the header queue still only admits `capacity` in-flight records
        self.capacity = capacity if capacity is not None else len(slot_names)


class _ShmOps:
    """send/recv over ``self._rings`` — shared by the parent transport and
    the picklable child endpoint."""

    name = "shm"
    _rings: dict

    # the shm coalesce budget is capped by the ring's slot size: a batch
    # must fit ONE slot, or _flush_one silently degrades to per-record
    # sends and the fast path never engages.  The setter clamps (with a
    # warning) so a mis-sized budget is visible instead of silent.
    @property
    def coalesce_bytes(self) -> int:
        return getattr(self, "_coalesce_bytes", 0)

    @coalesce_bytes.setter
    def coalesce_bytes(self, value: int) -> None:
        value = int(value)
        limit = self._slot_limit()
        if value > 0 and limit and value > limit:
            import warnings
            warnings.warn(
                f"shm: coalesce_bytes={value} exceeds slot_bytes={limit}; "
                f"clamping to {limit} (a coalesced batch must fit one ring "
                "slot or every batch falls back to per-record sends)",
                RuntimeWarning, stacklevel=2)
            value = limit
        self._coalesce_bytes = value

    def _slot_limit(self) -> int:
        sb = getattr(self, "slot_bytes", 0)  # the owning transport
        if sb:
            return sb
        rings = getattr(self, "_rings", None)  # a child endpoint
        if rings:
            return min((r.slot_bytes for r in rings.values()), default=0)
        return 0

    def _attached(self) -> dict:
        cache = getattr(self, "_shm_cache", None)
        if cache is None:
            cache = self._shm_cache = {}
        return cache

    def _slot(self, ring: _ShmRing, idx: int):
        cache = self._attached()
        name = ring.slot_names[idx]
        if name not in cache:
            cache[name] = _attach_shm(name)
        return cache[name]

    def send(self, chan, ci: int, value) -> None:
        if self.coalesce_bytes > 0:
            if isinstance(value, str) and value == EOS:
                # EOS flushes what precedes it, then ships alone (unwrapped)
                self.flush_sends(chan)
                self._send_one(chan, ci, value)
                return
            full = None
            with self._buf_lock():
                buf = self._pending_map().setdefault(chan, [[], 0])
                buf[0].append((ci, value))  # RAW values; packed into a
                buf[1] += _payload_nbytes(value)  # slot at flush time
                if buf[1] >= self.coalesce_bytes:
                    full = self._send_pending.pop(chan)
            if full is not None:  # pack + ship outside the lock
                self._flush_one(chan, full)
            return
        self._send_one(chan, ci, value)

    def _send_one(self, chan, ci: int, value, *,
                  best_effort: bool = False) -> None:
        ring = self._rings[chan]
        if isinstance(value, str):  # SKIP / EOS markers need no slot
            self._put_header(ring, chan, (self.epoch, ci, ("marker", value)),
                             best_effort=best_effort)
            return
        import jax
        arrs = jax.tree_util.tree_map(_as_contig, value)
        leaves = jax.tree_util.tree_leaves(arrs)
        total = sum(a.nbytes for a in leaves)
        if total > ring.slot_bytes or any(not _rawable(a) for a in leaves):
            # graceful fallback: oversized / exotic chunks ship inline
            self._put_header(ring, chan,
                             (self.epoch, ci, ("inline", pack_raw(arrs))),
                             best_effort=best_effort)
            return
        try:
            idx = ring.free_q.get(timeout=0.1 if best_effort
                                  else self.recv_timeout_s)
        except queue.Empty:
            if best_effort:
                return  # stale-epoch flush: replay re-sends the drop
            raise TransportError(
                f"{self.name}: channel {chan} has no free slot for "
                f"{self.recv_timeout_s}s (consumer host stalled?)") from None
        buf = self._slot(ring, idx).buf
        offset = 0

        def _write(a):
            nonlocal offset
            meta = _ShmLeaf(a.dtype.str, a.shape, offset)
            if a.nbytes:  # ONE copy, straight into shared memory (tobytes()
                # would materialise a second, transient copy per leaf)
                dst = np.frombuffer(buf, dtype=a.dtype, count=a.size,
                                    offset=offset).reshape(a.shape)
                np.copyto(dst, a)
            offset += a.nbytes
            return meta

        meta_tree = jax.tree_util.tree_map(_write, arrs)
        self._put_header(ring, chan, (self.epoch, ci,
                                      ("slot", idx, meta_tree)),
                         best_effort=best_effort)

    def _flush_one(self, chan, buf, *, best_effort: bool = False) -> None:
        records = buf[0]
        if len(records) == 1:  # no batching win — ship the plain record
            self._send_one(chan, records[0][0], records[0][1],
                           best_effort=best_effort)
            return
        ring = self._rings[chan]
        import jax
        prepped, total, exotic = [], 0, False
        for ci, value in records:
            if isinstance(value, str):
                prepped.append((ci, value, None))
                continue
            arrs = jax.tree_util.tree_map(_as_contig, value)
            if any(not _rawable(a)
                   for a in jax.tree_util.tree_leaves(arrs)):
                exotic = True
                break
            prepped.append((ci, None, arrs))
            total += sum(a.nbytes
                         for a in jax.tree_util.tree_leaves(arrs))
        if exotic or total > ring.slot_bytes:
            # the batch cannot share one slot: fall back per record
            for ci, value in records:
                self._send_one(chan, ci, value, best_effort=best_effort)
            return
        try:
            idx = ring.free_q.get(timeout=0.1 if best_effort
                                  else self.recv_timeout_s)
        except queue.Empty:
            if best_effort:
                return
            raise TransportError(
                f"{self.name}: channel {chan} has no free slot for "
                f"{self.recv_timeout_s}s (consumer host stalled?)") from None
        slot_buf = self._slot(ring, idx).buf
        offset = 0

        def _write(a):
            nonlocal offset
            meta = _ShmLeaf(a.dtype.str, a.shape, offset)
            if a.nbytes:
                dst = np.frombuffer(slot_buf, dtype=a.dtype, count=a.size,
                                    offset=offset).reshape(a.shape)
                np.copyto(dst, a)
            offset += a.nbytes
            return meta

        entries = []
        for ci, marker, arrs in prepped:
            if marker is not None:
                entries.append((ci, ("marker", marker)))
            else:
                entries.append((ci, ("tree",
                                     jax.tree_util.tree_map(_write, arrs))))
        self._put_header(ring, chan,
                         (self.epoch, records[0][0],
                          ("cbatch", idx, entries)),
                         best_effort=best_effort)

    def _put_header(self, ring: _ShmRing, chan, item, *,
                    best_effort: bool = False) -> None:
        try:
            ring.data_q.put(item, timeout=0.1 if best_effort
                            else self.recv_timeout_s)
        except queue.Full:
            if best_effort:
                header = item[2]  # dropping the header must still recycle
                if header[0] in ("slot", "cbatch"):  # its slot
                    ring.free_q.put(header[1])
                return
            raise TransportError(
                f"{self.name}: channel {chan} full for "
                f"{self.recv_timeout_s}s (consumer host stalled?)") from None

    def _discard_header(self, ring: _ShmRing, header) -> None:
        """Drop a header, recycling its slot (the ring invariant is that
        free slots + in-flight slots == capacity)."""
        if header[0] in ("slot", "cbatch"):
            ring.free_q.put(header[1])

    def _consume_header(self, ring: _ShmRing, header):
        """Decode a header into its value, recycling the slot."""
        if header[0] == "marker":
            return header[1]
        if header[0] == "inline":
            return unpack_raw(header[1])
        _, idx, meta_tree = header
        buf = self._slot(ring, idx).buf
        import jax

        def _read(meta):
            if not isinstance(meta, _ShmLeaf):
                return meta
            dt = np.dtype(meta.dtype)
            n = int(np.prod(meta.shape, dtype=np.int64)) if meta.shape else 1
            a = np.frombuffer(buf, dtype=dt, count=n,
                              offset=meta.offset).reshape(meta.shape)
            return a.copy()  # the slot is recycled the moment we return it

        out = jax.tree_util.tree_map(_read, meta_tree)
        ring.free_q.put(idx)
        return out

    def _consume_batch(self, ring: _ShmRing, header) -> list:
        """Decode every record of a ``("cbatch", idx, entries)`` header out
        of its slot (copying — the slot is recycled once, at the end) and
        return ``[(ci, value), ...]`` in send order."""
        _, idx, entries = header
        slot_buf = self._slot(ring, idx).buf
        import jax

        def _read(meta):
            if not isinstance(meta, _ShmLeaf):
                return meta
            dt = np.dtype(meta.dtype)
            n = int(np.prod(meta.shape, dtype=np.int64)) if meta.shape else 1
            a = np.frombuffer(slot_buf, dtype=dt, count=n,
                              offset=meta.offset).reshape(meta.shape)
            return a.copy()

        out = []
        for ci, entry in entries:
            if entry[0] == "marker":
                out.append((ci, entry[1]))
            else:
                out.append((ci, jax.tree_util.tree_map(_read, entry[1])))
        ring.free_q.put(idx)
        return out

    def recv(self, chan, ci: int):
        ring = self._rings[chan]
        deadline = _time.monotonic() + (self.recv_timeout_s if ci >= 0
                                        else 1.0)
        exploded = self._exploded_map()
        while True:
            buf = exploded.get(chan)
            while buf:  # read-ahead from an exploded coalesced batch
                got_ci, value = buf.pop(0)
                if not buf:
                    exploded.pop(chan, None)
                if isinstance(value, str) and value == EOS:
                    return EOS
                if ci < 0:
                    return value
                if got_ci < ci:
                    continue  # replayed duplicate of an already-folded chunk
                if got_ci > ci:
                    raise TransportError(
                        f"{self.name}: channel {chan} out of order: "
                        f"expected chunk {ci}, got {got_ci}")
                return value
            try:
                ep, got_ci, header = ring.data_q.get(
                    timeout=max(deadline - _time.monotonic(), 0.01))
            except queue.Empty:
                raise TransportError(
                    f"{self.name}: channel {chan} empty for "
                    f"{self.recv_timeout_s}s (producer host died?)") from None
            if header[0] == "cbatch":
                # ONE epoch check for the whole batch, then explode into the
                # read-ahead buffer (sub-records hit the dup/order filter)
                if ci >= 0 and ep < self.epoch:
                    self._discard_header(ring, header)
                    continue
                if ci >= 0 and ep > self.epoch:
                    self._discard_header(ring, header)
                    raise TransportError(
                        f"{self.name}: channel {chan} carries epoch {ep} "
                        f"but this endpoint is at {self.epoch} (controller "
                        "out of sync)")
                exploded.setdefault(chan, []).extend(
                    self._consume_batch(ring, header))
                continue
            is_eos = header[0] == "marker" and header[1] == EOS
            if ci < 0:  # draining: any record at any epoch
                return EOS if is_eos else self._consume_header(ring, header)
            if ep < self.epoch:
                self._discard_header(ring, header)  # pre-recovery leftover
                continue
            if ep > self.epoch:
                self._discard_header(ring, header)
                raise TransportError(
                    f"{self.name}: channel {chan} carries epoch {ep} but "
                    f"this endpoint is at {self.epoch} (controller out of "
                    "sync)")
            if is_eos:
                return EOS  # stream terminator outranks the order check
            if got_ci < ci:
                self._discard_header(ring, header)  # replayed duplicate
                continue
            if got_ci > ci:
                self._discard_header(ring, header)
                raise TransportError(
                    f"{self.name}: channel {chan} out of order: expected "
                    f"chunk {ci}, got {got_ci}")
            return self._consume_header(ring, header)

    def channel_depths(self) -> dict:
        out = {}
        for chan, ring in self._rings.items():
            try:
                out[chan] = ring.data_q.qsize()
            except (NotImplementedError, OSError):
                out[chan] = -1
        return out

    def channel_capacities(self) -> dict:
        return {chan: getattr(ring, "capacity", len(ring.slot_names))
                for chan, ring in self._rings.items()}


class SharedMemoryRing(_ShmOps, ChannelTransport):
    """Zero-copy cut channels over ``multiprocessing.shared_memory``.

    Each channel preallocates ``capacity`` fixed-size slots; a send writes
    the chunk's leaves into a free slot (raw buffer copy — no pickling of
    array payloads) and queues a tiny placement header; the receiver
    reconstructs the leaves straight out of the slot and recycles it.  A
    producer that outruns its consumer blocks on the empty free-slot queue:
    the ring IS the CSP channel buffer, sized by ``ChannelDef.capacity``
    exactly like every other transport.

    Chunks larger than ``slot_bytes`` (and object/structured dtypes) fall
    back to inline header+buffer encoding through the header queue, so the
    transport never wedges on an unexpected payload — it just loses the
    zero-copy fast path for that chunk.
    """

    name = "shm"
    process_hosts = True

    def __init__(self, ctx=None, slot_bytes: int = 1 << 20,
                 double_buffer: bool = False):
        self.ctx = _spawn_context() if ctx is None else ctx
        self.slot_bytes = slot_bytes
        # 2× physical slots per ring (same logical CSP capacity): a producer
        # packs the next slot while the consumer is still unpacking the
        # previous one, instead of blocking on free_q
        self.double_buffer = double_buffer
        self._rings: dict = {}
        self._caps: dict = {}   # chan -> capacity, kept for rebuilds
        self._owned: dict = {}  # chan -> created segments; we unlink them
        self._atexit_armed = False

    def _make_ring(self, chan, capacities) -> _ShmRing:
        from multiprocessing import shared_memory
        cap = capacities.get(chan, 0) or DEFAULT_CAPACITY
        n_slots = cap * 2 if self.double_buffer else cap
        slots = [shared_memory.SharedMemory(create=True,
                                            size=self.slot_bytes)
                 for _ in range(n_slots)]
        self._owned[chan] = slots
        self._attached().update({s.name: s for s in slots})
        free_q = self.ctx.Queue()
        for i in range(n_slots):
            free_q.put(i)
        data_q = self.ctx.Queue(maxsize=cap)  # the CSP bound, not slot count
        return _ShmRing([s.name for s in slots], self.slot_bytes,
                        free_q, data_q, capacity=cap)

    def setup(self, cut_channels, capacities) -> None:
        self._caps.update(capacities)
        for chan in cut_channels:
            self._rings[chan] = self._make_ring(chan, capacities)
        # a process that dies without a clean close() must not strand the
        # segments: /dev/shm outlives us, so unlink from atexit as a net
        if not self._atexit_armed:
            import atexit
            atexit.register(self._unlink_owned)
            self._atexit_armed = True

    def reconfigure(self, cut_channels, capacities) -> None:
        self._caps.update(capacities)
        keep = set(cut_channels)
        for chan in list(self._rings):
            if chan not in keep:
                self._release_ring(chan)
        for chan in cut_channels:
            if chan not in self._rings:
                self._rings[chan] = self._make_ring(chan, capacities)

    def bricked_channels(self, channels=None) -> set:
        """A ring has TWO reader locks a corpse can hold: the header queue's
        (consumer killed mid-``recv``) and the free-slot queue's (producer
        killed waiting for a slot).  Either one wedges the channel."""
        out = set()
        for chan in (list(self._rings) if channels is None else channels):
            ring = self._rings.get(chan)
            if ring is None:
                continue
            for q in (ring.data_q, ring.free_q):
                rlock = getattr(q, "_rlock", None)
                if rlock is None:
                    continue
                if rlock.acquire(True, _BRICK_PROBE_S):
                    rlock.release()
                else:
                    out.add(chan)
                    break
        return out

    def rebuild_channel(self, chan) -> bool:
        if chan not in self._rings:
            return False
        self.forget_channel(chan)
        self._rings[chan] = self._make_ring(chan, self._caps)
        return True

    def forget_channel(self, chan) -> None:
        if chan not in self._rings:
            return
        try:  # release slots + queues of the bricked ring; best effort —
            self._release_ring(chan)  # the corpse may hold its locks
        except Exception:
            # a wedged queue close must not strand the segments: they are
            # only ever unlinked through _owned, so walk it here too
            self._rings.pop(chan, None)
            cache = self._attached()
            for shm in self._owned.pop(chan, ()):
                cache.pop(shm.name, None)
                try:
                    shm.close()
                    shm.unlink()
                except Exception:
                    pass

    def _release_ring(self, chan) -> None:
        ring = self._rings.pop(chan)
        cache = self._attached()
        for shm in self._owned.pop(chan, ()):
            cache.pop(shm.name, None)
            try:
                shm.close()
                shm.unlink()
            except Exception:
                pass
        for q in (ring.free_q, ring.data_q):
            q.close()

    def drain(self, channels=None, *, keep=frozenset()) -> dict:
        out = {}
        for chan in (self._rings if channels is None else channels):
            ring = self._rings[chan]
            records, empties, failures = [], 0, 0
            while empties < 2 and failures < 10_000:
                try:
                    records.append(ring.data_q.get(timeout=_DRAIN_POLL_S))
                    empties = 0
                except queue.Empty:
                    empties += 1
                except Exception:  # a peer killed mid-put can corrupt a
                    failures += 1  # pickled header — count it lost, move on
            kept, dropped = [], failures
            for ep, ci, header in records:
                if header[0] == "cbatch":
                    if chan in keep:
                        for rci, rv in self._consume_batch(ring, header):
                            if rci >= 0 and not (isinstance(rv, str)
                                                 and rv == EOS):
                                kept.append((rci, rv))
                            else:
                                dropped += 1
                    else:
                        self._discard_header(ring, header)
                        dropped += 1
                    continue
                is_eos = header[0] == "marker" and header[1] == EOS
                if chan in keep and ci >= 0 and not is_eos:
                    # decode out of the slot (recycling it): holding slots
                    # hostage would starve the producer's free-slot ring
                    kept.append((ci, self._consume_header(ring, header)))
                else:
                    self._discard_header(ring, header)
                    dropped += 1
            # sweep the unflushed coalesce buffers (raw values, send order)
            for rci, rv in self._sweep_pending(chan):
                if (chan in keep and rci >= 0
                        and not (isinstance(rv, str) and rv == EOS)):
                    kept.append((rci, rv))
                else:
                    dropped += 1
            out[chan] = (kept, dropped)
        return out

    def _requeue_limit(self, chan) -> int:
        ring = self._rings[chan]
        return getattr(ring, "capacity", len(ring.slot_names))

    def inject_eos(self, chan) -> bool:
        try:
            self._rings[chan].data_q.put(
                (self.epoch, -1, ("marker", EOS)), timeout=0.1)
            return True
        except queue.Full:
            return False

    def endpoint(self, host: int):
        ep = _ShmEndpoint(self._rings)
        ep.recv_timeout_s = self.recv_timeout_s  # keep any override
        ep.coalesce_bytes = self.coalesce_bytes
        return ep

    def _unlink_owned(self) -> None:
        for slots in self._owned.values():
            for shm in slots:
                try:
                    shm.close()
                    shm.unlink()
                except Exception:
                    pass
        self._owned = {}

    def close(self) -> None:
        self._unlink_owned()
        if self._atexit_armed:
            import atexit
            atexit.unregister(self._unlink_owned)
            self._atexit_armed = False
        for ring in self._rings.values():
            for q in (ring.free_q, ring.data_q):
                q.close()
                q.join_thread()


class _ShmEndpoint(_ShmOps, ChannelTransport):
    """Child-process handle of a SharedMemoryRing (picklable via Process
    args inheritance; attaches slots lazily, once per process)."""

    name = "shm"
    process_hosts = True

    def __init__(self, rings: dict):
        self._rings = rings


class JaxMesh(InProcess):
    """Cross-host channels over one JAX mesh: host *h* owns submesh *h*
    (``device_split``), and a send materialises the chunk on the consumer's
    submesh.  When the consumer's first stage is a jitted Worker/Engine, the
    placement is instead folded into that stage jit as its input sharding
    (the runtime seeds the executor's ``_in_spec``), so the cross-host
    transfer and the stage body are one dispatch, not an eager copy first."""

    name = "jaxmesh"

    def __init__(self, mesh=None, devices=None):
        super().__init__()
        import jax
        self._jax = jax
        if devices is None:
            devices = list(mesh.devices.flat) if mesh is not None \
                else jax.devices()
        self.devices = devices
        self._dst_sharding: dict = {}
        self._folded: set = set()  # chans whose consumer stage folds the put

    def device_split(self, n_hosts: int) -> list:
        """Round-robin split of the device list into per-host submeshes
        (degenerates gracefully when hosts outnumber devices)."""
        return [self.devices[h % len(self.devices)] for h in range(n_hosts)]

    def bind(self, cut_channels, dst_hosts: dict, n_hosts: int,
             folded=()) -> None:
        """Record each channel's consumer submesh; ``folded`` channels skip
        the eager put (their stage jit holds the sharding constraint)."""
        split = self.device_split(n_hosts)
        for chan in cut_channels:
            self._dst_sharding[chan] = \
                self._jax.sharding.SingleDeviceSharding(
                    split[dst_hosts[chan]])
        self._folded = set(folded)

    def _put(self, chan, value):
        sharding = self._dst_sharding.get(chan)
        if sharding is None or chan in self._folded:
            return value

        def _one(leaf):
            if hasattr(leaf, "ndim"):
                return self._jax.device_put(leaf, sharding)
            return leaf

        return self._jax.tree_util.tree_map(_one, value)

    def _send_transform(self, chan, value):
        # per-host endpoints route their sends through this hook, so the
        # consumer-submesh placement happens no matter which handle sends
        return self._put(chan, value)

    def send(self, chan, ci: int, value) -> None:
        if not isinstance(value, str):
            value = self._send_transform(chan, value)
        super().send(chan, ci, value)


def make_transport(kind: str, **kw) -> ChannelTransport:
    kinds = {"inprocess": InProcess, "pipe": MultiProcessPipe,
             "shm": SharedMemoryRing, "jaxmesh": JaxMesh}
    if kind not in kinds:
        raise NetworkError(
            f"unknown transport {kind!r}; pick one of {sorted(kinds)}")
    coalesce = kw.pop("coalesce_bytes", 0)  # accepted by every kind
    t = kinds[kind](**kw)
    if coalesce:
        t.coalesce_bytes = int(coalesce)
    return t
