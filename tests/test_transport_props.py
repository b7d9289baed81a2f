"""Property tests of the transport layer: pack/unpack round-trips, the
epoch protocol, and drain/requeue losslessness — across all four transports.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster.transport import (InProcess, JaxMesh, MultiProcessPipe,
                                     SharedMemoryRing, _PipeEndpoint,
                                     _RawLeaf, pack_raw, unpack_raw)

_DTYPES = ["<f4", ">f4", "<f8", ">f8", "<i2", ">i2", "<i8", ">i8",
           "<u4", ">u4", "uint8", "bool"]
_SHAPES = [(), (1,), (3,), (0,), (2, 3), (0, 4), (4, 1, 2)]


def _make_array(dtype: str, shape: tuple, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    dt = np.dtype(dtype)
    if dt == np.bool_:
        a = rng.integers(0, 2, size=n).astype(bool)
    elif dt.kind in "iu":
        a = rng.integers(0, 100, size=n).astype(dt)
    else:
        a = rng.standard_normal(n).astype(dt)
    return a.reshape(shape)


class TestPackRoundTripProperties:
    """Satellite: `MultiProcessPipe._pack`/unpack round-trips over random
    dtypes (byte order included), 0-d and empty-shape arrays."""

    @given(dtype=st.sampled_from(_DTYPES), shape=st.sampled_from(_SHAPES),
           seed=st.integers(0, 7))
    @settings(max_examples=40, deadline=None)
    def test_pack_raw_roundtrip(self, dtype, shape, seed):
        a = _make_array(dtype, shape, seed)
        packed = pack_raw({"x": a})
        assert isinstance(packed["x"], _RawLeaf)
        dec = unpack_raw(packed)["x"]
        assert dec.dtype == a.dtype
        assert dec.shape == a.shape
        assert dec.tobytes() == np.ascontiguousarray(a).tobytes()

    @given(dtype=st.sampled_from(_DTYPES), shape=st.sampled_from(_SHAPES),
           seed=st.integers(0, 7))
    @settings(max_examples=40, deadline=None)
    def test_pipe_endpoint_pack_roundtrip(self, dtype, shape, seed):
        """The exact _pack/_unpack pair a pipe endpoint applies (encode +
        raw header/buffer), property-swept."""
        ep = _PipeEndpoint({})
        a = _make_array(dtype, shape, seed)
        out = ep._unpack(ep._pack({"x": a, "nested": (a, a.T)}))
        for got, want in ((out["x"], a), (out["nested"][0], a),
                          (out["nested"][1], a.T)):
            assert got.dtype == want.dtype
            assert got.shape == want.shape
            assert got.tobytes() == np.ascontiguousarray(want).tobytes()

    @given(shape=st.sampled_from(_SHAPES), seed=st.integers(0, 7))
    @settings(max_examples=20, deadline=None)
    def test_noncontiguous_views_roundtrip(self, shape, seed):
        a = _make_array("<f8", shape, seed)
        view = a.T  # Fortran-ordered view for ndim >= 2
        dec = unpack_raw(pack_raw(view))
        assert dec.shape == view.shape
        np.testing.assert_array_equal(dec, np.ascontiguousarray(view))

    @given(nbytes=st.sampled_from([0, 8, 63, 64, 65, 256, 4096]),
           seed=st.integers(0, 3))
    @settings(max_examples=30, deadline=None)
    def test_shm_oversize_inline_fallback(self, nbytes, seed):
        """Satellite: chunks larger than slot_bytes (and empty ones) fall
        back to inline headers on SharedMemoryRing — bit-identical either
        way, and the slot ring never leaks a slot."""
        t = SharedMemoryRing(slot_bytes=64)
        try:
            t.setup([("a", "b")], {("a", "b"): 2})
            a = _make_array("<f8", (nbytes // 8,), seed)
            t.send(("a", "b"), 0, {"x": a})
            out = t.recv(("a", "b"), 0)
            assert out["x"].dtype == a.dtype and out["x"].shape == a.shape
            np.testing.assert_array_equal(out["x"], a)
            ring = t._rings[("a", "b")]
            assert ring.free_q.qsize() == 2  # every slot back on the ring
        finally:
            t.close()


def _mk_transport(kind: str):
    if kind == "inprocess":
        return InProcess()
    if kind == "pipe":
        return MultiProcessPipe()
    if kind == "shm":
        return SharedMemoryRing(slot_bytes=1 << 12)
    return JaxMesh()


def _payload(kind: str, ci: int):
    return {"v": np.full((3,), float(ci))}


def _fifo_len(t, chan) -> int:
    if isinstance(t, SharedMemoryRing):
        return t._rings[chan].data_q.qsize()
    return t._queues[chan].qsize()


def _settle(t, chan, n: int) -> None:
    """mp queues publish through a feeder thread: wait until the FIFO
    really holds ``n`` records before draining, so the model and the
    transport agree on what drain sees."""
    import time
    deadline = time.monotonic() + 5.0
    while _fifo_len(t, chan) != n and time.monotonic() < deadline:
        time.sleep(0.005)
    assert _fifo_len(t, chan) == n


_TRANSPORTS = ["inprocess", "pipe", "shm", "jaxmesh"]


class TestEpochProtocolProperty:
    """Satellite: for any interleaving of send / duplicate-send / drain /
    requeue / epoch-bump, recv never yields a stale-epoch or duplicate
    ``(epoch, ci)`` record — checked against an exact model of the FIFO,
    on all four transports."""

    @given(kind=st.sampled_from(_TRANSPORTS), seed=st.integers(0, 9))
    @settings(max_examples=40, deadline=None)
    def test_interleavings_never_deliver_stale_or_duplicate(self, kind,
                                                            seed):
        import random
        rng = random.Random(seed)
        chan = ("a", "b")
        cap = 8
        t = _mk_transport(kind)
        try:
            t.setup([chan], {chan: cap})
            pending = []      # model of the FIFO: [(epoch, ci), ...]
            send_ci = 0       # producer's next fresh chunk
            expect_ci = 0     # consumer's next expected chunk
            delivered = set()  # every (epoch, ci) recv handed out
            for _ in range(rng.randrange(8, 20)):
                op = rng.choice(("send", "send", "send", "dup", "recv",
                                 "recv", "bump", "discard"))
                if op == "send" and len(pending) < cap:
                    t.send(chan, send_ci, _payload(kind, send_ci))
                    pending.append((t.epoch, send_ci))
                    send_ci += 1
                elif op == "dup" and expect_ci > 0 and len(pending) < cap:
                    # replayed duplicate of an already-delivered chunk
                    ci = rng.randrange(expect_ci)
                    t.send(chan, ci, _payload(kind, ci))
                    pending.append((t.epoch, ci))
                elif op == "recv":
                    # deliverable iff the model, after protocol drops,
                    # holds (t.epoch, expect_ci); otherwise recv would
                    # block on the empty/stale FIFO
                    live = [(e, c) for e, c in pending
                            if e == t.epoch and c >= expect_ci]
                    if not (live and live[0][1] == expect_ci):
                        continue
                    got = t.recv(chan, expect_ci)
                    np.testing.assert_array_equal(
                        got["v"], _payload(kind, expect_ci)["v"])
                    rec = (t.epoch, expect_ci)
                    assert rec not in delivered, \
                        f"duplicate delivery {rec}"
                    delivered.add(rec)
                    # protocol consumed everything up to and incl. the hit
                    idx = pending.index(rec)
                    pending = pending[idx + 1:]
                    expect_ci += 1
                elif op in ("bump", "discard"):
                    _settle(t, chan, len(pending))
                    keep = {chan} if op == "bump" else frozenset()
                    drained = t.drain([chan], keep=keep)[chan][0]
                    want = [c for _, c in pending] if op == "bump" else []
                    assert [ci for ci, _ in drained] == want  # FIFO order
                    t.set_epoch(t.epoch + 1)
                    pending = []
                    if op == "bump" and drained:
                        n = t.requeue(chan, drained)
                        assert n == len(drained)  # capacity covers a FIFO
                        pending = [(t.epoch, ci) for ci, _ in drained]
                        # replaying from the first undelivered chunk again
                        expect_ci = min(ci for ci, _ in drained)
            # every delivery was unique per (epoch, ci) and none stale
            assert len(delivered) == len(set(delivered))
            for e, _ in delivered:
                assert e <= t.epoch
        finally:
            t.close()


class TestCoalescingProperties:
    """Tentpole: the batching fast path is protocol-invisible — same
    records in the same order, EOS and epoch bumps flush the buffer, and
    drain/requeue stay lossless over partially-coalesced state."""

    @given(kind=st.sampled_from(["pipe", "shm"]),
           dtype=st.sampled_from(_DTYPES),
           shape=st.sampled_from(_SHAPES),
           seed=st.integers(0, 5))
    @settings(max_examples=40, deadline=None)
    def test_batched_pack_roundtrip(self, kind, dtype, shape, seed):
        """Several records coalesced into ONE write/slot decode back
        bit-identical — over byte orders, 0-d and empty shapes."""
        chan = ("a", "b")
        t = _mk_transport(kind)
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 8})
            arrs = [_make_array(dtype, shape, seed + i) for i in range(5)]
            for ci, a in enumerate(arrs):
                t.send(chan, ci, {"x": a, "pair": (a, a)})
            t.flush_sends()
            for ci, a in enumerate(arrs):
                got = t.recv(chan, ci)
                for g in (got["x"], got["pair"][0], got["pair"][1]):
                    assert g.dtype == a.dtype and g.shape == a.shape
                    assert (g.tobytes()
                            == np.ascontiguousarray(a).tobytes())
            assert _fifo_len(t, chan) == 0
        finally:
            t.close()

    @given(kind=st.sampled_from(_TRANSPORTS), seed=st.integers(0, 9))
    @settings(max_examples=30, deadline=None)
    def test_eos_flushes_pending(self, kind, seed):
        """EOS lands BEHIND every buffered record: the consumer sees the
        full stream, in order, then the marker — no explicit flush."""
        import random
        rng = random.Random(seed)
        chan = ("a", "b")
        t = _mk_transport(kind)
        t.coalesce_bytes = 1 << 12  # budget >> payloads: nothing
        try:                        # auto-flushes before the EOS
            t.setup([chan], {chan: 8})
            k = rng.randrange(1, 6)
            for ci in range(k):
                t.send(chan, ci, _payload(kind, ci))
            from repro.cluster.transport import EOS
            t.send(chan, k, EOS)
            for ci in range(k):
                got = t.recv(chan, ci)
                np.testing.assert_array_equal(got["v"],
                                              _payload(kind, ci)["v"])
            got = t.recv(chan, k)
            assert isinstance(got, str) and got == EOS
        finally:
            t.close()

    @given(kind=st.sampled_from(_TRANSPORTS), seed=st.integers(0, 9))
    @settings(max_examples=30, deadline=None)
    def test_epoch_bump_flushes_under_old_epoch(self, kind, seed):
        """Records coalesced before an epoch bump are flushed stamped with
        the OLD epoch — the new-epoch consumer drops them as stale instead
        of mistaking them for current records."""
        import random
        rng = random.Random(seed)
        chan = ("a", "b")
        t = _mk_transport(kind)
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 8})
            k = rng.randrange(1, 5)
            for ci in range(k):  # abandoned epoch-1 records, still buffered
                t.send(chan, ci, {"v": np.full((3,), -1.0)})
            assert _fifo_len(t, chan) == 0  # nothing hit the FIFO yet
            t.set_epoch(2)                  # bump flushes, stamped epoch 1
            _settle(t, chan, 1)
            for ci in range(k):             # the replay, under epoch 2
                t.send(chan, ci, _payload(kind, ci))
            t.flush_sends()
            for ci in range(k):  # stale epoch-1 batch dropped silently
                got = t.recv(chan, ci)
                np.testing.assert_array_equal(got["v"],
                                              _payload(kind, ci)["v"])
        finally:
            t.close()

    @given(kind=st.sampled_from(_TRANSPORTS), seed=st.integers(0, 9))
    @settings(max_examples=30, deadline=None)
    def test_drain_sweeps_partial_coalesce_buffer(self, kind, seed):
        """Drain sees BOTH the flushed FIFO contents and the producer's
        still-buffered partial batch — requeue then replays every record
        exactly once under the new epoch (the contiguous-prefix contract
        recovery depends on)."""
        import random
        rng = random.Random(seed)
        chan = ("a", "b")
        t = _mk_transport(kind)
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 8})
            k = rng.randrange(2, 7)
            j = rng.randrange(0, k)  # flushed prefix; the rest stays local
            for ci in range(j):
                t.send(chan, ci, _payload(kind, ci))
            if j:
                t.flush_sends()
                _settle(t, chan, 1)
            for ci in range(j, k):
                t.send(chan, ci, _payload(kind, ci))
            drained = t.drain([chan], keep={chan})[chan]
            assert [ci for ci, _ in drained[0]] == list(range(k))
            assert drained[1] == 0          # losslessness: nothing dropped
            t.set_epoch(2)
            n = t.requeue(chan, drained[0])
            assert n == k
            for ci in range(k):             # exactly once, in order
                got = t.recv(chan, ci)
                np.testing.assert_array_equal(got["v"],
                                              _payload(kind, ci)["v"])
            assert _fifo_len(t, chan) == 0
        finally:
            t.close()


class TestDrainRequeueLosslessness:
    """Satellite: every undelivered chunk reappears exactly once under the
    new epoch; nothing is delivered twice, nothing is lost."""

    @given(kind=st.sampled_from(_TRANSPORTS), seed=st.integers(0, 9))
    @settings(max_examples=40, deadline=None)
    def test_undelivered_chunks_survive_exactly_once(self, kind, seed):
        import random
        rng = random.Random(seed)
        chan = ("a", "b")
        cap = rng.randrange(4, 9)
        k = rng.randrange(1, cap + 1)       # chunks sent
        j = rng.randrange(0, k + 1)         # chunks consumer folded
        t = _mk_transport(kind)
        try:
            t.setup([chan], {chan: cap})
            for ci in range(k):
                t.send(chan, ci, _payload(kind, ci))
            for ci in range(j):
                got = t.recv(chan, ci)
                np.testing.assert_array_equal(got["v"],
                                              _payload(kind, ci)["v"])
            _settle(t, chan, k - j)
            drained = t.drain([chan], keep={chan})[chan]
            assert [ci for ci, _ in drained[0]] == list(range(j, k))
            assert drained[1] == 0          # losslessness: nothing dropped
            t.set_epoch(2)
            n = t.requeue(chan, drained[0])
            assert n == k - j               # capacity covers one FIFO
            seen = []
            for ci in range(j, k):          # each reappears exactly once,
                got = t.recv(chan, ci)      # in order, under the new epoch
                np.testing.assert_array_equal(got["v"],
                                              _payload(kind, ci)["v"])
                seen.append(ci)
            assert seen == list(range(j, k))
            assert _fifo_len(t, chan) == 0  # ... and exactly once: empty
        finally:
            t.close()


class TestThreadEndpointIsolation:
    """Regression: thread transports (InProcess/JaxMesh) used to return
    ``self`` from endpoint(), so with coalescing on every host thread shared
    one ``_send_pending``/``_recv_exploded`` — a host resetting for a
    replay-from-scratch cleared a stall-resuming peer's read-ahead (records
    already off the FIFO, never replayed: silent loss), and a flush-pop
    could race a concurrent append.  Each host now gets its own
    ``_ThreadEndpoint`` over the shared FIFOs."""

    def test_endpoints_distinct_stable_share_fifos(self):
        chan = ("a", "b")
        t = InProcess()
        try:
            t.setup([chan], {chan: 4})
            ep0, ep1 = t.endpoint(0), t.endpoint(1)
            assert ep0 is not ep1 and ep0 is not t
            assert t.endpoint(0) is ep0          # stable across calls
            assert ep0._queues is t._queues      # live FIFO view
            ep0.send(chan, 0, {"v": np.arange(3.0)})
            got = ep1.recv(chan, 0)
            np.testing.assert_array_equal(got["v"], np.arange(3.0))
        finally:
            t.close()

    def test_clear_read_buffers_is_host_local(self):
        """Host 1 explodes a coalesced batch into its read-ahead and folds
        a prefix; host 2 resetting for a from-scratch replay must NOT
        destroy the remainder — those records are off the FIFO and, per the
        exactly-once invariant, are never replayed."""
        c1, c2 = ("a", "b"), ("a", "c")
        t = InProcess()
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([c1, c2], {c1: 4, c2: 4})
            ep1, ep2 = t.endpoint(1), t.endpoint(2)
            for ci in range(3):
                t.send(c1, ci, {"v": np.full((3,), float(ci))})
            t.flush_sends()
            got = ep1.recv(c1, 0)   # explodes the batch: 1, 2 read ahead
            np.testing.assert_array_equal(got["v"], np.zeros(3))
            assert ep1._recv_exploded[c1]
            ep2.clear_read_buffers()  # the peer's reset ...
            for ci in (1, 2):         # ... leaves the survivor intact
                got = ep1.recv(c1, ci)
                np.testing.assert_array_equal(got["v"],
                                              np.full((3,), float(ci)))
        finally:
            t.close()

    def test_parent_drain_sweeps_endpoint_buffers(self):
        """A host thread's unflushed coalesce buffer is part of what drain
        must surface: that producer believes the records were sent."""
        chan = ("a", "b")
        t = InProcess()
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 4})
            ep = t.endpoint(0)
            ep.send(chan, 0, {"v": np.arange(3.0)})  # buffered, unflushed
            assert _fifo_len(t, chan) == 0
            drained = t.drain([chan], keep={chan})[chan]
            assert [ci for ci, _ in drained[0]] == [0]
            assert drained[1] == 0
            assert not ep._send_pending              # buffer detached
        finally:
            t.close()

    def test_epoch_bump_flushes_endpoint_buffers_stale(self):
        """The controller's epoch bump is a flush barrier for EVERY host's
        buffers: endpoint records buffered before the bump ship stamped
        with the OLD epoch and are discarded as stale by the consumer."""
        chan = ("a", "b")
        t = InProcess()
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 4})
            ep = t.endpoint(0)
            ep.send(chan, 0, {"v": np.full((3,), -1.0)})  # doomed record
            assert _fifo_len(t, chan) == 0
            t.set_epoch(2)
            assert not ep._send_pending      # flushed by the bump ...
            assert _fifo_len(t, chan) == 1   # ... under epoch 1
            assert ep.epoch == 2             # endpoint tracks the parent
            ep.send(chan, 0, {"v": np.arange(3.0)})
            ep.flush_sends()
            got = ep.recv(chan, 0)           # stale flush dropped silently
            np.testing.assert_array_equal(got["v"], np.arange(3.0))
        finally:
            t.close()

    def test_concurrent_host_sends_lose_nothing(self):
        """Two host threads coalescing concurrently: every record arrives
        exactly once, in order (the old shared-buffer flush-pop/append race
        could land a record in an already-detached buffer)."""
        import threading
        t = InProcess()
        t.coalesce_bytes = 200  # a handful of records per batch
        chans = [("p0", "c"), ("p1", "c")]
        n = 200
        try:
            t.setup(chans, {c: 64 for c in chans})

            def producer(h, chan):
                ep = t.endpoint(h)
                for ci in range(n):
                    ep.send(chan, ci, {"v": np.full((4,), float(ci))})
                ep.flush_sends()

            threads = [threading.Thread(target=producer, args=(h, c))
                       for h, c in enumerate(chans)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            consumer = t.endpoint(2)
            for chan in chans:
                for ci in range(n):
                    got = consumer.recv(chan, ci)
                    np.testing.assert_array_equal(
                        got["v"], np.full((4,), float(ci)))
        finally:
            t.close()

    def test_jaxmesh_endpoint_send_places_and_roundtrips(self):
        """JaxMesh's consumer-submesh placement must survive the move to
        per-host endpoints: an endpoint send routes through the parent's
        placement hook."""
        import jax
        chan = ("a", "b")
        t = JaxMesh()
        t.coalesce_bytes = 1 << 12
        try:
            t.setup([chan], {chan: 4})
            t.bind([chan], {chan: 0}, 1)
            ep = t.endpoint(0)
            ep.send(chan, 0, {"v": np.arange(3.0)})
            ep.flush_sends()
            got = t.endpoint(1).recv(chan, 0)
            assert isinstance(got["v"], jax.Array)  # placement happened
            np.testing.assert_array_equal(np.asarray(got["v"]),
                                          np.arange(3.0))
        finally:
            t.close()

    def test_shm_coalesce_budget_clamps_to_slot_bytes(self):
        """A coalesce budget larger than slot_bytes would silently degrade
        every batch to per-record sends; the shm transport clamps it (with
        a warning) so the fast path stays engaged."""
        t = SharedMemoryRing(slot_bytes=1 << 12)
        try:
            with pytest.warns(RuntimeWarning, match="clamping"):
                t.coalesce_bytes = 1 << 13
            assert t.coalesce_bytes == 1 << 12
            t.coalesce_bytes = 256  # within the slot: no warning, kept
            assert t.coalesce_bytes == 256
        finally:
            t.close()
