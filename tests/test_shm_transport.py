"""Zero-copy transport unit tests (ISSUE 18): the mmap SPSC frame ring,
the socket-shaped endpoint that rides two of them, the Z attach
handshake (codec + live client/hub negotiation, decline and fallback
paths), the batched hub receiver, and the recording-socket pin that the
quickack/batch-depth hub knobs leave the wire bytes untouched.
"""

import mmap
import os
import socket
import threading
import time

import numpy as np
import pytest

from conftest import join_all
from distkeras_tpu import observability as obs
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.parameter_server import (
    DeltaParameterServer, PSClient)


# -- the ring ------------------------------------------------------------------

def test_shm_ring_roundtrip_wraps_and_eofs(tmp_path):
    """Bytes written come back in order across many wraps of a tiny ring,
    and a closed producer reads as EOF (0) once drained — the recv_into
    contract the socket helpers depend on."""
    path = str(tmp_path / "ring")
    prod = net.ShmFrameRing.create(path, "producer", capacity=4096)
    cons = net.ShmFrameRing.open(path, "consumer")
    assert prod.capacity == 4096 and cons.capacity == 4096
    payload = bytes(range(256)) * 3  # 768 B: 40 rounds lap the ring ~7x
    buf = bytearray(1024)
    for _ in range(40):
        prod.write(payload, timeout=1.0)
        got = b""
        while len(got) < len(payload):
            n = cons.read_into(memoryview(buf), timeout=1.0)
            assert n > 0
            got += bytes(buf[:n])
        assert got == payload
    assert cons.pending == 0
    prod.close()
    assert cons.read_into(memoryview(buf), timeout=1.0) == 0  # EOF
    cons.close()


def test_shm_ring_capacity_rounds_up_to_power_of_two(tmp_path):
    ring = net.ShmFrameRing.create(str(tmp_path / "r"), "producer",
                                   capacity=5000)
    assert ring.capacity == 8192
    ring.close()


def test_shm_ring_open_rejects_junk_and_truncated_files(tmp_path):
    junk = tmp_path / "junk"
    junk.write_bytes(b"\x00" * (net.SHM_RING_HEADER + mmap.PAGESIZE))
    with pytest.raises(net.ProtocolError, match="magic"):
        net.ShmFrameRing.open(str(junk), "consumer")
    small = tmp_path / "small"
    small.write_bytes(b"not a ring")
    with pytest.raises(net.ProtocolError, match="too small"):
        net.ShmFrameRing.open(str(small), "consumer")
    with pytest.raises(ValueError, match="role"):
        net.ShmFrameRing.create(str(tmp_path / "r2"), "observer")


def test_shm_ring_full_parks_then_unblocks_and_times_out(tmp_path):
    """sendall semantics under backpressure: a full ring blocks the
    producer until the consumer drains, and a deadline overrun raises
    socket.timeout (so reconnect paths built for sockets keep working)."""
    path = str(tmp_path / "ring")
    prod = net.ShmFrameRing.create(path, "producer", capacity=4096)
    cons = net.ShmFrameRing.open(path, "consumer")
    prod.write(b"x" * 4096, timeout=1.0)  # exactly full
    with pytest.raises(socket.timeout):
        prod.write(b"y", timeout=0.05)

    def drain():
        time.sleep(0.05)
        buf = bytearray(2048)
        cons.read_into(memoryview(buf), timeout=1.0)

    t = threading.Thread(target=drain)
    t.start()
    prod.write(b"z" * 8, timeout=2.0)  # unblocks once the drain lands
    join_all([t], "the ring consumer")
    prod.close()
    cons.close()


def test_shm_ring_mark_closed_wakes_parked_reader(tmp_path):
    """The sever path: mark_closed raises BOTH flags, so a reader parked
    on an empty ring wakes with EOF instead of spinning forever."""
    path = str(tmp_path / "ring")
    prod = net.ShmFrameRing.create(path, "producer", capacity=4096)
    cons = net.ShmFrameRing.open(path, "consumer")
    result = {}

    def read():
        buf = bytearray(64)
        result["n"] = cons.read_into(memoryview(buf), timeout=5.0)

    t = threading.Thread(target=read)
    t.start()
    time.sleep(0.05)
    prod.mark_closed()
    t.join(timeout=2.0)
    assert not t.is_alive() and result["n"] == 0
    prod.close()
    cons.close()
    # a hub's stop() severs a connection whose own thread has already
    # closed its rings: marking a released ring is a no-op, not an error
    prod.mark_closed()
    cons.mark_closed()


def test_shm_endpoint_carries_frames_byte_identically(tmp_path):
    """Two endpoints over a crossed ring pair move encode_tensors frames
    unchanged — the structural bit-identity claim at the object level."""
    a2b = net.ShmFrameRing.create(str(tmp_path / "a2b"), "producer")
    b2a_path = str(tmp_path / "b2a")
    b2a = net.ShmFrameRing.create(b2a_path, "consumer")
    sa, sb = socket.socketpair()
    end_a = net.ShmEndpoint(sa, a2b, b2a)
    end_b = net.ShmEndpoint(sb, net.ShmFrameRing.open(b2a_path, "producer"),
                            net.ShmFrameRing.open(str(tmp_path / "a2b"),
                                                  "consumer"))
    end_a.settimeout(2.0)
    end_b.settimeout(2.0)
    arrays = [np.arange(12, dtype=np.float32),
              np.ones((3, 4), np.float32)]
    frame = net.encode_tensors(net.ACTION_COMMIT, arrays)
    net.send_frame(end_a, frame)
    payload = net.recv_frame(end_b)
    assert bytes(payload) == bytes(frame)
    action, blobs = net.decode_tensors(payload)
    assert action == net.ACTION_COMMIT
    np.testing.assert_array_equal(
        np.frombuffer(blobs[0], np.float32), arrays[0])
    end_a.close()
    end_b.close()


# -- the handshake codec -------------------------------------------------------

def test_shm_handshake_codec_roundtrips():
    action, blobs = net.decode_tensors(net.encode_shm_request(1 << 16))
    assert action == net.ACTION_SHM
    assert net.decode_shm_request(blobs) == (net.SHM_VERSION, 1 << 16)

    action, blobs = net.decode_tensors(net.encode_shm_offer("/a.c2h",
                                                            "/b.h2c"))
    assert action == net.ACTION_SHM
    assert net.decode_shm_offer(blobs) == ("/a.c2h", "/b.h2c")

    _, blobs = net.decode_tensors(net.encode_shm_decline())
    assert net.decode_shm_offer(blobs) is None  # decline = zero blobs

    for attached in (True, False):
        _, blobs = net.decode_tensors(net.encode_shm_confirm(attached))
        assert net.decode_shm_confirm(blobs) is attached

    with pytest.raises(net.ProtocolError):
        net.decode_shm_request([b"\x01"])  # truncated header blob
    with pytest.raises(net.ProtocolError):
        net.decode_shm_offer([b"/only-one-path"])


# -- live negotiation against a real hub ---------------------------------------

def _weights():
    return [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32)]


def test_psclient_attaches_and_center_matches_tcp(tmp_path):
    """A shm=True client negotiates onto the rings (transport == "shm",
    ring files unlinked after the handshake), and the hub center after a
    session is identical to the same session over plain TCP."""
    t = _weights()
    results = {}
    for shm in (False, True):
        hub = DeltaParameterServer([w.copy() for w in t], port=0,
                                   idle_timeout=None,
                                   shm_dir=str(tmp_path))
        hub.start()
        try:
            with PSClient("127.0.0.1", hub.port, templates=t,
                          shm=shm) as c:
                assert c.transport == ("shm" if shm else "tcp")
                c.pull()
                c.commit([np.full_like(w, 0.25) for w in t])
                pulled = [w.copy() for w in c.pull()]
            results[shm] = ([w.copy() for w in hub.center], pulled)
        finally:
            hub.stop()
    (center_tcp, pulled_tcp), (center_shm, pulled_shm) = \
        results[False], results[True]
    for x, y in zip(center_tcp, center_shm):
        np.testing.assert_array_equal(x, y)
    for x, y in zip(pulled_tcp, pulled_shm):
        np.testing.assert_array_equal(x, y)
    # handshake cleanup: no ring files left behind
    assert [f for f in os.listdir(str(tmp_path)) if f.startswith("ring-")] \
        == []


def test_psclient_decline_falls_back_to_tcp():
    """A hub without shm_dir declines the Z request; the client degrades
    to plain TCP and the session still works."""
    t = _weights()
    hub = DeltaParameterServer([w.copy() for w in t], port=0,
                               idle_timeout=None)
    hub.start()
    try:
        with PSClient("127.0.0.1", hub.port, templates=t, shm=True) as c:
            assert c.transport == "tcp"
            c.pull()
            c.commit([np.full_like(w, 0.5) for w in t])
            c.drain()
        assert float(hub.center[0][0, 0]) == 0.5
    finally:
        hub.stop()


def test_shm_counters_flow_during_attached_session(tmp_path):
    t = _weights()
    hub = DeltaParameterServer([w.copy() for w in t], port=0,
                               idle_timeout=None, shm_dir=str(tmp_path))
    hub.start()
    obs.reset()
    obs.enable()
    try:
        with PSClient("127.0.0.1", hub.port, templates=t, shm=True) as c:
            assert c.transport == "shm"
            for _ in range(4):
                c.pull()
                c.commit([np.full_like(w, 0.1) for w in t])
        counters = obs.snapshot()["counters"]
        assert counters.get("ps.shm_frames_total", 0) > 0
    finally:
        obs.disable()
        obs.reset()
        hub.stop()


# -- the batched receiver ------------------------------------------------------

def _frames(n):
    t = [np.full((3,), float(i), np.float32) for i in range(2)]
    payload = bytes(net.encode_tensors(net.ACTION_COMMIT, t))
    return [len(payload).to_bytes(8, "big") + payload for _ in range(n)]


def test_batched_receiver_parses_a_burst_and_tracks_pending():
    """A burst of queued frames is served from buffered bytes (pending
    drains to 0 only after the last frame), each parsed view matching
    what recv_frame would have produced."""
    a, b = socket.socketpair()
    try:
        frames = _frames(6)
        a.sendall(b"".join(frames))
        rx = net.BatchedReceiver(b, frame_hint=len(frames[0]), depth=4)
        for want in frames:
            view = rx.recv_frame_into()
            assert bytes(view) == want[8:]  # payload, header stripped
        assert rx.pending() == 0
    finally:
        a.close()
        b.close()


def test_batched_receiver_observes_batch_depth_histogram():
    a, b = socket.socketpair()
    obs.reset()
    obs.enable()
    try:
        frames = _frames(5)
        rx = net.BatchedReceiver(b, frame_hint=len(frames[0]), depth=4)
        a.sendall(b"".join(frames))
        for _ in frames:
            rx.recv_frame_into()
        # the histogram records on the NEXT blocking fill; trigger it
        a.sendall(frames[0])
        rx.recv_frame_into()
        hist = obs.snapshot()["histograms"].get("ps_recv_batch_depth") or {}
        assert (hist.get("count") or 0) >= 1
        assert (hist.get("max") or 0) >= 2  # the burst actually batched
    finally:
        obs.disable()
        obs.reset()
        a.close()
        b.close()


def test_batched_io_guard_is_bool_and_types_cached():
    avail = net.batched_io_available()
    assert isinstance(avail, bool)
    if avail:  # resolvable symbol implies the ctypes scaffolding works
        ctypes_mod, iovec, mmsghdr = net._mmsg_types()
        assert ctypes_mod.sizeof(iovec) in (8, 16)


# -- wire pins -----------------------------------------------------------------

class _RecordingSock:
    def __init__(self, sock):
        self._sock = sock
        self.tx = bytearray()

    def sendall(self, data):
        self.tx += bytes(data)
        return self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


def _session_bytes(port, templates):
    with PSClient("127.0.0.1", port, templates=templates) as c:
        rec = _RecordingSock(c.sock)
        c.sock = rec
        c.pull()
        c.commit([np.full_like(t, 0.5) for t in templates])
        c.pull()
        c.drain()
    return bytes(rec.tx)


def test_quickack_and_recv_batch_leave_client_bytes_identical(tmp_path):
    """The hub-side perf knobs (TCP_QUICKACK on accept, recvmmsg batch
    depth, an attached shm_dir) are invisible on the wire: an un-upgraded
    client's byte stream is identical against a plain hub and a
    fully-tuned one, and carries no Z frame."""
    t = _weights()
    plain = DeltaParameterServer([w.copy() for w in t], port=0,
                                 idle_timeout=None)
    plain.start()
    tuned = DeltaParameterServer([w.copy() for w in t], port=0,
                                 idle_timeout=None,
                                 shm_dir=str(tmp_path), recv_batch_depth=8)
    tuned.start()
    try:
        baseline = _session_bytes(plain.port, t)
        against_tuned = _session_bytes(tuned.port, t)
    finally:
        plain.stop()
        tuned.stop()
    assert baseline == against_tuned
    i = 0
    while i < len(baseline):  # stream stays attach-free
        n = int.from_bytes(baseline[i:i + 8], "big")
        assert baseline[i + 8:i + 9] != net.ACTION_SHM
        i += 8 + n
