"""``FlatFrameCodec.send_streamed``: a frame sent without being packed.

The bytes on the wire are ``encode_tensors``' (so the hub, Python or C++,
cannot tell a streamed commit from a packed one), whatever the sizes of
the leaves and however slowly the other end reads; a mismatch with the
schema raises before a byte leaves; small pieces share a write; and a
codec that only streams never makes the frame buffer."""

import socket
import threading
import time

import numpy as np
import pytest

from distkeras_tpu.runtime import networking as net

DIRECT = net.FlatFrameCodec._STREAM_DIRECT


def _mixed():
    """A zero-size leaf, 4-byte leaves around it, a few small ones and one
    leaf of 6 MB (larger than both socket buffers together)."""
    rng = np.random.default_rng(7)
    shapes = [(1,), (0, 3), (1,), (5, 7), (1536, 1024), (1,), (33,)]
    return [rng.standard_normal(s).astype(np.float32) for s in shapes]


def _wire(action, arrays):
    generic = net.encode_tensors(action, arrays)
    return len(generic).to_bytes(8, "big") + generic


class _Writes:
    """A socket-shaped recorder: what ``sendall`` was given, call by call."""

    def __init__(self):
        self.pieces = []

    def sendall(self, data):
        self.pieces.append(bytes(data))


def _slow_reader(sock, total, chunk, pause_every, out):
    buf = bytearray()
    reads = 0
    while len(buf) < total:
        got = sock.recv(min(chunk, total - len(buf)))
        if not got:
            break
        buf += got
        reads += 1
        if reads % pause_every == 0:
            time.sleep(0.001)
    out.append(bytes(buf))


def test_streamed_bytes_equal_encode_tensors_under_partial_sends(telemetry):
    """Small kernel buffers and a reader that takes 64 kB at a time force
    ``send`` to take the 6 MB leaf in many parts; the bytes that arrive
    are the generic encoder's, and the transmit counters grow by one frame
    of ``frame_len`` bytes."""
    arrays = _mixed()
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    a, b = socket.socketpair()
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 1 << 14)
    b.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 14)
    got = []
    reader = threading.Thread(target=_slow_reader,
                              args=(b, codec.frame_len, 1 << 16, 8, got))
    reader.start()
    try:
        before = telemetry.snapshot()["counters"]
        codec.send_streamed(a, net.ACTION_COMMIT, arrays)
        reader.join(timeout=60)
        assert not reader.is_alive()
        assert got[0] == _wire(net.ACTION_COMMIT, arrays)
        after = telemetry.snapshot()["counters"]
        assert (after["net_tx_bytes_total"]
                - before.get("net_tx_bytes_total", 0)) == codec.frame_len
        assert (after["net_tx_frames_total"]
                - before.get("net_tx_frames_total", 0)) == 1
        assert codec._tx is None    # nothing was packed, no frame was made
    finally:
        a.close()
        b.close()


def test_streamed_frame_round_trips_through_recv_into():
    arrays = _mixed()
    tmpl = [np.zeros_like(a) for a in arrays]
    codec = net.FlatFrameCodec(tmpl)
    a, b = socket.socketpair()
    sender = threading.Thread(
        target=codec.send_streamed, args=(a, net.ACTION_WEIGHTS, arrays))
    sender.start()
    try:
        out = [np.empty(t.shape, t.dtype) for t in tmpl]
        assert net.FlatFrameCodec(tmpl).recv_into(b, out) \
            == net.ACTION_WEIGHTS
        sender.join(timeout=60)
        assert not sender.is_alive()
        for g, want in zip(out, arrays):
            np.testing.assert_array_equal(g, want)
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("bad,match", [
    (lambda arrays: arrays[:-1], "schema has"),
    (lambda arrays: arrays[:4] + [arrays[4].astype(np.float64)] + arrays[5:],
     "does not match"),
    (lambda arrays: arrays[:4] + [arrays[4][:-1]] + arrays[5:],
     "does not match"),
], ids=["count", "dtype", "size"])
def test_schema_mismatch_raises_before_a_byte_leaves(bad, match):
    """The offending leaf is the FIFTH: the header and four good leaves
    precede it on the wire, and none of them may have left."""
    arrays = _mixed()
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    writes = _Writes()
    with pytest.raises(ValueError, match=match):
        codec.send_streamed(writes, net.ACTION_COMMIT, bad(arrays))
    assert writes.pieces == []


def test_small_pieces_share_a_write_and_large_bodies_leave_directly():
    """Header, prefixes and leaves under the threshold are gathered; a body
    at or over it is one write of its own, straight from the array.  No
    write but the last is shorter than a small leaf with its prefix."""
    small = DIRECT // 8
    shapes = [(4,), (small // 4,), (DIRECT // 4,), (4,), (4,),
              (DIRECT // 2,), (small // 4,)]
    arrays = [np.full(s, i + 0.5, np.float32) for i, s in enumerate(shapes)]
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    writes = _Writes()
    codec.send_streamed(writes, net.ACTION_COMMIT, arrays)
    assert b"".join(writes.pieces) == _wire(net.ACTION_COMMIT, arrays)
    lengths = [len(p) for p in writes.pieces]
    assert lengths == [
        13 + (8 + 16) + (8 + small) + 8,    # up to the first large body
        DIRECT,                             # it
        (8 + 16) + (8 + 16) + 8,            # two tiny leaves, next prefix
        2 * DIRECT,
        8 + small,                          # the tail
    ]


def test_many_small_leaves_flush_when_the_scratch_is_full():
    """More small leaves than one scratch holds (four fit): every write is
    a full-ish scratch, never one leaf a packet."""
    leaf = DIRECT // 4 - 64
    arrays = [np.full((leaf // 4,), i, np.float32) for i in range(11)]
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    writes = _Writes()
    codec.send_streamed(writes, net.ACTION_COMMIT, arrays)
    assert b"".join(writes.pieces) == _wire(net.ACTION_COMMIT, arrays)
    # a prefix joins the write before its body when the body does not fit
    assert [len(p) for p in writes.pieces] == [
        13 + 4 * (8 + leaf) + 8, 4 * (8 + leaf), 3 * (8 + leaf) - 8]


def test_strided_and_read_only_leaves_go_out_in_c_order():
    """A host copy can come back in another layout than C order (seen from
    the TPU), and a landed device array's host value is read-only."""
    base = np.arange(DIRECT, dtype=np.float32).reshape(512, -1)
    fortran = np.asfortranarray(base)
    frozen = base[:3].copy()
    frozen.setflags(write=False)
    arrays = [fortran, frozen, np.array(2.5, np.float32)]
    assert not fortran.flags.c_contiguous
    codec = net.FlatFrameCodec([np.zeros(np.shape(a), np.float32)
                                for a in arrays])
    writes = _Writes()
    codec.send_streamed(writes, net.ACTION_COMMIT, arrays)
    assert b"".join(writes.pieces) == _wire(net.ACTION_COMMIT,
                                            [base] + arrays[1:])


def test_device_leaves_whose_copies_were_issued_stream_their_host_values():
    """``jax.Array`` leaves, ``copy_to_host_async()`` called on each: the
    codec reads dtype and size off the arrays, turns each into its host
    value when its bytes are due, and sends the same bytes as for numpy."""
    import jax

    arrays = _mixed()
    device = [jax.device_put(a) for a in arrays]
    for leaf in device:
        leaf.copy_to_host_async()
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    writes = _Writes()
    codec.send_streamed(writes, net.ACTION_COMMIT, device)
    assert b"".join(writes.pieces) == _wire(net.ACTION_COMMIT, arrays)


def test_pack_makes_the_frame_buffer_on_first_use_only():
    """The buffer appears with the first ``pack`` and is then reused; a
    packed frame after a streamed one is unchanged by it."""
    arrays = _mixed()
    codec = net.FlatFrameCodec([np.zeros_like(a) for a in arrays])
    assert codec._tx is None
    codec.send_streamed(_Writes(), net.ACTION_WEIGHTS, arrays)
    assert codec._tx is None
    codec.pack(net.ACTION_COMMIT, arrays)
    first = codec._tx
    assert bytes(first) == _wire(net.ACTION_COMMIT, arrays)
    codec.pack(net.ACTION_WEIGHTS, arrays)
    assert codec._tx is first
    writes = _Writes()
    codec.send_packed(writes)
    assert writes.pieces == [_wire(net.ACTION_WEIGHTS, arrays)]
