"""Framed socket transport — reference parity for ``distkeras/networking.py``.

The reference framed **pickled** objects with a length prefix over TCP
(``send_data``/``recv_data``; SURVEY.md §2.12).  Pickle executes arbitrary
code at load time, so this re-design keeps the framing but replaces the
payload encodings with two safe forms:

- **JSON frames** (:func:`send_json` / :func:`recv_json`) for control-plane
  messages (job submission, PS handshakes).
- **Tensor frames** (:func:`send_tensors` / :func:`recv_tensors`) for the
  gradient plane: a 1-byte action tag + raw tensor byte blobs.  Dtype and
  shape travel out-of-band (both ends hold the model template), keeping the
  hot path a straight ``memcpy`` — this exact layout is also what the C++
  hub (``native/ps_server.cpp``) parses.

Wire format (all integers big-endian):

    frame        := u64 payload_len, payload
    json payload := utf-8 JSON bytes
    tensor payload := u8 action, u32 num_tensors,
                      num_tensors * (u64 nbytes, raw bytes)

Actions: ``P`` pull request, ``C`` commit, ``Q`` int8-compressed commit,
``B`` bye, ``W`` weights reply, ``A`` ack.

Two implementations move tensor frames:

- the **generic path** (:func:`send_tensors` / :func:`recv_tensors`) builds
  and parses frames ad hoc — control plane, tests, peers without a shared
  schema;
- the **flat path** (:class:`FlatFrameCodec`, :func:`recv_frame_into`,
  :func:`decode_tensor_views`) moves the SAME bytes through preallocated
  storage for connections with a fixed tensor schema (the PS pull/commit
  hot loop): the send frame is built once with every constant byte
  prewritten and per message only the action byte and tensor payloads are
  stamped in (one ``memcpy`` per tensor, zero intermediate ``bytes``),
  while receives scatter straight into the caller's arrays with
  ``recv_into`` — the payload is written exactly once, by the kernel, at
  its final destination.  Wire bytes are identical between the two paths,
  so the C++ hub and pre-existing peers interoperate unchanged.

``Q`` commits carry each tensor as a 4-byte big-endian float32 scale
followed by the int8-quantized values (symmetric per-tensor:
``q = round(d / scale)``, ``scale = max|d| / 127``) — 4x fewer wire
bytes than ``C``.  The hub dequantizes and applies the SAME scaling
rules as a plain commit; workers keep the quantization residual and add
it to the next window's delta (error feedback), so the committed sum
tracks the true delta sum and compression does not bias training (the
property ``tests/test_runtime.py`` pins).  The reference always shipped
full-precision pickled weight lists (SURVEY §2.12); this is the
DCN-bandwidth headroom lever for the genuinely-async PS topology.
"""

from __future__ import annotations

import json
import mmap
import os
import socket
import struct
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distkeras_tpu import observability as obs

MAX_FRAME = 1 << 34  # 16 GiB sanity bound on a single frame

ACTION_PULL = b"P"
ACTION_COMMIT = b"C"
ACTION_QCOMMIT = b"Q"
ACTION_BYE = b"B"
ACTION_WEIGHTS = b"W"
ACTION_ACK = b"A"
ACTION_PING = b"H"  # client heartbeat-on-idle; hub replies with an ack
# trace-context announce: one JSON blob (job_id/worker_id/span_id); the hub
# remembers the context for this connection's spans and replies with an
# action-T frame carrying one 8-byte big-endian blob = the hub's monotonic
# clock in ns (the NTP-style sample the client's offset estimate is built
# from).  Sent only when distributed tracing is configured, so pre-T hubs
# never see it (the PR 3/4 convention: wire bytes of every pre-existing
# frame are unchanged, new frames are opt-in).
ACTION_TRACE = b"T"
# health-report push (live fleet health plane, ISSUE 8): a worker
# periodically sends one M frame carrying a single JSON blob — its
# compact per-worker metric report (windows, rolling window wall,
# reconnect/failover totals) — which the hub folds into the process
# HealthCollector and acks (the ack coalesces into later receives like a
# commit ack, so reports ride the pipelined FIFO instead of their own
# round trip).  Opt-in like ``T``: no M frame ever moves unless the
# trainer sets ``health_interval_s``, so pre-M peers interoperate
# byte-identically.
ACTION_HEALTH = b"M"
# receive-bound allowance for control-plane frames (the single-JSON-blob
# payloads of actions T and M, whose size derives from report contents,
# not from the model): the hub receives against
# max(largest tensor frame, CONTROL_PAYLOAD_MAX), so a verbose health
# report fits even on a tiny center while a garbage length prefix still
# cannot conjure more than ~64 KiB
CONTROL_PAYLOAD_MAX = 64 * 1024
# hub-to-hub replication feed (hot-standby HA): a replica hub announces
# itself to its primary with an R "hello" frame (one 9-byte header blob);
# the primary replies on the same connection with one R full-sync frame
# (header + the whole center at one clock) and thereafter streams one R
# delta frame per APPLIED commit (header + the post-aggregation scaled
# delta), sent BEFORE the committing worker's ack leaves — see
# ``encode_repl_header``.  Opt-in like ``T``: no R frame ever moves unless
# a replica connects, so pre-R peers interoperate byte-identically.
ACTION_REPL = b"R"

# R-frame header kinds (first blob, 9 bytes big-endian: u64 clock, u8 kind)
REPL_DELTA = 0  # primary->replica: blobs[1:] = scaled applied delta
REPL_SYNC = 1   # primary->replica: blobs[1:] = full center at `clock`
REPL_HELLO = 2  # replica->primary: no tensor blobs; `clock` = replica's clock
# sparse row-delta frame (hyperscale embedding tier, ISSUE 15): blobs[1:]
# carry the applied commit in the U-commit layout — per center leaf in
# template order, one full f32 delta blob for dense leaves and TWO blobs
# (int64 row ids, f32 [k, dim] scaled row deltas) for sparse leaves — so
# replication cost is proportional to the touched rows, not the model.
# The standby applies ``center[ids] += delta`` behind the same clock
# fence as a dense delta.  A primary sends these ONLY to replicas whose
# hello announced REPL_CAP_SPARSE (attach-time capability): a legacy
# standby keeps receiving the dense-materialized REPL_DELTA stream, so
# an old-generation standby attached to a new primary is never handed a
# frame kind it cannot parse
REPL_SPARSE = 3

# hello capability bits (optional 10th byte of the hello header blob —
# a 9-byte hello reads as capabilities 0, and a pre-ISSUE-15 primary
# slices the first 9 bytes off a 10-byte hello, so both directions of
# version skew degrade to the dense stream instead of a torn one)
REPL_CAP_SPARSE = 1

# row-sparse embedding traffic (ISSUE 9): a worker whose model declares
# EmbeddingTable leaves (shape [rows, dim], registered as ``sparse_leaves``
# on both ends) exchanges only the rows a batch touches —
#
#   ``S`` sparse pull request: one int64 sorted-unique row-id blob per
#         sparse table (ascending leaf order); dense leaves need no
#         request payload, they always ride the reply whole.
#   ``V`` sparse weights reply: one blob per CENTER LEAF in template
#         order — the full leaf (f32) for dense leaves, the requested
#         ``[k, dim]`` row block (f32) for sparse leaves.
#   ``U`` sparse f32 commit: per leaf in template order — one full f32
#         delta blob for dense leaves, TWO blobs (int64 row ids, f32
#         ``[k, dim]`` row grads) for sparse leaves.
#   ``X`` sparse int8 commit: same layout with every value blob carried
#         as a ``Q`` blob (be-f32 scale + int8 values; the row block is
#         quantized as one unit).
#
# Row ids are int64 in native byte order — the same raw-tensor-bytes
# convention every other blob uses — sorted and unique, so the hub's
# ``center[ids] += rows`` apply is race-free under its lock.  Opt-in like
# ``T``/``M``/``R``: no S/V/U/X frame ever moves unless BOTH ends declare
# sparse tables, so every pre-existing frame stays byte-identical and
# un-upgraded peers interoperate unchanged.
ACTION_SPARSE_PULL = b"S"
ACTION_SPARSE_WEIGHTS = b"V"
ACTION_SPARSE_COMMIT = b"U"
ACTION_SPARSE_QCOMMIT = b"X"

# reconnect-storm backpressure (ISSUE 10): an ADAPTIVE client announces
# every reconnect with a ``G`` frame (one 8-byte big-endian blob — the
# hub-paced waits it has ALREADY taken this reconnect episode) as the
# FIRST frame on the fresh connection; the hub replies with a ``Y`` frame
# carrying a retry-after hint in milliseconds (one 8-byte big-endian
# blob).  Hint 0 means proceed; a positive hint asks the client to close,
# wait that long, and redial — the hub hands each member of a thundering
# herd a LATER slot instead of absorbing the whole herd at once, and an
# announcer that already waited its slot (blob > 0) is admitted, so every
# client waits at most once per storm.  Opt-in like ``T``/``M``: no G
# frame ever moves unless the client was constructed with
# ``adaptive=True``, so every pre-existing frame stays byte-identical and
# un-upgraded clients keep plain exponential backoff.
ACTION_RECONNECT = b"G"
ACTION_RETRY = b"Y"

# shared-memory transport attach (zero-copy same-host path, ISSUE 18): a
# client constructed with ``shm=True`` sends one ``Z`` request (one blob:
# u8 version, u64 big-endian ring-capacity hint) right after its optional
# ``T`` announce; the hub replies with a ``Z`` frame carrying TWO path
# blobs (client->hub ring file, hub->client ring file) or ZERO blobs (a
# decline — different host, shm disabled, unsupported version).  On an
# offer the client mmaps both rings and sends one ``Z`` confirm over TCP
# (one blob: ``b"\x01"`` attached / ``b"\x00"`` abort); only after the
# hub reads an attached confirm do BOTH ends switch the very next frame
# onto the rings — the TCP FIFO makes the switch point exact, so the
# stream is never torn (``analysis/protocol_model.py`` walks this
# three-step handshake exhaustively).  The rings carry the SAME framed
# bytes as the socket, so trajectories are bit-identical and every
# recording-socket pin keeps holding.  Opt-in like ``T``/``M``/``G``: no
# Z frame ever moves unless the client asked for shm, so every
# pre-existing frame stays byte-identical and un-upgraded peers
# interoperate unchanged; a legacy hub closing on the unknown action
# reads as a decline and the client redials plain TCP.
ACTION_SHM = b"Z"

SHM_VERSION = 1  # bumped only if the ring layout changes incompatibly

ROW_ID_DTYPE = np.dtype(np.int64)


class ProtocolError(ValueError):
    """A frame violated the wire contract: garbage/oversized length prefix,
    truncated payload, tensor layout that does not match the schema.  After
    one of these the stream is desynchronized — callers must drop (and may
    re-establish) the connection.  Subclasses ``ValueError`` so every
    pre-existing ``except ValueError`` stays correct; the distinct type
    lets resilience layers (PSClient reconnect, hub eviction) treat
    malformed bytes as a connection fault rather than a caller bug."""


def determine_host_address() -> str:
    """Best-effort routable address of this host (reference:
    ``networking.determine_host_address``).  Uses a connected UDP socket so
    no traffic is actually sent."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("8.8.8.8", 80))
        return s.getsockname()[0]
    except OSError:
        return "127.0.0.1"
    finally:
        s.close()


MIN_SOCKET_BUF = 64 << 10   # floor for SO_SNDBUF/SO_RCVBUF requests
MAX_SOCKET_BUF = 8 << 20    # cap — beyond one large frame, memory not speed


def configure_socket(sock: socket.socket, payload_hint: Optional[int] = None,
                     nodelay: bool = True, quickack: bool = False) -> None:
    """Hot-path tuning applied to BOTH ends of every PS/client connection.

    - ``TCP_NODELAY``: the exchange is strictly request/response, so Nagle
      buys nothing and its interaction with delayed acks can park the
      13-byte ack/pull frames for tens of milliseconds — longer than an
      entire training window.
    - ``SO_SNDBUF``/``SO_RCVBUF`` sized to ``payload_hint`` (one full
      weights/commit frame, clamped to [64 KiB, 8 MiB]): a pipelined
      sender must be able to park a whole commit in the kernel and return
      to compute instead of blocking in ``sendall`` at the default buffer
      size.  Best-effort — the kernel may clamp further.  Without a hint
      the kernel defaults stand (control-plane connections don't need
      frame-sized buffers).
    - ``TCP_QUICKACK`` (opt-in, Linux-only, best-effort): the hub sets it
      on accepted connections so its coalesced 13-byte acks leave
      immediately instead of riding the delayed-ack timer — acks are the
      one latency-critical tiny send left on the pipelined commit path.
      Purely a kernel-timing knob: wire BYTES are unchanged (pinned by a
      recording-socket test), and platforms without the option silently
      keep delayed acks."""
    if nodelay:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    if quickack:
        try:
            sock.setsockopt(socket.IPPROTO_TCP,
                            getattr(socket, "TCP_QUICKACK"), 1)
        except (AttributeError, OSError):
            pass  # non-Linux / kernel policy; delayed acks still correct
    if payload_hint is None:
        return
    size = max(MIN_SOCKET_BUF, min(int(payload_hint) + 4096, MAX_SOCKET_BUF))
    for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
        try:
            sock.setsockopt(socket.SOL_SOCKET, opt, size)
        except OSError:
            pass  # kernel policy may forbid resizing; defaults still work


def connect(host: str, port: int, disable_nagle: bool = True,
            timeout: Optional[float] = None,
            payload_hint: Optional[int] = None) -> socket.socket:
    """TCP connect (reference: ``networking.connect``); Nagle off by default —
    the PS exchange is request/response and latency-bound.  ``payload_hint``
    sizes the kernel buffers to the frame this connection will move."""
    sock = socket.create_connection((host, port), timeout=timeout)
    configure_socket(sock, payload_hint=payload_hint, nodelay=disable_nagle)
    return sock


def _recv_exact_into(sock: socket.socket, view: memoryview) -> None:
    """Fill ``view`` completely from the socket (zero-copy receive)."""
    got, n = 0, view.nbytes
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError(f"peer closed mid-frame ({got}/{n} bytes)")
        got += r


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    _recv_exact_into(sock, memoryview(buf))
    return bytes(buf)


def send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(struct.pack(">Q", len(payload)) + payload)
    # count only after sendall returned: a frame dropped by a dying peer
    # must not inflate the tx accounting (mirrors the rx side's contract)
    if obs.enabled():
        obs.counter("net_tx_frames_total").inc()
        obs.counter("net_tx_bytes_total").inc(8 + len(payload))


def recv_frame(sock: socket.socket, limit: int = MAX_FRAME) -> bytes:
    """Receive one frame; ``limit`` bounds the declared payload size BEFORE
    any allocation happens, so an untrusted peer can't force a huge
    ``bytearray`` with an 8-byte header (servers pass a small limit until
    the peer has authenticated)."""
    (n,) = struct.unpack(">Q", _recv_exact(sock, 8))
    if n > limit:
        raise ProtocolError(f"frame of {n} bytes exceeds limit={limit}")
    return _recv_exact(sock, n)


def recv_frame_into(sock: socket.socket, buf: bytearray,
                    limit: int = MAX_FRAME, body_span=None) -> memoryview:
    """Receive one frame into the reusable ``buf`` (grown once to the
    largest frame seen, then steady-state zero-allocation), returning a
    memoryview of exactly the payload bytes.  The view aliases ``buf`` —
    it is valid only until the next call.  This is the long-lived-
    connection receive: the PS hub's handler loop reads every request
    through one of these per connection.

    ``body_span(action, n)`` (tensor frames only: the payload starts with
    its action byte) returns the context manager the rest of the payload
    is read under — how the hub times a commit's receive apart from the
    idle wait for the next request.  The length prefix and the action byte
    then arrive in ONE 9-byte read, so the syscalls are the same as
    without it."""
    head = _recv_exact(sock, 8 if body_span is None else 9)
    (n,) = struct.unpack(">Q", head[:8])
    if n < len(head) - 8:
        raise ProtocolError("empty frame where a tensor frame was due")
    if n > limit:
        raise ProtocolError(f"frame of {n} bytes exceeds limit={limit}")
    if len(buf) < n:
        try:
            buf.extend(bytes(n - len(buf)))
        except BufferError:
            # live views of the previous frame pin the caller's buffer
            # (bytearray cannot resize with exports outstanding); receive
            # this oversized frame into a fresh buffer instead — the
            # caller's steady-state buffer is simply not grown this time
            buf = bytearray(n)
    mv = memoryview(buf)[:n]
    if body_span is None:
        _recv_exact_into(sock, mv)
    else:
        mv[:1] = head[8:]
        with body_span(head[8:], n):
            _recv_exact_into(sock, mv[1:])
    return mv


def send_raw_frame(sock: socket.socket, frame: bytes) -> None:
    """Send an already-framed byte string (8-byte header included) — for
    prebuilt constant frames (acks, pull requests) on the hot path."""
    sock.sendall(frame)
    if obs.enabled():
        obs.counter("net_tx_frames_total").inc()
        obs.counter("net_tx_bytes_total").inc(len(frame))


# -- control plane: JSON frames -----------------------------------------------

def send_json(sock: socket.socket, obj: Dict[str, Any]) -> None:
    send_frame(sock, json.dumps(obj).encode("utf-8"))


def recv_json(sock: socket.socket, limit: int = MAX_FRAME) -> Dict[str, Any]:
    return json.loads(recv_frame(sock, limit=limit).decode("utf-8"))


# -- gradient plane: action + raw tensor frames -------------------------------

def encode_tensors(action: bytes, arrays: Sequence[np.ndarray]) -> bytes:
    parts = [action, struct.pack(">I", len(arrays))]
    for a in arrays:
        raw = np.ascontiguousarray(a).tobytes()
        parts.append(struct.pack(">Q", len(raw)))
        parts.append(raw)
    return b"".join(parts)


def decode_tensors(payload: bytes) -> Tuple[bytes, List[bytes]]:
    action = payload[0:1]
    (count,) = struct.unpack(">I", payload[1:5])
    blobs: List[bytes] = []
    off = 5
    for _ in range(count):
        (nbytes,) = struct.unpack(">Q", payload[off:off + 8])
        off += 8
        blobs.append(payload[off:off + nbytes])
        off += nbytes
    if off != len(payload):
        raise ProtocolError(f"tensor frame has {len(payload) - off} trailing bytes")
    return action, blobs


def decode_tensor_views(payload) -> Tuple[bytes, List[memoryview]]:
    """:func:`decode_tensors` without the copies: blobs come back as
    memoryview slices into ``payload`` (pass the ``recv_frame_into`` view
    directly).  The views alias the receive buffer — decode/apply them
    before the next frame lands."""
    mv = payload if isinstance(payload, memoryview) else memoryview(payload)
    action = bytes(mv[0:1])
    (count,) = struct.unpack(">I", mv[1:5])
    blobs: List[memoryview] = []
    off = 5
    for _ in range(count):
        (nbytes,) = struct.unpack(">Q", mv[off:off + 8])
        off += 8
        if off + nbytes > len(mv):
            raise ProtocolError("tensor frame truncated mid-blob")
        blobs.append(mv[off:off + nbytes])
        off += nbytes
    if off != len(mv):
        raise ProtocolError(f"tensor frame has {len(mv) - off} trailing bytes")
    return action, blobs


def _scatter_recv_into(sock: socket.socket, out: Sequence[np.ndarray],
                       scratch: memoryview, limit: int) -> bytes:
    """The one scatter-receive core (shared by ``FlatFrameCodec.recv_into``
    and the templated ``recv_tensors`` path, so their frame validation can
    never drift apart): read one tensor frame whose layout must match
    ``out`` exactly — prefixes land in the 13-byte ``scratch``, payloads
    land in ``out`` via ``recv_into`` — and return the action byte.  Any
    mismatch raises ``ValueError`` with the stream desynchronized."""
    _recv_exact_into(sock, scratch[:8])
    (n,) = struct.unpack(">Q", scratch[:8])
    if n > limit:
        raise ProtocolError(f"frame of {n} bytes exceeds limit={limit}")
    expected = 5 + sum(8 + a.nbytes for a in out)
    if n != expected:
        raise ProtocolError(f"tensor frame of {n} payload bytes does not match "
                         f"the expected layout ({expected} bytes)")
    _recv_exact_into(sock, scratch[:5])
    action = bytes(scratch[:1])
    (count,) = struct.unpack(">I", scratch[1:5])
    if count != len(out):
        raise ProtocolError(f"frame has {count} tensors, expected {len(out)}")
    for dst in out:
        _recv_exact_into(sock, scratch[:8])
        (nbytes,) = struct.unpack(">Q", scratch[:8])
        if nbytes != dst.nbytes or not dst.flags.c_contiguous:
            raise ProtocolError(f"tensor of {nbytes} bytes does not match its "
                             f"output slot ({dst.nbytes} bytes, contiguous)")
        if nbytes:
            # zero-byte blobs are legal (an all-hit hot-tier pull, an
            # untouched per-table id set) and an empty ndarray cannot be
            # cast to a flat memoryview
            _recv_exact_into(sock, memoryview(dst).cast("B"))
    return action


def empty_tensor_frame(action: bytes) -> bytes:
    """The complete 13-byte frame of a tensor-less message (pull request,
    ack, bye) — header included, built once and reused via
    :func:`send_raw_frame`."""
    return struct.pack(">Q", 5) + action + struct.pack(">I", 0)


def recv_action(sock: socket.socket) -> bytes:
    """Receive a frame known to carry zero tensors (the ack/control leg of
    the pipelined client) and return its action byte."""
    (n,) = struct.unpack(">Q", _recv_exact(sock, 8))
    if n != 5:
        raise ProtocolError(f"expected a tensor-less frame, got {n}-byte payload")
    payload = _recv_exact(sock, 5)
    (count,) = struct.unpack(">I", payload[1:5])
    if count != 0:
        raise ProtocolError(f"expected zero tensors, frame declares {count}")
    return payload[0:1]


# -- trace-context announce (action T) ----------------------------------------

def encode_context_payload(context_json: bytes) -> bytes:
    """The client->hub trace-context announce payload: an action-``T``
    tensor frame whose single blob is the UTF-8 JSON encoding of the
    announcing worker's :class:`~distkeras_tpu.observability.distributed.
    TraceContext`."""
    return encode_tensors(ACTION_TRACE, [np.frombuffer(context_json, np.uint8)])


def encode_health_payload(report_json: bytes) -> bytes:
    """The worker->hub health-report payload (action ``M``): a tensor
    frame whose single blob is the UTF-8 JSON report the
    :class:`~distkeras_tpu.observability.health.HealthCollector`
    ingests."""
    return encode_tensors(ACTION_HEALTH,
                          [np.frombuffer(report_json, np.uint8)])


def encode_time_payload(t_ns: int) -> bytes:
    """The hub->client ``T`` reply payload: one 8-byte big-endian blob
    carrying the hub's monotonic clock in nanoseconds."""
    return ACTION_TRACE + struct.pack(">I", 1) + struct.pack(">Q", 8) \
        + struct.pack(">Q", t_ns)


def decode_time_payload(blobs: Sequence) -> int:
    """Inverse of :func:`encode_time_payload` given the decoded blob list."""
    if not blobs:
        raise ProtocolError("T reply carries no timestamp blob")
    raw = bytes(memoryview(blobs[0]))[:8]
    if len(raw) != 8:
        raise ProtocolError(f"T timestamp blob has {len(raw)} bytes, want 8")
    (t_ns,) = struct.unpack(">Q", raw)
    return t_ns


def encode_admission_payload(t_ns: int, admitted: bool,
                             reason: str = "") -> bytes:
    """The hub->client ``T`` reply to a job-scoped announce (ISSUE 19):
    a tensor frame whose single blob is the UTF-8 JSON admission verdict
    ``{"t", "admitted", "reason"}``.  Only sent to a client that put a
    ``job_ns`` key on its announce — a plain trace announce keeps the
    8-byte :func:`encode_time_payload` reply, byte-identical to HEAD."""
    doc = json.dumps({"t": int(t_ns), "admitted": bool(admitted),
                      "reason": reason}).encode("utf-8")
    return encode_tensors(ACTION_TRACE, [np.frombuffer(doc, np.uint8)])


def decode_admission_payload(blobs: Sequence) -> Tuple[int, bool, str]:
    """Inverse of :func:`encode_admission_payload` given the decoded blob
    list: ``(t_ns, admitted, reason)``."""
    if not blobs:
        raise ProtocolError("T admission reply carries no blob")
    try:
        doc = json.loads(bytes(memoryview(blobs[0])).decode("utf-8"))
        return int(doc["t"]), bool(doc["admitted"]), str(doc.get("reason", ""))
    except (ValueError, KeyError, TypeError, UnicodeDecodeError) as ex:
        raise ProtocolError(f"malformed T admission reply: {ex}")


# -- reconnect backpressure (actions G / Y) -----------------------------------

def encode_reconnect_payload(waits_taken: int) -> bytes:
    """The adaptive client's reconnect announce (action ``G``): a tensor
    frame whose single blob is the number of hub-paced waits this client
    has already taken in the CURRENT reconnect episode, as an 8-byte
    big-endian integer.  The hub hands slot hints only to announcers at
    0 — a client that already waited is admitted, so a shed herd spreads
    exactly once instead of looping on ever-later slots."""
    return encode_tensors(
        ACTION_RECONNECT,
        [np.frombuffer(struct.pack(">Q", int(waits_taken)), np.uint8)])


def encode_retry_payload(retry_after_ms: int) -> bytes:
    """The hub's ``Y`` reply payload: one 8-byte big-endian blob carrying
    the retry-after hint in milliseconds (0 = proceed now)."""
    return encode_tensors(
        ACTION_RETRY,
        [np.frombuffer(struct.pack(">Q", int(retry_after_ms)), np.uint8)])


def decode_retry_payload(blobs: Sequence) -> int:
    """Inverse of :func:`encode_retry_payload` given the decoded blobs."""
    if not blobs:
        raise ProtocolError("Y reply carries no retry-after blob")
    raw = bytes(memoryview(blobs[0]))[:8]
    if len(raw) != 8:
        raise ProtocolError(f"Y retry-after blob has {len(raw)} bytes, want 8")
    (ms,) = struct.unpack(">Q", raw)
    return ms


def decode_reconnect_payload(blobs: Sequence) -> int:
    """Inverse of :func:`encode_reconnect_payload` -> waits already taken
    (tolerant: a malformed blob reads as 0 — backpressure must not take
    down a reconnecting worker, it just gets a slot like a fresh one)."""
    try:
        raw = bytes(memoryview(blobs[0]))[:8]
        (attempt,) = struct.unpack(">Q", raw)
        return attempt
    except (IndexError, struct.error, TypeError):
        return 0


# -- replication feed (action R) ----------------------------------------------

def encode_repl_header(clock: int, kind: int) -> np.ndarray:
    """The 9-byte R-frame header blob (u64 clock, u8 kind) as a uint8
    array — blob 0 of every replication frame, sized so the header rides
    the same fixed-schema :class:`FlatFrameCodec` as the tensor payload."""
    return np.frombuffer(struct.pack(">QB", int(clock), int(kind)), np.uint8)


def decode_repl_header(blob) -> Tuple[int, int]:
    """Inverse of :func:`encode_repl_header` -> ``(clock, kind)``."""
    raw = bytes(memoryview(blob))[:9]
    if len(raw) != 9:
        raise ProtocolError(f"R header blob has {len(raw)} bytes, want 9")
    clock, kind = struct.unpack(">QB", raw)
    return int(clock), int(kind)


def encode_repl_hello(clock: int, capabilities: int = 0) -> bytes:
    """The replica->primary handshake payload: an action-``R`` frame whose
    single blob is the hello header (the replica's current clock rides
    along for observability; the primary always full-syncs regardless).
    Nonzero ``capabilities`` (:data:`REPL_CAP_SPARSE`) appends a tenth
    byte announcing what frame kinds this standby can apply — absent
    (the pre-ISSUE-15 9-byte hello) reads as 0, the dense-only stream."""
    hdr = encode_repl_header(clock, REPL_HELLO)
    if capabilities:
        hdr = np.concatenate(
            [hdr, np.frombuffer(struct.pack(">B", int(capabilities)),
                                np.uint8)])
    return encode_tensors(ACTION_REPL, [hdr])


def decode_repl_caps(blob) -> int:
    """Capability bits of a hello header blob: the optional 10th byte,
    0 when absent (a 9-byte pre-ISSUE-15 hello = dense-only standby)."""
    raw = bytes(memoryview(blob))
    return raw[9] if len(raw) >= 10 else 0


def repl_frame_templates(center: Sequence[np.ndarray]) -> List[np.ndarray]:
    """The fixed tensor schema of a full R delta/sync frame over ``center``
    (header blob + one f32 tensor per center leaf) — feed both ends'
    :class:`FlatFrameCodec` with this so primary sends and replica receives
    move through preallocated storage."""
    return [np.zeros(9, np.uint8)] + [np.zeros(c.shape, np.float32)
                                      for c in center]


def encoded_tensors_size(arrays: Sequence[np.ndarray]) -> int:
    """Exact wire size of ``encode_tensors(action, arrays)`` — kept next to
    the encoder so senders can pre-flight size limits without duplicating
    the frame layout."""
    return 5 + sum(8 + np.asarray(a).nbytes for a in arrays)


def max_request_payload(templates: Sequence[np.ndarray],
                        sparse_leaves: Sequence[int] = ()) -> int:
    """Largest VALID request payload a hub serving ``templates`` may
    receive: per tensor the larger of the f32 blob (``4*size``) and the
    int8 ``Q`` blob (``4 + size`` — bigger for scalar leaves), floored at
    the control-frame allowance so a ``T`` announce / ``M`` health report
    fits even when the center is tiny; with sparse tables, a sparse f32
    commit touching every row additionally carries one int64 id blob per
    table.  The ONE accounting both hubs receive against — the Python
    hub's handler bound and the value ``runtime/native.py`` hands
    ``dk_ps_create`` — so a garbage length prefix is rejected identically
    by either implementation."""
    arrays = [np.asarray(t) for t in templates]
    dense = 5 + sum(8 + max(w.nbytes, 4 + w.size) for w in arrays)
    bound = max(dense, CONTROL_PAYLOAD_MAX)
    if sparse_leaves:
        bound = max(bound, dense + sum(8 + 8 * arrays[i].shape[0]
                                       for i in sparse_leaves))
    return bound


def tensor_frame_len(templates: Sequence[np.ndarray]) -> int:
    """Full on-the-wire size (8-byte header included) of one tensor frame
    carrying exactly ``templates``' payloads — the ``payload_hint`` every
    PS/client socket is tuned with (:func:`configure_socket`).  Kept next
    to the layout so the hub's accounting, the codec's ``frame_len`` and
    socket-buffer sizing can never drift apart.  Under the sharded hub
    each shard connection is hinted with ITS tensor subset, so N shard
    connections cost roughly one model's worth of kernel buffers in
    total, not N models' worth."""
    return 8 + encoded_tensors_size(templates)


class FlatFrameCodec:
    """Zero-copy tensor framing for a FIXED schema (the PS hot path).

    Both directions of the pull/commit exchange move frames whose layout
    is fully determined by the tensor templates; only the action byte and
    the tensor payloads vary per message.  So the codec derives all
    lengths once at construction:

    - **send, packed** (:meth:`pack` + :meth:`send_packed`, or
      :meth:`send`): one frame buffer (made on the first ``pack``) holds
      the prewritten frame length, tensor count, and per-tensor length
      prefixes; per message the action byte is stamped and each tensor is
      memcpy'd into its slot through a writable numpy view, then the whole
      frame leaves in a single ``sendall(memoryview)``.  For the sender
      that must fix the frame's content at one instant and send it at
      another: the hub packs a reply under its center lock and sends it
      after releasing it.
    - **send, streamed** (:meth:`send_streamed`): no frame is made.  The
      frame has no checksum and nothing that depends on its whole body,
      and every length is the schema's, so the bytes leave in wire order
      straight out of each tensor's own buffer, one tensor at a time.
      For the sender whose tensors are still ARRIVING (a ``jax.Array``
      whose copy to the host was issued: ``np.asarray`` waits for that
      one leaf and returns the landed buffer): the commit's copy-out, the
      copy into a frame and the send become one stretch that the wire
      bounds.  A codec that only streams never allocates the frame
      buffer.
    - **recv_into**: the frame is scatter-read with ``recv_into``
      directly into caller-provided preallocated arrays; prefixes land in
      a small reusable scratch and are validated against the schema.

    Wire bytes are IDENTICAL to :func:`encode_tensors` on either send
    path, so either end may be a generic peer (including the C++ hub).
    Not thread-safe: one codec per connection/direction owner.  After any
    mid-frame exception the stream is desynchronized — drop the
    connection."""

    # streamed send: a piece shorter than this (the header, a prefix, a
    # LayerNorm scale between two matrices) rides a scratch buffer with
    # its neighbours and leaves with them in one write, so that
    # TCP_NODELAY makes no packet of 8 bytes; a body at least this long
    # leaves straight from its tensor's buffer
    _STREAM_DIRECT = 1 << 18

    def __init__(self, templates: Sequence[np.ndarray]):
        self.templates = [np.asarray(t) for t in templates]
        self.payload_len = 5 + sum(8 + t.nbytes for t in self.templates)
        self.frame_len = 8 + self.payload_len
        # frame length, action byte (stamped per message), tensor count
        self._head = bytearray(13)
        struct.pack_into(">Q", self._head, 0, self.payload_len)
        struct.pack_into(">I", self._head, 9, len(self.templates))
        self._prefixes = [struct.pack(">Q", t.nbytes)
                          for t in self.templates]
        self._tx: Optional[bytearray] = None  # the packed frame, on demand
        self._tx_slots: List[np.ndarray] = []
        self._small = memoryview(bytearray(self._STREAM_DIRECT))
        self._scratch = memoryview(bytearray(13))

    def _check(self, arrays: Sequence[Any]) -> None:
        """Count, dtype and size of every tensor against the schema, read
        off the attributes alone: a device array is not waited for."""
        if len(arrays) != len(self.templates):
            raise ValueError(f"got {len(arrays)} tensors, schema has "
                             f"{len(self.templates)}")
        for tmpl, a in zip(self.templates, arrays):
            if a.dtype != tmpl.dtype or a.size != tmpl.size:
                raise ValueError(f"tensor {a.dtype}[{a.size}] does not match "
                                 f"schema {tmpl.dtype}[{tmpl.size}]")

    def pack(self, action: bytes, arrays: Sequence[np.ndarray]) -> None:
        """Stamp ``action`` and memcpy each tensor into its frame slot.
        Split from :meth:`send_packed` so a server can pack under its
        center lock and send after releasing it."""
        arrays = [np.asarray(a) for a in arrays]
        self._check(arrays)
        if self._tx is None:
            self._tx = bytearray(self.frame_len)
            self._tx[:13] = self._head
            mv = memoryview(self._tx)
            pos = 13
            for t, prefix in zip(self.templates, self._prefixes):
                mv[pos:pos + 8] = prefix
                pos += 8
                self._tx_slots.append(np.frombuffer(mv[pos:pos + t.nbytes],
                                                    dtype=t.dtype))
                pos += t.nbytes
        self._tx[8:9] = action
        for slot, a in zip(self._tx_slots, arrays):
            slot[...] = a.reshape(-1)

    def send_packed(self, sock: socket.socket) -> None:
        sock.sendall(memoryview(self._tx))
        self._count_frame()

    def _count_frame(self) -> None:
        if obs.enabled():
            obs.counter("net_tx_frames_total").inc()
            obs.counter("net_tx_bytes_total").inc(self.frame_len)

    def send(self, sock: socket.socket, action: bytes,
             arrays: Sequence[np.ndarray]) -> None:
        self.pack(action, arrays)
        self.send_packed(sock)

    def send_streamed(self, sock: socket.socket, action: bytes,
                      arrays: Sequence[Any]) -> None:
        """Send one frame of this schema WITHOUT packing it: header, then
        per tensor its length prefix and its bytes, straight from the
        tensor's buffer.  ``arrays`` are numpy arrays or device arrays
        (anything with ``dtype`` / ``size`` that ``np.asarray`` turns into
        its host value); each is turned into one only when its bytes are
        due, so a device array whose copy-out is still in flight holds up
        the bytes behind it and nothing before it.  A mismatch with the
        schema raises before the first byte leaves; any later failure
        leaves the stream mid-frame, as a failed ``sendall`` does."""
        self._check(arrays)
        self._head[8:9] = action

        def pieces():
            yield self._head
            for prefix, a in zip(self._prefixes, arrays):
                yield prefix
                # C order is the wire's; a host copy that came back
                # strided (seen from the TPU for a [256, 10] leaf) is
                # made so here
                a = np.ascontiguousarray(a)
                if a.nbytes:
                    yield memoryview(a.reshape(-1).view(np.uint8))

        small, n = self._small, 0
        for piece in pieces():
            k = len(piece)
            if n and n + k > len(small):
                sock.sendall(small[:n])
                n = 0
            if k >= len(small):
                sock.sendall(piece)
            else:
                small[n:n + k] = piece
                n += k
        if n:
            sock.sendall(small[:n])
        self._count_frame()

    def recv_into(self, sock: socket.socket,
                  out: Sequence[np.ndarray]) -> bytes:
        """Scatter-receive one frame of this schema directly into ``out``
        (preallocated, C-contiguous, template-shaped) and return the
        action byte.  Any schema mismatch raises ``ValueError`` with the
        stream desynchronized — callers drop the connection."""
        if len(out) != len(self.templates):
            raise ValueError(f"got {len(out)} output slots, schema has "
                             f"{len(self.templates)}")
        for tmpl, dst in zip(self.templates, out):
            if dst.nbytes != tmpl.nbytes:
                raise ValueError(f"output slot of {dst.nbytes} bytes does "
                                 f"not match schema ({tmpl.nbytes} bytes)")
        # out now mirrors the schema exactly, so the shared core's
        # layout-vs-out validation IS the schema validation (and
        # limit=payload_len rejects any differently-sized frame outright)
        return _scatter_recv_into(sock, out, self._scratch,
                                  limit=self.payload_len)


class VarFrameEncoder:
    """:class:`FlatFrameCodec`'s zero-intermediate-bytes packing for frames
    whose blob count/sizes vary per message — the sparse pull/commit plane
    (actions ``S``/``V``/``U``/``X``), where each frame's row blobs are
    sized by whatever the batch touched.

    One grow-once tx buffer: per message the header, action, count and
    per-blob length prefixes are stamped in and each blob is memcpy'd into
    place, then the whole frame leaves in a single ``sendall`` — no
    per-blob ``tobytes()``, no ``join``.  Wire bytes are IDENTICAL to
    :func:`encode_tensors`, so generic peers decode these frames with the
    ordinary :func:`decode_tensor_views` path.  Not thread-safe (one
    encoder per connection owner); :meth:`pack`'s returned view aliases
    the buffer and is valid until the next pack."""

    def __init__(self, initial: int = 4096):
        self._tx = bytearray(int(initial))
        self.frame_len = 0  # of the most recent pack

    def pack(self, action: bytes, arrays: Sequence[np.ndarray]) -> memoryview:
        arrays = [np.ascontiguousarray(a) for a in arrays]
        payload = 5 + sum(8 + a.nbytes for a in arrays)
        total = 8 + payload
        if len(self._tx) < total:
            self._tx = bytearray(total)
        struct.pack_into(">Q", self._tx, 0, payload)
        self._tx[8:9] = action
        struct.pack_into(">I", self._tx, 9, len(arrays))
        mv = memoryview(self._tx)
        pos = 13
        for a in arrays:
            struct.pack_into(">Q", self._tx, pos, a.nbytes)
            pos += 8
            if a.nbytes:
                mv[pos:pos + a.nbytes] = memoryview(a).cast("B")
            pos += a.nbytes
        self.frame_len = total
        return mv[:total]

    def send(self, sock: socket.socket, action: bytes,
             arrays: Sequence[np.ndarray]) -> int:
        """Pack and send one frame; returns its full on-the-wire length."""
        frame = self.pack(action, arrays)
        sock.sendall(frame)
        if obs.enabled():
            obs.counter("net_tx_frames_total").inc()
            obs.counter("net_tx_bytes_total").inc(self.frame_len)
        return self.frame_len


def check_row_ids(ids: np.ndarray, rows: int, leaf: int) -> np.ndarray:
    """Validate one table's canonical wire row-id array: in-bounds,
    strictly ascending (sorted AND unique — what makes the fancy-indexed
    ``center[ids] += grads`` apply exact).  The ONE validation contract
    both hub implementations enforce — peers present canonical ids, the
    hub REJECTS rather than repairs (repairing would hide a desynced
    caller).  Returns ``ids`` unchanged (callers pass zero-copy views)."""
    if ids.size:
        if ids[0] < 0 or ids[-1] >= rows:
            raise ValueError(f"sparse leaf {leaf}: row ids outside "
                             f"[0, {rows})")
        if ids.size > 1 and not (np.diff(ids) > 0).all():
            raise ValueError(f"sparse leaf {leaf}: row ids must be "
                             f"sorted and unique")
    return ids


def normalize_row_ids(ids, rows: int) -> np.ndarray:
    """Canonical wire form of one sparse table's touched-row set: flat
    int64, sorted, unique, bounds-checked against the table's ``rows``.
    The sorted-unique contract is what makes the hub's fancy-indexed
    ``center[ids] += grads`` apply exact (duplicate ids would drop all
    but one addend)."""
    arr = np.unique(np.asarray(ids).ravel().astype(ROW_ID_DTYPE, copy=False))
    if arr.size and (arr[0] < 0 or arr[-1] >= rows):
        raise ValueError(f"row ids outside [0, {rows}): "
                         f"[{arr[0]}, {arr[-1]}]")
    return arr


# -- int8 commit compression (action Q blobs) ---------------------------------

def quantize_q_blob(delta: np.ndarray) -> Tuple[bytes, np.ndarray]:
    """One tensor -> (wire blob, float32 quantization residual).

    Blob = big-endian f32 scale + int8 values; residual = what rounding
    dropped, for the caller's error-feedback accumulator.  An all-zero
    delta keeps scale 1.0 so dequantization never divides by zero."""
    d = np.ascontiguousarray(delta, dtype=np.float32)
    amax = float(np.max(np.abs(d))) if d.size else 0.0
    scale = amax / 127.0 if amax > 0.0 else 1.0
    q = np.clip(np.rint(d / scale), -127, 127).astype(np.int8)
    residual = d - q.astype(np.float32) * np.float32(scale)
    return struct.pack(">f", scale) + q.tobytes(), residual


def dequantize_q_blob(blob: bytes, size: int) -> np.ndarray:
    """Inverse of :func:`quantize_q_blob`: flat float32 array of ``size``."""
    if len(blob) != 4 + size:
        raise ProtocolError(f"Q blob of {len(blob)} bytes != 4 + {size}")
    (scale,) = struct.unpack(">f", blob[:4])
    return np.frombuffer(blob, dtype=np.int8, offset=4).astype(np.float32) * np.float32(scale)


def send_tensors(sock: socket.socket, action: bytes, arrays: Sequence[np.ndarray]) -> None:
    send_frame(sock, encode_tensors(action, arrays))


def recv_tensors(sock: socket.socket, templates: Optional[Sequence[np.ndarray]] = None,
                 limit: int = MAX_FRAME,
                 out: Optional[Sequence[np.ndarray]] = None) -> Tuple[bytes, List[np.ndarray]]:
    """Receive an (action, tensors) frame.

    With ``templates`` (the out-of-band schema) the frame is scatter-read
    with ``recv_into`` DIRECTLY into the result arrays — freshly allocated
    from the templates, or the caller's preallocated ``out`` — so the
    payload is written exactly once, by the kernel, at its destination (no
    intermediate frame buffer, no per-blob slice copies).  A frame that
    does not match the template layout raises ``ValueError`` with the
    stream desynchronized — drop the connection.

    Without templates, raw ``uint8`` copies are returned (the
    control-plane path: tolerant of any tensor count/size)."""
    if templates is None and out is None:
        action, blobs = decode_tensors(recv_frame(sock, limit=limit))
        return action, [np.frombuffer(b, dtype=np.uint8) for b in blobs]
    if out is None:
        out = [np.empty(np.asarray(t).shape, np.asarray(t).dtype)
               for t in templates]
    action = _scatter_recv_into(sock, out, memoryview(bytearray(13)),
                                limit=limit)
    return action, list(out)


# -- zero-copy shared-memory transport (action Z, ISSUE 18) -------------------
#
# Same-host workers can move the EXACT framed byte stream of a TCP
# connection through a pair of mmap-backed SPSC byte rings instead of the
# kernel socket stack.  Each direction gets its own ring file; each ring
# has exactly one producer and one consumer, so the only shared mutable
# state is two monotonically increasing byte counters (head: total bytes
# written, tail: total bytes read) plus two closed flags.  The counters
# are aligned 8-byte words in the header page, written with single
# aligned stores (atomic on every platform the repo targets; the C++ hub
# maps the same offsets as ``std::atomic`` with acquire/release), and
# each side only ever WRITES its own counter — the classic SPSC ticket
# protocol, no lock, no futex.  Waits are busy-then-park: a short spin
# (the common case — the peer is actively draining) escalating to short
# sleeps, so an idle ring costs no CPU.
#
# Ring file layout (native-endian — both ends share the host):
#
#     offset    0  u64  magic (SHM_RING_MAGIC — layout version 1)
#     offset    8  u64  capacity (power of two, data-region bytes)
#     offset   64  u64  head   — producer-owned, total bytes written
#     offset  128  u64  tail   — consumer-owned, total bytes read
#     offset  192  u32  producer_closed
#     offset  196  u32  consumer_closed
#     offset 4096  data region (capacity bytes, indexed mod capacity)
#
# head/tail live on their own cache lines so producer and consumer never
# false-share, and the data region starts on a page boundary.

SHM_RING_MAGIC = 0x646B2D72696E6731  # "dk-ring1"
SHM_RING_HEADER = 4096
SHM_RING_DEFAULT_CAPACITY = 1 << 20
# u64-index offsets into the header page (memoryview cast "Q")
_SHM_Q_MAGIC = 0
_SHM_Q_CAPACITY = 1
_SHM_Q_HEAD = 8      # byte 64
_SHM_Q_TAIL = 16     # byte 128
# u32-index offsets (memoryview cast "I")
_SHM_I_PRODUCER_CLOSED = 48  # byte 192
_SHM_I_CONSUMER_CLOSED = 49  # byte 196


class ShmFrameRing:
    """One direction of the zero-copy transport: an mmap-backed SPSC byte
    ring carrying the SAME framed bytes the socket would (so bit-identity
    with TCP is structural, not re-proven per message).  Exactly one
    producer and one consumer; this object takes ONE of the two roles.

    ``write``/``read_into`` mirror ``sendall``/``recv_into`` semantics —
    write moves every byte or raises, read returns whatever contiguous
    run is available (possibly fewer bytes than asked) and 0 only when
    the producer closed with the ring drained, so the socket receive
    helpers treat a dead ring peer exactly like a closed socket.  A full
    ring parks the producer (counted in ``ps.shm_ring_full_waits``); a
    deadline overrun raises ``socket.timeout`` so reconnect/heartbeat
    paths built for sockets keep working unchanged."""

    _SPIN = 200          # busy iterations before the first sleep
    _PARK_MIN = 10e-6    # first sleep
    _PARK_MAX = 1e-3     # sleep ceiling while parked

    def __init__(self, path: str, mm: mmap.mmap, role: str):
        if role not in ("producer", "consumer"):
            raise ValueError(f"role must be 'producer' or 'consumer', "
                             f"got {role!r}")
        self.path = path
        self.role = role
        self._mm = mm
        self._q = memoryview(mm).cast("Q")
        self._i = memoryview(mm).cast("I")
        if self._q[_SHM_Q_MAGIC] != SHM_RING_MAGIC:
            self._release()
            raise ProtocolError(f"{path}: bad shm ring magic")
        self.capacity = int(self._q[_SHM_Q_CAPACITY])
        if self.capacity <= 0 or self.capacity & (self.capacity - 1):
            self._release()
            raise ProtocolError(f"{path}: ring capacity {self.capacity} "
                                f"is not a power of two")
        self._mask = self.capacity - 1
        self._data = memoryview(mm)[SHM_RING_HEADER:
                                    SHM_RING_HEADER + self.capacity]

    # -- construction ---------------------------------------------------------

    @classmethod
    def create(cls, path: str, role: str,
               capacity: int = SHM_RING_DEFAULT_CAPACITY) -> "ShmFrameRing":
        """Create and map a fresh ring file (the hub side of the attach
        handshake).  ``capacity`` is rounded up to a power of two."""
        cap = 1
        while cap < max(int(capacity), mmap.PAGESIZE):
            cap <<= 1
        fd = os.open(path, os.O_RDWR | os.O_CREAT | os.O_EXCL, 0o600)
        try:
            os.ftruncate(fd, SHM_RING_HEADER + cap)
            mm = mmap.mmap(fd, SHM_RING_HEADER + cap)
        finally:
            os.close(fd)
        q = memoryview(mm).cast("Q")
        q[_SHM_Q_CAPACITY] = cap
        # magic is stamped LAST: an opener seeing it sees a complete header
        q[_SHM_Q_MAGIC] = SHM_RING_MAGIC
        del q
        return cls(path, mm, role)

    @classmethod
    def open(cls, path: str, role: str) -> "ShmFrameRing":
        """Map an existing ring file (the client side of the handshake)."""
        fd = os.open(path, os.O_RDWR)
        try:
            size = os.fstat(fd).st_size
            if size < SHM_RING_HEADER + mmap.PAGESIZE:
                raise ProtocolError(f"{path}: ring file too small ({size} B)")
            mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        return cls(path, mm, role)

    # -- the SPSC protocol ----------------------------------------------------

    def _park(self, spins: int, started: float,
              timeout: Optional[float]) -> int:
        """One wait step while the ring is full/empty; returns the updated
        spin count.  Raises ``socket.timeout`` past the deadline."""
        if timeout is not None and time.monotonic() - started >= timeout:
            raise socket.timeout("timed out waiting on shm ring")
        if spins < self._SPIN:
            return spins + 1
        time.sleep(min(self._PARK_MIN * (1 << min(spins - self._SPIN, 7)),
                       self._PARK_MAX))
        return spins + 1

    def write(self, data, timeout: Optional[float] = None) -> None:
        """Move ALL of ``data`` into the ring (``sendall`` semantics)."""
        src = memoryview(data).cast("B") if not isinstance(data, memoryview) \
            else data.cast("B")
        off, n = 0, len(src)
        head = int(self._q[_SHM_Q_HEAD])
        spins, started, parked = 0, time.monotonic(), False
        while off < n:
            if self._i[_SHM_I_CONSUMER_CLOSED]:
                raise ConnectionError("shm ring consumer closed")
            free = self.capacity - (head - int(self._q[_SHM_Q_TAIL]))
            if free == 0:
                if not parked and obs.enabled():
                    obs.counter("ps.shm_ring_full_waits").inc()
                parked = True
                spins = self._park(spins, started, timeout)
                continue
            pos = head & self._mask
            k = min(n - off, free, self.capacity - pos)
            self._data[pos:pos + k] = src[off:off + k]
            off += k
            head += k
            # publish AFTER the payload bytes are in place: the consumer
            # never reads past head, so it can never see torn data
            self._q[_SHM_Q_HEAD] = head
            spins, parked = 0, False

    def read_into(self, view, timeout: Optional[float] = None) -> int:
        """Fill ``view`` with whatever contiguous bytes are available
        (``recv_into`` semantics: may return fewer than asked; returns 0
        only when the producer closed and the ring is drained)."""
        dst = memoryview(view)
        if dst.nbytes == 0:
            return 0
        dst = dst.cast("B")
        tail = int(self._q[_SHM_Q_TAIL])
        spins, started = 0, time.monotonic()
        while True:
            avail = int(self._q[_SHM_Q_HEAD]) - tail
            if avail:
                break
            if self._i[_SHM_I_PRODUCER_CLOSED]:
                # re-check head once: close flag may land after final bytes
                if int(self._q[_SHM_Q_HEAD]) - tail == 0:
                    return 0
                continue
            spins = self._park(spins, started, timeout)
        pos = tail & self._mask
        k = min(dst.nbytes, avail, self.capacity - pos)
        dst[:k] = self._data[pos:pos + k]
        self._q[_SHM_Q_TAIL] = tail + k
        return k

    @property
    def pending(self) -> int:
        """Bytes written but not yet read (either role may ask)."""
        return int(self._q[_SHM_Q_HEAD]) - int(self._q[_SHM_Q_TAIL])

    # -- lifecycle ------------------------------------------------------------

    def mark_closed(self) -> None:
        """Raise BOTH closed flags without unmapping — the shutdown-style
        wakeup: parked peers (local threads and the process across the
        ring alike) observe the flag on their next wait iteration and
        fall out with EOF/``ConnectionError`` instead of sleeping on."""
        try:
            self._i[_SHM_I_PRODUCER_CLOSED] = 1
            self._i[_SHM_I_CONSUMER_CLOSED] = 1
        except (TypeError, ValueError):
            # already closed: the connection's own thread saw its peer
            # leave and released the ring (``_i`` is None) before the
            # hub's stop() came round to sever it
            pass

    def _release(self) -> None:
        self._q = self._i = self._data = None
        try:
            self._mm.close()
        except (BufferError, ValueError):
            pass  # an in-flight view pins the map; the OS reclaims at exit

    def close(self) -> None:
        """Raise this role's closed flag and unmap.  Idempotent."""
        try:
            if self.role == "producer":
                self._i[_SHM_I_PRODUCER_CLOSED] = 1
            else:
                self._i[_SHM_I_CONSUMER_CLOSED] = 1
        except (TypeError, ValueError):
            pass  # already closed
        self._release()

    def unlink(self) -> None:
        """Remove the ring file (creator-side cleanup); map stays valid."""
        try:
            os.unlink(self.path)
        except OSError:
            pass


class ShmEndpoint:
    """A socket-shaped duplex endpoint over two :class:`ShmFrameRing`\\ s
    (one per direction) — the object that replaces ``PSClient.sock`` /
    the hub's per-connection socket after a successful Z attach.  Every
    transport helper in this module only touches ``sendall`` /
    ``recv_into`` / ``settimeout`` / ``shutdown`` / ``close``, so the
    swap is invisible to the framing layer and the bytes that move are
    identical to what the socket would have carried.

    The original TCP socket is retained (unread, unwritten) purely as a
    liveness anchor: closing the endpoint closes it too, so a peer death
    is observable by the OS even if the dead process never set its ring
    closed flag."""

    def __init__(self, sock: socket.socket, tx_ring: ShmFrameRing,
                 rx_ring: ShmFrameRing):
        self.sock = sock
        self.tx_ring = tx_ring
        self.rx_ring = rx_ring
        self._timeout = sock.gettimeout()

    def sendall(self, data) -> None:
        self.tx_ring.write(data, timeout=self._timeout)
        if obs.enabled():
            obs.counter("ps.shm_frames_total").inc()

    def recv_into(self, view, nbytes: int = 0) -> int:
        mv = memoryview(view)
        if nbytes:
            mv = mv.cast("B")[:nbytes]
        return self.rx_ring.read_into(mv, timeout=self._timeout)

    def recv(self, n: int) -> bytes:
        buf = bytearray(n)
        got = self.recv_into(memoryview(buf), n)
        return bytes(buf[:got])

    def settimeout(self, timeout: Optional[float]) -> None:
        self._timeout = timeout
        try:
            self.sock.settimeout(timeout)
        except OSError:
            pass

    def gettimeout(self) -> Optional[float]:
        return self._timeout

    def fileno(self) -> int:
        return self.sock.fileno()

    def shutdown(self, how: int = socket.SHUT_RDWR) -> None:
        """Wake every parked reader/writer on both rings (both processes)
        and sever the anchor socket — the eviction path's guarantee that
        nothing stays asleep holding a dead connection."""
        self.tx_ring.mark_closed()
        self.rx_ring.mark_closed()
        try:
            self.sock.shutdown(how)
        except OSError:
            pass

    def close(self) -> None:
        self.tx_ring.close()
        self.rx_ring.close()
        try:
            self.sock.close()
        except OSError:
            pass


# -- the Z attach handshake payloads ------------------------------------------

def encode_shm_request(capacity_hint: int = SHM_RING_DEFAULT_CAPACITY) -> bytes:
    """Step 1, client->hub: one blob = u8 layout version + u64 big-endian
    ring-capacity hint (the hub may round it; the mapped header is
    authoritative)."""
    blob = struct.pack(">BQ", SHM_VERSION, int(capacity_hint))
    return encode_tensors(ACTION_SHM, [np.frombuffer(blob, np.uint8)])


def decode_shm_request(blobs: Sequence) -> Tuple[int, int]:
    """Inverse of :func:`encode_shm_request` -> (version, capacity_hint)."""
    if not blobs:
        raise ProtocolError("Z request carries no header blob")
    raw = bytes(memoryview(blobs[0]))[:9]
    if len(raw) != 9:
        raise ProtocolError(f"Z request blob has {len(raw)} bytes, want 9")
    version, hint = struct.unpack(">BQ", raw)
    return int(version), int(hint)


def encode_shm_offer(c2h_path: str, h2c_path: str) -> bytes:
    """Step 2, hub->client (accept): TWO utf-8 path blobs — the
    client->hub ring file, then the hub->client ring file.  Both already
    exist and are fully initialized when this frame leaves."""
    return encode_tensors(ACTION_SHM, [
        np.frombuffer(c2h_path.encode("utf-8"), np.uint8),
        np.frombuffer(h2c_path.encode("utf-8"), np.uint8)])


def encode_shm_decline() -> bytes:
    """Step 2, hub->client (decline): zero blobs — the connection simply
    stays pure TCP, byte-identical to a hub with shm disabled."""
    return encode_tensors(ACTION_SHM, [])


def decode_shm_offer(blobs: Sequence) -> Optional[Tuple[str, str]]:
    """Inverse of the step-2 reply: ``(c2h_path, h2c_path)`` on an offer,
    ``None`` on a decline."""
    if not blobs:
        return None
    if len(blobs) != 2:
        raise ProtocolError(f"Z offer carries {len(blobs)} blobs, want 2")
    return (bytes(memoryview(blobs[0])).decode("utf-8"),
            bytes(memoryview(blobs[1])).decode("utf-8"))


def encode_shm_confirm(attached: bool) -> bytes:
    """Step 3, client->hub over TCP: one 1-byte blob — ``b"\\x01"`` the
    client mapped both rings and its NEXT frame rides them, ``b"\\x00"``
    mapping failed, stay on TCP.  Because TCP is FIFO, the hub reading
    this frame knows exactly which transport every subsequent frame uses
    — the stream can never tear."""
    return encode_tensors(ACTION_SHM, [
        np.frombuffer(b"\x01" if attached else b"\x00", np.uint8)])


def decode_shm_confirm(blobs: Sequence) -> bool:
    """Inverse of :func:`encode_shm_confirm`."""
    if not blobs or len(bytes(memoryview(blobs[0]))) != 1:
        raise ProtocolError("Z confirm carries no status byte")
    return bytes(memoryview(blobs[0]))[0] == 1


# -- batched socket receive (remote-worker path, ISSUE 18) --------------------

_LIBC = None
_MMSG_TYPES = None


def _libc():
    global _LIBC
    if _LIBC is None:
        import ctypes
        _LIBC = ctypes.CDLL(None, use_errno=True)
    return _LIBC


def batched_io_available() -> bool:
    """Runtime guard (the ``require_tool`` idiom, but for a libc symbol):
    True when ``recvmmsg`` is resolvable, so the batched receive path can
    drain a commit storm with one syscall per batch.  When False — or on
    any runtime failure — :class:`BatchedReceiver` silently degrades to
    plain nonblocking ``recv_into`` drains, which still amortize the
    parse but not the syscall."""
    try:
        return hasattr(_libc(), "recvmmsg")
    except OSError:
        return False


def _mmsg_types():
    """The ctypes mirror of ``struct mmsghdr`` (built once)."""
    global _MMSG_TYPES
    if _MMSG_TYPES is None:
        import ctypes

        class IoVec(ctypes.Structure):
            _fields_ = [("iov_base", ctypes.c_void_p),
                        ("iov_len", ctypes.c_size_t)]

        class MsgHdr(ctypes.Structure):
            _fields_ = [("msg_name", ctypes.c_void_p),
                        ("msg_namelen", ctypes.c_uint),
                        ("msg_iov", ctypes.POINTER(IoVec)),
                        ("msg_iovlen", ctypes.c_size_t),
                        ("msg_control", ctypes.c_void_p),
                        ("msg_controllen", ctypes.c_size_t),
                        ("msg_flags", ctypes.c_int)]

        class MMsgHdr(ctypes.Structure):
            _fields_ = [("msg_hdr", MsgHdr), ("msg_len", ctypes.c_uint)]

        _MMSG_TYPES = (ctypes, IoVec, MMsgHdr)
    return _MMSG_TYPES


class BatchedReceiver:
    """Frame-granular batched receive for one hub connection: one blocking
    ``recv_into`` pulls whatever the kernel has (typically MANY pipelined
    frames from a committing worker), opportunistic nonblocking drains
    top the buffer up, and subsequent frames are parsed straight out of
    the buffer with zero syscalls.  The per-batch frame count lands in
    the ``ps_recv_batch_depth`` histogram, which shows whether the
    batching actually batches.

    ``recv_frame_into`` mirrors :func:`recv_frame_into`'s contract: the
    returned memoryview aliases the internal buffer and is valid only
    until the next call.  Strictly single-reader (the hub's per-
    connection handler thread)."""

    def __init__(self, sock: socket.socket, frame_hint: int, depth: int = 8):
        self.sock = sock
        self.depth = max(1, int(depth))
        self._buf = bytearray(max(int(frame_hint) + 8, 4096) * self.depth)
        self._head = 0   # parse offset
        self._tail = 0   # fill offset
        self._batch_frames = 0  # frames served since the last blocking fill

    def pending(self) -> int:
        """Bytes buffered but not yet parsed — must be 0 at any transport
        handoff (R replication attach, Z shm switch), else frames meant
        for the next owner were already consumed here."""
        return self._tail - self._head

    def _compact(self) -> None:
        if self._head:
            rem = self._tail - self._head
            self._buf[:rem] = self._buf[self._head:self._tail]
            self._head, self._tail = 0, rem

    def _drain_nonblocking(self) -> None:
        """Top the buffer up without blocking — one ``recvmmsg`` when libc
        has it, else a ``MSG_DONTWAIT`` recv loop — so a storm of queued
        frames is consumed in as few syscalls as the kernel allows."""
        if self.depth > 1 and batched_io_available():
            try:
                self._recvmmsg_drain()
                return
            except OSError:
                pass  # fall through to the plain-recv drain
        while self._tail < len(self._buf):
            try:
                n = self.sock.recv_into(
                    memoryview(self._buf)[self._tail:], 0, socket.MSG_DONTWAIT)
            except (BlockingIOError, InterruptedError):
                return
            except OSError:
                return  # let the next blocking read surface the real error
            if n == 0:
                return  # EOF surfaces on the next blocking read
            self._tail += n

    def _recvmmsg_drain(self) -> None:
        """One nonblocking ``recvmmsg`` over the free buffer space, carved
        into ``depth`` iovec segments.  On a stream socket a segment may
        come back short while a later one still fills, so received runs
        are compacted back into one contiguous stream before parsing."""
        ctypes, IoVec, MMsgHdr = _mmsg_types()
        room = len(self._buf) - self._tail
        seg = max(room // self.depth, 1)
        k = min(self.depth, room // seg)
        if k <= 0 or room <= 0:
            return
        base = ctypes.addressof(ctypes.c_char.from_buffer(self._buf,
                                                          self._tail))
        iovs = (IoVec * k)()
        msgs = (MMsgHdr * k)()
        for i in range(k):
            iovs[i].iov_base = base + i * seg
            iovs[i].iov_len = seg if i < k - 1 else room - (k - 1) * seg
            msgs[i].msg_hdr.msg_iov = ctypes.pointer(iovs[i])
            msgs[i].msg_hdr.msg_iovlen = 1
        r = _libc().recvmmsg(self.sock.fileno(), msgs, k,
                             socket.MSG_DONTWAIT, None)
        if r <= 0:
            return  # EAGAIN/EOF/error — the next blocking read decides
        pos = self._tail
        for i in range(r):
            ln = int(msgs[i].msg_len)
            start = self._tail + i * seg
            if start != pos and ln:
                self._buf[pos:pos + ln] = self._buf[start:start + ln]
            pos += ln
        self._tail = pos

    def _fill_blocking(self) -> None:
        """One blocking read (honors the socket timeout), then drain."""
        self._compact()
        if obs.enabled() and self._batch_frames:
            obs.histogram("ps_recv_batch_depth").observe(self._batch_frames)
        self._batch_frames = 0
        n = self.sock.recv_into(memoryview(self._buf)[self._tail:])
        if n == 0:
            raise ConnectionError("peer closed between frames")
        self._tail += n
        self._drain_nonblocking()

    def _ensure(self, need: int) -> None:
        while self._tail - self._head < need:
            if self._head + need > len(self._buf):
                self._compact()
            if self._head + need > len(self._buf):
                # one frame larger than the whole batch buffer: grow once
                self._buf.extend(bytes(self._head + need - len(self._buf)))
            self._fill_blocking()

    def recv_frame_into(self, limit: int = MAX_FRAME) -> memoryview:
        """Parse one frame out of the batch buffer (refilling as needed)
        and return its payload view — drop-in for the hub handler's
        :func:`recv_frame_into` call, same validation, same counters."""
        self._ensure(8)
        (n,) = struct.unpack_from(">Q", self._buf, self._head)
        if n > limit:
            raise ProtocolError(f"frame of {n} bytes exceeds limit={limit}")
        self._ensure(8 + n)
        start = self._head + 8
        self._head += 8 + n
        self._batch_frames += 1
        return memoryview(self._buf)[start:start + n]
