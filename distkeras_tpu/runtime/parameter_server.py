"""Parameter-server hub + worker client — reference parity for
``distkeras/parameter_servers.py`` (SURVEY.md §2.11, §3.4).

The reference ran a driver-side thread that bound a TCP socket, accepted
one connection per Spark worker, and dispatched pickled ``'pull'`` /
``'commit'`` messages under a single mutex.  This re-design keeps that
architecture — it is the *genuinely asynchronous* execution option for the
DOWNPOUR/EASGD family (SURVEY §7 "hard parts", option b), used when worker
processes drive their own chips over DCN — with three changes:

- the wire protocol is raw tensor frames, not pickle
  (:mod:`distkeras_tpu.runtime.networking`) — moved through the zero-copy
  flat path (preallocated frames, ``recv_into`` scatter receives), with
  a pipelined client (prefetched pulls, coalesced acks) for the async
  trainers' hot loop;
- the center is a flat ``float32`` weight list (the pytree structure stays
  with the trainer), so commits are pure vectorized numpy adds;
- the same protocol is implemented by a C++ hub
  (:mod:`distkeras_tpu.runtime.native`) that applies commits without the
  GIL; this Python hub is the portable fallback and the executable spec;
- co-located workers may skip the wire entirely: ``pull_direct`` /
  ``commit_direct`` (and :class:`InprocPSClient` over them) run the same
  center logic under the same lock — the ``transport="inproc"`` path,
  trajectory-identical to sockets (ARCHITECTURE.md "Async transport").

Server classes mirror the reference's:
``SocketParameterServer`` (base, pull/commit loop),
``DeltaParameterServer`` (unscaled adds — DOWNPOUR, elastic),
``ADAGParameterServer`` (delta / num_workers),
``DynSGDParameterServer`` (delta / (staleness + 1) with a global clock).

The hub also scales OUT (ISSUE 6, ARCHITECTURE.md "Sharded hub"): a
deterministic, size-balanced leaf->shard assignment (:func:`shard_plan`)
partitions the center across N hub shards — one hub, lock, listener and
commit clock per shard (:class:`ShardedParameterServer` owns the set) —
and :class:`ShardedPSClient` stripes every pull/commit across per-shard
connections reusing the same pipelined/zero-copy machinery per
connection.  ``num_shards=1`` is byte-identical to the single-hub wire.

This module and :mod:`distkeras_tpu.runtime.networking` import NO JAX, and
must stay that way: one process owns the chip, and ``chip_smoke.py`` and
the tests start hubs and wire-only workers as children of a parent that
already holds it.  A child that imported a JAX backend here would fail or hang on
the chip its parent has.
"""

from __future__ import annotations

import contextlib
import heapq
import itertools
import json
import os
import queue
import random
import socket
import threading
import warnings
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import time

import numpy as np

from distkeras_tpu import observability as obs
from distkeras_tpu.observability import distributed as dtrace
from distkeras_tpu.runtime import networking as net


class HubSnapshotter:
    """Periodic durability for a PS hub: every ``interval`` seconds (and
    once at stop) the hub's full recoverable state — center weights, commit
    clock, update count, algorithm extras — is written through
    :class:`distkeras_tpu.checkpoint.Checkpointer` (atomic tmp+rename, so a
    hub SIGKILLed mid-save leaves the previous snapshot intact).  A
    restarted hub calls :meth:`restore_latest` BEFORE serving: the center
    resumes from the last snapshot and the commit clock re-arms behind a
    fence (``restore_state`` on the hub) that neutralizes pre-restart stale
    clocks.  Works against any hub exposing ``snapshot_state()`` /
    ``restore_state()`` — the Python hubs here and the C++ hub wrapper
    (:mod:`distkeras_tpu.runtime.native`) both do.

    Telemetry: ``ps.snapshot_ms`` save-latency histogram,
    ``ps_snapshots_total`` counter."""

    def __init__(self, hub: Any, directory: str, interval: float = 30.0,
                 keep: int = 3):
        from distkeras_tpu.checkpoint import Checkpointer

        self.hub = hub
        self.interval = float(interval)
        self.checkpointer = Checkpointer(directory, keep=keep)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # serializes the periodic loop against the final stop() snapshot
        self._save_lock = threading.Lock()
        self._next_step = (self.checkpointer.latest_step() or 0) + 1

    def restore_latest(self) -> bool:
        """Load the newest readable snapshot into the hub; ``True`` if one
        was restored.  Corrupt/partial snapshots (killed mid-write by
        something stronger than the atomic rename — disk truncation, a
        torn copy) are skipped with a warning, falling back to the next
        older one."""
        templates = self.hub.get_weights()
        for step in reversed(self.checkpointer.all_steps()):
            try:
                trees = self.checkpointer.restore({"center": templates}, step=step)
                meta = self.checkpointer.metadata(step=step).get("metadata", {})
            except Exception as e:
                warnings.warn(f"skipping unreadable PS snapshot step {step}: "
                              f"{type(e).__name__}: {e}")
                continue
            self.hub.restore_state(trees["center"], meta)
            # under the save lock: restore normally runs once at start,
            # but it is public API — racing a live snapshot loop must
            # not lose a step advance (guarded-by contract, ISSUE 14)
            with self._save_lock:
                self._next_step = max(self._next_step, step + 1)
            return True
        return False

    def save_now(self) -> None:
        with self._save_lock, obs.span("ps.snapshot"):
            t0 = time.perf_counter()
            center, state = self.hub.snapshot_state()
            self.checkpointer.save(
                self._next_step, {"center": center},
                metadata={"kind": "ps-hub-snapshot", **state})
            self._next_step += 1
            if obs.enabled():
                obs.histogram("ps.snapshot_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
                obs.counter("ps_snapshots_total").inc()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.save_now()
            except Exception as e:  # a full disk must not kill the hub
                warnings.warn(f"PS snapshot failed: {type(e).__name__}: {e}")

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self, final_snapshot: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if final_snapshot:
            try:
                self.save_now()
            except Exception as e:
                warnings.warn(f"final PS snapshot failed: {type(e).__name__}: {e}")


class ReplicationFeed:
    """Primary-side hot-standby stream (ISSUE 7): every APPLIED commit —
    the post-aggregation scaled delta plus the commit clock — is framed as
    an opt-in action-``R`` message and written to each attached replica
    connection BEFORE the committing worker's ack leaves.  A commit the
    worker saw acknowledged is therefore already in the kernel's send
    queue toward the replica, which the kernel flushes even if the primary
    process is SIGKILLed right after — the "replica center >= last
    primary-acked clock" guarantee the failover drills pin (a dead HOST
    additionally needs replica acks; out of scope, see ARCHITECTURE.md
    "High availability").

    Created lazily on the first replica handshake, so a hub nobody
    replicates pays nothing (``active()`` is one attribute read on the
    commit path).  ``attach`` full-syncs the new replica (whole center +
    clock, one R frame) under the publish lock, so the sync and the delta
    stream can never interleave inconsistently: deltas at or below the
    sync clock are skipped per connection, later deltas all flow.  Adds
    commute, so cross-thread publish-order inversions only reorder
    float additions (same tolerance class as async SGD itself).

    A replica that stops draining stalls commits at most
    ``REPLICA_SEND_TIMEOUT`` seconds, then is detached (warned + counted)
    — availability of the primary wins over completeness of a sick
    replica's feed.

    Telemetry: ``ps_replicas_connected`` gauge, ``ps.replicate_ms`` send
    latency, ``ps_replication_lag`` gauge (commits applied but not yet
    streamed at publish time — bounded by construction, measured so an
    operator sees it), ``ps_replica_disconnects_total``."""

    REPLICA_SEND_TIMEOUT = 30.0

    def __init__(self, hub: "SocketParameterServer"):
        self.hub = hub
        self._lock = threading.Lock()  # serializes attach + publish
        # [socket, conn ordinal, attach-time SYNC clock, sparse-capable]
        # per replica.  The sync clock is IMMUTABLE after attach: it only
        # filters deltas the full sync already covered.  It must never
        # advance on sends — concurrent handlers publish out of clock
        # order (apply under the hub lock, publish under this one), and a
        # moving watermark would skip (lose) the lower-clock delta behind
        # a higher one.  The capability flag is likewise attach-time
        # immutable (the hello announced it): a sparse commit streams as
        # one REPL_SPARSE row-delta frame to capable replicas and as the
        # dense-materialized REPL_DELTA to legacy ones — never a frame
        # kind the peer cannot parse
        self._conns: List[List[Any]] = []
        self._codec = net.FlatFrameCodec(net.repl_frame_templates(hub.center))
        # sparse row-delta frames vary per commit (row blobs sized by the
        # touched set), so they ride a grow-once variable encoder
        self._sp_enc = net.VarFrameEncoder()
        # cumulative row-delta bytes actually published (the `RΔ` series
        # distkeras-top renders from the hub pseudo-worker's metrics)
        self.repl_sparse_bytes = 0

    def active(self) -> bool:
        # racy read by design (publish re-checks under the lock): the
        # commit hot path must not take the feed lock when nobody listens
        return bool(self._conns)

    def _set_gauge(self) -> None:
        if obs.enabled():
            obs.gauge("ps_replicas_connected",
                      **self.hub._mlabels).set(len(self._conns))

    def attach(self, conn: socket.socket, conn_idx: int,
               capabilities: int = 0) -> None:
        """Handshake a replica connection: full-sync it (center + clock,
        captured under the hub lock) and register it for the delta
        stream.  Registration happens BEFORE the center snapshot: a commit
        applying after the registration sees ``active()`` and publishes
        (blocking on this lock until the sync is out, then skipped iff the
        sync already covered it), while a commit applying before it is in
        the snapshot — snapshotting first instead would let a commit slip
        into the gap unpublished AND unsynced.  ``capabilities`` is the
        hello's attach-time announcement (:data:`networking.
        REPL_CAP_SPARSE`): it decides the frame kinds this replica is
        ever sent."""
        conn.settimeout(self.REPLICA_SEND_TIMEOUT)
        sparse_ok = bool(capabilities & net.REPL_CAP_SPARSE)
        with self._lock:
            entry: List[Any] = [conn, conn_idx, -1, sparse_ok]
            self._conns.append(entry)
            try:
                with self.hub._lock:
                    # pack the center STRAIGHT into the sync frame under
                    # the lock (one memcpy per tensor — the pull handler's
                    # idiom); the send happens after release so a slow
                    # replica can't hold the center
                    clock = self.hub._clock
                    self._codec.pack(
                        net.ACTION_REPL,
                        [net.encode_repl_header(clock, net.REPL_SYNC)]
                        + list(self.hub.center))
                self._codec.send_packed(conn)  # lint: blocking-ok full-sync must serialize with the delta stream; stall bounded by REPLICA_SEND_TIMEOUT
            except BaseException:
                self._conns.remove(entry)
                raise
            entry[2] = clock
        if obs.enabled():
            obs.counter("ps_replicas_attached_total",
                        **self.hub._mlabels).inc()
            self._set_gauge()

    def _densify(self, scaled: Sequence[Any]) -> List[np.ndarray]:
        """Center-shaped materialization of a (possibly row-sparse) scaled
        commit — the dense-``R`` fallback frame a legacy replica applies.
        Scattering ``full[ids] = g`` makes the standby's ``center +=
        full`` perform the touched rows' float additions exactly as the
        primary's ``center[ids] += g`` did (idle rows add 0.0)."""
        out: List[np.ndarray] = []
        for c, p in zip(self.hub.center, scaled):
            if isinstance(p, tuple):
                ids, g = p
                full = np.zeros_like(c)
                if ids.size:
                    full[ids] = g
                out.append(full)
            else:
                out.append(np.asarray(p, np.float32))
        return out

    def _sparse_blobs(self, header: np.ndarray,
                      scaled: Sequence[Any]) -> List[np.ndarray]:
        """Blob list of one REPL_SPARSE frame: header + the U-commit
        layout (dense leaves whole, sparse leaves as (ids, rows))."""
        blobs: List[np.ndarray] = [header]
        for p in scaled:
            if isinstance(p, tuple):
                blobs.append(np.ascontiguousarray(p[0], net.ROW_ID_DTYPE))
                blobs.append(np.ascontiguousarray(p[1], np.float32))
            else:
                blobs.append(np.ascontiguousarray(p, np.float32))
        return blobs

    def publish(self, clock: int, scaled: Sequence[Any]) -> None:
        """Stream one applied commit to every attached replica; returns
        once the frame is written (kernel-owned) everywhere — the caller
        acks its worker only after.  ``scaled`` is per-leaf parts aligned
        with the center: full arrays for dense leaves, ``(ids, scaled row
        deltas)`` tuples for row-sparse leaves of a sparse commit.  Row-
        sparse parts stream as ONE REPL_SPARSE frame to sparse-capable
        replicas (cost ∝ touched rows) and are densified — outside the
        center lock, only when a legacy replica is actually attached —
        into the pre-ISSUE-15 REPL_DELTA frame for the rest."""
        telemetry = obs.enabled()
        t0 = time.perf_counter() if telemetry else 0.0
        has_rows = any(isinstance(p, tuple) for p in scaled)
        sp_sent = 0
        sp_frame_len = 0
        with self._lock:
            if not self._conns:
                return
            packed = False
            sp_frame: Optional[memoryview] = None
            dead = []
            for entry in self._conns:
                conn, conn_idx, sync_clock, sparse_ok = entry
                if sync_clock >= clock:
                    continue  # already covered by this replica's full sync
                try:
                    if has_rows and sparse_ok:
                        if sp_frame is None:
                            sp_frame = self._sp_enc.pack(
                                net.ACTION_REPL, self._sparse_blobs(
                                    net.encode_repl_header(
                                        clock, net.REPL_SPARSE), scaled))
                        conn.sendall(sp_frame)  # lint: blocking-ok send-before-ack IS the zero-loss replication contract; stall bounded by REPLICA_SEND_TIMEOUT, then detach
                        sp_sent += 1
                        if telemetry:
                            obs.counter("net_tx_frames_total").inc()
                            obs.counter("net_tx_bytes_total").inc(
                                self._sp_enc.frame_len)
                    else:
                        if not packed:
                            self._codec.pack(
                                net.ACTION_REPL,
                                [net.encode_repl_header(clock,
                                                        net.REPL_DELTA)]
                                + (self._densify(scaled) if has_rows
                                   else list(scaled)))
                            packed = True
                        self._codec.send_packed(conn)  # lint: blocking-ok send-before-ack IS the zero-loss replication contract; stall bounded by REPLICA_SEND_TIMEOUT, then detach
                except (OSError, ValueError) as e:
                    dead.append((entry, e))
            for entry, e in dead:
                self._detach_locked(entry, e)
            if sp_sent:
                # counted (and frame_len snapshotted) under the feed lock:
                # a concurrent publish repacks the shared encoder the
                # moment we release it
                sp_frame_len = self._sp_enc.frame_len
                self.repl_sparse_bytes += sp_sent * sp_frame_len
                repl_sparse_total = self.repl_sparse_bytes
        if sp_sent and telemetry:
            # bytes the row-delta framing saved vs the dense-R frame each
            # capable replica would otherwise have been sent
            obs.counter("ps.repl_sparse_bytes_saved",
                        **self.hub._mlabels).inc(
                sp_sent * max(0, self._codec.frame_len - sp_frame_len))
        if sp_sent:
            # the live collector's cumulative RΔ series (rate = bytes/s
            # in distkeras-top), under the hub pseudo-worker key like
            # replication_lag below
            self.hub._observe_health(
                f"hub{'' if self.hub.shard_id is None else self.hub.shard_id}",
                "repl_sparse_bytes_total", repl_sparse_total, any_shard=True)
        # commits the hub applied while this publish waited its turn:
        # the feed's real-time backlog (clock reads race commits by
        # design — it is a gauge, not an invariant)
        lag = max(0, self.hub._clock - clock)
        if telemetry:
            obs.histogram("ps.replicate_ms", **self.hub._mlabels).observe(
                (time.perf_counter() - t0) * 1e3)
            obs.gauge("ps_replication_lag", **self.hub._mlabels).set(lag)
        # and into the live collector under the hub's own pseudo-worker
        # key, so the replication-lag-growth detector sees it as a moving
        # series.  NOT behind the registry flag: the health plane has its
        # own opt-in (a worker reporting health activates it), and the
        # fold self-guards to a few None checks when the plane is off.
        # any_shard: the KEY carries the shard — every shard's lag is
        # its own series, and shard N's must not be gated on shard 0
        self.hub._observe_health(
            f"hub{'' if self.hub.shard_id is None else self.hub.shard_id}",
            "replication_lag", lag, any_shard=True)

    def _detach_locked(self, entry: List[Any], cause: BaseException) -> None:
        conn, conn_idx = entry[0], entry[1]
        self._conns.remove(entry)
        warnings.warn(f"replica connection {conn_idx} dropped from the "
                      f"replication feed: {type(cause).__name__}: {cause}")
        try:
            conn.close()
        except OSError:
            pass
        with self.hub._conn_lock:
            if conn in self.hub._conns:
                self.hub._conns.remove(conn)
        if obs.enabled():
            obs.counter("ps_replica_disconnects_total",
                        **self.hub._mlabels).inc()
            self._set_gauge()


# -- adaptive aggregation (ISSUE 10) -------------------------------------------
# The monitoring stack (PR 5/8) can name every async pathology — per-worker
# staleness, stragglers, reconnect storms — but nothing ACTS on any of it.
# The pieces below close that loop hub-side: queued commits merge
# Adasum-style ("Scaling Distributed Training with Adaptive Summation",
# arXiv:2006.02924) instead of applying sequentially, per-worker commit
# scales follow the live staleness series (the DynSGD response of
# arXiv:1611.04581, re-based on the fleet), and reconnect storms are shed
# with retry-after hints instead of absorbed as a thundering herd.  All of
# it rides ``adaptive=True``; the default-off path is byte-identical to the
# pre-adaptive hub.
#
# A "commit" here is a per-leaf parts list aligned with the center: a full
# ndarray for a dense leaf, an ``(ids, grads)`` pair (sorted-unique int64
# ids, ``[k, dim]`` f32 grads) for a sparse leaf — the ONE representation
# the merge rule, the combiner and the replication materialization all
# share, so dense and sparse-row commits compose under the same math.


def _apply_phase(t_request_ns: int, **attrs: Any):
    """The leaf phase ``ps.apply``, opened just inside ``with hub._lock:``
    around the apply: the time since ``t_request_ns`` (taken before the
    ``with``) is how long the caller waited for the center lock."""
    return obs.phase("ps.apply", lock_wait_us=(
        time.perf_counter_ns() - t_request_ns) // 1000, **attrs)


# -- the dense commit's scaled add ---------------------------------------------
# float32 elements of the hub's reusable scratch: 256 KiB, small enough to
# stay in L2 between the multiply that fills it and the add that drains it
_APPLY_BLOCK = 1 << 16


def _add_scaled_commit(center: Sequence[np.ndarray],
                       delta: Sequence[np.ndarray], scale: float,
                       scratch: np.ndarray) -> None:
    """``c += d * scale`` for every leaf, in place and allocation-free: the
    same two float32 roundings (multiply, then add), block by block through
    ``scratch`` (``_APPLY_BLOCK`` float32, the hub's, used under its center
    lock), so the center is bit for bit what the expression gives — and
    what the replication branch of ``_apply_commit_locked`` and the C++
    hub compute — without a temporary the size of the leaf.  ``scale ==
    1.0`` skips the multiply (``x * float32(1.0)`` is exact).  A delta
    leaf that is not C-contiguous float32 (``commit_direct`` alone can
    hand one in) is converted first, as the replication branch does."""
    scale = np.float32(scale)
    one = scale == np.float32(1.0)
    block = scratch.size
    for c, d in zip(center, delta):
        if d.dtype != np.float32 or not d.flags.c_contiguous:
            d = np.asarray(d, np.float32, order="C")
        if one:
            c += d
        elif not c.flags.c_contiguous:
            # a center built from Fortran-ordered weights has no flat view
            c += d * scale
        else:
            cf, df = c.reshape(-1), d.reshape(-1)
            for i in range(0, cf.size, block):
                cb = cf[i:i + block]
                s = scratch[:cb.size]
                np.multiply(df[i:i + block], scale, out=s)
                np.add(cb, s, out=cb)


def _adasum_dot(a_parts: Sequence[Any], b_parts: Sequence[Any]) -> float:
    """Inner product of two commits in the center's flat vector space.
    Sparse x sparse pairs contribute only their intersecting rows."""
    total = 0.0
    for a, b in zip(a_parts, b_parts):
        if isinstance(a, tuple) and isinstance(b, tuple):
            ids_a, ga = a
            ids_b, gb = b
            common, ia, ib = np.intersect1d(ids_a, ids_b,
                                            assume_unique=True,
                                            return_indices=True)
            if common.size:
                total += float(np.dot(ga[ia].ravel(), gb[ib].ravel()))
        elif isinstance(a, tuple) or isinstance(b, tuple):
            raise ValueError("adasum needs matching per-leaf representations"
                             " (dense vs sparse); densify mixed batches "
                             "first")
        else:
            total += float(np.dot(np.asarray(a).ravel(),
                                  np.asarray(b).ravel()))
    return total


def _adasum_normsq(parts: Sequence[Any]) -> float:
    total = 0.0
    for p in parts:
        flat = (p[1] if isinstance(p, tuple) else np.asarray(p)).ravel()
        total += float(np.dot(flat, flat))
    return total


def _scale_parts(parts: Sequence[Any], scale: np.float32) -> List[Any]:
    """One commit scaled by a float32 scalar (sparse rows scale in their
    compact form — idle rows stay implicit zeros)."""
    return [(p[0], p[1] * scale) if isinstance(p, tuple)
            else np.asarray(p) * scale
            for p in parts]


def adasum_pair(a_parts: Sequence[Any], b_parts: Sequence[Any]) -> List[Any]:
    """Adasum combine (arXiv:2006.02924) of two commits:

        merged = (1 - <a,b> / 2|a|^2) * a  +  (1 - <a,b> / 2|b|^2) * b

    — the plain sum when the two are orthogonal (independent progress
    adds), the average when they are parallel (the same step must not
    apply twice), and a smooth interpolation in between that never blows
    the magnitude up.  A zero-norm side passes the other through
    unchanged.  Symmetric in its arguments (the commutativity property
    ``tests/test_adaptive.py`` pins); sparse leaves merge on the union of
    their touched rows, so idle rows cost nothing."""
    na = _adasum_normsq(a_parts)
    nb = _adasum_normsq(b_parts)
    if na == 0.0:
        return list(b_parts)
    if nb == 0.0:
        return list(a_parts)
    dot = _adasum_dot(a_parts, b_parts)
    alpha = np.float32(1.0 - dot / (2.0 * na))
    beta = np.float32(1.0 - dot / (2.0 * nb))
    merged: List[Any] = []
    for a, b in zip(a_parts, b_parts):
        if isinstance(a, tuple):
            ids_a, ga = a
            ids_b, gb = b
            ids = np.union1d(ids_a, ids_b)
            out = np.zeros((ids.size, ga.shape[1]), np.float32)
            if ids_a.size:
                out[np.searchsorted(ids, ids_a)] += alpha * ga
            if ids_b.size:
                out[np.searchsorted(ids, ids_b)] += beta * gb
            merged.append((ids, out))
        else:
            merged.append(alpha * np.asarray(a, np.float32)
                          + beta * np.asarray(b, np.float32))
    return merged


def _mixed_repr(commits: Sequence[Sequence[Any]]) -> bool:
    """True when any leaf is carried sparse ``(ids, grads)`` by one
    commit and dense by another — a full-delta control commit
    interleaving with sparse workers.  The combiner applies such a batch
    SEQUENTIALLY: densifying the sparse sides to merge them would
    materialize whole embedding tables under the center lock (the exact
    cost the row-sparse service exists to avoid)."""
    first = commits[0]
    return any(
        any(isinstance(c[i], tuple) != isinstance(first[i], tuple)
            for c in commits[1:])
        for i in range(len(first)))


def adasum_merge(commits: Sequence[Sequence[Any]]) -> List[Any]:
    """Balanced pairwise-tree Adasum reduction over a batch of commits —
    the one merge rule the adaptive hub applies to every queued batch,
    dense and sparse-row commits alike (per-leaf representations must
    match across the batch; the combiner applies rare mixed batches
    sequentially instead)."""
    items = [list(c) for c in commits]
    if not items:
        raise ValueError("adasum_merge of an empty batch")
    while len(items) > 1:
        nxt = [adasum_pair(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


class AdaptiveRateController:
    """DynSGD-style per-worker commit scaling driven by LIVE health events
    (ISSUE 10; the degradation response of arXiv:1611.04581, re-based on
    the fleet instead of clock zero).

    The adaptive hub subscribes this controller to the process
    :class:`~distkeras_tpu.observability.health.HealthMonitor`
    (:meth:`~distkeras_tpu.observability.health.HealthMonitor.subscribe`
    — push, not polling); each staleness/straggler event naming a worker
    updates that worker's multiplicative commit scale — composed ON TOP
    of the algorithm's own ``commit_scale(staleness)`` — from the
    event's rolling-series evidence:

    - ``staleness_drift`` (rolling mean vs fleet median):
      ``(median + 1) / (mean + 1)``;
    - ``staleness_spike`` (latest vs own rolling baseline):
      ``(baseline + 1) / (staleness + 1)``;
    - ``straggler`` (window wall vs fleet median): ``1 / factor``.

    Scales clamp to ``[floor, 1.0]`` and EXPIRE after ``hold_s`` without
    a refreshing event — detector cooldowns re-fire while a condition
    persists, so a still-sick worker stays scaled and a recovered one
    drifts back to 1.0.  Verdicts are kept PER EVENT KIND (the applied
    scale is the min across a worker's unexpired kinds): a fresh event
    of one kind REPLACES that kind's verdict — so a worker that improves
    from severe to mild tracks the improving evidence — while a severe
    verdict from another detector keeps its own clock and is never
    silently extended by a weaker one.  ``scale_for`` is the commit
    path's one dict read under a short lock."""

    def __init__(self, floor: float = 0.1, hold_s: float = 30.0):
        self.floor = float(floor)
        self.hold_s = float(hold_s)
        self._lock = threading.Lock()
        # (worker, event kind) -> (scale, expires_monotonic)
        self._scales: Dict[Tuple[str, str], Tuple[float, float]] = {}

    def _propose(self, worker: str, kind: str, scale: float) -> None:
        scale = min(1.0, max(self.floor, float(scale)))
        with self._lock:
            self._scales[(worker, kind)] = (scale,
                                            time.monotonic() + self.hold_s)

    def on_event(self, event: Any) -> None:
        """:meth:`HealthMonitor.subscribe` callback.  Malformed evidence
        is ignored — adaptation must never take down the path that
        emitted the event."""
        worker = getattr(event, "worker", None)
        if worker is None:
            return
        ev = getattr(event, "evidence", None) or {}
        try:
            kind = event.kind
            if kind == "staleness_drift":
                self._propose(worker, kind,
                              (float(ev.get("fleet_median", 0.0)) + 1.0)
                              / (float(ev.get("staleness_mean", 0.0)) + 1.0))
            elif kind == "staleness_spike":
                self._propose(worker, kind,
                              (float(ev.get("baseline", 0.0)) + 1.0)
                              / (float(ev.get("staleness", 0.0)) + 1.0))
            elif kind == "straggler":
                self._propose(worker, kind,
                              1.0 / max(1.0, float(ev.get("factor", 1.0))))
        except (TypeError, ValueError):
            return

    def scale_for(self, worker: Any) -> float:
        """The live multiplicative scale for one worker: the min across
        its unexpired per-kind verdicts (1.0 when unknown, unattributed,
        or fully expired)."""
        if worker is None:
            return 1.0
        wkey = str(worker)
        now = time.monotonic()
        scale = 1.0
        with self._lock:
            for (w, kind), (s, expires) in list(self._scales.items()):
                if now >= expires:
                    del self._scales[(w, kind)]
                elif w == wkey:
                    scale = min(scale, s)
        return scale

    def snapshot(self) -> Dict[str, float]:
        """Live (unexpired) per-worker scales (min across kinds),
        JSON-safe."""
        now = time.monotonic()
        out: Dict[str, float] = {}
        with self._lock:
            for (w, _), (s, exp) in self._scales.items():
                if exp > now:
                    out[w] = min(out.get(w, 1.0), s)
        return out


class _AdaptiveCombiner:
    """Flat-combining commit application for an adaptive hub (ISSUE 10).

    A plain hub serializes commits behind the center lock: while one
    handler applies, the others block, and the fleet experiences the
    queue as added staleness.  With ``adaptive=True`` every commit is
    SUBMITTED here instead: each submitter enqueues its (parts, pull
    clock, worker) and races for the drain lock; the winner grabs
    everything queued at that instant as one BATCH, scales each member
    by its own ``commit_scale(staleness)`` x the per-worker adaptive
    rate, merges the batch pairwise Adasum-style (:func:`adasum_merge`)
    and applies the merged delta as ONE center update.  Losers find
    their entry already applied when they get the lock and return
    immediately — commits that would have queued are combined, and an
    uncontended hub degenerates to batches of one (whose apply is
    bit-identical to the plain path at scale 1).

    Clock semantics: a batch of K commits still advances the commit
    clock and ``num_updates`` by K — staleness bookkeeping, elastic
    denominators and the zero-acked-loss failover bound keep their
    meaning; all members of a batch see the same base clock (they apply
    simultaneously by construction).

    Replication: the batch's merged delta is materialized center-shaped
    and published as ONE ``R`` frame at the batch's final clock BEFORE
    any member is acked, so a standby's center tracks the primary bit
    for bit (its ``num_updates`` counts feed frames, not logical
    commits — the CLOCK remains the failover bound, as before)."""

    def __init__(self, hub: "SocketParameterServer",
                 rate: Optional[AdaptiveRateController] = None):
        self.hub = hub
        self.rate = rate
        self._qlock = threading.Lock()
        self._drain = threading.Lock()
        self._queue: List[Dict[str, Any]] = []
        self.batches_total = 0
        self.merged_total = 0  # commits folded into a larger batch
        self.max_batch = 0

    def commit(self, parts: Sequence[Any], last_pull_clock: int,
               worker: Any = None) -> Dict[str, Any]:
        """Submit one commit; returns its entry once APPLIED (and, when a
        replica is attached, published), carrying the staleness and
        scale it applied with.  The caller's buffers must stay valid
        until return — handler threads block right here, so wire views
        into their receive buffers are safe."""
        entry: Dict[str, Any] = {"parts": list(parts),
                                 "clock": int(last_pull_clock),
                                 "worker": worker, "done": False,
                                 "error": None,
                                 "staleness": 0, "fenced": False,
                                 "fence": 0, "scale": 1.0,
                                 "rate_scale": 1.0, "batch": 1}
        with self._qlock:
            self._queue.append(entry)
        with self._drain:
            # the drain lock's release/acquire orders a predecessor's
            # apply (and its done/error writes) before these reads.
            # Invariant: an entry is either still in the queue (we will
            # grab it below) or was grabbed by a predecessor, which
            # marked it done or error before releasing — so the batch we
            # grab always contains our own entry
            if not entry["done"] and entry["error"] is None:
                with self._qlock:
                    batch, self._queue = self._queue, []
                try:
                    self._apply_batch(batch)
                except BaseException as e:
                    # a failed batch must not strand its members: mark
                    # every un-applied entry so each submitter RAISES
                    # (its connection drops / its worker sees the error
                    # — never a false ack for a commit that was dropped)
                    for en in batch:
                        if not en["done"]:
                            en["error"] = e
                    raise
        err = entry["error"]
        if err is not None:
            raise err
        return entry

    def _apply_batch(self, batch: List[Dict[str, Any]]) -> None:
        hub = self.hub
        telemetry = obs.enabled()
        t0_ns = time.perf_counter_ns() if telemetry else 0
        with hub._lock:
            # leaf phase: the batch's apply under the center lock, named
            # for the one worker it serves or for all of the batch's
            with (_apply_phase(t0_ns, batch=len(batch), clock=hub._clock,
                               worker=(batch[0]["worker"] if len(batch) == 1
                                       else ",".join(str(e["worker"])
                                                     for e in batch)))
                  if telemetry else obs.NULL_SPAN):
                # the replicate decision is made UNDER the center lock, like
                # _apply_commit_locked's: a replica attaching concurrently
                # registers BEFORE snapshotting the center under this same
                # lock, so either its sync includes this batch or active()
                # is already True here and the batch is published — deciding
                # earlier could lose the batch delta to a replica whose sync
                # predates the apply
                feed = hub._feed
                replicate = feed is not None and feed.active()
                clock0 = hub._clock
                fence = hub._clock_fence
                scaled_all: List[List[Any]] = []
                for entry in batch:
                    lpc = entry["clock"]
                    if lpc < fence:
                        lpc = fence
                        entry["fenced"] = True
                        entry["fence"] = fence
                    staleness = clock0 - lpc
                    wscale = (self.rate.scale_for(entry["worker"])
                              if self.rate is not None else 1.0)
                    scale = float(hub.commit_scale(staleness)) * wscale
                    entry["staleness"] = staleness
                    entry["scale"] = scale
                    entry["rate_scale"] = wscale
                    entry["batch"] = len(batch)
                    scaled_all.append(
                        _scale_parts(entry["parts"], np.float32(scale)))
                    if telemetry:
                        hub._touch_rows_locked(
                            (i, p[0]) for i, p in enumerate(entry["parts"])
                            if isinstance(p, tuple))
                if len(scaled_all) > 1 and not _mixed_repr(scaled_all):
                    applied = [adasum_merge(scaled_all)]
                else:
                    # batch of one — or the RARE mixed dense/sparse batch,
                    # applied sequentially (plain queue-order semantics):
                    # merging it would densify sparse sides under this lock
                    applied = scaled_all
                if replicate and len(applied) > 1:
                    # the RARE sequential (mixed dense/sparse) batch keeps the
                    # pre-ISSUE-15 replica contract: ONE center-shaped delta
                    # for the whole batch, applied exactly as published, so
                    # primary and replica perform IDENTICAL float additions
                    dense = [np.zeros_like(c) for c in hub.center]
                    for parts in applied:
                        for full, p in zip(dense, parts):
                            if isinstance(p, tuple):
                                ids, g = p
                                if ids.size:
                                    full[ids] += g
                            else:
                                full += p
                    for c, full in zip(hub.center, dense):
                        c += full
                    publish_parts = dense
                else:
                    # ONE commit (uncontended, or the whole batch Adasum-
                    # merged): apply in its native representation — sparse
                    # leaves touch only their merged ROW UNION — and hand the
                    # same parts to the feed, which frames them sparse for
                    # capable replicas (cost ∝ touched rows) and densifies
                    # only for legacy ones (_scale_parts/adasum own storage)
                    publish_parts = applied[0] if replicate else None
                    for parts in applied:
                        for c, p in zip(hub.center, parts):
                            if isinstance(p, tuple):
                                ids, g = p
                                if ids.size:
                                    c[ids] += g
                            else:
                                c += p
                hub.num_updates += len(batch)
                hub._clock += len(batch)
                commit_clock = hub._clock
        if replicate:
            feed.publish(commit_clock, publish_parts)
        size = len(batch)
        self.batches_total += 1
        if size > self.max_batch:
            self.max_batch = size
        if size > 1:
            self.merged_total += size - 1
        if telemetry:
            obs.gauge("ps_merge_queue_depth", **hub._mlabels).set(size)
            obs.histogram("ps.merge_batch", **hub._mlabels).observe(size)
            if size > 1:
                obs.counter("ps_merged_commits_total",
                            **hub._mlabels).inc(size - 1)
            fenced = sum(1 for e in batch if e["fenced"])
            if fenced:
                obs.counter("ps_fenced_commits_total",
                            **hub._mlabels).inc(fenced)
            obs.TRACER.record_span("ps.merge", t0_ns,
                                   time.perf_counter_ns(), batch=size,
                                   **hub._shard_attrs)
        # live health plane: applied scale joins each worker's series and
        # the batch size joins the hub pseudo-worker's — distkeras-top's
        # SCALE / MQ columns and fleet_report["adaptive"] read these
        for entry in batch:
            if entry["rate_scale"] < 1.0:
                if telemetry:
                    obs.counter("ps_rate_scaled_commits_total",
                                **hub._mlabels).inc()
            if entry["worker"] is not None:
                hub._observe_health(entry["worker"], "adaptive_scale",
                                    entry["rate_scale"])
        hub._observe_health(
            f"hub{'' if hub.shard_id is None else hub.shard_id}",
            "merge_queue_depth", size, any_shard=True)
        for entry in batch:
            entry["done"] = True


#: wire actions whose frame body is a commit (ps.recv_commit times them)
_COMMIT_ACTIONS = frozenset((net.ACTION_COMMIT, net.ACTION_QCOMMIT,
                             net.ACTION_SPARSE_COMMIT,
                             net.ACTION_SPARSE_QCOMMIT))


class JobAdmissionError(net.ProtocolError):
    """The hub rejected a client's job-scoped announce (ISSUE 19): the
    shard's job slots or memory/throughput budget are exhausted.  A
    distinct type so callers can tell an admission verdict from a torn
    stream; it still subclasses ``ProtocolError``, so a mid-run
    re-announce rejection (reconnect landing on a full hub) rides the
    normal retry/rotate machinery instead of escaping uncaught."""

    def __init__(self, job: str, reason: str):
        super().__init__(f"job {job!r} admission rejected: {reason}")
        self.job = job
        self.reason = reason


class _JobState:
    """One admitted non-default job namespace (ISSUE 19): a private copy
    of the center (seeded from the hub's center at admission time) with
    its own commit clock.  Every field is guarded by the owning hub's
    center lock — job commits take the SAME lock as default-job commits,
    so fairness is lock-scheduling fairness, and a job's state can never
    tear against an admission or a snapshot cut.

    Deliberately OUTSIDE the adaptive combiner, replication feed and
    snapshot plane: isolation is the point of the namespace — one job's
    machinery must not move another job's latency — and HA/persistence
    for secondary jobs is future work (documented in MIGRATION.md)."""

    __slots__ = ("job", "center", "clock", "num_updates")

    def __init__(self, job: str, center: Sequence[np.ndarray]):
        self.job = job
        self.center = [np.array(w, dtype=np.float32) for w in center]
        self.clock = 0
        self.num_updates = 0


class SocketParameterServer:
    """Hub-and-spoke PS: one handler thread per worker connection, one lock
    around the center variable — the reference's concurrency model
    (SURVEY §3.4), minus pickle and minus the GIL-heavy payload decode.

    Telemetry (``distkeras_tpu.observability``, off by default): pull/
    commit counts and payload bytes (``ps_pulls_total``,
    ``ps_commits_total``, ``ps_pull_bytes_total``,
    ``ps_commit_bytes_total``) and the per-connection staleness gauge
    ``ps_staleness{conn=N}`` (N is the hub's accept ordinal modulo 256 —
    workers carry no identity on the wire, and the wrap bounds label
    cardinality under elastic connection churn) — the commit clock the paper lineage's
    staleness analysis (arXiv:1611.04581) is about, now a live signal
    instead of a number internal to DynSGD's scaling rule.  Instruments
    are looked up per RPC while telemetry is on (a dict get next to a
    socket exchange) so a mid-run ``obs.reset()`` cannot orphan them, and
    nothing is registered at all while telemetry is off."""

    # reconnect-storm backpressure tuning (adaptive hubs, ISSUE 10):
    # >= STORM_HELLOS reconnect hellos (action G) inside STORM_WINDOW_S
    # arm shedding for STORM_SHED_S; each hello during shedding is handed
    # the next RETRY_BASE_MS slot, capped at RETRY_CAP_MS — the herd is
    # spread over time instead of absorbed at once.  Instance attributes,
    # so tests and deployments can retune without subclassing
    STORM_HELLOS = 3
    STORM_WINDOW_S = 5.0
    STORM_SHED_S = 3.0
    RETRY_BASE_MS = 50
    RETRY_CAP_MS = 2000

    def __init__(self, weights: Sequence[np.ndarray], host: str = "0.0.0.0", port: int = 0,
                 idle_timeout: Optional[float] = 300.0,
                 snapshot_dir: Optional[str] = None,
                 snapshot_interval: float = 30.0,
                 snapshot_keep: int = 3,
                 restore: bool = False,
                 shard_id: Optional[int] = None,
                 replica_of: Optional[Tuple[str, int]] = None,
                 replica_feed_retries: int = 3,
                 replica_feed_backoff: float = 0.2,
                 sparse_leaves: Sequence[int] = (),
                 adaptive: bool = False,
                 shm_dir: Optional[str] = None,
                 recv_batch_depth: int = 0,
                 max_jobs: int = 4,
                 job_budget_bytes: Optional[int] = None):
        self.center: List[np.ndarray] = [np.array(w, dtype=np.float32) for w in weights]
        self.host = host
        self.port = int(port)
        self.num_updates = 0
        # sharded-hub identity (ISSUE 6): when this hub serves one shard of
        # a partitioned center, every span and metric it emits carries the
        # shard label so a slow shard is as nameable as a slow worker —
        # and so per-shard counters stay separate series that aggregators
        # can sum (bytes) or max (logical commits) without double-counting.
        # None (the default, and the whole num_shards=1 path) emits the
        # exact pre-sharding unlabeled series
        self.shard_id = None if shard_id is None else int(shard_id)
        self._shard_attrs = ({} if shard_id is None
                             else {"shard": int(shard_id)})
        self._mlabels = ({} if shard_id is None
                         else {"shard": str(int(shard_id))})
        self._clock = 0  # total commits applied (DynSGD's global clock)
        # restore-time fence: connections and inproc clients born before a
        # hub restart carry pull clocks from the PREVIOUS incarnation;
        # clamping them here re-bases their staleness at the restart point
        # instead of letting a pre-restart clock fake a huge (DynSGD) or
        # negative staleness
        self._clock_fence = 0
        self._lock = threading.Lock()
        # the dense apply's block scratch, used only under ``_lock``
        self._apply_scratch = np.empty(_APPLY_BLOCK, np.float32)
        self._listener: Optional[socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._handlers: List[threading.Thread] = []
        self._conns: List[socket.socket] = []  # live worker connections
        self._conn_lock = threading.Lock()
        self._running = False
        self._center_bytes = sum(w.nbytes for w in self.center)
        # row-sparse embedding tables (ISSUE 9): leaf indices whose PS
        # traffic is row-sparse — pulled by row set (action S/V) and
        # committed as (row_ids, row_grads) pairs (action U/X) under the
        # SAME staleness clock and commit_scale rules as dense commits.
        # Empty (the default) keeps every path byte-for-byte pre-sparse;
        # a sparse-capable hub still serves the full dense P/C/Q exchange
        # too (initial syncs, control clients, un-upgraded workers)
        self.sparse_leaves = tuple(sorted({int(i) for i in sparse_leaves}))
        for i in self.sparse_leaves:
            if not 0 <= i < len(self.center):
                raise ValueError(f"sparse leaf index {i} out of range for "
                                 f"{len(self.center)} center leaves")
            if self.center[i].ndim != 2:
                raise ValueError(
                    f"sparse leaf {i} must be a [rows, dim] table, got "
                    f"shape {self.center[i].shape}")
        self._sparse_set = frozenset(self.sparse_leaves)
        # hyperscale row-touch telemetry (ISSUE 15): one exponentially-
        # decayed per-row touch counter array per sparse table, folded on
        # every sparse pull/commit UNDER the center lock (the ids are
        # already validated there) while telemetry is on.  Every
        # TOUCH_DECAY_EVERY folds the counters halve; the count of rows
        # still at or above TOUCH_HOT_MIN is then a decayed estimate of
        # the live hot set — the ``ps.sparse_hot_rows{table=}`` gauge an
        # operator sizes ``sparse_cache_rows`` from.  Cost when off: one
        # enabled() check per sparse request; memory: 4 bytes/row/table
        # (dim/4 of the table the hub already holds)
        self._sparse_touch: Dict[int, np.ndarray] = {
            i: np.zeros(self.center[i].shape[0], np.float32)
            for i in self.sparse_leaves}
        self._touch_folds = 0
        # full flat-frame size of a pull reply / f32 commit (header, action,
        # count, per-tensor prefixes, payload) — the socket-buffer hint.
        # A shard hub computes this from ITS center subset, so per-shard
        # connections get per-shard-sized kernel buffers
        self._frame_bytes = net.tensor_frame_len(self.center)
        # largest VALID payload a peer may declare — the handler receives
        # against this bound, so a garbage length prefix is a typed
        # ProtocolError instead of a 16 GiB bytearray.  The accounting is
        # SHARED with the C++ hub (net.max_request_payload), so both hub
        # implementations reject the exact same oversized prefixes
        self._max_payload = net.max_request_payload(self.center,
                                                    self.sparse_leaves)
        # zero-copy shm transport (ISSUE 18): a directory to create ring
        # files in arms the action-Z attach handshake — same-host clients
        # constructed with shm=True move their framed byte stream through
        # a pair of mmap SPSC rings instead of the kernel socket stack.
        # None (the default) declines every Z request, byte-identical to
        # a pre-Z hub from the client's point of view
        self.shm_dir = None if shm_dir is None else str(shm_dir)
        self._shm_seq = 0  # ring-file ordinal (under _conn_lock)
        # batched receive (ISSUE 18): >0 sizes a per-connection
        # BatchedReceiver to that many frames — a commit storm is drained
        # with one syscall per batch (recvmmsg when libc has it) instead
        # of one per frame.  0 (the default) keeps the per-frame
        # recv_frame_into path untouched
        self.recv_batch_depth = max(0, int(recv_batch_depth))
        # multi-job service (ISSUE 19): a session that puts a ``job_ns``
        # key on its T announce gets an admission-controlled private
        # center namespace (dense P/C/Q only).  Admission projects the
        # shard's memory cost — one center copy per job plus the decayed
        # hot-row working set from the PR-14 touch counters — against
        # ``job_budget_bytes`` (default 4x the center) and caps the job
        # count at ``max_jobs``.  A session that never announces a
        # job_ns rides the default namespace: the hub's own center,
        # byte-for-byte the pre-multi-job exchange
        self.max_jobs = max(0, int(max_jobs))
        self.job_budget_bytes = (4 * max(1, self._center_bytes)
                                 if job_budget_bytes is None
                                 else int(job_budget_bytes))
        self._jobs: Dict[str, _JobState] = {}  # under _lock
        self.jobs_admitted = 0
        self.jobs_rejected = 0
        self._conn_seq = 0  # connection ordinal -> staleness gauge label
        # half-open liveness: a peer that dies without FIN used to park its
        # handler in recv() forever.  With idle_timeout set, a connection
        # silent for that long (no pull/commit/heartbeat) is evicted
        self.idle_timeout = None if idle_timeout is None else float(idle_timeout)
        # live-worker membership (elastic denominators): a connection joins
        # on its first commit — pull-only peers (snapshot readers, final
        # center fetches) never count — is touched by every action, and
        # leaves on disconnect or idle eviction
        self._members: Dict[int, float] = {}
        self._member_lock = threading.Lock()
        self._member_seq = 0
        # hot-standby HA (ISSUE 7).  Primary side: the replication feed is
        # created lazily when a replica handshakes (action R), so an
        # unreplicated hub's commit path is byte-for-byte the pre-HA one.
        # Replica side: replica_of=(host, port) starts this hub in STANDBY
        # — it binds and serves pulls like any hub (clients can fail over
        # to it at any time) while a feed thread tracks the primary's
        # center; it PROMOTES itself (arming the PR-4 clock fence at its
        # current clock) when the feed is lost past the retry budget, or
        # immediately when a failed-over worker commits to it
        self._feed: Optional[ReplicationFeed] = None
        self._feed_lock = threading.Lock()
        # live health plane (ISSUE 8): bound lazily on the FIRST action-M
        # report — a hub no worker reports to never imports the health
        # module, and the commit path's only cost is one `is None` check
        self._health: Optional[Any] = None
        self._health_monitor: Optional[Any] = None
        self._health_mod: Optional[Any] = None  # cached module ref (peek path)
        # telemetry-driven adaptive aggregation (ISSUE 10), OFF by
        # default — the off path is byte-identical to the pre-adaptive
        # hub (no combiner, no health subscription, no new wire frames).
        # On: queued commits merge Adasum-style through the combiner,
        # per-worker commit scales follow live health events, and
        # reconnect hellos (action G) are answered with retry-after
        # hints while a reconnect storm is live
        self.adaptive = bool(adaptive)
        self._rate: Optional[AdaptiveRateController] = None
        self._combiner: Optional[_AdaptiveCombiner] = None
        self._health_unsub: Optional[Any] = None
        self._bp_lock = threading.Lock()
        self._hello_times: Deque[float] = deque()
        self._storm_until = 0.0
        self._retry_seq = 0
        self.backpressure_hints = 0  # nonzero hints issued (drills read it)
        if self.adaptive:
            self._rate = AdaptiveRateController()
            self._combiner = _AdaptiveCombiner(self, self._rate)
        self.replica_of = (None if replica_of is None
                           else (str(replica_of[0]), int(replica_of[1])))
        self.replica_feed_retries = int(replica_feed_retries)
        self.replica_feed_backoff = float(replica_feed_backoff)
        self._standby = self.replica_of is not None
        self.promoted = False
        # the replica's clock AT promotion — the number the zero
        # acked-commit-loss bound is checked against (reading num_updates
        # later is vacuous: post-failover commits inflate it)
        self.promoted_at_clock: Optional[int] = None
        self._synced = threading.Event()  # set on the first applied REPL_SYNC
        self._replica_stop = threading.Event()
        self._replica_thread: Optional[threading.Thread] = None
        self._replica_sock: Optional[socket.socket] = None
        self.snapshotter: Optional[HubSnapshotter] = None
        self._restore = bool(restore)
        if restore and snapshot_dir is None:
            # silently serving FRESH weights after an operator asked for a
            # restore would discard a job's progress without a sound
            raise ValueError("restore=True requires snapshot_dir")
        if snapshot_dir is not None:
            self.snapshotter = HubSnapshotter(self, snapshot_dir,
                                              interval=snapshot_interval,
                                              keep=snapshot_keep)

    # -- lifecycle (reference: ParameterServer.start/stop) ---------------------
    def start(self) -> None:
        if self.adaptive:
            # bind the health plane eagerly and SUBSCRIBE (ISSUE 10): the
            # per-commit staleness folds need a collector from commit
            # one, and the rate controller / storm shedding must hear
            # detector events the moment they fire — push, not polling
            from distkeras_tpu.observability import health as _health

            self._health_mod = _health
            if self._health is None:
                self._health = _health.collector()
            if self._health_monitor is None:
                self._health_monitor = _health.monitor()
            self._health_unsub = self._health_monitor.subscribe(
                self._on_health_event)
        if self._restore and self.snapshotter is not None:
            # load BEFORE binding: the first pull any worker lands must
            # already observe the restored center and fenced clock
            if not self.snapshotter.restore_latest():
                if self.snapshotter.checkpointer.all_steps():
                    # progress exists on disk but none of it is readable —
                    # binding anyway would hand workers a fresh center and
                    # silently discard the job; that needs a human
                    raise RuntimeError(
                        f"restore requested: snapshots exist in "
                        f"{self.snapshotter.checkpointer.directory} but none "
                        f"is readable (see warnings)")
                # no snapshot yet (first boot under a restart-with-restore
                # supervisor loop): serving initial weights is correct,
                # but say so
                warnings.warn("restore requested but no snapshot exists "
                              "yet; serving initial weights")
        if self.shm_dir is not None:
            os.makedirs(self.shm_dir, exist_ok=True)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((self.host, self.port))
        self.port = self._listener.getsockname()[1]
        self._listener.listen(128)
        self._running = True
        self._accept_thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._accept_thread.start()
        if self.replica_of is not None:
            self._replica_stop.clear()
            self._replica_thread = threading.Thread(target=self._replica_loop,
                                                    daemon=True)
            self._replica_thread.start()
        if self.snapshotter is not None:
            self.snapshotter.start()

    def stop(self) -> None:
        self._shutdown(final_snapshot=True)

    def kill(self) -> None:
        """Crash-like teardown for chaos tests and recovery drills: sever
        everything WITHOUT a final snapshot — recovery must come from the
        last periodic snapshot, exactly as after a SIGKILL.  (From the
        workers' side this is indistinguishable from a killed process:
        connections reset mid-exchange, port goes dark.)"""
        self._shutdown(final_snapshot=False)

    def _shutdown(self, final_snapshot: bool) -> None:
        self._running = False
        if self._health_unsub is not None and self._health_monitor is not None:
            # a stopped hub must not keep reacting to a later run's events
            self._health_monitor.unsubscribe(self._health_unsub)
            self._health_unsub = None
        # stop tracking the primary BEFORE severing anything: a teardown
        # must never race the feed thread into a promotion
        self._replica_stop.set()
        sock = self._replica_sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        if self.snapshotter is not None:
            # on stop(): final snapshot while the center is still intact
            # (commits may still be landing — snapshot_state copies under
            # the lock); on kill(): just halt the periodic thread
            self.snapshotter.stop(final_snapshot=final_snapshot)
        if self._listener is not None:
            try:
                # shutdown BEFORE close: close() alone does not wake a
                # thread blocked in accept() on Linux, so every stop()
                # silently burned the full join timeout and leaked the
                # accept thread.  shutdown() fails the pending accept
                # immediately (same idiom as the C++ hub's stop())
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # not listening / already gone; close still applies
            try:
                self._listener.close()
            except OSError:
                pass
        # sever live worker connections (matching the C++ hub): a blocked
        # handler wakes with EOF and exits, and the worker's next receive
        # surfaces a clean ConnectionError instead of hanging on a hub
        # that will never reply — the fault-injection behavior
        # tests/test_runtime.py pins
        with self._conn_lock:
            for conn in list(self._conns):
                try:
                    conn.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=5)
        if self._replica_thread is not None:
            self._replica_thread.join(timeout=5)
            self._replica_thread = None
        for t in self._handlers:
            t.join(timeout=5)

    def get_weights(self) -> List[np.ndarray]:
        with self._lock:
            return [w.copy() for w in self.center]

    # -- durability (hub snapshots + clock fence) ------------------------------
    def snapshot_state(self) -> Tuple[List[np.ndarray], Dict[str, Any]]:
        """One atomic view of everything a restarted hub needs: (center
        copy, state dict).  The state rides the snapshot's JSON metadata,
        so it must stay JSON-typed."""
        with self._lock:
            return self._snapshot_state_locked()

    def _snapshot_state_locked(self) -> Tuple[List[np.ndarray], Dict[str, Any]]:
        """:meth:`snapshot_state` body, caller holds the center lock — the
        coordinated snapshot barrier holds EVERY shard's lock at once and
        reads each shard through this, so the N per-shard snapshots are
        one causal cut (no commit can land anywhere between the reads)."""
        center = [w.copy() for w in self.center]
        state = {"clock": int(self._clock),
                 "num_updates": int(self.num_updates)}
        state.update(self._algo_state())
        return center, state

    def _algo_state(self) -> Dict[str, Any]:
        """Subclass hook: algorithm state to persist alongside the center
        (called under the center lock)."""
        return {}

    def restore_state(self, center: Sequence[np.ndarray],
                      state: Dict[str, Any]) -> None:
        """Load a snapshot: center in place (buffer identity preserved — the
        frame-size accounting and any live codecs stay valid), clock
        resumed, and the clock FENCE armed at the restored clock so any
        pre-restart pull clock presented to :meth:`commit_direct` is
        clamped to the restart point."""
        if len(center) != len(self.center):
            raise ValueError(f"snapshot has {len(center)} tensors, center has "
                             f"{len(self.center)}")
        with self._lock:
            for c, w in zip(self.center, center):
                c[...] = np.asarray(w, np.float32).reshape(c.shape)
            self._clock = int(state.get("clock", 0))
            self._clock_fence = self._clock
            self.num_updates = int(state.get("num_updates", 0))

    # -- hot standby (replica side) --------------------------------------------
    def is_standby(self) -> bool:
        """True while this hub is a replica tracking its primary (not yet
        promoted): its center is feed-driven and commits will trigger
        promotion."""
        return self._standby

    def _standby_commit_gate(self) -> None:
        """Split-brain guard: a commit arriving while the feed socket is
        still CONNECTED must not flip the hub — one misdirected worker
        landing on the standby while the other workers keep committing to
        the healthy primary would cause permanent divergence.  The commit
        is refused, and the connected feed socket is severed as a probe: a
        live primary resyncs and the hub stays standby, a silently dead
        one (host loss, no FIN) now fails the feed loop's reconnects and
        promotes within its budget — after which the worker's retried
        commit (under its own reconnect budget) lands.

        When the feed is already DOWN (``_replica_sock is None`` — the
        loop observed a loss and is between reconnect attempts) the
        primary is presumed dead and the gate returns: the caller
        promotes immediately, fence armed before the commit's staleness
        is computed, instead of making the failed-over worker wait out
        ``replica_feed_retries``.  Called with ``_synced`` already
        checked."""
        sock = self._replica_sock
        if sock is None:
            return  # feed lost: caller promotes (first failed-over commit)
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        raise net.ProtocolError(
            "commit into a standby refused (not promoted yet; verifying "
            "the primary — retry)")

    def wait_synced(self, timeout: Optional[float] = None) -> bool:
        """Block until this replica has applied its first full sync from
        the primary (True), or ``timeout`` elapsed (False).  Callers that
        are about to COMMIT into a freshly-started standby — e.g. a
        trainer whose own hub is a ``replica_of`` — must wait here first:
        a commit into an unsynced standby promotes it over its fresh init
        weights, silently discarding the primary's state."""
        return self._synced.wait(timeout)

    def promote(self, reason: str = "manual") -> bool:
        """Promote a standby replica to primary: arm the clock fence at the
        current (replicated) clock — so pre-failover pull clocks presented
        after the switch are clamped to the promotion point, exactly the
        PR-4 restore semantics — and stop applying feed frames forever.
        Idempotent; returns True if this call performed the promotion."""
        with self._lock:
            if not self._standby or self.promoted:
                return False
            self.promoted = True
            self._standby = False
            self._clock_fence = self._clock
            clock = self._clock
            self.promoted_at_clock = clock
        t0_ns = time.perf_counter_ns()
        warnings.warn(f"replica hub promoting to primary at clock {clock}: "
                      f"{reason}")
        # the feed thread must stop (and never re-apply a late frame —
        # promoted is checked under the lock before every apply)
        self._replica_stop.set()
        sock = self._replica_sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        if obs.enabled():
            obs.counter("ps_promotions_total", **self._mlabels).inc()
            obs.TRACER.record_span("ps.promote", t0_ns,
                                   time.perf_counter_ns(),
                                   clock=clock, reason=reason,
                                   **self._shard_attrs)
        # live health plane (ISSUE 8): a promotion IS a failover event —
        # record it through the process monitor so distkeras-top / the
        # punchcard health pull see it DURING the run, naming the promoted
        # standby.  Promotion is rare (never the hot path) and must not be
        # taken down by a health-pipeline hiccup
        try:
            from distkeras_tpu.observability import health as _health

            _health.monitor().emit(
                "failover", "critical", shard=self.shard_id,
                dedup=f"promote:{self.host}:{self.port}",
                promoted=f"{self.host}:{self.port}", clock=clock,
                reason=reason)
        except Exception:
            pass
        return True

    def _apply_repl_frame(self, clock: int, kind: int, blobs) -> None:
        """Apply one replication frame of ANY kind under the center lock —
        the sparse-capable standby's receive leg.  ``blobs`` are the
        frame's tensor blobs past the header (views into the feed's
        receive buffer, consumed before the next frame lands).  A
        REPL_SPARSE frame carries the U-commit layout: full f32 delta
        blobs for dense leaves, ``(ids, scaled rows)`` blob pairs for
        sparse leaves — applied ``center[ids] += rows`` behind the same
        clock fence semantics as a dense delta.  Malformed layouts raise
        ``ProtocolError`` (feed loss; the loop reconnects/promotes under
        its budget)."""
        with self._lock:
            if self.promoted:
                return  # late frame post-promotion: never lands
            if kind in (net.REPL_SYNC, net.REPL_DELTA):
                if len(blobs) != len(self.center):
                    raise net.ProtocolError(
                        f"replication frame has {len(blobs)} blobs, center "
                        f"has {len(self.center)}")
                for c, b in zip(self.center, blobs):
                    arr = np.frombuffer(b, np.float32)
                    if arr.size != c.size:
                        raise net.ProtocolError(
                            f"replication blob of {arr.size} values does "
                            f"not match its leaf ({c.size})")
                    if kind == net.REPL_SYNC:
                        c[...] = arr.reshape(c.shape)
                    else:
                        c += arr.reshape(c.shape)
                if kind == net.REPL_SYNC:
                    self._clock = clock
                    self.num_updates = clock
                    self._synced.set()
                else:
                    self._clock = max(self._clock, clock)
                    self.num_updates += 1
            elif kind == net.REPL_SPARSE:
                expected = len(self.center) + len(self.sparse_leaves)
                if len(blobs) != expected:
                    raise net.ProtocolError(
                        f"sparse replication frame has {len(blobs)} blobs, "
                        f"expected {expected}")
                it = iter(blobs)
                for i, c in enumerate(self.center):
                    if i in self._sparse_set:
                        ids = self._check_row_ids(
                            np.frombuffer(next(it), net.ROW_ID_DTYPE), i)
                        rows = np.frombuffer(next(it), np.float32)
                        if rows.size != ids.size * c.shape[1]:
                            raise net.ProtocolError(
                                f"sparse replication leaf {i}: {rows.size} "
                                f"values for {ids.size} rows of dim "
                                f"{c.shape[1]}")
                        if ids.size:
                            c[ids] += rows.reshape(ids.size, c.shape[1])
                    else:
                        arr = np.frombuffer(next(it), np.float32)
                        if arr.size != c.size:
                            raise net.ProtocolError(
                                f"replication blob of {arr.size} values "
                                f"does not match its leaf ({c.size})")
                        c += arr.reshape(c.shape)
                self._clock = max(self._clock, clock)
                self.num_updates += 1
            else:
                raise net.ProtocolError(f"unknown replication kind {kind}")

    def _replica_loop(self) -> None:
        """Track the primary: connect, handshake (action R hello), apply the
        full sync then every streamed delta under the center lock.  On feed
        loss, retry within ``replica_feed_retries`` (exponential backoff);
        once the budget is gone the primary is presumed dead and the
        replica promotes itself.  A worker commit arriving first wins the
        promotion race instead (see the commit paths)."""
        host, port = self.replica_of
        codec = net.FlatFrameCodec(net.repl_frame_templates(self.center))
        hdr = np.empty(9, np.uint8)
        bufs = [np.empty(c.shape, np.float32) for c in self.center]
        # a sparse-capable standby (this hub serves row-sparse tables)
        # announces REPL_CAP_SPARSE and receives through the generic
        # variable-frame path: the stream then mixes fixed-size
        # SYNC/DELTA frames with row-delta REPL_SPARSE frames whose blob
        # sizes vary per commit.  A dense hub keeps the pre-ISSUE-15
        # fixed-codec loop byte for byte
        sparse_feed = bool(self.sparse_leaves)
        caps = net.REPL_CAP_SPARSE if sparse_feed else 0
        # largest valid feed payload: a full sync frame plus, for sparse
        # frames, one worst-case id blob per table
        feed_limit = codec.payload_len + sum(
            8 + 8 * self.center[i].shape[0] for i in self.sparse_leaves)
        rx = bytearray(4096) if sparse_feed else None
        failures = 0
        warned_unsynced = False
        while not self._replica_stop.is_set():
            try:
                # a short connect timeout: _shutdown cannot interrupt a
                # thread blocked INSIDE connect (the socket object does
                # not exist yet), so this bounds how long a stopping
                # standby's feed thread can outlive it
                sock = net.connect(host, port, timeout=5.0,
                                   payload_hint=codec.frame_len)
                # the connect timeout must NOT linger as a recv timeout:
                # the feed is silent between commits (no heartbeat), and a
                # 30 s idle primary would otherwise read as feed loss —
                # tearing down and FULL-RESYNCING the center in a loop
                # while both hubs are healthy.  Block indefinitely instead;
                # a dead primary still surfaces as EOF/RST, teardown wakes
                # the recv via shutdown(), and a silent host death is
                # covered by commit-triggered promotion
                sock.settimeout(None)
            except OSError:
                sock = None
            if sock is not None and self._replica_stop.is_set():
                # teardown landed while connect was in flight: exit WITHOUT
                # the hello — a zombie handshake would trigger a spurious
                # full-center sync on whatever now owns that port
                try:
                    sock.close()
                except OSError:
                    pass
                return
            if sock is not None:
                self._replica_sock = sock
                try:
                    net.send_frame(sock, net.encode_repl_hello(
                        self._clock, capabilities=caps))
                    while not self._replica_stop.is_set():
                        if sparse_feed:
                            payload = net.recv_frame_into(sock, rx,
                                                          limit=feed_limit)
                            action, blobs = net.decode_tensor_views(payload)
                            if action != net.ACTION_REPL:
                                raise net.ProtocolError(
                                    f"replica feed expected R, got "
                                    f"{action!r}")
                            clock, kind = net.decode_repl_header(blobs[0])
                            self._apply_repl_frame(clock, kind, blobs[1:])
                            if self.promoted:
                                return  # late frame post-promotion
                            failures = 0
                            if obs.enabled():
                                obs.counter("ps_replica_frames_total",
                                            **self._mlabels).inc()
                                obs.gauge("ps_replica_clock",
                                          **self._mlabels).set(clock)
                            continue
                        action = codec.recv_into(sock, [hdr] + bufs)
                        if action != net.ACTION_REPL:
                            raise net.ProtocolError(
                                f"replica feed expected R, got {action!r}")
                        clock, kind = net.decode_repl_header(hdr)
                        with self._lock:
                            if self.promoted:
                                return  # late frame post-promotion: never lands
                            if kind == net.REPL_SYNC:
                                for c, b in zip(self.center, bufs):
                                    c[...] = b
                                self._clock = clock
                                self.num_updates = clock
                                self._synced.set()
                            elif kind == net.REPL_DELTA:
                                for c, b in zip(self.center, bufs):
                                    c += b
                                self._clock = max(self._clock, clock)
                                self.num_updates += 1
                            else:
                                raise net.ProtocolError(
                                    f"unknown replication kind {kind}")
                        failures = 0  # a live stream resets the loss budget
                        if obs.enabled():
                            obs.counter("ps_replica_frames_total",
                                        **self._mlabels).inc()
                            obs.gauge("ps_replica_clock",
                                      **self._mlabels).set(clock)
                except (OSError, ValueError, ConnectionError):
                    pass  # feed lost (or teardown severed it): fall through
                finally:
                    self._replica_sock = None
                    try:
                        sock.close()
                    except OSError:
                        pass
            if self._replica_stop.is_set() or self.promoted:
                return
            failures += 1
            if failures > self.replica_feed_retries:
                if self._synced.is_set():
                    self.promote(reason=f"primary {host}:{port} lost "
                                        f"({failures - 1} reconnect "
                                        f"attempts exhausted)")
                    return
                # never synced: there is nothing to take over — promoting
                # would serve fresh init weights as if they were the
                # job's.  Keep retrying (capped backoff) until the primary
                # appears; operators see one warning, not a storm
                if not warned_unsynced:
                    warnings.warn(
                        f"replica feed to {host}:{port} failing before any "
                        f"sync arrived; retrying until the primary appears "
                        f"(a never-synced standby does not promote)")
                    warned_unsynced = True
                failures = self.replica_feed_retries  # cap the backoff
            self._replica_stop.wait(
                self.replica_feed_backoff * (2.0 ** (failures - 1)))

    # -- elastic membership ----------------------------------------------------
    def _member_join(self, token: int) -> None:
        with self._member_lock:
            self._members[token] = time.monotonic()
        if obs.enabled():
            obs.gauge("ps_live_workers",
                      **self._mlabels).set(self.live_workers())

    def _member_touch(self, token: int) -> None:
        with self._member_lock:
            if token in self._members:
                self._members[token] = time.monotonic()

    def _member_leave(self, token: int) -> None:
        with self._member_lock:
            self._members.pop(token, None)
        if obs.enabled():
            obs.gauge("ps_live_workers",
                      **self._mlabels).set(self.live_workers())

    def live_workers(self) -> int:
        """Workers currently believed alive: joined (committed at least
        once), not departed, and — when ``idle_timeout`` is set — heard
        from within it (heartbeat-lapse detection for peers whose
        connection is technically open but silent)."""
        now = time.monotonic()
        with self._member_lock:
            if self.idle_timeout is None:
                return len(self._members)
            return sum(1 for last in self._members.values()
                       if now - last <= self.idle_timeout)

    # -- live health plane (ISSUE 8) -------------------------------------------
    def _ingest_health(self, report: Dict[str, Any]) -> None:
        """Fold one worker health report into the process-default
        :class:`~distkeras_tpu.observability.health.HealthCollector` and
        give the detectors a (rate-limited) chance to run.  Lazy binding:
        the health module only loads once a report actually arrives."""
        # bind collector and monitor INDEPENDENTLY: _observe_health's
        # any_shard path may have pre-bound _health (joining an active
        # plane) without a monitor — a combined check would then deref
        # None on the first wire report and tear down the connection
        if self._health is None or self._health_monitor is None:
            from distkeras_tpu.observability import health as _health

            if self._health is None:
                self._health = _health.collector()
            if self._health_monitor is None:
                self._health_monitor = _health.monitor()
        self._health.ingest(report, shard=self.shard_id)
        self._health_monitor.maybe_check()

    def _observe_health(self, worker: Any, metric: str, value: float,
                        any_shard: bool = False) -> None:
        """Hub-side signal fold (per-commit staleness, replication lag)
        into the SAME per-worker series the wire reports feed.  By
        default shard-0 only under a sharded hub — one logical commit
        lands on every shard, and the fleet view must count it once (the
        ``fleet_report`` convention); ``any_shard`` is for series whose
        KEY already carries the shard (the hub's own pseudo-worker)."""
        if worker is None:
            return
        if not any_shard and self.shard_id is not None and self.shard_id != 0:
            return
        if self._health is None:
            if not any_shard:
                return
            # wire reports only ever land on shard 0 (and on the facade's
            # shard-0 route), so a shard-N hub's _ingest_health never runs
            # — its own pseudo-worker series (replication lag) must join
            # an ALREADY-active plane here.  active_collector never
            # creates and is a lock-free global peek; the module ref is
            # cached on self so the plane-off cost per publish is two
            # attribute loads and a None check
            if self._health_mod is None:
                from distkeras_tpu.observability import health as _health

                self._health_mod = _health
            bound = self._health_mod.active_collector()
            if bound is None:
                return
            self._health = bound
        self._health.observe(str(worker), metric, float(value),
                             shard=self.shard_id)

    # -- adaptive reaction (ISSUE 10) ------------------------------------------
    def _on_health_event(self, event: Any) -> None:
        """:meth:`HealthMonitor.subscribe` callback (adaptive hubs only):
        staleness/straggler events drive the per-worker rate controller,
        and storm events arm reconnect backpressure — so a storm detected
        from worker health REPORTS sheds load even before this hub has
        seen a single reconnect hello itself."""
        if getattr(event, "kind", None) in ("reconnect_storm",
                                            "failover_storm"):
            now = time.monotonic()
            with self._bp_lock:
                if now >= self._storm_until:
                    self._retry_seq = 0
                self._storm_until = max(self._storm_until,
                                        now + self.STORM_SHED_S)
        if self._rate is not None:
            self._rate.on_event(event)

    def _commit_adaptive(self, parts: Sequence[Any], last_pull_clock: int,
                         worker: Any) -> Dict[str, Any]:
        """Route one commit through the combiner (clock, fence, scaling
        and replication ordering all live there) and give the detectors a
        rate-limited chance to run off the commit path — an adaptive run
        with no worker health reports still reacts to the hub's own
        staleness folds."""
        entry = self._combiner.commit(parts, last_pull_clock, worker=worker)
        mon = self._health_monitor
        if mon is not None:
            mon.maybe_check()
        return entry

    def _commit_one(self, parts: Sequence[Any], last_pull_clock: int,
                    worker: Any, sparse: bool,
                    telemetry: bool) -> Tuple[int, int]:
        """The ONE commit dispatch every commit path (dense/sparse x
        socket/inproc) runs: adaptive routes through the combiner (clock,
        fence, scaling, Adasum merge and replication ordering live
        there); plain runs the pre-adaptive sequence verbatim — fence
        clamp under the center lock, apply, advance clock, publish to
        the replicas BEFORE returning (so the caller's ack keeps the
        acked-commit-is-kernel-owned replication contract).  Returns
        ``(staleness, last_pull_clock)``, the clock re-based when the
        fence clamped it — a commit retried without a fresh pull must
        not carry a dead incarnation's (or pre-promotion) clock as
        staleness."""
        if self._combiner is not None:
            entry = self._commit_adaptive(parts, last_pull_clock, worker)
            if entry["fenced"]:
                last_pull_clock = entry["fence"]
            return entry["staleness"], last_pull_clock
        t_req = time.perf_counter_ns() if telemetry else 0
        with self._lock:
            with (_apply_phase(t_req, batch=1, clock=self._clock)
                  if telemetry else obs.NULL_SPAN):
                if last_pull_clock < self._clock_fence:
                    last_pull_clock = self._clock_fence
                    if telemetry:
                        obs.counter("ps_fenced_commits_total",
                                    **self._mlabels).inc()
                staleness = self._clock - last_pull_clock
                scaled = (self._apply_sparse_commit_locked(parts, staleness)
                          if sparse else
                          self._apply_commit_locked(parts, staleness))
                self.num_updates += 1
                self._clock += 1
                commit_clock = self._clock
        if scaled is not None:
            self._feed.publish(commit_clock, scaled)
        return staleness, last_pull_clock

    # -- multi-job admission + job-scoped serving (ISSUE 19) -------------------

    def _job_working_set_bytes_locked(self) -> int:
        """The shard's decayed hot-row working set in bytes (caller holds
        the center lock): rows still at or above ``TOUCH_HOT_MIN`` in the
        PR-14 touch counters, times their row bytes.  This is the live
        per-job memory signal admission projects against the budget —
        a shard whose embedding hot set already fills memory must not
        also take on another job's center copy."""
        total = 0
        for leaf, touch in self._sparse_touch.items():
            hot = int(np.count_nonzero(touch >= self.TOUCH_HOT_MIN))
            total += hot * int(self.center[leaf].shape[1]) * 4
        return total

    def _admit_job(self, job: str) -> Tuple[bool, str, Optional[_JobState]]:
        """Admission-control one job-scoped announce.  Returns
        ``(admitted, reason, state)``; re-announcing an already-admitted
        job (a reconnecting worker) re-attaches to the existing
        namespace.  The verdict settles under the center lock BEFORE any
        pull/commit is served on the announcing connection
        (``FLEET_RULES.admission_before_attach``)."""
        job = str(job)
        reason = ""
        with self._lock:
            state = self._jobs.get(job)
            n_jobs = len(self._jobs)
            if state is None:
                if self._standby and not self.promoted:
                    reason = ("standby hubs hold no job namespaces "
                              "(admission is primary-only)")
                elif self.max_jobs <= 0:
                    reason = "multi-job serving is disabled (max_jobs=0)"
                elif n_jobs >= self.max_jobs:
                    reason = f"job slots exhausted ({n_jobs}/{self.max_jobs})"
                else:
                    ws = self._job_working_set_bytes_locked()
                    projected = self._center_bytes * (n_jobs + 1) + ws
                    if projected > self.job_budget_bytes:
                        reason = (
                            f"shard memory budget exceeded: projected "
                            f"{projected} bytes ({n_jobs + 1} job center "
                            f"copies + {ws}-byte hot working set) > "
                            f"budget {self.job_budget_bytes}")
                    else:
                        state = _JobState(job, self.center)
                        self._jobs[job] = state
                        self.jobs_admitted += 1
            if state is None:
                self.jobs_rejected += 1
        return (state is not None), reason, state

    def _job_commit_one(self, state: _JobState, delta: Sequence[np.ndarray],
                        last_pull_clock: int) -> Tuple[int, int]:
        """Job-scoped twin of :meth:`_commit_one`: same staleness and
        ``commit_scale`` semantics (the hub flavor's rule — ADAG's
        membership-weighted denominator, DynSGD's ``1/(s+1)``) applied
        to the JOB's center under the SAME center lock.  No adaptive
        combiner, replication or snapshot participation — isolation is
        the contract (see :class:`_JobState`)."""
        telemetry = obs.enabled()
        t_req = time.perf_counter_ns() if telemetry else 0
        with self._lock:
            with (_apply_phase(t_req, batch=1, clock=state.clock)
                  if telemetry else obs.NULL_SPAN):
                staleness = state.clock - last_pull_clock
                _add_scaled_commit(state.center, delta,
                                   self.commit_scale(staleness),
                                   self._apply_scratch)
                state.num_updates += 1
                state.clock += 1
        return staleness, last_pull_clock

    def fleet_info(self) -> Dict[str, Any]:
        """The hub's membership/job surface (ISSUE 19) — one JSON-safe
        dict the fleet controller, ``distkeras-top`` and the launcher
        all read.  The native hub's wrapper maps its C++ stat keys onto
        the same shape, so callers never branch on hub implementation."""
        with self._lock:
            jobs = {name: {"clock": s.clock, "num_updates": s.num_updates}
                    for name, s in self._jobs.items()}
            clock = self._clock
            num_updates = self.num_updates
            admitted, rejected = self.jobs_admitted, self.jobs_rejected
        return {"live_workers": self.live_workers(), "jobs": jobs,
                "clock": clock, "num_updates": num_updates,
                "jobs_admitted": admitted, "jobs_rejected": rejected}

    def _retry_after_ms(self, waits_taken: int = 0) -> int:
        """Answer one reconnect hello (action ``G``): 0 = proceed now,
        else the caller's retry-after slot in milliseconds.  Every hub
        answers ``G`` (an adaptive client may dial any hub of this
        generation), but only an adaptive hub in a live storm hints
        nonzero — and only to announcers that have NOT already waited a
        slot this episode (``waits_taken == 0``), so the herd spreads
        exactly once and every member is admitted on its paced return.
        Storms arm two ways: the health monitor's storm detectors (via
        the subscription), and self-detection from the hello arrival
        rate here — a herd reconnecting after a network blip is shed
        even when no worker reports health."""
        if not self.adaptive:
            return 0
        now = time.monotonic()
        storm_started = False
        with self._bp_lock:
            if waits_taken <= 0:
                # only FRESH reconnects are storm evidence: a shed herd's
                # paced returns (waits_taken > 0) are the drain, not the
                # storm — counting them would re-arm shedding against
                # the next innocent lone reconnect
                self._hello_times.append(now)
            while self._hello_times and \
                    now - self._hello_times[0] > self.STORM_WINDOW_S:
                self._hello_times.popleft()
            if now >= self._storm_until \
                    and len(self._hello_times) >= self.STORM_HELLOS:
                self._storm_until = now + self.STORM_SHED_S
                self._retry_seq = 0
                storm_started = True
            if now < self._storm_until and waits_taken <= 0:
                self._retry_seq += 1
                hint = min(self.RETRY_CAP_MS,
                           self.RETRY_BASE_MS * self._retry_seq)
                # counted under the lock: concurrent handler threads
                # during a storm must not lose increments
                self.backpressure_hints += 1
            else:
                hint = 0
        if storm_started:
            # observable like any monitor-detected storm; the emit also
            # re-arms shedding through the subscription (idempotent)
            try:
                mon = self._health_monitor
                if mon is not None:
                    mon.emit("reconnect_storm", "critical",
                             shard=self.shard_id,
                             dedup=f"hub-hellos:{self.host}:{self.port}",
                             hellos=len(self._hello_times),
                             window_s=self.STORM_WINDOW_S)
            except Exception:
                pass
        if hint and obs.enabled():
            obs.counter("ps_backpressure_hints_total",
                        **self._mlabels).inc()
            obs.histogram("ps.retry_after_ms",
                          **self._mlabels).observe(hint)
        return hint

    # -- serving loop (reference: SocketParameterServer.run) -------------------
    def _accept_loop(self) -> None:
        assert self._listener is not None
        while self._running:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                break  # listener closed by stop()
            # registration races stop(): linearize on _conn_lock — either
            # this append lands before stop()'s sever loop (which then
            # shuts the conn down), or we observe _running False here and
            # close it ourselves.  Without the re-check a conn accepted in
            # the gap would spawn a handler that blocks in recv forever,
            # resurrecting the leaked-handler stall stop() just fixed
            with self._conn_lock:
                if not self._running:
                    try:
                        conn.close()
                    except OSError:
                        pass
                    break
                self._conns.append(conn)
            # Nagle off + kernel buffers sized to one full weights/commit
            # frame — times the receive batch depth when batching is on,
            # so the kernel can actually hold the storm of frames one
            # recvmmsg batch will drain.  TCP_QUICKACK on the hub side:
            # the coalesced 13-byte acks are the one latency-critical
            # tiny send left, and they must not ride the delayed-ack
            # timer (wire bytes unchanged — pinned by recording-socket)
            net.configure_socket(
                conn,
                payload_hint=self._frame_bytes
                * max(1, self.recv_batch_depth),
                quickack=True)
            # ordinal wraps at a fixed slot count so the staleness gauge's
            # label cardinality stays bounded even under elastic-run
            # connection churn (ordinals already restart at 0 per hub,
            # so slots only conflate workers past 256 live connections)
            conn_idx = self._conn_seq % 256
            self._conn_seq += 1
            t = threading.Thread(target=self._handle_connection,
                                 args=(conn, conn_idx), daemon=True)
            t.start()
            # prune finished handlers as connections churn: a long-lived
            # hub under elastic reconnects must not accumulate one dead
            # Thread object per connection ever accepted
            self._handlers = [h for h in self._handlers if h.is_alive()]
            self._handlers.append(t)

    def _decode_delta(self, blobs) -> List[np.ndarray]:
        """f32 commit: reinterpret each wire blob in place (zero-copy views
        into the connection's receive buffer, consumed before the next
        frame overwrites it)."""
        if len(blobs) != len(self.center):
            raise ValueError(f"commit has {len(blobs)} tensors, center has {len(self.center)}")
        out = []
        for blob, c in zip(blobs, self.center):
            arr = np.frombuffer(blob, dtype=c.dtype)
            if arr.size != c.size:
                raise ValueError(f"commit tensor size {arr.size} != center size {c.size}")
            out.append(arr.reshape(c.shape))
        return out

    def _decode_qdelta(self, blobs) -> List[np.ndarray]:
        """int8 commit (action Q): per-tensor f32 scale + int8 values."""
        if len(blobs) != len(self.center):
            raise ValueError(f"commit has {len(blobs)} tensors, center has {len(self.center)}")
        return [net.dequantize_q_blob(blob, c.size).reshape(c.shape)
                for blob, c in zip(blobs, self.center)]

    # -- row-sparse embedding traffic (ISSUE 9) --------------------------------
    # decay cadence of the hot-set estimate: halve every N folds, count
    # rows still >= TOUCH_HOT_MIN.  Instance-tunable (tests retune)
    TOUCH_DECAY_EVERY = 64
    TOUCH_HOT_MIN = 1.0

    def _touch_rows_locked(self, pairs) -> None:
        """Fold touched rows into the decayed per-table counters (caller
        holds the center lock and checked ``obs.enabled()``).  ``pairs``
        yields ``(leaf, ids)``; on each decay tick the
        ``ps.sparse_hot_rows{table=}`` gauges refresh."""
        for leaf, ids in pairs:
            touch = self._sparse_touch.get(leaf)
            if touch is not None and ids.size:
                touch[ids] += np.float32(1.0)
        self._touch_folds += 1
        if self._touch_folds >= self.TOUCH_DECAY_EVERY:
            self._touch_folds = 0
            for leaf, touch in self._sparse_touch.items():
                touch *= np.float32(0.5)
                obs.gauge("ps.sparse_hot_rows", table=str(leaf),
                          **self._mlabels).set(
                    int(np.count_nonzero(touch >= self.TOUCH_HOT_MIN)))

    def _q_payload_bytes(self) -> int:
        """Payload bytes of a DENSE int8 (action Q) commit over this
        center — the like-for-like baseline ``ps.sparse_wire_bytes_saved``
        compares an X commit against."""
        return 5 + sum(8 + 4 + w.size for w in self.center)

    def _check_row_ids(self, ids: np.ndarray, leaf: int) -> np.ndarray:
        """Validate one table's wire row-id blob against this center's
        row count (the shared :func:`networking.check_row_ids`
        contract)."""
        return net.check_row_ids(ids, self.center[leaf].shape[0], leaf)

    def _decode_sparse_ids(self, blobs) -> List[np.ndarray]:
        """Action-``S`` request payload -> one validated id array per
        sparse table (ascending leaf order).  The arrays are views into
        the connection's receive buffer — consumed before the next frame
        lands, like every other wire view."""
        if len(blobs) != len(self.sparse_leaves):
            raise ValueError(f"sparse pull has {len(blobs)} id blobs, hub "
                             f"has {len(self.sparse_leaves)} sparse tables")
        return [self._check_row_ids(np.frombuffer(blob, net.ROW_ID_DTYPE), i)
                for blob, i in zip(blobs, self.sparse_leaves)]

    def _decode_sparse_commit(self, blobs, quantized: bool) -> List[Any]:
        """Action-``U``/``X`` payload -> per-leaf parts aligned with the
        center: a full delta array for dense leaves, an ``(ids, grads)``
        pair for sparse leaves."""
        expected = len(self.center) + len(self.sparse_leaves)
        if len(blobs) != expected:
            raise ValueError(f"sparse commit has {len(blobs)} blobs, "
                             f"expected {expected}")
        parts: List[Any] = []
        it = iter(blobs)
        for i, c in enumerate(self.center):
            if i in self._sparse_set:
                ids = self._check_row_ids(
                    np.frombuffer(next(it), net.ROW_ID_DTYPE), i)
                dim = c.shape[1]
                blob = next(it)
                if quantized:
                    grads = net.dequantize_q_blob(blob, ids.size * dim)
                else:
                    grads = np.frombuffer(blob, np.float32)
                    if grads.size != ids.size * dim:
                        raise ValueError(
                            f"sparse leaf {i}: {grads.size} grad values for "
                            f"{ids.size} rows of dim {dim}")
                parts.append((ids, grads.reshape(ids.size, dim)))
            else:
                blob = next(it)
                if quantized:
                    arr = net.dequantize_q_blob(blob, c.size).reshape(c.shape)
                else:
                    arr = np.frombuffer(blob, np.float32)
                    if arr.size != c.size:
                        raise ValueError(f"commit tensor size {arr.size} != "
                                         f"center size {c.size}")
                    arr = arr.reshape(c.shape)
                parts.append(arr)
        return parts

    def _apply_sparse_commit_locked(self, parts: Sequence[Any],
                                    staleness: int) -> Optional[List[np.ndarray]]:
        """Sparse analogue of :meth:`_apply_commit_locked` (caller holds
        the center lock): dense leaves apply exactly like a dense commit,
        sparse leaves apply only their touched rows —
        ``center[ids] += commit_scale(staleness) * grads`` — under the
        SAME staleness clock and scaling rule the dense paths and the
        replication feed already share.  When a replica is attached the
        applied scaled parts are returned for the feed IN ROW-SPARSE FORM
        (``(ids, scaled rows)`` tuples; owned copies): the feed streams
        them as one REPL_SPARSE row-delta frame to sparse-capable
        replicas and densifies — outside this lock, only if a legacy
        replica is attached — for the dense-``R`` fallback.  Returns
        None with no replica (the pre-HA in-place path)."""
        feed = self._feed
        replicate = feed is not None and feed.active()
        scale = np.float32(self.commit_scale(staleness))
        one = scale == np.float32(1.0)
        scaled: Optional[List[Any]] = [] if replicate else None
        for c, p in zip(self.center, parts):
            if isinstance(p, tuple):
                ids, grads = p
                g = grads if one else grads * scale
                if replicate:
                    # OWNED copies for the feed (wire ids/grads are views
                    # into the receive buffer) — `* scale` already owns
                    # except on the scale-1 fast path
                    scaled.append((np.array(ids, net.ROW_ID_DTYPE),
                                   np.array(g, np.float32) if one else g))
                if ids.size:
                    c[ids] += g
            else:
                arr = np.asarray(p, np.float32)
                g = arr if one else arr * scale
                if replicate:
                    g = np.array(g, np.float32) if one else g
                    scaled.append(g)
                c += g
        if obs.enabled():
            self._touch_rows_locked(
                (i, p[0]) for i, p in enumerate(parts)
                if isinstance(p, tuple))
        return scaled

    def _handle_connection(self, conn: socket.socket, conn_idx: int = 0) -> None:
        # connections born after a restore start AT the fence: their first
        # commit-before-pull is stale relative to the restart point, not to
        # clock zero of a previous incarnation
        with self._lock:
            last_pull_clock = self._clock_fence
        with self._member_lock:
            self._member_seq += 1
            member_token = self._member_seq
        joined = False
        # trace context announced via action T (None until the worker
        # announces): every span this handler records is tagged with it,
        # so hub-side work is attributable to the worker that caused it
        ctx_attrs: Dict[str, Any] = {}
        # multi-job (ISSUE 19): set when this connection's T announce
        # carried a job_ns key and the admission verdict settled.  A
        # rejected session is never served (FLEET_RULES.
        # reject_never_serves); an admitted one is routed to its job's
        # private center with its own pull clock
        job_state: Optional[_JobState] = None
        job_rejected = False
        job_pull_clock = 0
        # per-connection reusable storage: the receive buffer grows once to
        # the largest frame this worker sends (a commit), the reply codec
        # holds one prepacked weights frame, the ack is a 13-byte constant
        # — steady-state the handler loop allocates nothing
        rx = bytearray(self._frame_bytes)
        reply = net.FlatFrameCodec(self.center)
        # sparse replies vary per message (row blobs sized by the request),
        # so they ride a grow-once variable encoder instead of the fixed
        # codec; None on a dense hub — zero cost when sparse is off
        sp_enc = net.VarFrameEncoder() if self.sparse_leaves else None
        ack = net.empty_tensor_frame(net.ACTION_ACK)
        # batched receive (ISSUE 18): with a depth configured, frames are
        # parsed out of one big per-connection buffer that a single
        # blocking recv (plus nonblocking recvmmsg drains) refills — a
        # pipelined commit storm costs one syscall per BATCH.  The
        # receiver wraps the raw TCP socket only; it is retired (asserted
        # drained) at any transport handoff below
        receiver = (net.BatchedReceiver(conn, self._frame_bytes,
                                        self.recv_batch_depth)
                    if self.recv_batch_depth > 0 else None)
        # set when this connection turns out to be a replica handshake: the
        # socket's ownership moves to the replication feed and this thread
        # must exit WITHOUT closing it
        handoff = False
        if self.idle_timeout is not None:
            # per-recv liveness bound: a peer that dies without FIN (host
            # crash, cable pull) no longer parks this handler forever
            conn.settimeout(self.idle_timeout)

        def recv_phase(action: bytes, n: int):
            # leaf phase over a commit frame's body coming off the
            # connection, the idle wait for the request left out (reads
            # ctx_attrs as the latest T announce left them; the batched
            # receiver parses frames out of one buffer and has no such
            # moment)
            if action in _COMMIT_ACTIONS:
                return obs.phase("ps.recv_commit", conn=conn_idx, bytes=n,
                                 **self._shard_attrs, **ctx_attrs)
            return obs.NULL_SPAN
        try:
            while True:
                # raw receive: pull/bye carry zero tensors, commit carries
                # len(center) — decode against the center only on commit.
                # The bound is the largest VALID frame (an f32 commit), so
                # a garbage length prefix raises ProtocolError instead of
                # allocating whatever the 8 bytes happened to say
                try:
                    if receiver is not None:
                        payload = receiver.recv_frame_into(
                            limit=self._max_payload)
                    else:
                        payload = net.recv_frame_into(conn, rx,
                                                      limit=self._max_payload,
                                                      body_span=recv_phase)
                except socket.timeout:
                    # silent past the liveness window (no heartbeat, no
                    # traffic): evict — half-open peers must not hold a
                    # handler thread and a membership slot forever
                    if obs.enabled():
                        obs.counter("ps_idle_evictions_total",
                                    **self._mlabels).inc()
                        with obs.span("ps.evict", conn=conn_idx,
                                      **self._shard_attrs, **ctx_attrs):
                            pass
                    break
                action, blobs = net.decode_tensor_views(payload)
                if joined:
                    self._member_touch(member_token)
                telemetry = obs.enabled()
                if action == net.ACTION_PULL:
                    if job_rejected:
                        raise net.ProtocolError(
                            "pull on a rejected job session refused "
                            "(the admission verdict was reject)")
                    if job_state is not None:
                        with obs.span("ps.handle_pull", conn=conn_idx,
                                      **self._shard_attrs, **ctx_attrs):
                            with self._lock:
                                reply.pack(net.ACTION_WEIGHTS,
                                           job_state.center)
                                job_pull_clock = job_state.clock
                            with obs.phase("ps.send_weights",
                                           clock=job_pull_clock):
                                reply.send_packed(conn)
                        if telemetry:
                            obs.counter("ps_pulls_total",
                                        **self._mlabels).inc()
                            obs.counter("ps_pull_bytes_total",
                                        **self._mlabels).inc(
                                self._center_bytes)
                        continue
                    if self._standby and not self._synced.is_set():
                        # same rule as commits: seed weights must never be
                        # served as if they were the job's state — a
                        # failed-over worker's re-pull here would train a
                        # window on garbage before its commit is refused
                        raise net.ProtocolError(
                            "pull from a never-synced standby refused "
                            "(it holds no job state yet)")
                    with obs.span("ps.handle_pull", conn=conn_idx,
                                  **self._shard_attrs, **ctx_attrs):
                        with self._lock:
                            # pack the center STRAIGHT into the reply frame
                            # (one memcpy per tensor) under the lock; the
                            # send happens after release so a slow peer
                            # can't hold the center
                            reply.pack(net.ACTION_WEIGHTS, self.center)
                            last_pull_clock = self._clock
                        # leaf phase: the reply going out, after the lock
                        with obs.phase("ps.send_weights",
                                       clock=last_pull_clock):
                            reply.send_packed(conn)
                    if telemetry:
                        obs.counter("ps_pulls_total", **self._mlabels).inc()
                        obs.counter("ps_pull_bytes_total",
                                    **self._mlabels).inc(self._center_bytes)
                elif action in (net.ACTION_COMMIT, net.ACTION_QCOMMIT):
                    if job_rejected:
                        raise net.ProtocolError(
                            "commit on a rejected job session refused "
                            "(the admission verdict was reject)")
                    delta = (self._decode_delta(blobs)
                             if action == net.ACTION_COMMIT
                             else self._decode_qdelta(blobs))
                    if job_state is not None:
                        if not joined:
                            joined = True
                            self._member_join(member_token)
                        with obs.span("ps.handle_commit", conn=conn_idx,
                                      **self._shard_attrs,
                                      **ctx_attrs) as sp:
                            staleness, job_pull_clock = self._job_commit_one(
                                job_state, delta, job_pull_clock)
                            net.send_raw_frame(conn, ack)
                            if getattr(sp, "attrs", None) is not None:
                                sp.attrs["staleness"] = staleness
                        self._observe_health(ctx_attrs.get("worker"),
                                             "staleness", staleness)
                        if telemetry:
                            obs.counter("ps_commits_total",
                                        **self._mlabels).inc()
                            obs.counter("ps_commit_bytes_total",
                                        **self._mlabels).inc(
                                sum(b.nbytes for b in blobs))
                            obs.gauge("ps_staleness", conn=str(conn_idx),
                                      **self._mlabels).set(staleness)
                            obs.histogram("ps_commit_staleness",
                                          **self._mlabels).observe(staleness)
                        continue
                    if self._standby:
                        if not self._synced.is_set():
                            # no sync ever landed: this standby holds
                            # fresh init weights, NOT the job's state —
                            # promoting would silently restart training
                            # from seed.  Refuse (drops the connection;
                            # the worker retries under its budget and
                            # fails LOUDLY if nothing recovers), matching
                            # the feed-loss path's never-synced rule
                            raise net.ProtocolError(
                                "commit into a never-synced standby "
                                "refused (it has no state to take over)")
                        self._standby_commit_gate()
                        # the feed is down too: the primary is presumed
                        # dead.  Promote NOW (fence armed before this
                        # commit's staleness is computed) — losing the
                        # race to the feed-loss detector is fine,
                        # promote() is idempotent
                        self.promote(reason="commit received while standby "
                                            "(worker failed over)")
                    if not joined:
                        # first commit = this peer is a WORKER (pull-only
                        # readers never join): membership drives the
                        # elastic denominators
                        joined = True
                        self._member_join(member_token)
                    with obs.span("ps.handle_commit", conn=conn_idx,
                                  **self._shard_attrs, **ctx_attrs) as sp:
                        # one shared dispatch (adaptive combiner or the
                        # pre-adaptive fence/apply/publish sequence);
                        # either way the commit is applied AND replicated
                        # before the ack below leaves
                        staleness, last_pull_clock = self._commit_one(
                            delta, last_pull_clock, ctx_attrs.get("worker"),
                            sparse=False, telemetry=telemetry)
                        net.send_raw_frame(conn, ack)
                        if getattr(sp, "attrs", None) is not None:
                            # the span's attribution payload: the staleness
                            # this exact commit applied with (fleet_report
                            # joins it to the announcing worker)
                            sp.attrs["staleness"] = staleness
                    # live health plane: this commit's staleness joins the
                    # announcing worker's sliding-window series (no-op —
                    # one attribute check — until a worker reports health)
                    self._observe_health(ctx_attrs.get("worker"),
                                         "staleness", staleness)
                    if telemetry:
                        obs.counter("ps_commits_total", **self._mlabels).inc()
                        obs.counter("ps_commit_bytes_total",
                                    **self._mlabels).inc(
                            sum(b.nbytes for b in blobs))
                        # per-connection staleness: commits the hub applied
                        # between this worker's last pull and its commit —
                        # the quantity DynSGD scales by, now visible for
                        # EVERY hub flavor.  Created lazily so a hub with
                        # telemetry off never registers per-connection state
                        obs.gauge("ps_staleness", conn=str(conn_idx),
                                  **self._mlabels).set(staleness)
                        obs.histogram("ps_commit_staleness",
                                      **self._mlabels).observe(staleness)
                elif action == net.ACTION_SPARSE_PULL:
                    if job_state is not None or job_rejected:
                        raise net.ProtocolError(
                            "sparse actions are default-namespace only "
                            "(job-scoped sessions exchange dense P/C/Q)")
                    if sp_enc is None:
                        raise net.ProtocolError(
                            "sparse pull against a hub with no sparse "
                            "tables (pass sparse_leaves to the hub)")
                    if self._standby and not self._synced.is_set():
                        raise net.ProtocolError(
                            "pull from a never-synced standby refused "
                            "(it holds no job state yet)")
                    ids_list = self._decode_sparse_ids(blobs)
                    rows_pulled = int(sum(ids.size for ids in ids_list))
                    with obs.span("ps.handle_pull", conn=conn_idx,
                                  sparse_rows=rows_pulled,
                                  **self._shard_attrs, **ctx_attrs):
                        with self._lock:
                            # fancy-indexed row gathers copy; dense leaves
                            # are memcpy'd straight into the frame by
                            # pack() — all under the lock, send after
                            it = iter(ids_list)
                            arrays = [self.center[i][next(it)]
                                      if i in self._sparse_set
                                      else self.center[i]
                                      for i in range(len(self.center))]
                            frame = sp_enc.pack(net.ACTION_SPARSE_WEIGHTS,
                                                arrays)
                            last_pull_clock = self._clock
                            if telemetry:
                                self._touch_rows_locked(
                                    zip(self.sparse_leaves, ids_list))
                        with obs.phase("ps.send_weights",
                                       clock=last_pull_clock):
                            net.send_raw_frame(conn, frame)
                    if telemetry:
                        obs.counter("ps_pulls_total", **self._mlabels).inc()
                        # raw tensor bytes, the same basis the dense pull
                        # (_center_bytes) and both commit paths use: a
                        # sparse-vs-dense ratio must not compare framed
                        # bytes against raw bytes
                        obs.counter("ps_pull_bytes_total",
                                    **self._mlabels).inc(
                            sum(a.nbytes for a in arrays))
                        obs.counter("ps.sparse_rows_pulled",
                                    **self._mlabels).inc(rows_pulled)
                        obs.counter("ps.sparse_wire_bytes_saved",
                                    **self._mlabels).inc(
                            max(0, self._frame_bytes - sp_enc.frame_len))
                elif action in (net.ACTION_SPARSE_COMMIT,
                                net.ACTION_SPARSE_QCOMMIT):
                    if job_state is not None or job_rejected:
                        raise net.ProtocolError(
                            "sparse actions are default-namespace only "
                            "(job-scoped sessions exchange dense P/C/Q)")
                    if not self.sparse_leaves:
                        raise net.ProtocolError(
                            "sparse commit against a hub with no sparse "
                            "tables (pass sparse_leaves to the hub)")
                    parts = self._decode_sparse_commit(
                        blobs,
                        quantized=(action == net.ACTION_SPARSE_QCOMMIT))
                    if self._standby:
                        if not self._synced.is_set():
                            raise net.ProtocolError(
                                "commit into a never-synced standby "
                                "refused (it has no state to take over)")
                        self._standby_commit_gate()
                        self.promote(reason="commit received while standby "
                                            "(worker failed over)")
                    if not joined:
                        joined = True
                        self._member_join(member_token)
                    rows_committed = int(sum(
                        p[0].size for p in parts if isinstance(p, tuple)))
                    with obs.span("ps.handle_commit", conn=conn_idx,
                                  sparse_rows=rows_committed,
                                  **self._shard_attrs, **ctx_attrs) as sp:
                        staleness, last_pull_clock = self._commit_one(
                            parts, last_pull_clock, ctx_attrs.get("worker"),
                            sparse=True, telemetry=telemetry)
                        net.send_raw_frame(conn, ack)
                        if getattr(sp, "attrs", None) is not None:
                            sp.attrs["staleness"] = staleness
                    self._observe_health(ctx_attrs.get("worker"),
                                         "staleness", staleness)
                    if telemetry:
                        wire = sum(b.nbytes for b in blobs)
                        dense_equiv = (
                            self._frame_bytes - 8
                            if action == net.ACTION_SPARSE_COMMIT
                            else self._q_payload_bytes())
                        obs.counter("ps_commits_total", **self._mlabels).inc()
                        obs.counter("ps_commit_bytes_total",
                                    **self._mlabels).inc(wire)
                        obs.counter("ps.sparse_rows_committed",
                                    **self._mlabels).inc(rows_committed)
                        obs.counter("ps.sparse_wire_bytes_saved",
                                    **self._mlabels).inc(
                            max(0, dense_equiv - wire))
                        obs.gauge("ps_staleness", conn=str(conn_idx),
                                  **self._mlabels).set(staleness)
                        obs.histogram("ps_commit_staleness",
                                      **self._mlabels).observe(staleness)
                elif action == net.ACTION_TRACE:
                    # trace-context announce: tag this connection's spans
                    # with the worker's identity and reply with this hub's
                    # monotonic clock (the NTP-style sample the client's
                    # offset estimate is built from).  Malformed context is
                    # ignored, not fatal — tracing must never take down a
                    # training connection
                    raw = bytes(blobs[0]) if blobs else b""
                    try:
                        ctx = dtrace.TraceContext.from_json(raw)
                        ctx_attrs = ctx.span_attrs()
                    except Exception:
                        # any malformed blob shape (missing blob, non-object
                        # JSON, null fields -> TypeError/AttributeError):
                        # an unattributed connection, never a dropped one
                        ctx_attrs = {}
                    # multi-job announce (ISSUE 19): a job_ns key turns
                    # this T into a job-scoped announce whose reply is the
                    # admission verdict.  Absent (every pre-multi-job
                    # client), the reply below is the exact HEAD timestamp
                    # frame — byte-identical wire
                    job_ns = None
                    try:
                        doc = json.loads(raw.decode("utf-8"))
                        if isinstance(doc, dict):
                            job_ns = doc.get("job_ns")
                    except Exception:
                        job_ns = None
                    if job_ns is None:
                        if job_state is not None:
                            # a later plain trace announce on an admitted
                            # session must not drop the job attribution
                            ctx_attrs["job"] = job_state.job
                        net.send_frame(conn, net.encode_time_payload(
                            time.perf_counter_ns()))
                    else:
                        admitted, reason, job_state = self._admit_job(
                            str(job_ns))
                        job_rejected = not admitted
                        if admitted:
                            # the namespace IS the job for every span and
                            # health series this connection produces —
                            # fairness reporting groups by it
                            ctx_attrs["job"] = job_state.job
                        net.send_frame(conn, net.encode_admission_payload(
                            time.perf_counter_ns(), admitted, reason))
                elif action == net.ACTION_REPL:
                    # replica handshake: this peer is a hot standby, not a
                    # worker.  Attach it to the replication feed (full
                    # sync + delta stream) and hand the socket over — the
                    # feed owns it from here, this handler thread exits
                    clock_hdr, kind = net.decode_repl_header(blobs[0])
                    if kind != net.REPL_HELLO:
                        raise net.ProtocolError(
                            f"unexpected replication kind {kind} from a peer "
                            f"(only hello initiates a feed)")
                    if receiver is not None and receiver.pending():
                        # bytes batched past the hello belong to the feed's
                        # stream, which reads the raw socket — handing the
                        # socket over would silently drop them
                        raise net.ProtocolError(
                            "frames batched past a replication hello")
                    with self._feed_lock:
                        if self._feed is None:
                            self._feed = ReplicationFeed(self)
                        feed = self._feed
                    with obs.span("ps.replica_attach", conn=conn_idx,
                                  replica_clock=clock_hdr,
                                  **self._shard_attrs):
                        feed.attach(conn, conn_idx,
                                    capabilities=net.decode_repl_caps(
                                        blobs[0]))
                    handoff = True
                    return
                elif action == net.ACTION_HEALTH:
                    # worker health report (ISSUE 8): fold into the live
                    # collector and ack — the ack coalesces into the
                    # client's later receives exactly like a commit ack,
                    # so reports ride the pipelined FIFO.  The guard is
                    # BROAD on purpose: malformed JSON, a broken detector,
                    # a full-disk JSONL sink — health must never take down
                    # a training connection (the malformed-T rule)
                    try:
                        self._ingest_health(json.loads(bytes(blobs[0])))
                    except Exception:
                        pass
                    net.send_raw_frame(conn, ack)
                elif action == net.ACTION_RECONNECT:
                    # adaptive reconnect announce (ISSUE 10): answer with
                    # a retry-after hint (0 = proceed; announcers that
                    # already waited their slot are admitted).  Every hub
                    # of this generation answers G — the frame only ever
                    # moves when the CLIENT opted in with adaptive=True,
                    # so pre-existing byte streams are untouched
                    net.send_frame(conn, net.encode_retry_payload(
                        self._retry_after_ms(
                            net.decode_reconnect_payload(blobs))))
                elif action == net.ACTION_SHM:
                    # zero-copy attach handshake (ISSUE 18), entirely
                    # inside this dispatch arm so the switch point is
                    # exact: reply with an offer (two freshly created ring
                    # files) or a decline, then — on an offer — read the
                    # client's confirm off the SAME TCP stream.  Only an
                    # attached confirm swaps this connection onto the
                    # rings; a decline, an abort, or a mapping failure
                    # leaves it pure TCP, byte-identical to a pre-Z hub
                    # (analysis/protocol_model.py walks all of this)
                    version, cap_hint = net.decode_shm_request(blobs)
                    rings = None
                    if (self.shm_dir is not None
                            and version == net.SHM_VERSION
                            and not isinstance(conn, net.ShmEndpoint)):
                        with self._conn_lock:
                            self._shm_seq += 1
                            tag = self._shm_seq
                        base = os.path.join(
                            self.shm_dir, f"ring-{self.port}-{tag}")
                        # each ring must hold at least a couple of this
                        # connection's largest frames or the transport
                        # would deadlock pipelined exchanges on capacity
                        cap = max(int(cap_hint), 2 * self._frame_bytes,
                                  net.SHM_RING_DEFAULT_CAPACITY)
                        try:
                            rings = (net.ShmFrameRing.create(
                                         base + ".c2h", "consumer", cap),
                                     net.ShmFrameRing.create(
                                         base + ".h2c", "producer", cap))
                        except OSError:
                            rings = None  # can't create -> decline
                    if rings is None:
                        net.send_frame(conn, net.encode_shm_decline())
                    else:
                        rx_ring, tx_ring = rings
                        try:
                            net.send_frame(conn, net.encode_shm_offer(
                                rx_ring.path, tx_ring.path))
                            # the confirm is the very next frame on the
                            # TCP FIFO — read it where the batched
                            # receiver (if any) already is
                            if receiver is not None:
                                c_payload = receiver.recv_frame_into(
                                    limit=self._max_payload)
                            else:
                                c_payload = net.recv_frame_into(
                                    conn, rx, limit=self._max_payload)
                            c_action, c_blobs = net.decode_tensor_views(
                                c_payload)
                            if c_action != net.ACTION_SHM:
                                raise net.ProtocolError(
                                    f"expected Z confirm after shm offer, "
                                    f"got {c_action!r}")
                            attached = net.decode_shm_confirm(c_blobs)
                        except BaseException:
                            rx_ring.close()
                            tx_ring.close()
                            rx_ring.unlink()
                            tx_ring.unlink()
                            raise
                        # the client has mapped (or abandoned) the files;
                        # either way the names can leave the filesystem —
                        # the mappings keep the memory alive
                        rx_ring.unlink()
                        tx_ring.unlink()
                        if attached:
                            if receiver is not None and receiver.pending():
                                raise net.ProtocolError(
                                    "frames batched past an shm attach")
                            receiver = None  # rings need no syscall batching
                            endpoint = net.ShmEndpoint(conn, tx_ring,
                                                       rx_ring)
                            # stop()'s sever loop must wake the ring, not
                            # just the now-idle anchor socket
                            with self._conn_lock:
                                if conn in self._conns:
                                    self._conns[self._conns.index(conn)] = \
                                        endpoint
                            conn = endpoint
                            if self.idle_timeout is not None:
                                conn.settimeout(self.idle_timeout)
                        else:
                            rx_ring.close()
                            tx_ring.close()
                elif action == net.ACTION_PING:
                    # heartbeat-on-idle: proves liveness (resetting the
                    # idle clock above) and keeps a slow-but-alive worker's
                    # membership from lapsing; acked so the client can
                    # bound its own round trips
                    net.send_raw_frame(conn, ack)
                elif action == net.ACTION_BYE:
                    break
                else:
                    raise net.ProtocolError(f"unknown action {action!r}")
        except (ConnectionError, ValueError, OSError):
            pass  # worker vanished mid-exchange; reference behavior: drop it
        finally:
            self._member_leave(member_token)
            if not handoff:
                try:
                    conn.close()
                except OSError:
                    pass
                # forget the socket so stop() never shuts down an unrelated
                # descriptor that reuses this slot
                with self._conn_lock:
                    if conn in self._conns:
                        self._conns.remove(conn)

    # -- in-process transport (transport="inproc") -----------------------------
    # Co-located workers skip sockets and framing entirely and call the
    # SAME center logic the handlers run, under the same lock.  The pair
    # below is the whole inproc wire protocol: pull_direct is the 'P'
    # branch minus the frame, commit_direct is the 'C' branch minus the
    # decode.  The C++ hub exposes the same pair (runtime/native.py), so
    # InprocPSClient works against either hub.

    def pull_direct(self) -> Tuple[List[np.ndarray], int]:
        """Snapshot (center copy, clock at snapshot) — the caller passes the
        clock back with its commit, exactly like a socket worker's
        connection state does."""
        if self._standby and not self._synced.is_set():
            # same rule as the socket pull path: seed weights must never
            # be served as if they were the job's state
            raise RuntimeError(
                "pull_direct from a never-synced standby refused "
                "(it holds no job state yet); wait_synced() first")
        telemetry = obs.enabled()
        # the inproc call runs IN the worker's thread, so the committing
        # worker's thread-local trace context IS the right attribution
        with obs.span("ps.handle_pull", transport="inproc",
                      **self._shard_attrs, **dtrace.current_span_attrs()):
            with self._lock:
                snapshot = [w.copy() for w in self.center]
                clock = self._clock
        if telemetry:
            obs.counter("ps_pulls_total", **self._mlabels).inc()
        return snapshot, clock

    def commit_direct(self, delta: Sequence[np.ndarray], last_pull_clock: int) -> None:
        """Apply one commit with the staleness implied by ``last_pull_clock``
        (the value returned by the matching :meth:`pull_direct`)."""
        if len(delta) != len(self.center):
            raise ValueError(f"commit has {len(delta)} tensors, center has {len(self.center)}")
        for d, c in zip(delta, self.center):
            if np.asarray(d).size != c.size:
                raise ValueError(f"commit tensor size {np.asarray(d).size} != "
                                 f"center size {c.size}")
        telemetry = obs.enabled()
        if self._standby:
            if not self._synced.is_set():
                # same rule as the socket path: a never-synced standby has
                # nothing to take over — refuse loudly rather than promote
                # fresh init weights into "the job's state"
                raise RuntimeError(
                    "commit_direct into a never-synced standby refused "
                    "(it has no state to take over); wait_synced() first")
            self._standby_commit_gate()
            # an inproc commit into a standby means its owner considers it
            # the live hub: promote (fence first, then apply)
            self.promote(reason="commit_direct while standby")
        # dtype/shape normalization outside the lock (no-op views for the
        # trainers' float32 payloads)
        arrays = [np.asarray(d, np.float32).reshape(c.shape)
                  for d, c in zip(delta, self.center)]
        with obs.span("ps.handle_commit", transport="inproc",
                      **self._shard_attrs, **dtrace.current_span_attrs()) as sp:
            # the inproc call runs IN the worker's thread, so its
            # thread-local trace context names the worker; the re-based
            # clock is discarded — inproc callers present theirs per call
            staleness, _ = self._commit_one(
                arrays, last_pull_clock,
                dtrace.current_span_attrs().get("worker"),
                sparse=False, telemetry=telemetry)
            if getattr(sp, "attrs", None) is not None:
                sp.attrs["staleness"] = staleness
        if self._health is not None:
            # guarded HERE so the disabled path never even builds the span
            # attrs dict (the zero-cost-when-off contract)
            self._observe_health(dtrace.current_span_attrs().get("worker"),
                                 "staleness", staleness)
        if telemetry:
            obs.counter("ps_commits_total", **self._mlabels).inc()
            obs.histogram("ps_commit_staleness",
                          **self._mlabels).observe(staleness)

    def pull_sparse_direct(self, ids_list: Sequence[np.ndarray]
                           ) -> Tuple[List[Any], int]:
        """The S/V exchange minus the frame (InprocPSClient's sparse
        path): one validated sorted-unique id array per sparse table in,
        ``(per-leaf values, clock)`` out — full copies for dense leaves,
        the requested ``[k, dim]`` row blocks for sparse leaves."""
        if not self.sparse_leaves:
            raise RuntimeError("pull_sparse_direct on a hub with no sparse "
                               "tables (pass sparse_leaves to the hub)")
        if self._standby and not self._synced.is_set():
            raise RuntimeError(
                "pull_sparse_direct from a never-synced standby refused "
                "(it holds no job state yet); wait_synced() first")
        if len(ids_list) != len(self.sparse_leaves):
            # checked BEFORE the zip below, which would silently truncate
            raise ValueError(f"got {len(ids_list)} id arrays, hub has "
                             f"{len(self.sparse_leaves)} sparse tables")
        ids_list = [self._check_row_ids(
            np.asarray(ids, net.ROW_ID_DTYPE), i)
            for ids, i in zip(ids_list, self.sparse_leaves)]
        telemetry = obs.enabled()
        rows_pulled = int(sum(ids.size for ids in ids_list))
        with obs.span("ps.handle_pull", transport="inproc",
                      sparse_rows=rows_pulled, **self._shard_attrs,
                      **dtrace.current_span_attrs()):
            with self._lock:
                it = iter(ids_list)
                values: List[Any] = [
                    self.center[i][next(it)] if i in self._sparse_set
                    else self.center[i].copy()
                    for i in range(len(self.center))]
                clock = self._clock
                if telemetry:
                    self._touch_rows_locked(
                        zip(self.sparse_leaves, ids_list))
        if telemetry:
            obs.counter("ps_pulls_total", **self._mlabels).inc()
            obs.counter("ps.sparse_rows_pulled",
                        **self._mlabels).inc(rows_pulled)
        return values, clock

    def commit_sparse_direct(self, parts: Sequence[Any],
                             last_pull_clock: int) -> None:
        """Apply one row-sparse commit (the U exchange minus the frame):
        ``parts`` aligned with the center — full f32 delta for dense
        leaves, ``(ids, grads)`` for sparse leaves — with the staleness
        implied by ``last_pull_clock``."""
        if not self.sparse_leaves:
            raise RuntimeError("commit_sparse_direct on a hub with no "
                               "sparse tables (pass sparse_leaves)")
        if len(parts) != len(self.center):
            raise ValueError(f"commit has {len(parts)} parts, center has "
                             f"{len(self.center)}")
        norm: List[Any] = []
        for i, (p, c) in enumerate(zip(parts, self.center)):
            if i in self._sparse_set:
                ids, grads = p
                ids = self._check_row_ids(np.asarray(ids, net.ROW_ID_DTYPE), i)
                grads = np.asarray(grads, np.float32).reshape(
                    ids.size, c.shape[1])
                norm.append((ids, grads))
            else:
                norm.append(np.asarray(p, np.float32).reshape(c.shape))
        telemetry = obs.enabled()
        if self._standby:
            if not self._synced.is_set():
                raise RuntimeError(
                    "commit_sparse_direct into a never-synced standby "
                    "refused (it has no state to take over); "
                    "wait_synced() first")
            self._standby_commit_gate()
            self.promote(reason="commit_sparse_direct while standby")
        rows_committed = int(sum(
            p[0].size for p in norm if isinstance(p, tuple)))
        with obs.span("ps.handle_commit", transport="inproc",
                      sparse_rows=rows_committed, **self._shard_attrs,
                      **dtrace.current_span_attrs()) as sp:
            staleness, _ = self._commit_one(
                norm, last_pull_clock,
                dtrace.current_span_attrs().get("worker"),
                sparse=True, telemetry=telemetry)
            if getattr(sp, "attrs", None) is not None:
                sp.attrs["staleness"] = staleness
        if self._health is not None:
            self._observe_health(dtrace.current_span_attrs().get("worker"),
                                 "staleness", staleness)
        if telemetry:
            obs.counter("ps_commits_total", **self._mlabels).inc()
            obs.counter("ps.sparse_rows_committed",
                        **self._mlabels).inc(rows_committed)
            obs.histogram("ps_commit_staleness",
                          **self._mlabels).observe(staleness)

    # -- commit rules ----------------------------------------------------------
    def apply_commit(self, delta: List[np.ndarray], staleness: int) -> None:  # pragma: no cover
        raise NotImplementedError

    def commit_scale(self, staleness: int) -> float:  # pragma: no cover
        """The scalar this hub multiplies a commit by before adding it to
        the center.  The replication path (``replica_of`` standbys)
        materializes ``delta * commit_scale`` so the replica applies the
        exact post-aggregation bytes the primary did; ``apply_commit``
        stays the non-replicated in-place fast path, and the two must
        agree.  Subclasses with a scaling rule override both."""
        raise NotImplementedError

    def _apply_commit_locked(self, delta: Sequence[np.ndarray],
                             staleness: int) -> Optional[List[np.ndarray]]:
        """Apply one commit (caller holds the center lock) and return the
        scaled applied arrays for the replication feed, or ``None`` when no
        replica is attached — then ``apply_commit`` runs, which for the
        scaling hubs is in place and allocation-free
        (:func:`_add_scaled_commit`).  The replicated branch below performs
        the identical two float32 roundings, multiply then add (and ``x *
        float32(1.0)`` is exact), so a replicated primary's center
        trajectory matches an unreplicated one bit for bit."""
        feed = self._feed
        if feed is None or not feed.active():
            self.apply_commit(list(delta), staleness)
            return None
        scale = np.float32(self.commit_scale(staleness))
        # materialize OWNED copies: socket deltas are views into the
        # connection's receive buffer, which the next frame overwrites —
        # the feed must outlive that
        scaled = [np.asarray(d, np.float32) * scale for d in delta]
        for c, s in zip(self.center, scaled):
            c += s
        return scaled


class DeltaParameterServer(SocketParameterServer):
    """Unscaled delta adds: ``center += delta``.  Reference
    ``DeltaParameterServer`` — serves DOWNPOUR (accumulated gradients) and
    the elastic family (workers pre-scale by alpha)."""

    def apply_commit(self, delta: List[np.ndarray], staleness: int) -> None:
        for c, d in zip(self.center, delta):
            c += d

    def commit_scale(self, staleness: int) -> float:
        return 1.0


class ADAGParameterServer(SocketParameterServer):
    """ADAG normalization: ``center += delta / num_workers`` (reference
    ``ADAGParameterServer.handle_commit``, SURVEY §2.6), applied in place
    with no temporary (:func:`_add_scaled_commit`).

    ``elastic=True`` replaces the static configured denominator with the
    LIVE worker count from hub membership (join on first commit, leave on
    disconnect/idle-lapse, capped at num_workers): when a worker dies
    permanently mid-run, the survivors' deltas stop being diluted by a
    ghost — degraded-but-correct averaging under churn, the elastic
    coordination the EASGD lineage (arXiv:1412.6651) is built on.  The
    cap keeps transient over-registration (a worker reconnecting before
    its old handler noticed the death) from scaling commits UP past the
    configured cohort; zero membership (commits arriving via
    ``commit_direct`` — the inproc transport, which has no connections to
    track) falls back to the static ``num_workers`` denominator."""

    def __init__(self, weights: Sequence[np.ndarray], num_workers: int,
                 elastic: bool = False, **kwargs):
        super().__init__(weights, **kwargs)
        self.num_workers = int(num_workers)
        self.elastic = bool(elastic)

    def _algo_state(self) -> Dict[str, Any]:
        return {"num_workers": self.num_workers, "elastic": self.elastic}

    def commit_scale(self, staleness: int) -> float:
        n = self.num_workers
        if self.elastic:
            live = self.live_workers()
            # membership is a SOCKET-connection concept (join on first
            # commit, leave on disconnect): a socket committer is always
            # its own live member, so live >= 1 here for wire commits.
            # live == 0 means this commit arrived via commit_direct
            # (inproc workers bypass connections) — fall back to the
            # static denominator rather than scaling by 1/1, which would
            # over-apply every inproc delta num_workers-fold
            n = min(live, self.num_workers) if live >= 1 else self.num_workers
        return 1.0 / n

    def apply_commit(self, delta: List[np.ndarray], staleness: int) -> None:
        _add_scaled_commit(self.center, delta, self.commit_scale(staleness),
                           self._apply_scratch)


class DynSGDParameterServer(SocketParameterServer):
    """Staleness-aware scaling: ``center += delta / (staleness + 1)`` where
    staleness = commits applied since this worker's last pull (reference
    ``DynSGDParameterServer.handle_commit``, SURVEY §2.7)."""

    def commit_scale(self, staleness: int) -> float:
        return 1.0 / (staleness + 1.0)

    def apply_commit(self, delta: List[np.ndarray], staleness: int) -> None:
        _add_scaled_commit(self.center, delta, self.commit_scale(staleness),
                           self._apply_scratch)


def _normalize_failover(entry) -> List[Tuple[str, int]]:
    """One shard's failover spec -> list of (host, port): accepts ``None``
    (no standby), one ``(host, port)`` pair, or a sequence of pairs.  A
    bare string (a pair's stray host, or a sliced-up pair) is a caller
    bug — iterating its characters would fabricate garbage addresses."""
    if entry is None:
        return []
    if isinstance(entry, (str, bytes)):
        raise ValueError(f"failover entry {entry!r} is a bare string; "
                         f"pass a (host, port) pair or a list of them")
    entry = list(entry)
    if entry and isinstance(entry[0], (str, bytes)):
        return [(str(entry[0]), int(entry[1]))]
    return [(str(h), int(p)) for h, p in entry]


class StripeLostError(ConnectionError):
    """One stripe of a sharded PS deployment is gone: the per-shard
    connection named here exhausted its reconnect/failover budget (or was
    configured fail-fast) mid fan-out.  Subclasses ``ConnectionError`` so
    every pre-existing handler still catches it; the shard identity
    (index + address) rides the exception so an operator knows WHICH hub
    to look at instead of a generic connection error."""

    def __init__(self, shard_index: int, host: str, port: int,
                 cause: BaseException):
        self.shard_index = int(shard_index)
        self.host = str(host)
        self.port = int(port)
        super().__init__(
            f"PS stripe lost: shard {self.shard_index} at "
            f"{self.host}:{self.port} ({type(cause).__name__}: {cause})")


def _quantize_commit(delta: Sequence[np.ndarray],
                     residual: List[np.ndarray]) -> List[np.ndarray]:
    """Advance the int8 error-feedback chain one commit: quantize each
    delta WITH its carried residual, store the new residual in place, and
    return the wire blobs (uint8 arrays: be-f32 scale + int8 values).

    The one implementation both transports call — the socket client frames
    the blobs as an action-``Q`` message, the inproc client dequantizes
    them right back — so the quantize/residual math can never fork between
    transports (the bit-parity property ``tests/test_transport.py`` pins)."""
    blobs = []
    for i, d in enumerate(delta):
        carried = np.asarray(d, np.float32) + residual[i]
        blob, residual[i] = net.quantize_q_blob(carried)
        blobs.append(np.frombuffer(blob, dtype=np.uint8))
    return blobs


def _sparse_commit_arrays(delta: Sequence[np.ndarray],
                          templates: Sequence[np.ndarray],
                          sparse_set, ids_list: Sequence[np.ndarray],
                          residual: Optional[List[np.ndarray]],
                          compress: Optional[str]) -> List[np.ndarray]:
    """Full-order delta + per-table touched-row ids -> the U/X wire blob
    arrays (advancing the int8 residuals in place) — the one
    implementation both transports share, so the row-gather and
    quantize/residual math can never fork between sockets and inproc.

    int8 residuals use the documented DENSE-residual fallback: one
    full-table float32 residual per sparse leaf (the same array the dense
    path would keep), indexed by the touched rows — per-row error
    feedback without a second bookkeeping structure.  Each table's row
    block is quantized as ONE unit (one scale for the [k, dim] block)."""
    arrays: List[np.ndarray] = []
    it = iter(ids_list)
    for i, d in enumerate(delta):
        if i in sparse_set:
            ids = next(it)
            rows = np.ascontiguousarray(np.asarray(d, np.float32)[ids])
            if compress == "int8":
                carried = rows + residual[i][ids]
                blob, r = net.quantize_q_blob(carried)
                residual[i][ids] = r
                arrays.append(ids)
                arrays.append(np.frombuffer(blob, np.uint8))
            else:
                arrays.append(ids)
                arrays.append(rows)
        else:
            if compress == "int8":
                carried = np.asarray(d, np.float32) + residual[i]
                blob, residual[i] = net.quantize_q_blob(carried)
                arrays.append(np.frombuffer(blob, np.uint8))
            else:
                arrays.append(np.asarray(d, np.float32))
    return arrays


def _sparse_parts_from_arrays(arrays: Sequence[np.ndarray],
                              templates: Sequence[np.ndarray],
                              sparse_set,
                              compress: Optional[str]) -> List[Any]:
    """Inverse of :func:`_sparse_commit_arrays` at the VALUE level: what
    the hub would reconstruct from those wire blobs — the inproc client
    round-trips every sparse commit through this so compressed inproc
    runs stay trajectory-identical to the wire (the
    ``tests/test_transport.py`` contract, extended to sparse)."""
    parts: List[Any] = []
    it = iter(arrays)
    for i, t in enumerate(templates):
        if i in sparse_set:
            ids = next(it)
            val = next(it)
            dim = t.shape[1]
            if compress == "int8":
                grads = net.dequantize_q_blob(
                    memoryview(val), ids.size * dim).reshape(ids.size, dim)
            else:
                grads = val
            parts.append((ids, grads))
        else:
            val = next(it)
            if compress == "int8":
                parts.append(net.dequantize_q_blob(
                    memoryview(val), t.size).reshape(t.shape))
            else:
                parts.append(val)
    return parts


def _init_hot_tier(client: Any, sparse_cache_rows: Optional[int],
                   compress: Optional[str]) -> None:
    """Shared hot-tier state constructor (PSClient + InprocPSClient):
    validates ``sparse_cache_rows``, builds either the PR-9 full-size
    per-table caches (``None``) or one bounded :class:`_RowLRU` per
    table, and the evict-forces-flush overflow.  Requires
    ``client.templates`` / ``client._sparse`` to be set."""
    client._cache_rows = (None if sparse_cache_rows is None
                          else int(sparse_cache_rows))
    if client._cache_rows is not None:
        if not client._sparse:
            raise ValueError("sparse_cache_rows needs sparse_leaves")
        if client._cache_rows < 1:
            raise ValueError(f"sparse_cache_rows must be >= 1, got "
                             f"{client._cache_rows}")
    if client._cache_rows is None:
        client._cache = {i: np.array(client.templates[i], np.float32)
                         for i in client._sparse}
        client._lru = {}
    else:
        client._cache = {}
        client._lru = {
            i: _RowLRU(min(client._cache_rows,
                           client.templates[i].shape[0]),
                       client.templates[i].shape[1],
                       residual=(compress == "int8"))
            for i in client._sparse}
    # evict-forces-flush overflow (int8 cache mode): leaf -> {row id ->
    # pending residual row} accumulated at eviction, flushed as extra
    # (ids, residual) rows on the next sparse commit of that leaf —
    # eviction never LOSES a pending residual
    client._flush_pending = {i: {} for i in client._sparse}


def _hot_tier_gather(client: Any, ids_list: Sequence[np.ndarray]
                     ) -> Tuple[List[np.ndarray], List[np.ndarray],
                                List[np.ndarray]]:
    """Resolve one pull's ids against the LRUs NOW (hit values copied
    into fresh result blocks at this instant); returns
    ``(blocks, miss_positions, miss_ids)`` per table.  Counts the hits
    into the registry."""
    hits0 = client.sparse_cache_hits
    blocks: List[np.ndarray] = []
    miss_pos: List[np.ndarray] = []
    miss: List[np.ndarray] = []
    for ids, i in zip(ids_list, client._sparse):
        block = np.empty((ids.size, client.templates[i].shape[1]),
                         np.float32)
        mp, miss_ids = client._lru[i].gather(ids, block)
        blocks.append(block)
        miss_pos.append(mp)
        miss.append(miss_ids)
    if obs.enabled() and client.sparse_cache_hits > hits0:
        obs.counter("ps_sparse_cache_hits_total",
                    **getattr(client, "_mlabels", {})).inc(
            client.sparse_cache_hits - hits0)
    return blocks, miss_pos, miss


def _hot_tier_file_misses(client: Any, leaf: int, miss_ids: np.ndarray,
                          rows: np.ndarray) -> None:
    """File one table's freshly-pulled miss rows into its LRU,
    accumulating evicted rows' pending int8 residuals into the flush
    overflow (the evict-forces-flush rule)."""
    for rid, res_row in client._lru[leaf].insert(miss_ids, rows):
        pend = client._flush_pending[leaf]
        if rid in pend:
            pend[rid] += res_row
        else:
            pend[rid] = res_row


def _count_cache_misses(client: Any, misses0: int) -> None:
    if obs.enabled() and client.sparse_cache_misses > misses0:
        obs.counter("ps_sparse_cache_misses_total",
                    **getattr(client, "_mlabels", {})).inc(
            client.sparse_cache_misses - misses0)


def _hot_tier_seed(client: Any, leaf: int, full: np.ndarray) -> None:
    """A full pull's table values refresh every RESIDENT row and, on
    first contact, seed the LRU with the table's lowest ids (CTR
    vocabularies conventionally place frequent ids low; a wrong guess
    only costs misses)."""
    lru = client._lru[leaf]
    full = np.asarray(full, np.float32)
    if not lru.slots:
        seed = np.arange(lru.cap, dtype=net.ROW_ID_DTYPE)
        lru.insert(seed, full[:lru.cap])
        lru.misses -= lru.cap  # seeding is not demand misses
    else:
        for rid, slot in lru.slots.items():
            lru.vals[slot] = full[rid]


def _hot_tier_commit_arrays(client: Any, delta: Sequence[np.ndarray],
                            ids_list: Sequence[np.ndarray]
                            ) -> List[np.ndarray]:
    """The ONE hot-tier commit implementation both transports share (the
    ``_sparse_commit_arrays`` convention extended to the bounded LRU):
    ``client`` is a PSClient/InprocPSClient in cache mode — its per-leaf
    LRUs supply residual state, evicted-residual flushes join the id set,
    and the post-wire rows merge into resident entries in place."""
    arrays: List[np.ndarray] = []
    it = iter(ids_list)
    for i, d in enumerate(delta):
        if i not in client._sparse_set:
            if client.compress == "int8":
                carried = np.asarray(d, np.float32) + client._residual[i]
                blob, client._residual[i] = net.quantize_q_blob(carried)
                arrays.append(np.frombuffer(blob, np.uint8))
            else:
                arrays.append(np.asarray(d, np.float32))
            continue
        ids = next(it)
        lru = client._lru[i]
        dim = client.templates[i].shape[1]
        pend = client._flush_pending[i]
        if pend:
            ids_all = np.union1d(
                ids, np.fromiter(pend.keys(), np.int64, len(pend)))
        else:
            ids_all = ids
        rows = np.ascontiguousarray(np.asarray(d, np.float32)[ids_all])
        if client.compress == "int8":
            carried = rows + lru.residual_rows(ids_all)
            if pend:
                for pos, rid in enumerate(ids_all):
                    r = pend.pop(int(rid), None)
                    if r is not None:
                        carried[pos] += r
            blob, res = net.quantize_q_blob(carried)
            lru.store_residuals(ids_all, res)
            wire_rows = net.dequantize_q_blob(
                blob, ids_all.size * dim).reshape(ids_all.size, dim)
            arrays.append(ids_all)
            arrays.append(np.frombuffer(blob, np.uint8))
        else:
            wire_rows = rows
            arrays.append(ids_all)
            arrays.append(rows)
        lru.merge(ids_all, wire_rows)
    return arrays


class _RowLRU:
    """Bounded host store for ONE sparse table's hot rows (the hyperscale
    client tier, ISSUE 15): ``cap`` value rows (+ int8 residual rows when
    error feedback is on) keyed by row id, least-recently-used eviction.

    This replaces the full-size per-table host cache AND residual slab of
    the PR-9 client — host memory per table drops from ``rows x dim x 4``
    (x2 under int8) to ``cap x dim x 4`` (x2), so a client serving a
    hundred-GB vocabulary holds only its hot tier.  Semantics:

    - ``gather`` resolves a pull's ids against the store: hit rows are
      copied out IMMEDIATELY (so later merges/evictions can never tear a
      pull that was already resolved) and only the misses go to the wire;
    - ``insert`` files a miss reply's fresh rows, evicting LRU victims;
      an evicted row's pending int8 residual is RETURNED to the caller
      (the evict-forces-flush rule — it piggybacks on the next commit,
      never silently dropped);
    - ``merge`` folds the client's OWN committed rows into resident
      entries in place (hits merge in place), keeping a hit's value
      exact under scale-1 hubs and within the async staleness tolerance
      otherwise (other workers' updates arrive when the row next
      misses).

    Not thread-safe: owned by the client's caller thread like every other
    pipeline structure."""

    def __init__(self, cap: int, dim: int, residual: bool):
        self.cap = max(1, int(cap))
        self.dim = int(dim)
        self.vals = np.zeros((self.cap, self.dim), np.float32)
        self.res = (np.zeros((self.cap, self.dim), np.float32)
                    if residual else None)
        # id -> slot; Python dicts preserve insertion order, so re-inserting
        # on touch makes the FIRST key the LRU victim (an OrderedDict
        # without the import)
        self.slots: Dict[int, int] = {}
        self._free = list(range(self.cap - 1, -1, -1))
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def nbytes(self) -> int:
        return self.vals.nbytes + (self.res.nbytes if self.res is not None
                                   else 0)

    def _touch(self, rid: int, slot: int) -> None:
        del self.slots[rid]
        self.slots[rid] = slot

    def gather(self, ids: np.ndarray, out: np.ndarray
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve ``ids`` (sorted unique) against the store: hit rows are
        copied into their positions of ``out`` ([k, dim], the pull's
        result block) now; returns ``(miss_positions, miss_ids)`` — the
        rows the wire must fetch."""
        miss_pos: List[int] = []
        for pos, rid in enumerate(ids):
            slot = self.slots.get(int(rid))
            if slot is None:
                miss_pos.append(pos)
            else:
                out[pos] = self.vals[slot]
                self._touch(int(rid), slot)
                self.hits += 1
        mp = np.asarray(miss_pos, np.int64)
        return mp, ids[mp]

    def insert(self, ids: np.ndarray, rows: np.ndarray
               ) -> List[Tuple[int, np.ndarray]]:
        """File freshly-pulled rows (misses, or a seeding pass); returns
        ``[(evicted id, pending residual row)]`` for victims whose int8
        residual was nonzero (the evict-forces-flush payload)."""
        flushed: List[Tuple[int, np.ndarray]] = []
        for pos, rid in enumerate(ids):
            rid = int(rid)
            slot = self.slots.get(rid)
            if slot is not None:
                self.vals[slot] = rows[pos]
                self._touch(rid, slot)
                continue
            self.misses += 1
            if self._free:
                slot = self._free.pop()
            else:
                victim, slot = next(iter(self.slots.items()))
                del self.slots[victim]
                self.evictions += 1
                if self.res is not None and self.res[slot].any():
                    flushed.append((victim, self.res[slot].copy()))
            self.vals[slot] = rows[pos]
            if self.res is not None:
                self.res[slot] = 0.0
            self.slots[rid] = slot
        return flushed

    def merge(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Fold the client's own committed (post-wire) rows into resident
        entries in place; absent rows are skipped (they re-pull fresh on
        their next miss)."""
        for pos, rid in enumerate(ids):
            slot = self.slots.get(int(rid))
            if slot is not None:
                self.vals[slot] += rows[pos]

    def residual_rows(self, ids: np.ndarray) -> np.ndarray:
        """[k, dim] residual block for ``ids``: resident rows read their
        slot, absent rows read zero (their pending residual, if any, was
        already flushed at eviction)."""
        out = np.zeros((len(ids), self.dim), np.float32)
        if self.res is not None:
            for pos, rid in enumerate(ids):
                slot = self.slots.get(int(rid))
                if slot is not None:
                    out[pos] = self.res[slot]
        return out

    def store_residuals(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Write back post-quantization residual rows for resident ids;
        a non-resident id's fresh rounding error (at most one quantization
        step of this block) is dropped — the documented flush tail."""
        if self.res is None:
            return
        for pos, rid in enumerate(ids):
            slot = self.slots.get(int(rid))
            if slot is not None:
                self.res[slot] = rows[pos]


class _HotTierCacheSurface:
    """The hot-tier cache accessors both transports share (ISSUE 15):
    hit/miss totals for health reports + registry deltas, and the host
    bytes the sparse caches hold — bounded LRU stores in cache mode, the
    full-size per-table caches (+ int8 residual slabs) otherwise."""

    @property
    def sparse_cache_hits(self) -> int:
        """Pulled rows served from the hot-tier LRU (zero wire cost);
        0 for full-cache clients."""
        return sum(lru.hits for lru in self._lru.values())

    @property
    def sparse_cache_misses(self) -> int:
        """Pulled rows that took a wire fetch + LRU slot; 0 for
        full-cache clients."""
        return sum(lru.misses for lru in self._lru.values())

    def sparse_cache_bytes(self) -> int:
        """Host bytes the sparse-table caches hold (compare against the
        full-vocabulary footprint)."""
        if self._cache_rows is not None:
            return sum(lru.nbytes() for lru in self._lru.values())
        total = sum(c.nbytes for c in self._cache.values())
        if self._residual is not None:
            total += sum(self._residual[i].nbytes for i in self._sparse)
        return total


_CLIENT_ORDINALS = itertools.count()


class PSClient(_HotTierCacheSurface):
    """Worker-side connection: ``pull()`` / ``commit(delta)`` (reference:
    ``NetworkWorker.pull/commit``, SURVEY §2.10) — plus the pipelined
    fire-and-forget API the async hot path runs on
    (``pull_nowait`` / ``land_weights`` / ``wait_weights`` /
    ``commit_nowait`` / ``drain``).

    Framing is the zero-copy flat path (:class:`~.networking.FlatFrameCodec`):
    a dense float32 commit is STREAMED — no frame is packed, each leaf's
    bytes leave from the leaf's own buffer in wire order, and a leaf handed
    over as a device array whose copy-out was issued is waited for only
    when its bytes are due (``send_streamed``); int8 and row-sparse commits,
    which need whole arrays first, leave through a packed frame buffer
    (one memcpy per tensor, single ``sendall``).  Pulls scatter-receive
    with ``recv_into`` into one of two reusable landing buffers —
    double-buffered because the caller may still be consuming pull *k*
    while the prefetched pull *k+1* streams in.  Arrays returned by ``pull``/``wait_weights`` therefore
    alias client-owned storage that is REUSED two pulls later; copy
    anything that must outlive that.

    Pipelining: the nowait methods send a request and record the expected
    reply in a FIFO; replies are consumed lazily, in wire order, by
    ``land_weights``/``wait_weights``/``drain`` — commit acks coalesce into
    the next weights receive instead of costing their own blocking round
    trip.  ``land_weights`` is the receive a caller places where its thread
    has nothing else to do (beside the window program): a reply larger than
    the socket's buffers crosses the wire only while this end reads.  At
    most ``max_inflight`` commits ride unacknowledged (enforced by
    consuming replies before sending more: wire back-pressure, not an
    unbounded queue).  After any mid-frame error the stream is
    desynchronized — the connection is single-use, callers drop it.

    ``compress="int8"`` sends commits as action-``Q`` frames — symmetric
    per-tensor int8 with a float32 scale (4x fewer wire bytes) — keeping
    the quantization residual client-side and folding it into the next
    commit (error feedback: the sum of dequantized commits tracks the sum
    of true deltas, so compression does not bias the center).  The
    residual chain advances at QUANTIZATION time: pipelined commits have
    no per-commit ack to gate on, and a dead connection is fatal to the
    worker anyway (nothing reconnects and retries a half-sent commit).
    Pulls always stay full precision: weight error hits the model
    directly, while delta rounding error is recycled.

    Resilience (``timeout`` is the per-recv/send socket timeout — a hub
    that stops responding surfaces as ``socket.timeout`` instead of a
    hang): with ``max_reconnects > 0``, any connection fault (reset, EOF,
    recv timeout, desynchronized stream) triggers reconnection with
    exponential backoff + jitter — in-flight pipelined state is DISCARDED
    (unacked commits are lost; async SGD tolerates dropped updates),
    in-flight pulls are re-issued against the new connection so the next
    ``wait_weights`` observes the (possibly restarted) hub's fresh center,
    and the interrupted operation is retried.  ``max_reconnects`` is a
    lifetime budget (a flapping hub cannot storm forever);
    ``reconnect_backoff`` seeds the exponential delay, capped at
    ``reconnect_backoff_max``, each attempt jittered into
    ``[0.5, 1.0] x`` the nominal delay so a fleet of workers does not
    thundering-herd a restarted hub.  With the default
    ``max_reconnects=0`` faults raise exactly as before.

    ``heartbeat_interval`` (seconds, default off) starts a daemon thread
    that sends a 13-byte ping whenever the connection has been idle that
    long with nothing in flight — keeping a slow-but-alive worker (long
    compile, big window) from tripping the hub's ``idle_timeout``
    eviction.  Socket sends and reply bookkeeping share one lock so the
    ping and its ack slot into the reply FIFO without racing the hot path.

    Telemetry (client side): ``ps.commit_bytes`` wire bytes,
    ``ps.pull_latency_ms`` / ``ps.commit_latency_ms`` send-to-reply-
    consumed latencies, ``ps.pull_stall_ms`` time BLOCKED receiving
    weights (in ``wait_weights`` and ``commit_nowait``'s guard the stall the
    trainer pays; in ``land_weights`` it lies beside the device's compute),
    ``ps_pulls_landed_early_total`` replies claimed by ``land_weights``,
    ``ps_commits_streamed_total`` commits sent without a packed frame
    (beside the hub's ``ps_commits_total``),
    ``ps.serialize_ms`` frame-pack time, ``ps.inflight_depth`` unacked
    commits, ``ps.reconnects`` successful reconnections and
    ``ps.reconnect_ms`` fault-to-reconnected recovery time."""

    def __init__(self, host: str, port: int, templates: Sequence[np.ndarray],
                 timeout: Optional[float] = 60.0,
                 compress: Optional[str] = None,
                 max_inflight: int = 2,
                 max_reconnects: int = 0,
                 reconnect_backoff: float = 0.1,
                 reconnect_backoff_max: float = 5.0,
                 heartbeat_interval: Optional[float] = None,
                 trace_context: Optional["dtrace.TraceContext"] = None,
                 shard_id: Optional[int] = None,
                 failover: Sequence[Tuple[str, int]] = (),
                 sparse_leaves: Sequence[int] = (),
                 adaptive: bool = False,
                 sparse_cache_rows: Optional[int] = None,
                 shm: bool = False,
                 job: Optional[str] = None):
        if compress not in (None, "int8"):
            raise ValueError(f"unknown compress {compress!r}; use None or 'int8'")
        self.templates = [np.asarray(t, dtype=np.float32) for t in templates]
        self.compress = compress
        # row-sparse embedding tables (ISSUE 9): leaf indices exchanged by
        # row set.  The client keeps one full-size host CACHE per table: a
        # full pull (sparse_rows=None) seeds it, each sparse pull merges
        # just the touched rows into it, and wait_weights hands the cache
        # out in place of a landing buffer — callers see full-order weight
        # lists either way while only touched rows cross the wire.  Rows
        # the hub updated that this worker never re-pulls stay stale in
        # the cache, which is exactly the per-row staleness the async
        # algorithms already tolerate (untouched rows also receive no
        # gradient, so their committed delta is zero)
        self._sparse = tuple(sorted({int(i) for i in sparse_leaves}))
        for i in self._sparse:
            if not 0 <= i < len(self.templates):
                raise ValueError(f"sparse leaf index {i} out of range for "
                                 f"{len(self.templates)} templates")
            if self.templates[i].ndim != 2:
                raise ValueError(f"sparse leaf {i} must be a [rows, dim] "
                                 f"table, got {self.templates[i].shape}")
        self._sparse_set = frozenset(self._sparse)
        # hot-tier client caching (ISSUE 15): ``sparse_cache_rows=N``
        # replaces the full-size per-table host cache (and, under int8,
        # the full-size residual slab) with one bounded :class:`_RowLRU`
        # per table — host memory scales with the configured hot tier,
        # not the vocabulary.  A sparse pull then fetches only the rows
        # NOT resident (hits are gathered locally at issue time, so a
        # hot row costs zero wire), ``wait_weights`` hands back a
        # ``[k, dim]`` row block aligned with the request ids instead of
        # a full-shape table, and the client's own commits merge into
        # resident rows in place.  ``None`` (default) keeps the PR-9
        # full-cache path byte-identical.
        _init_hot_tier(self, sparse_cache_rows, compress)
        self._sp_enc = net.VarFrameEncoder() if self._sparse else None
        # ids of in-flight sparse pulls, FIFO-aligned with the
        # ACTION_SPARSE_WEIGHTS entries in _pending (a reconnect re-issues
        # from here, so it never clears with _pending).  Full-cache mode
        # entries are the per-table id lists; cache mode entries are
        # richer records (request ids + the partially-gathered result
        # blocks + the miss subsets the wire was asked for)
        self._sparse_pull_ids: Deque[Any] = deque()
        # per-shard connection of a striped client (ShardedPSClient): every
        # client-side metric/span carries the shard label so the per-shard
        # wall/wire decomposition is readable straight off the registry.
        # None (all unsharded callers) emits the exact pre-sharding series
        self.shard_id = None if shard_id is None else int(shard_id)
        self._mlabels = ({} if shard_id is None
                         else {"shard": str(int(shard_id))})
        # failover-event dedup key: a process-monotonic ordinal, NOT
        # id(self) — CPython reuses addresses after GC, and a recycled id
        # would let a replacement client's failover land inside the dead
        # client's cooldown and vanish
        self._client_ordinal = next(_CLIENT_ORDINALS)
        # int8 error-feedback residuals: full-shape per leaf — except the
        # sparse leaves of a hot-tier client, whose residuals live in the
        # bounded LRU slots (None placeholders keep leaf alignment)
        self._residual = ([None if (self._cache_rows is not None
                                    and i in self._sparse_set)
                           else np.zeros(t.shape, np.float32)
                           for i, t in enumerate(self.templates)]
                          if compress else None)
        self._codec = net.FlatFrameCodec(self.templates)
        # int8 commits have their own fixed layout (4-byte scale + one int8
        # per element), so they get their own preallocated frame
        self._q_codec = (net.FlatFrameCodec(
            [np.zeros(4 + t.size, np.uint8) for t in self.templates])
            if compress == "int8" else None)
        self.max_inflight = max(1, int(max_inflight))
        self._pending: Deque[Tuple[bytes, float]] = deque()  # expected replies, wire order
        self._pull_frame = net.empty_tensor_frame(net.ACTION_PULL)
        # hot-tier mode keeps NO preallocated full-shape landing storage
        # for sparse leaves (that storage is the memory the LRU bounds);
        # the rare full pull (initial seed, explicit re-sync) lands those
        # slots in transient arrays allocated per call
        # np.empty(shape), never empty_like: a template only carries shape
        # and dtype, and empty_like would copy its STRIDES too — on the TPU
        # np.asarray of a device array can come back in a non-C layout
        # (seen on v5e for a [256, 10] leaf), and a landing buffer must be
        # C-contiguous to take the wire's row-major bytes
        if self._cache_rows is None:
            self._pull_bufs = tuple(
                [np.empty(t.shape, t.dtype) for t in self.templates]
                for _ in range(2))
        else:
            self._pull_bufs = tuple(
                [None if i in self._sparse_set else np.empty(t.shape, t.dtype)
                 for i, t in enumerate(self.templates)]
                for _ in range(2))
        self._flip = 0
        # weights replies consumed off the wire but not yet claimed by
        # wait_weights (land_weights and commit_nowait's guard put them
        # here); two landing buffers bound this queue at two entries.
        # Each entry carries the full-cache rows its reply brought: they
        # are written into ``_cache`` only at hand-out (_hand_out), since
        # the cache arrays are what the PREVIOUS pull handed out and a
        # caller that lands a reply early may have a window program still
        # reading them (a device_put can alias, or still be copying, a
        # numpy buffer)
        self._ready: Deque[Tuple[List[np.ndarray],
                                 List[Tuple[int, Any, np.ndarray]]]] = deque()
        self.host, self.port, self.timeout = host, int(port), timeout
        # failover address list (ISSUE 7): the primary's address first,
        # then each hot standby.  Reconnect attempts rotate through the
        # list (retry the current address once, then walk the standbys),
        # all under the ONE lifetime budget — failing over is just a
        # reconnect that lands elsewhere, so the backoff/jitter/budget
        # semantics PR 4 established apply unchanged
        self._addresses: List[Tuple[str, int]] = (
            [(str(host), int(port))]
            + [(str(h), int(p)) for h, p in (failover or ())])
        self._addr_idx = 0
        self.max_reconnects = int(max_reconnects)
        self.reconnect_backoff = float(reconnect_backoff)
        self.reconnect_backoff_max = float(reconnect_backoff_max)
        self.reconnects_used = 0
        # reconnects that LANDED on a different (standby) address — the
        # cumulative count the worker's health reports carry (ISSUE 8), so
        # the hub-side failover-storm detector sees it as a moving series
        self.failovers_used = 0
        # reconnect-storm backpressure (ISSUE 10): adaptive clients
        # announce every reconnect with an action-G frame and honor the
        # hub's retry-after hint — a shed herd spreads over time instead
        # of hammering the hub in lockstep.  Default off: no G frame ever
        # moves, the byte stream is exactly the pre-adaptive one
        self.adaptive = bool(adaptive)
        self.backpressure_waits = 0
        # multi-job namespace (ISSUE 19): job="name" announces a job_ns
        # key on a T frame at every (re)connect and trains against the
        # hub's admission-controlled private center for that job.  None
        # (default): no announce — the default namespace, byte-identical
        # to the pre-multi-job client
        self.job = None if job is None else str(job)
        # zero-copy shm transport (ISSUE 18): shm=True asks every fresh
        # connection for an shm attach (action Z).  The hub offers a ring
        # pair (same host, shm armed) or declines; a LEGACY hub closing
        # on the unknown action reads as a decline too — the client
        # redials plain TCP once, so the stream is never torn.  transport
        # reports what this connection actually rides ("tcp"/"shm") —
        # health reports carry it, distkeras-top displays it
        self.shm = bool(shm)
        self.transport = "tcp"
        # entropy-seeded ON PURPOSE: the jitter exists so a fleet of
        # workers severed by one hub restart does NOT retry in lockstep —
        # a shared deterministic seed would reproduce exactly that herd
        self._jitter = random.Random()
        self._closed = False
        self._consuming = False  # caller blocked in a reply recv
        # serializes socket SENDS and their _pending bookkeeping between
        # the caller thread and the heartbeat thread, so the reply FIFO
        # always matches wire order (receives stay single-threaded: only
        # the caller consumes).  Without a heartbeat thread the caller is
        # the ONLY thread touching the socket, so the hot path takes a
        # no-op guard instead of a real lock — the pipelined exchange pays
        # nothing for resilience it hasn't enabled
        self._io_lock = (threading.Lock() if heartbeat_interval is not None
                         else contextlib.nullcontext())
        self._last_io = time.monotonic()
        self.sock = self._connect_any()
        self._maybe_attach_shm()
        # distributed tracing (ISSUE #5): this worker's trace context,
        # announced over the wire (action T) so the hub's spans are
        # attributable, with the local->hub clock offset estimated from
        # the announce round trips (NTP-style midpoint).  Off (None) by
        # default: an un-announced client sends exactly the pre-T byte
        # stream, so it interoperates with pre-T hubs
        self.trace_context = trace_context
        self.clock_offset_ns = 0
        self.clock_error_ns: Optional[int] = None
        self.heartbeat_interval = (None if heartbeat_interval is None
                                   else float(heartbeat_interval))
        self._hb_stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        self._ping_frame = net.empty_tensor_frame(net.ACTION_PING)
        # job announce first (ISSUE 19): the admission verdict must
        # settle before ANY other traffic — a rejected job fails loudly
        # at construction instead of training on the default center.
        # Same failure contract as the trace announce below: close the
        # socket, leave a closeable object, re-raise
        if self.job is not None:
            try:
                self._announce_job()
            except BaseException:
                try:
                    self.sock.close()
                except OSError:
                    pass
                raise
        # announce AFTER every attribute exists (a failed announce —
        # e.g. tracing enabled against a pre-T hub — must leave an object
        # whose close() works) and BEFORE the heartbeat thread starts
        # (the announce round trips own the socket exclusively)
        if trace_context is not None:
            try:
                self._announce_and_sync()
            except BaseException:
                try:
                    self.sock.close()
                except OSError:
                    pass
                raise
        if self.heartbeat_interval is not None:
            self._hb_thread = threading.Thread(target=self._heartbeat_loop,
                                               daemon=True)
            self._hb_thread.start()

    # -- multi-job namespace (ISSUE 19) ----------------------------------------
    def _announce_job(self) -> None:
        """Send the job-scoped T announce (a ``job_ns`` JSON key) and
        settle the admission verdict.  Runs on a freshly-connected
        socket before any pipelined traffic — the strict reply FIFO is
        never disturbed — and raises :class:`JobAdmissionError` on a
        reject, so a rejected job can never be silently served the
        default center."""
        doc = json.dumps({"job_ns": self.job}).encode("utf-8")
        net.send_frame(self.sock, net.encode_context_payload(doc))
        action, blobs = net.recv_tensors(self.sock)
        if action != net.ACTION_TRACE:
            raise net.ProtocolError(
                f"expected T reply to job announce, got {action!r}")
        _t_ns, admitted, reason = net.decode_admission_payload(blobs)
        if not admitted:
            raise JobAdmissionError(self.job, reason)

    # -- distributed tracing ---------------------------------------------------
    def _announce_and_sync(self, rounds: int = 3) -> None:
        """Send the action-T context announce and estimate the local->hub
        clock offset from its round trips: the hub stamps its monotonic
        clock into each reply, ``offset = hub_ts - (t0 + t1) / 2``, and
        the minimum-RTT sample wins (its error bound, rtt/2, is the
        alignment-error contract ``merge_traces`` documents).  Runs on the
        freshly-connected socket BEFORE any pipelined traffic, so the
        strict reply FIFO is never disturbed."""
        announce = net.encode_context_payload(
            self.trace_context.to_json().encode("utf-8"))
        best_rtt = best_offset = None
        for _ in range(max(1, rounds)):
            t0 = time.perf_counter_ns()
            net.send_frame(self.sock, announce)
            action, blobs = net.recv_tensors(self.sock)
            t1 = time.perf_counter_ns()
            if action != net.ACTION_TRACE:
                raise net.ProtocolError(
                    f"expected T reply to context announce, got {action!r}")
            hub_ns = net.decode_time_payload(blobs)
            rtt = t1 - t0
            if best_rtt is None or rtt < best_rtt:
                best_rtt = rtt
                best_offset = hub_ns - (t0 + t1) // 2
        self.clock_offset_ns = int(best_offset)
        self.clock_error_ns = int(best_rtt) // 2
        dtrace.record_clock_sync(self.clock_offset_ns, self.clock_error_ns)

    # -- resilience ------------------------------------------------------------
    _RETRYABLE = (ConnectionError, OSError, net.ProtocolError)
    # hub-paced retry-after waits are refunded from the reconnect budget
    # up to this many times; past it they start consuming budget again,
    # so a hub that never stops hinting cannot livelock a worker forever
    _MAX_BP_WAITS = 32
    # ceiling on any single honored hint: the hub caps its own at 2 s,
    # so a larger value is a version-skewed/buggy hub or a corrupted
    # blob — a worker must never be parked on a garbage uint64 of ms
    _MAX_RETRY_AFTER_MS = 10_000

    def _reconnect_hello(self, waits_taken: int) -> int:
        """The G/Y round trip on a freshly dialed connection (adaptive
        clients only): announce the reconnect — carrying how many
        hub-paced waits this episode already took, so a client that
        waited its slot is admitted — and return the hub's retry-after
        hint in milliseconds.  Connection faults raise the usual
        retryable types — the attempt's handler rotates and backs off
        exactly as for a failed dial."""
        net.send_frame(self.sock,
                       net.encode_reconnect_payload(waits_taken))
        action, blobs = net.recv_tensors(self.sock)
        if action != net.ACTION_RETRY:
            raise net.ProtocolError(
                f"expected Y reply to reconnect announce, got {action!r}")
        return min(net.decode_retry_payload(blobs), self._MAX_RETRY_AFTER_MS)

    def _connect_any(self) -> socket.socket:
        """Initial connect: the primary first, then each failover address
        in order — a worker (re)started AFTER a failover must be able to
        join the promoted standby without an operator rewriting its
        config.  Raises the primary's error when every address refuses."""
        first_err: Optional[BaseException] = None
        for i, (host, port) in enumerate(self._addresses):
            try:
                sock = net.connect(host, port, timeout=self.timeout,
                                   payload_hint=self._codec.frame_len)
            except OSError as e:
                if first_err is None:
                    first_err = e
                continue
            self._addr_idx = i
            self.host, self.port = host, port
            return sock
        raise first_err  # at least one address exists, so this is set

    def _maybe_attach_shm(self) -> None:
        """The action-Z attach on a freshly dialed connection (shm clients
        only): request, map the offered ring pair, confirm over TCP, then
        swap :attr:`sock` for a :class:`~.networking.ShmEndpoint` — every
        subsequent frame rides shared memory, byte-identical to what the
        socket would have carried.  A decline (or a mapping failure,
        aborted over TCP) leaves the connection pure TCP; a legacy hub
        CLOSING on the unknown action is treated as a decline and the
        client redials plain TCP once — the connection fault never
        escapes, so the protocol model's never-torn walk holds here."""
        self.transport = "tcp"
        if not self.shm:
            return
        try:
            net.send_frame(self.sock, net.encode_shm_request(
                max(net.SHM_RING_DEFAULT_CAPACITY,
                    2 * self._codec.frame_len)))
            action, blobs = net.recv_tensors(self.sock)
            if action != net.ACTION_SHM:
                raise net.ProtocolError(
                    f"expected Z reply to shm request, got {action!r}")
            offer = net.decode_shm_offer(blobs)
        except (ConnectionError, OSError, net.ProtocolError):
            # legacy hub: it dropped the connection on the unknown
            # action.  No frame beyond the Z request ever moved, so a
            # single plain-TCP redial resumes cleanly
            try:
                self.sock.close()
            except OSError:
                pass
            self.sock = self._connect_any()
            return
        if offer is None:
            return  # hub declined; stay on TCP
        c2h_path, h2c_path = offer
        try:
            tx_ring = net.ShmFrameRing.open(c2h_path, "producer")
        except (OSError, net.ProtocolError):
            net.send_frame(self.sock, net.encode_shm_confirm(False))
            return
        try:
            rx_ring = net.ShmFrameRing.open(h2c_path, "consumer")
        except (OSError, net.ProtocolError):
            tx_ring.close()
            net.send_frame(self.sock, net.encode_shm_confirm(False))
            return
        # confirm rides TCP: the hub reads it off the FIFO, so both ends
        # agree the very NEXT frame is on the rings — never a torn stream
        net.send_frame(self.sock, net.encode_shm_confirm(True))
        self.sock = net.ShmEndpoint(self.sock, tx_ring, rx_ring)
        self.sock.settimeout(self.timeout)
        self.transport = "shm"

    def _heartbeat_loop(self) -> None:
        interval = self.heartbeat_interval
        while not self._hb_stop.wait(interval / 4.0):
            with self._io_lock:
                if self._closed:
                    return
                # only ping a genuinely idle connection: traffic in flight
                # already proves liveness, and interleaving a ping between
                # a request and its reply is exactly what the FIFO forbids.
                # _consuming covers the caller mid-receive (it pops the
                # pending entry BEFORE its blocking recv, so _pending alone
                # can look empty while the socket is busy) — its rising
                # edge is serialized with this critical section, so a ping
                # round trip and a caller recv can never interleave
                if (self._pending or self._consuming
                        or time.monotonic() - self._last_io < interval):
                    continue
                try:
                    # the ping's ack is consumed HERE, under the io lock
                    # (the caller is idle by construction — nothing
                    # pending — so this thread owns the whole round trip;
                    # leaving the ack for the caller would stall the next
                    # ping behind a reply nobody is consuming).  The round
                    # trip runs under its OWN short timeout: a ping must
                    # never hold the io lock for the full data-plane
                    # timeout, or close()/reconnect would block behind an
                    # idle-liveness probe for up to a minute
                    ping_timeout = max(1.0, interval)
                    if self.timeout is not None:
                        ping_timeout = min(ping_timeout, self.timeout)
                    self.sock.settimeout(ping_timeout)
                    try:
                        self.sock.sendall(self._ping_frame)
                        net.recv_action(self.sock)
                    finally:
                        self.sock.settimeout(self.timeout)
                    self._last_io = time.monotonic()
                except (OSError, ValueError):
                    # poison the connection: a ping whose ack timed out may
                    # deliver that ack LATE, and a caller then parsing it
                    # as its own reply would desync the stream.  Closing
                    # here turns the caller's next op into a clean
                    # ConnectionError/EBADF — which reconnects when a
                    # budget is configured.  NOTE the whole ping (and this
                    # close) runs under the io lock, and _reconnect swaps
                    # the socket under the SAME lock with _last_io reset:
                    # a ping can never fire into a half-swapped socket,
                    # and a swap can never be poisoned by a stale ping —
                    # so a heartbeat racing a reconnect costs the caller
                    # ZERO budget beyond the real fault
                    # (tests/test_ha.py pins this)
                    try:
                        self.sock.close()
                    except OSError:
                        pass

    def _resilient(self, op):
        """Run ``op`` to completion, reconnecting (bounded) across any
        connection fault.  With ``max_reconnects=0`` the original
        exception propagates untouched — the pre-resilience contract."""
        while True:
            try:
                return op()
            except self._RETRYABLE as e:
                if self._closed or self.max_reconnects <= 0:
                    raise
                self._reconnect(e)

    def _reconnect(self, cause: BaseException) -> None:
        """Tear down the desynchronized connection, back off (exponential +
        jitter), reconnect, and re-issue any pulls that were in flight —
        the re-pull observes the (possibly restarted) hub's CURRENT
        center.  Unacked commits are dropped, not replayed: a commit whose
        send or ack failed may or may not have been applied, and async SGD
        tolerates a lost update far better than a doubled one.  Raises
        ``ConnectionError`` from ``cause`` once the lifetime budget is
        exhausted."""
        t_fault = time.perf_counter()
        t_fault_ns = time.perf_counter_ns()
        addr_at_fault = (self.host, self.port)
        # the ENTIRE teardown/backoff/redial runs under the io lock: the
        # heartbeat thread must neither ping a socket mid-replacement nor
        # close (its failure path) the freshly reconnected one — and with
        # no heartbeat the lock is a no-op context, so the common path
        # pays nothing.  Entered lock-free: every op releases the lock
        # before its exception reaches _resilient
        with self._io_lock:
            # in-flight pulls to re-issue, in wire order; sparse pulls
            # keep their ids in _sparse_pull_ids (which deliberately does
            # NOT clear with _pending — it is the re-issue source)
            lost_kinds = [kind for kind, _ in self._pending
                          if kind in (net.ACTION_WEIGHTS,
                                      net.ACTION_SPARSE_WEIGHTS)]
            self._pending.clear()
            try:
                self.sock.close()
            except OSError:
                pass
            # hub-paced waits taken in THIS reconnect episode: the G
            # announce carries it, so the hub admits us once we have
            # waited our slot (one wait per client per storm).  A redial
            # right after a slot wait skips the exponential backoff —
            # the hub just SCHEDULED our arrival; re-randomizing on top
            # would scramble the paced order the slots exist to create
            bp_episode = 0
            skip_backoff = False
            while True:
                if self.reconnects_used >= self.max_reconnects:
                    raise ConnectionError(
                        f"PS connection to {self.host}:{self.port} lost and the "
                        f"reconnect budget ({self.max_reconnects}) is exhausted"
                        + (f" across {len(self._addresses)} failover addresses"
                           if len(self._addresses) > 1 else "")
                    ) from cause
                self.reconnects_used += 1
                if skip_backoff:
                    skip_backoff = False
                else:
                    nominal = min(self.reconnect_backoff
                                  * (2.0 ** (self.reconnects_used - 1)),
                                  self.reconnect_backoff_max)
                    time.sleep(nominal * (0.5 + 0.5 * self._jitter.random()))
                # address rotation: the current address gets one retry,
                # then attempts walk the failover list — a dead primary's
                # refused connect fails fast, so the standby is reached
                # on the very next budgeted attempt
                host, port = self._addresses[self._addr_idx]
                try:
                    self.sock = net.connect(host, port,
                                            timeout=self.timeout,
                                            payload_hint=self._codec.frame_len)
                    self.host, self.port = host, port
                    # reconnect-storm backpressure (ISSUE 10): announce
                    # the reconnect (action G) and honor the hub's
                    # retry-after hint.  Hub-paced waits are
                    # budget-NEUTRAL (refunded, bounded by _MAX_BP_WAITS
                    # against a hub that never stops hinting): being told
                    # to wait by a healthy hub is not a fault, and a shed
                    # herd must not exhaust its reconnect budgets
                    if self.adaptive:
                        hint_ms = self._reconnect_hello(bp_episode)
                        if hint_ms > 0:
                            try:
                                self.sock.close()
                            except OSError:
                                pass
                            bp_episode += 1
                            self.backpressure_waits += 1
                            if bp_episode <= self._MAX_BP_WAITS:
                                self.reconnects_used -= 1
                            if obs.enabled():
                                obs.counter("ps.backpressure_waits",
                                            **self._mlabels).inc()
                                obs.histogram("ps.retry_after_wait_ms",
                                              **self._mlabels).observe(
                                    hint_ms)
                            time.sleep(hint_ms / 1000.0)
                            skip_backoff = True
                            continue
                    # re-negotiate the shm attach on the fresh connection
                    # (ring files are per-connection; the old pair died
                    # with the old socket).  Landing on TCP — a standby
                    # with shm off, a remote failover target — is a
                    # degrade, not a fault
                    self._maybe_attach_shm()
                    # re-announce the job namespace (admission is
                    # per-connection; a restarted hub re-admits, a full
                    # or standby hub rejects — a ProtocolError here
                    # rotates to the next address under the same budget)
                    if self.job is not None:
                        self._announce_job()
                    # re-announce the trace context on the fresh
                    # connection (a restarted hub has no memory of the
                    # old one) and refresh the clock-offset estimate
                    if self.trace_context is not None:
                        self._announce_and_sync()
                    # re-pull cleanly INSIDE the attempt: the discarded
                    # in-flight pulls are re-issued so wait_weights finds
                    # its reply.  A hub dying again right here must consume
                    # another budgeted attempt, not escape to the caller —
                    # this runs inside _resilient's except handler, where a
                    # raised exception would NOT be re-caught by its loop
                    si = 0
                    for kind in lost_kinds:
                        if kind == net.ACTION_WEIGHTS:
                            self.sock.sendall(self._pull_frame)
                        else:
                            # re-ask for the SAME rows; the reply observes
                            # the restarted hub's current center like any
                            # re-issued pull (hot-tier records re-send
                            # their recorded MISS subset — the hit rows
                            # were resolved locally at issue time)
                            sp = self._sparse_pull_ids[si]
                            self._sp_enc.send(self.sock,
                                              net.ACTION_SPARSE_PULL,
                                              sp["miss"]
                                              if isinstance(sp, dict)
                                              else sp)
                            si += 1
                        self._pending.append((kind, time.perf_counter()))
                    self._last_io = time.monotonic()
                    break
                except (OSError, net.ProtocolError):
                    # hub still down (or died again mid-re-pull/announce):
                    # drop any entries from the half-reconnected socket,
                    # rotate to the next address and back off further
                    self._pending.clear()
                    self._addr_idx = ((self._addr_idx + 1)
                                      % len(self._addresses))
                    continue
        failed_over = (self.host, self.port) != addr_at_fault
        if obs.enabled():
            # labelled by announced worker identity when tracing is on, so
            # fleet_report can attribute reconnect storms to a worker
            wattrs = (self.trace_context.span_attrs()
                      if self.trace_context is not None else {})
            obs.counter("ps.reconnects", **self._mlabels).inc()
            obs.histogram("ps.reconnect_ms", **self._mlabels).observe(
                (time.perf_counter() - t_fault) * 1e3)
            obs.TRACER.record_span("ps.reconnect", t_fault_ns,
                                   time.perf_counter_ns(), **self._mlabels,
                                   **wattrs)
            if failed_over:
                # the reconnect landed on a different (standby) address:
                # record the fault-to-recovered failover time — the
                # availability number the kill-primary drills pin
                obs.counter("ps.failovers", **self._mlabels).inc()
                obs.histogram("ps.failover_ms", **self._mlabels).observe(
                    (time.perf_counter() - t_fault) * 1e3)
                obs.TRACER.record_span(
                    "ps.failover", t_fault_ns, time.perf_counter_ns(),
                    from_addr=f"{addr_at_fault[0]}:{addr_at_fault[1]}",
                    to_addr=f"{self.host}:{self.port}",
                    **self._mlabels, **wattrs)
        if failed_over:
            self.failovers_used += 1
            warnings.warn(f"PS client failed over from "
                          f"{addr_at_fault[0]}:{addr_at_fault[1]} to "
                          f"{self.host}:{self.port}")
            # live health plane (ISSUE 8): surface the failover as a
            # HealthEvent in THIS process's monitor immediately — naming
            # the standby the client landed on — so a co-located
            # distkeras-top / punchcard health pull sees it during the
            # run (remote hubs additionally learn of it through the
            # failovers_total series in the next health report)
            try:
                from distkeras_tpu.observability import health as _health

                _health.monitor().emit(
                    "failover", "critical",
                    worker=(self.trace_context.worker_id
                            if self.trace_context is not None else None),
                    shard=self.shard_id,
                    # untraced clients carry no worker id: without a
                    # per-client dedup, every failover of a multi-worker
                    # fleet in one process would collapse to the first
                    dedup=f"client:{self._client_ordinal}",
                    from_addr=f"{addr_at_fault[0]}:{addr_at_fault[1]}",
                    to_addr=f"{self.host}:{self.port}",
                    failover_ms=round((time.perf_counter() - t_fault) * 1e3,
                                      1))
            except Exception:
                pass

    # -- pipelined API ---------------------------------------------------------
    def pull_nowait(self, sparse_rows: Optional[Sequence] = None) -> None:
        """Fire a pull request; the reply is consumed later — by
        :meth:`land_weights` where the caller has time to spare beside
        the device, else by ``commit_nowait``'s guard or by
        :meth:`wait_weights`, which hands it out.  Issue it while the
        device computes, then land it, and the weights' wire time hides
        under the window: the hub has the reply packed when the request
        arrives, but the bytes move only while this end reads.

        ``sparse_rows`` (sparse-configured clients only): one row-id array
        per sparse table — the pull moves only those rows (action ``S``),
        merging them into the client cache on receive.  ``None`` pulls the
        full center (action ``P``, the pre-sparse byte stream; also
        re-seeds the caches)."""
        with self._io_lock:
            outstanding = (sum(1 for kind, _ in self._pending
                               if kind in (net.ACTION_WEIGHTS,
                                           net.ACTION_SPARSE_WEIGHTS))
                           + len(self._ready))
        if outstanding >= 2:
            raise RuntimeError("at most 2 pulls may be outstanding (two "
                               "landing buffers); claim one with "
                               "wait_weights() first")
        if sparse_rows is None:
            self._resilient(self._pull_nowait_once)
            return
        if not self._sparse:
            raise ValueError("sparse_rows passed to a client with no "
                             "sparse_leaves configured")
        if len(sparse_rows) != len(self._sparse):
            raise ValueError(f"got {len(sparse_rows)} id arrays, client has "
                             f"{len(self._sparse)} sparse tables")
        ids_list = [net.normalize_row_ids(ids, self.templates[i].shape[0])
                    for ids, i in zip(sparse_rows, self._sparse)]
        if self._cache_rows is None:
            self._resilient(lambda: self._sparse_pull_once(ids_list))
            return
        # hot-tier path: resolve hits against the LRU NOW (their values
        # are copied into the result blocks at this instant — the center
        # state a full-cache client's pull would also have observed at
        # issue time) and ask the wire for only the misses.  The gather
        # runs once, outside the retry loop: a reconnect re-sends the
        # SAME miss subset
        blocks, miss_pos, miss = _hot_tier_gather(self, ids_list)
        record = {"ids": ids_list, "out": blocks, "miss_pos": miss_pos,
                  "miss": miss}
        self._resilient(lambda: self._sparse_pull_once(record["miss"],
                                                       record=record))

    def _pull_nowait_once(self) -> None:
        with self._io_lock:
            net.send_raw_frame(self.sock, self._pull_frame)
            self._pending.append((net.ACTION_WEIGHTS, time.perf_counter()))
            self._last_io = time.monotonic()

    def _sparse_pull_once(self, ids_list: List[np.ndarray],
                          record: Optional[Dict[str, Any]] = None) -> None:
        with self._io_lock:
            self._sp_enc.send(self.sock, net.ACTION_SPARSE_PULL, ids_list)
            self._pending.append((net.ACTION_SPARSE_WEIGHTS,
                                  time.perf_counter()))
            self._sparse_pull_ids.append(
                ids_list if record is None else record)
            self._last_io = time.monotonic()

    def commit_nowait(self, delta: Sequence[np.ndarray],
                      sparse_rows: Optional[Sequence] = None) -> None:
        """Send a commit without waiting for its ack (coalesced into a later
        receive).  Blocks only when ``max_inflight`` commits are already
        unacknowledged.

        ``sparse_rows`` (sparse-configured clients only): one row-id array
        per sparse table — the commit carries only those rows' gradients
        as ``(ids, grads)`` pairs (action ``U``, or ``X`` under int8)."""
        # the span covers the work the client actually does per commit
        # (back-pressure + quantize/pack + send); the ack wait is measured
        # separately by ps.commit_latency_ms when the reply is consumed
        with obs.span("ps.commit", compress=self.compress or "none",
                      **self._mlabels):
            self._resilient(
                lambda: self._commit_nowait_once(delta, sparse_rows))

    def land_weights(self) -> None:
        """Claim every pull reply still in flight into its landing buffer
        NOW, with whatever precedes it in the reply FIFO (the previous
        commit's ack); :meth:`wait_weights` hands it out later without
        touching the socket.  For the caller whose thread would otherwise
        only wait for the device: right after ``pull_nowait``, while the
        window program runs.  The hub's ``sendall`` of a reply larger
        than the socket's buffers advances only while this end reads, so
        a reply left for ``commit_nowait``'s guard crosses the wire AFTER
        the program, on the caller's critical path.  What the reply holds
        was fixed when the request arrived (the hub packs under its
        lock), so landing early changes no value; the buffer written is
        the one the previous ``wait_weights`` did NOT hand out.  With
        nothing in flight it returns at once."""
        before = len(self._ready)
        self._resilient(self._claim_pending_weights)
        landed = len(self._ready) - before
        if landed and obs.enabled():
            obs.counter("ps_pulls_landed_early_total",
                        **self._mlabels).inc(landed)

    def _claim_pending_weights(self) -> None:
        t0, claimed = time.perf_counter(), False
        while (self._has_pending(net.ACTION_WEIGHTS)
               or self._has_pending(net.ACTION_SPARSE_WEIGHTS)):
            self._consume_one()
            claimed = True
        if claimed and obs.enabled():
            # the receive time is pull wire-wait, so it lands in
            # ps.pull_stall_ms like any other pull block
            obs.histogram("ps.pull_stall_ms", **self._mlabels).observe(
                (time.perf_counter() - t0) * 1e3)

    def _commit_nowait_once(self, delta: Sequence[np.ndarray],
                            sparse_rows: Optional[Sequence] = None) -> None:
        # deadlock avoidance: never start a potentially-blocking large
        # send while a weights reply may still be in flight — the hub
        # does not read while it writes, so two big sendalls in
        # opposite directions can fill both kernel buffers and stall
        # forever once frames outgrow the socket buffers.  Claim any
        # pending pull into its landing buffer first (wait_weights
        # hands it out later); the hub is then parked in recv when the
        # commit bytes arrive.  A caller that landed its prefetch
        # (land_weights, as the async worker loop does) leaves this
        # guard nothing to claim; it stands for every caller that did not.
        # The three leaf phases below (drain, pack, send) split ps.commit.
        with obs.phase("ps.commit_drain"):
            self._claim_pending_weights()
            while self._unacked() >= self.max_inflight:
                self._consume_one()
        telemetry = obs.enabled()
        t0 = time.perf_counter() if telemetry else 0.0
        if sparse_rows is not None:
            if not self._sparse:
                raise ValueError("sparse_rows passed to a client with no "
                                 "sparse_leaves configured")
            if len(sparse_rows) != len(self._sparse):
                # checked BEFORE the zip below, which would truncate
                raise ValueError(f"got {len(sparse_rows)} id arrays, client "
                                 f"has {len(self._sparse)} sparse tables")
            with obs.phase("ps.commit_pack"):
                ids_list = [
                    net.normalize_row_ids(ids, self.templates[i].shape[0])
                    for ids, i in zip(sparse_rows, self._sparse)]
                if self._cache_rows is None:
                    arrays = _sparse_commit_arrays(
                        delta, self.templates, self._sparse_set, ids_list,
                        self._residual, self.compress)
                else:
                    arrays = self._cached_commit_arrays(delta, ids_list)
                action = (net.ACTION_SPARSE_QCOMMIT
                          if self.compress == "int8"
                          else net.ACTION_SPARSE_COMMIT)
                frame = self._sp_enc.pack(action, arrays)
            if telemetry:
                obs.histogram("ps.serialize_ms", **self._mlabels).observe(
                    (time.perf_counter() - t0) * 1e3)
                obs.counter("ps.commit_bytes",
                            **self._mlabels).inc(self._sp_enc.frame_len)
            with obs.phase("ps.commit_send"):
                with self._io_lock:
                    net.send_raw_frame(self.sock, frame)
                    self._pending.append((net.ACTION_ACK,
                                          time.perf_counter()))
                    self._last_io = time.monotonic()
            if telemetry:
                obs.gauge("ps.inflight_depth",
                          **self._mlabels).set(self._unacked())
            return
        # a dense float32 commit needs no whole arrays before its first
        # byte, so it is STREAMED: no frame is packed, each leaf's bytes
        # leave from the leaf's own buffer (FlatFrameCodec.send_streamed)
        # — for a device leaf whose copy-out was issued, as it lands.  A
        # retry after a failure mid-stream sends the whole commit again
        # from the same leaves (a landed device array keeps its host
        # value), on a fresh connection: the hub applies a commit only
        # once its last byte is in, so the cut one never counts.  int8
        # quantises whole arrays first and keeps pack + send_packed
        streamed = self.compress is None
        with obs.phase("ps.commit_pack"):
            if self.compress == "int8":
                codec, action = self._q_codec, net.ACTION_QCOMMIT
                # safe across a reconnect retry: the residual chain carries
                # only ROUNDING error, so re-quantizing the same delta
                # after a failed (never-applied) send still lands the
                # delta once
                arrays = _quantize_commit(delta, self._residual)
                codec.pack(action, arrays)
            else:
                codec, action = self._codec, net.ACTION_COMMIT
                # a float32 leaf goes as it is, device arrays included
                # (converting one here would wait for its copy-out)
                arrays = [d if getattr(d, "dtype", None) == np.float32
                          else np.asarray(d, np.float32) for d in delta]
        if telemetry:
            obs.histogram("ps.serialize_ms", **self._mlabels).observe(
                (time.perf_counter() - t0) * 1e3)
            obs.counter("ps.commit_bytes", **self._mlabels).inc(codec.frame_len)
        with obs.phase("ps.commit_send"):
            with self._io_lock:
                if streamed:
                    codec.send_streamed(self.sock, action, arrays)
                else:
                    codec.send_packed(self.sock)
                self._pending.append((net.ACTION_ACK, time.perf_counter()))
                self._last_io = time.monotonic()
        if telemetry:
            if streamed:
                obs.counter("ps_commits_streamed_total",
                            **self._mlabels).inc()
            obs.gauge("ps.inflight_depth", **self._mlabels).set(self._unacked())

    def _cached_commit_arrays(self, delta: Sequence[np.ndarray],
                              ids_list: List[np.ndarray]) -> List[np.ndarray]:
        """Hot-tier twin of :func:`_sparse_commit_arrays`: U/X wire blobs
        for one commit with the per-row state read from the bounded LRU
        instead of full-shape slabs.  Three extra duties:

        - **flush union**: row ids whose int8 residuals were evicted
          since the last commit join this commit's id set (their delta
          rows are the model's true gradient for those rows — zero when
          untouched — plus the flushed residual), so eviction never
          loses error-feedback state;
        - **slot residuals**: carried/stored per resident row; a row
          evicted AND flushed in the same interval contributes both its
          pending and (zeroed-at-reinsert) slot residual exactly once;
        - **hits merge in place**: the post-wire committed rows (the
          exact values the hub will apply at scale 1) fold into resident
          LRU entries, so a hot row's cached value tracks this client's
          own progress between misses.

        With ``cache_rows >= vocabulary`` (no evictions) the produced
        wire bytes are identical to the full-slab path's — the
        trajectory-parity property ``tests/test_hyperscale.py`` pins."""
        return _hot_tier_commit_arrays(self, delta, ids_list)

    def wait_weights(self) -> List[np.ndarray]:
        """Hand out the oldest in-flight pull, consuming replies (and any
        commit acks queued ahead of it) as needed."""
        telemetry = obs.enabled()
        t0 = time.perf_counter() if telemetry else 0.0
        self._resilient(self._fill_ready_once)
        if telemetry:
            obs.histogram("ps.pull_stall_ms", **self._mlabels).observe(
                (time.perf_counter() - t0) * 1e3)
        return self._hand_out()

    def _hand_out(self) -> List[np.ndarray]:
        weights, merges = self._ready.popleft()
        for i, ids, rows in merges:
            self._cache[i][ids] = rows
        return weights

    def _fill_ready_once(self) -> None:
        while not self._ready:
            if not self._pending:
                # caller bug, not a connection fault (RuntimeError keeps it
                # out of _RETRYABLE — it must not burn the reconnect
                # budget; matches InprocPSClient's contract)
                raise RuntimeError("wait_weights() with no pull in flight")
            self._consume_one()

    def drain(self) -> None:
        """Consume every outstanding reply — trailing commit acks at the end
        of a run, plus any prefetched pull that will go unused (the rows it
        brought still join the full cache, as when it was received)."""
        self._resilient(self._drain_once)
        while self._ready:
            self._hand_out()
        if obs.enabled():
            obs.gauge("ps.inflight_depth", **self._mlabels).set(0)

    def _drain_once(self) -> None:
        while self._pending:
            self._consume_one()

    # -- live health plane (ISSUE 8) -------------------------------------------
    def report_health(self, report: Dict[str, Any]) -> None:
        """Push one compact health report to the hub (wire action ``M``) —
        the worker half of the streaming collector.  Fire-and-forget on
        the pipelined FIFO: the hub's ack coalesces into later receives
        exactly like a commit ack, so a report costs one small send, not a
        round trip.  Opt-in like the ``T`` announce: a client that never
        reports sends exactly the pre-``M`` byte stream (and a report sent
        to a hub that predates action ``M`` surfaces as a connection
        fault, the documented upgrade contract)."""
        payload = net.encode_health_payload(
            json.dumps(report).encode("utf-8"))
        self._resilient(lambda: self._report_health_once(payload))

    def _report_health_once(self, payload: bytes) -> None:
        with self._io_lock:
            # send_frame (not send_raw_frame): encode_health_payload
            # returns the prefix-less payload, like the T announce.
            # Pending kind is ACTION_HEALTH, not ACTION_ACK: the hub's
            # reply frame is the same ack byte, but a health ack must not
            # land in ps.commit_latency_ms or hold a max_inflight commit
            # slot (_unacked counts ACTION_ACK entries only)
            net.send_frame(self.sock, payload)
            self._pending.append((net.ACTION_HEALTH, time.perf_counter()))
            self._last_io = time.monotonic()

    def _has_pending(self, kind: bytes) -> bool:
        # snapshot under the io lock: the heartbeat thread appends to
        # _pending, and a deque must not be iterated during a mutation
        with self._io_lock:
            return any(k == kind for k, _ in self._pending)

    def _unacked(self) -> int:
        with self._io_lock:
            return sum(1 for kind, _ in self._pending if kind == net.ACTION_ACK)

    def _consume_one(self) -> None:
        # mark the receive busy UNDER the io lock: if a heartbeat round
        # trip is in flight we wait for it to finish; once set, the
        # heartbeat thread will not start another until we clear it
        with self._io_lock:
            self._consuming = True
        try:
            self._consume_one_inner()
        finally:
            self._consuming = False

    def _consume_one_inner(self) -> None:
        kind, t_sent = self._pending.popleft()
        if kind == net.ACTION_SPARSE_WEIGHTS:
            # sparse pull reply: dense leaves scatter into the flip
            # landing buffers exactly like a full pull, row blocks land in
            # per-pull scratch.  Full-cache mode hands the per-table
            # caches out and merges the blocks into them then, at
            # hand-out (see ``_ready``); hot-tier mode
            # files the MISS rows into their result-block positions and
            # the LRU (hit rows were gathered at issue time), handing the
            # [k, dim] blocks out instead of full-shape tables
            entry = self._sparse_pull_ids[0]
            cached = isinstance(entry, dict)
            ids_list = entry["miss"] if cached else entry
            bufs = self._pull_bufs[self._flip]
            self._flip ^= 1
            out: List[np.ndarray] = []
            si = 0
            for i, t in enumerate(self.templates):
                if i in self._sparse_set:
                    out.append(np.empty((ids_list[si].size, t.shape[1]),
                                        np.float32))
                    si += 1
                else:
                    out.append(bufs[i])
            try:
                reply, _ = net.recv_tensors(self.sock, out=out)
                if reply != net.ACTION_SPARSE_WEIGHTS:
                    raise ConnectionError(
                        f"expected sparse weights reply, got {reply!r}")
            except Exception:
                self._flip ^= 1
                self._pending.appendleft((kind, t_sent))
                raise
            self._last_io = time.monotonic()  # lint: unguarded-ok receive leg runs outside the io lock by design; the _consuming flag excludes the heartbeat's round trips, and a racing timestamp store only under-reports idleness
            self._sparse_pull_ids.popleft()
            result: List[np.ndarray] = []
            merges: List[Tuple[int, Any, np.ndarray]] = []
            si = 0
            misses0 = self.sparse_cache_misses
            for i in range(len(self.templates)):
                if i in self._sparse_set:
                    if cached:
                        block = entry["out"][si]
                        mp = entry["miss_pos"][si]
                        if mp.size:
                            block[mp] = out[i]
                        _hot_tier_file_misses(self, i, entry["miss"][si],
                                              out[i])
                        result.append(block)
                    else:
                        ids = ids_list[si]
                        if ids.size:
                            merges.append((i, ids, out[i]))
                        result.append(self._cache[i])
                    si += 1
                else:
                    result.append(out[i])
            self._ready.append((result, merges))
            if cached:
                _count_cache_misses(self, misses0)
            if obs.enabled():
                obs.histogram("ps.pull_latency_ms", **self._mlabels).observe(
                    (time.perf_counter() - t_sent) * 1e3)
        elif kind != net.ACTION_WEIGHTS:
            # ACTION_ACK (commit) and ACTION_HEALTH (report) both await
            # the same ack byte; only the commit's round trip is a commit
            # latency sample
            reply = net.recv_action(self.sock)
            self._last_io = time.monotonic()  # lint: unguarded-ok receive leg runs outside the io lock by design; the _consuming flag excludes the heartbeat's round trips, and a racing timestamp store only under-reports idleness
            if reply != net.ACTION_ACK:
                raise ConnectionError(f"expected ack, got {reply!r}")
            if kind == net.ACTION_ACK and obs.enabled():
                obs.histogram("ps.commit_latency_ms", **self._mlabels).observe(
                    (time.perf_counter() - t_sent) * 1e3)
                obs.gauge("ps.inflight_depth", **self._mlabels).set(
                    self._unacked())
        else:
            bufs = self._pull_bufs[self._flip]
            self._flip ^= 1
            if self._cache_rows is None:
                out = bufs
            else:
                # hot-tier mode holds no full-shape landing storage for
                # sparse leaves — the rare full pull (initial seed,
                # explicit re-sync) lands them in transient arrays that
                # die with the caller's reference
                out = [np.empty(t.shape, t.dtype) if b is None else b
                       for b, t in zip(bufs, self.templates)]
            try:
                reply = self._codec.recv_into(self.sock, out)
                if reply != net.ACTION_WEIGHTS:
                    raise ConnectionError(f"expected weights reply, got {reply!r}")
            except Exception:
                # the receive died mid-weights: restore the entry (and the
                # landing buffer) so a reconnect counts this pull as lost
                # and re-issues it — without this, wait_weights retried
                # after a mid-frame fault would find "no pull in flight"
                self._flip ^= 1
                self._pending.appendleft((kind, t_sent))
                raise
            self._last_io = time.monotonic()  # lint: unguarded-ok receive leg runs outside the io lock by design; the _consuming flag excludes the heartbeat's round trips, and a racing timestamp store only under-reports idleness
            # a full pull re-seeds the sparse caches (at hand-out, like a
            # sparse pull's rows): the landing buffer is reused two pulls
            # later, the cache is the stable copy the sparse exchange
            # merges into.  Hot-tier mode seeds/refreshes its bounded LRU
            # instead (_hot_tier_seed), which no caller is ever handed
            merges = []
            for i in self._sparse:
                if self._cache_rows is None:
                    merges.append((i, Ellipsis, out[i]))
                else:
                    _hot_tier_seed(self, i, out[i])
            self._ready.append((out, merges))
            if obs.enabled():
                obs.histogram("ps.pull_latency_ms", **self._mlabels).observe(
                    (time.perf_counter() - t_sent) * 1e3)

    # -- blocking API (control plane + non-pipelined callers) ------------------
    def pull(self) -> List[np.ndarray]:
        with obs.span("ps.pull", **self._mlabels):
            self.pull_nowait()
            return self.wait_weights()

    def commit(self, delta: Sequence[np.ndarray],
               sparse_rows: Optional[Sequence] = None) -> None:
        self.commit_nowait(delta, sparse_rows=sparse_rows)
        self.drain()

    def close(self) -> None:
        self._hb_stop.set()
        # the BYE + close runs under the io lock: without it, a heartbeat
        # mid-ping (which owns the socket for its bounded round trip)
        # could interleave with the farewell frame, or poison-close a
        # socket close() is still writing to.  The bounded ping timeout
        # above caps how long this can wait
        with self._io_lock:
            self._closed = True
            try:
                net.send_raw_frame(self.sock,
                                   net.empty_tensor_frame(net.ACTION_BYE))
            except OSError:
                pass
            finally:
                try:
                    self.sock.close()
                except OSError:
                    pass
        if self._hb_thread is not None:
            self._hb_thread.join(timeout=5)
            self._hb_thread = None

    def __enter__(self) -> "PSClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class InprocPSClient(_HotTierCacheSurface):
    """:class:`PSClient` surface over a co-located hub (``transport="inproc"``).

    Pull/commit call the SAME center logic the socket handlers run —
    ``pull_direct`` / ``commit_direct``, under the hub's lock — with no
    sockets, no framing, and no wire copies; the staleness clock rides the
    client object instead of a connection.  Works against the Python hubs
    and the C++ hub (both expose the direct pair).

    The nowait/wait methods execute EAGERLY at the exact program points
    the socket client would *send* at, so a deterministic (single-worker)
    schedule observes identical center states on both transports — the
    trajectory-parity property ``tests/test_transport.py`` pins.

    ``compress="int8"`` round-trips every commit through the same
    quantize/dequantize + error-feedback math the wire path uses, so
    compressed runs also stay trajectory-identical across transports."""

    def __init__(self, ps: Any, templates: Sequence[np.ndarray],
                 compress: Optional[str] = None,
                 trace_context: Optional["dtrace.TraceContext"] = None,
                 sparse_leaves: Sequence[int] = (),
                 sparse_cache_rows: Optional[int] = None):
        if compress not in (None, "int8"):
            raise ValueError(f"unknown compress {compress!r}; use None or 'int8'")
        self.ps = ps
        self.templates = [np.asarray(t, dtype=np.float32) for t in templates]
        self.compress = compress
        # row-sparse tables (ISSUE 9): the inproc client mirrors the
        # socket client's cache-and-merge behavior over the hub's direct
        # sparse pair, so sparse runs stay trajectory-identical across
        # transports (no wire to save here — parity is the point).
        # Requires a co-located hub exposing pull_sparse_direct (both
        # unsharded hub implementations); the sharded facade has no
        # sparse direct pair — the trainer raises there
        self._sparse = tuple(sorted({int(i) for i in sparse_leaves}))
        self._sparse_set = frozenset(self._sparse)
        # hot-tier mode (ISSUE 15): the exact PSClient semantics minus
        # the wire — hits gather from the bounded LRU at pull time,
        # misses go through the direct pair, own commits merge in place
        # (one shared constructor with the socket client, so the two
        # transports' cache state can never drift)
        _init_hot_tier(self, sparse_cache_rows, compress)
        if self._sparse and not hasattr(ps, "pull_sparse_direct"):
            raise ValueError(
                f"sparse_leaves need a hub with a sparse direct pair "
                f"(pull_sparse_direct/commit_sparse_direct); "
                f"{type(ps).__name__} has none — use the socket transport "
                f"or an unsharded hub")
        self._residual = ([None if (self._cache_rows is not None
                                    and i in self._sparse_set)
                           else np.zeros(t.shape, np.float32)
                           for i, t in enumerate(self.templates)]
                          if compress else None)
        self._last_pull_clock = 0
        self._pulled: Optional[List[np.ndarray]] = None
        # (leaf, ids, rows) of a sparse pull not yet taken: merged into
        # the cache by wait_weights()
        self._pending_rows: List[Tuple[int, np.ndarray, np.ndarray]] = []
        # what the health plane's TRANS column reports for this worker
        # (PSClient: "tcp"/"shm" depending on the attach negotiation)
        self.transport = "inproc"
        # inproc shares the hub's process AND clock: the context needs no
        # wire announce (the hub reads the worker thread's context via
        # dtrace.current()), and the clock offset is exactly zero — which
        # is ALSO the process default when nothing ever syncs, so nothing
        # is recorded globally (an unbeatable error=0 record would pin a
        # later socket job in this process to a stale zero offset)
        self.trace_context = trace_context
        self.clock_offset_ns = 0
        self.clock_error_ns: Optional[int] = 0 if trace_context is not None else None
        # no connection, so nothing to reconnect or fail over — kept so
        # the worker loop's health reports read one uniform client surface
        self.reconnects_used = 0
        self.failovers_used = 0

    # -- live health plane (ISSUE 8) -------------------------------------------
    def report_health(self, report: Dict[str, Any]) -> None:
        """Same contract as :meth:`PSClient.report_health`, minus the wire:
        the report folds straight into the co-located hub's collector
        (Python hubs and the sharded facade ingest with their shard
        labels; a native hub's reports land in the process-default
        collector directly)."""
        ingest = getattr(self.ps, "_ingest_health", None)
        if ingest is not None:
            ingest(report)
            return
        from distkeras_tpu.observability import health as _health

        _health.collector().ingest(report)
        _health.monitor().maybe_check()

    # -- pipelined API (eager) -------------------------------------------------
    def pull_nowait(self, sparse_rows: Optional[Sequence] = None) -> None:
        telemetry = obs.enabled()
        t0 = time.perf_counter() if telemetry else 0.0
        if sparse_rows is not None:
            if not self._sparse:
                raise ValueError("sparse_rows passed to a client with no "
                                 "sparse_leaves configured")
            if len(sparse_rows) != len(self._sparse):
                raise ValueError(f"got {len(sparse_rows)} id arrays, "
                                 f"client has {len(self._sparse)} sparse "
                                 f"tables")
            ids_list = [net.normalize_row_ids(ids,
                                              self.templates[i].shape[0])
                        for ids, i in zip(sparse_rows, self._sparse)]
            if self._cache_rows is not None:
                # hot-tier: gather hits now, direct-pull only the misses,
                # file them, hand back [k, dim] blocks (PSClient parity —
                # the same shared helpers, so the transports can't drift)
                blocks, miss_pos, miss = _hot_tier_gather(self, ids_list)
                misses0 = self.sparse_cache_misses
                values, clock = self.ps.pull_sparse_direct(miss)
                result = []
                si = 0
                for i, v in enumerate(values):
                    if i in self._sparse_set:
                        if miss_pos[si].size:
                            blocks[si][miss_pos[si]] = v
                        _hot_tier_file_misses(self, i, miss[si],
                                              np.asarray(v, np.float32))
                        result.append(blocks[si])
                        si += 1
                    else:
                        result.append(v)
                _count_cache_misses(self, misses0)
                self._last_pull_clock = clock
                self._pulled = result
                if telemetry:
                    obs.histogram("ps.pull_latency_ms").observe(
                        (time.perf_counter() - t0) * 1e3)
                return
            values, clock = self.ps.pull_sparse_direct(ids_list)
            # the rows are fetched NOW (the center a socket pull sent here
            # would see) but merged into the cache only when the caller
            # takes the weights, as PSClient does: the cache arrays are
            # what the previous pull handed out, and a pipelined caller's
            # window program may still be reading them (on the CPU
            # backend jax.device_put can alias a numpy buffer)
            result: List[np.ndarray] = []
            si = 0
            for i, v in enumerate(values):
                if i in self._sparse_set:
                    if ids_list[si].size:
                        self._pending_rows.append((i, ids_list[si], v))
                    result.append(self._cache[i])
                    si += 1
                else:
                    result.append(v)
            self._last_pull_clock = clock
            self._pulled = result
        else:
            weights, clock = self.ps.pull_direct()
            for i in self._sparse:
                if self._cache_rows is None:
                    self._cache[i][...] = weights[i]
                else:
                    _hot_tier_seed(self, i, weights[i])
            self._last_pull_clock = clock
            self._pulled = weights
        if telemetry:
            obs.histogram("ps.pull_latency_ms").observe(
                (time.perf_counter() - t0) * 1e3)

    def land_weights(self) -> None:
        """Nothing is ever in flight here: ``pull_nowait`` copies the
        center at issue (:meth:`PSClient.land_weights`)."""

    def wait_weights(self) -> List[np.ndarray]:
        if self._pulled is None:
            raise RuntimeError("wait_weights() with no pull in flight")
        pulled, self._pulled = self._pulled, None
        for i, ids, rows in self._pending_rows:
            self._cache[i][ids] = rows
        self._pending_rows.clear()
        return pulled

    def commit_nowait(self, delta: Sequence[np.ndarray],
                      sparse_rows: Optional[Sequence] = None) -> None:
        with obs.span("ps.commit", transport="inproc",
                      compress=self.compress or "none"):
            telemetry = obs.enabled()
            t0 = time.perf_counter() if telemetry else 0.0
            if sparse_rows is not None:
                if not self._sparse:
                    raise ValueError("sparse_rows passed to a client with "
                                     "no sparse_leaves configured")
                if len(sparse_rows) != len(self._sparse):
                    raise ValueError(f"got {len(sparse_rows)} id arrays, "
                                     f"client has {len(self._sparse)} "
                                     f"sparse tables")
                ids_list = [net.normalize_row_ids(
                    ids, self.templates[i].shape[0])
                    for ids, i in zip(sparse_rows, self._sparse)]
                # same row gather + quantize/residual math as the wire
                # path, then straight back through the dequantizer — what
                # the hub would have reconstructed from the U/X frame
                if self._cache_rows is None:
                    arrays = _sparse_commit_arrays(
                        delta, self.templates, self._sparse_set, ids_list,
                        self._residual, self.compress)
                else:
                    arrays = _hot_tier_commit_arrays(self, delta, ids_list)
                parts = _sparse_parts_from_arrays(
                    arrays, self.templates, self._sparse_set, self.compress)
                self.ps.commit_sparse_direct(parts, self._last_pull_clock)
            elif self.compress == "int8":
                # same quantize + residual advance as the wire path, then
                # straight back through the dequantizer — what the hub
                # would have reconstructed from the Q frame
                blobs = _quantize_commit(delta, self._residual)
                arrays = [net.dequantize_q_blob(memoryview(b), t.size)
                          .reshape(t.shape)
                          for b, t in zip(blobs, self.templates)]
                self.ps.commit_direct(arrays, self._last_pull_clock)
            else:
                arrays = [np.asarray(d, np.float32) for d in delta]
                self.ps.commit_direct(arrays, self._last_pull_clock)
            if telemetry:
                obs.histogram("ps.commit_latency_ms").observe(
                    (time.perf_counter() - t0) * 1e3)

    def drain(self) -> None:
        pass  # nothing rides in flight: commits apply synchronously

    # -- blocking API ----------------------------------------------------------
    def pull(self) -> List[np.ndarray]:
        with obs.span("ps.pull", transport="inproc"):
            self.pull_nowait()
            return self.wait_weights()

    def commit(self, delta: Sequence[np.ndarray],
               sparse_rows: Optional[Sequence] = None) -> None:
        self.commit_nowait(delta, sparse_rows=sparse_rows)

    def close(self) -> None:
        pass  # no connection; the hub's lifecycle belongs to the trainer

    def __enter__(self) -> "InprocPSClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- sharded hub (ISSUE 6): stripe the center across N hub shards --------------
# One hub holding the whole center is a single-socket bandwidth and
# single-lock ceiling (the "weight-update state" that arXiv:2004.13336
# partitions across replicas).  The pieces below partition it across N
# independent hubs — each shard owns a subset of the center's leaves, runs
# its own lock, listener and commit clock — while the worker side stripes
# every pull/commit across all shards over per-shard connections reusing
# the existing pipelined/zero-copy machinery per connection.


class ShardPlan:
    """A deterministic leaf->shard assignment over a fixed template list.

    ``assignments[s]`` is the ASCENDING list of leaf indices shard ``s``
    owns — ascending so each shard's frame layout preserves template
    order (the 1-shard plan is exactly ``[[0..n-1]]``, whose frames are
    byte-identical to the unsharded codec's).  Built by
    :func:`shard_plan`; both ends of a sharded deployment (trainer
    workers, standalone ``distkeras-ps --shard-index`` hubs) derive the
    SAME plan from the same model, so no plan ever travels on the wire."""

    def __init__(self, num_shards: int, assignments: Sequence[Sequence[int]],
                 shard_bytes: Sequence[int],
                 sparse_ranges: Optional[Dict[int, Sequence[Tuple[int, int]]]]
                 = None):
        self.num_shards = int(num_shards)
        self.assignments: Tuple[Tuple[int, ...], ...] = tuple(
            tuple(int(i) for i in idxs) for idxs in assignments)
        self.shard_bytes: Tuple[int, ...] = tuple(int(b) for b in shard_bytes)
        # row-sparse tables (ISSUE 9): leaf index -> one contiguous
        # (row_lo, row_hi) range per shard.  A sparse leaf appears in
        # EVERY shard's assignment list (each shard owns its row range of
        # it), so ``num_leaves`` counts DISTINCT leaves
        self.sparse_ranges: Dict[int, Tuple[Tuple[int, int], ...]] = {
            int(k): tuple((int(a), int(b)) for a, b in v)
            for k, v in (sparse_ranges or {}).items()}
        if self.sparse_ranges:
            self.num_leaves = len({i for idxs in self.assignments
                                   for i in idxs})
        else:
            self.num_leaves = sum(len(idxs) for idxs in self.assignments)

    def local_sparse(self, shard: int) -> Tuple[int, ...]:
        """Positions of the sparse leaves WITHIN shard ``shard``'s leaf
        list — the per-shard hub/client ``sparse_leaves`` argument."""
        return tuple(pos for pos, i in enumerate(self.assignments[shard])
                     if i in self.sparse_ranges)

    def split(self, arrays: Sequence[Any]) -> List[List[Any]]:
        """Stripe a full-order leaf list into per-shard sublists (reference
        slicing, no copies: sparse leaves contribute their shard's
        contiguous row-range VIEW)."""
        if len(arrays) != self.num_leaves:
            raise ValueError(f"got {len(arrays)} leaves, plan covers "
                             f"{self.num_leaves}")
        out: List[List[Any]] = []
        for s, idxs in enumerate(self.assignments):
            part: List[Any] = []
            for i in idxs:
                rng = self.sparse_ranges.get(i)
                if rng is None:
                    part.append(arrays[i])
                else:
                    lo, hi = rng[s]
                    part.append(arrays[i][lo:hi])
            out.append(part)
        return out

    def assemble(self, shard_lists: Sequence[Sequence[Any]],
                 sparse_fill: Optional[Dict[int, Any]] = None) -> List[Any]:
        """Inverse of :meth:`split`: reassemble per-shard sublists into the
        full-order leaf list — by reference for whole leaves, so the
        per-shard landing buffers ARE the result's storage.  A row-range-
        split sparse leaf is rebuilt by concatenating its per-shard
        slices (one copy) — unless ``sparse_fill`` supplies the full
        array for it (the striped client's full cache, whose row-range
        views the per-shard slices already wrote into)."""
        out: List[Any] = [None] * self.num_leaves
        slices: Dict[int, List[Any]] = {i: [] for i in self.sparse_ranges}
        for idxs, vals in zip(self.assignments, shard_lists):
            if len(idxs) != len(vals):
                raise ValueError(f"shard holds {len(idxs)} leaves, got "
                                 f"{len(vals)} values")
            for i, v in zip(idxs, vals):
                if i in slices:
                    slices[i].append(v)
                else:
                    out[i] = v
        for i, parts in slices.items():
            if sparse_fill is not None and i in sparse_fill:
                out[i] = sparse_fill[i]
            else:
                out[i] = np.concatenate([np.asarray(p) for p in parts],
                                        axis=0)
        return out

    def __repr__(self) -> str:
        return (f"ShardPlan(num_shards={self.num_shards}, "
                f"leaves={self.num_leaves}, "
                f"shard_bytes={list(self.shard_bytes)}"
                + (f", sparse={sorted(self.sparse_ranges)}"
                   if self.sparse_ranges else "") + ")")


def shard_plan(templates: Sequence[np.ndarray], num_shards: int,
               sparse_leaves: Sequence[int] = ()) -> ShardPlan:
    """Deterministic, size-balanced leaf->shard assignment.

    Leaves are taken in a CANONICAL order — bytes descending, then dtype,
    then shape — and greedily assigned to the currently-smallest shard
    (lowest shard id on ties): classic LPT scheduling, so the heaviest
    shard exceeds the lightest by at most one leaf's bytes.  Because the
    canonical order depends only on each leaf's (nbytes, dtype, shape)
    identity, the assignment is STABLE under leaf reordering: permuting
    the template list maps each leaf to the same shard (leaves with fully
    identical layout are interchangeable — their mutual order falls back
    to input position, which only ever swaps byte-identical slots).

    ``sparse_leaves`` (ISSUE 9) names row-sparse ``[rows, dim]`` embedding
    tables: each is split across ALL shards by contiguous row range
    (near-equal row counts, earlier shards take the remainder), so a
    table that dwarfs the dense model never lands whole on one shard and
    sparse row traffic stripes naturally.  Dense leaves are then
    LPT-balanced over shards pre-loaded with their sparse-range bytes.

    ``num_shards=1`` returns the identity plan (all leaves, template
    order); more shards than leaves (when nothing is sparse) is an error
    — an empty shard would serve zero-tensor frames to no purpose."""
    n = len(templates)
    num_shards = int(num_shards)
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    arrs = [np.asarray(t) for t in templates]
    sparse = tuple(sorted({int(i) for i in sparse_leaves}))
    for i in sparse:
        if not 0 <= i < n:
            raise ValueError(f"sparse leaf index {i} out of range for "
                             f"{n} templates")
        if arrs[i].ndim != 2:
            raise ValueError(f"sparse leaf {i} must be a [rows, dim] table, "
                             f"got shape {arrs[i].shape}")
    if num_shards == 1:
        return ShardPlan(1, [list(range(n))], [sum(a.nbytes for a in arrs)],
                         sparse_ranges={i: [(0, arrs[i].shape[0])]
                                        for i in sparse})
    if not sparse and num_shards > n:
        raise ValueError(f"num_shards={num_shards} exceeds the model's "
                         f"{n} leaves; every shard must own at least one")
    loads = [0] * num_shards
    sparse_ranges: Dict[int, List[Tuple[int, int]]] = {}
    for i in sparse:
        rows = arrs[i].shape[0]
        if rows < num_shards:
            raise ValueError(f"sparse leaf {i} has {rows} rows < "
                             f"num_shards={num_shards}; every shard must "
                             f"own at least one row")
        row_bytes = arrs[i].nbytes // rows
        base, rem = divmod(rows, num_shards)
        bounds: List[Tuple[int, int]] = []
        lo = 0
        for s in range(num_shards):
            hi = lo + base + (1 if s < rem else 0)
            bounds.append((lo, hi))
            loads[s] += (hi - lo) * row_bytes
            lo = hi
        sparse_ranges[i] = bounds
    dense = [i for i in range(n) if i not in set(sparse)]
    order = sorted(dense,
                   key=lambda i: (-arrs[i].nbytes, str(arrs[i].dtype),
                                  arrs[i].shape, i))
    heap = [(loads[s], s) for s in range(num_shards)]  # (bytes, shard id)
    heapq.heapify(heap)
    assignments: List[List[int]] = [list(sparse) for _ in range(num_shards)]
    for i in order:
        filled, s = heapq.heappop(heap)
        assignments[s].append(i)
        heapq.heappush(heap, (filled + arrs[i].nbytes, s))
    for idxs in assignments:
        idxs.sort()
    shard_bytes = [
        sum((sparse_ranges[i][s][1] - sparse_ranges[i][s][0])
            * (arrs[i].nbytes // arrs[i].shape[0]) if i in sparse_ranges
            else arrs[i].nbytes
            for i in idxs)
        for s, idxs in enumerate(assignments)]
    return ShardPlan(num_shards, assignments, shard_bytes,
                     sparse_ranges=sparse_ranges)


class SnapshotSetCoordinator:
    """Fleet-consistent snapshot sets for a sharded hub (ISSUE 7).

    PR 6 left each shard hub snapshotting independently — a multi-shard
    restore could therefore resurrect a TORN center (shard 0 at clock
    1000, shard 1 at clock 400: a parameter vector no training state ever
    was).  This coordinator replaces the per-shard snapshotters when all
    shards live in one process: each tick briefly FENCES commits across
    every shard (all shard center locks held at once — safe because no
    commit path ever holds two shard locks) and reads all N shard states
    inside that barrier, so the N per-shard snapshots share one causal
    cut.  Native shard hubs keep their own internal atomicity per shard;
    the cross-shard cut is then only as tight as the read loop, but the
    recorded clock vector still makes a torn restore detectable.

    Every shard's snapshot is stamped with the SAME step number, a shared
    ``snapshot_set`` id and the full per-shard ``set_clocks`` vector;
    :meth:`restore_latest_set` restores only a step that is present,
    readable, same-set and clock-consistent on EVERY shard — falling back
    to the newest COMPLETE set when the newest is torn, and raising when
    sets exist but none survives the checks.

    Retention is set-level: saves skip the per-directory keep-N prune and
    the coordinator deletes each doomed step from EVERY ``shard-NN/``
    directory before advancing to the next, oldest first — a crash
    between prunes can strand at most the oldest step half-deleted, never
    leave step K readable on shard 0 but pruned on shard 1.

    Telemetry: ``ps.snapshot_set_ms`` (whole save), ``ps.snapshot_fence_ms``
    (how long commits were fenced — the barrier's cost),
    ``ps_snapshot_sets_total``."""

    def __init__(self, hubs: Sequence[Any], directory: str,
                 interval: float = 30.0, keep: int = 3):
        from distkeras_tpu.checkpoint import Checkpointer

        self.hubs = list(hubs)
        self.directory = directory
        self.interval = float(interval)
        self.keep = int(keep)
        self.checkpointers = [
            Checkpointer(os.path.join(directory, f"shard-{sid:02d}"),
                         keep=keep)
            for sid in range(len(self.hubs))]
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._save_lock = threading.Lock()
        self._next_step = 1 + max(
            (cp.latest_step() or 0) for cp in self.checkpointers)

    # -- the causal cut --------------------------------------------------------
    def _cut(self) -> List[Tuple[List[np.ndarray], Dict[str, Any]]]:
        locks = [getattr(hub, "_lock", None) for hub in self.hubs]
        if all(lk is not None for lk in locks):
            # Python hubs: a true barrier — every shard's center lock held
            # at once (commit handlers take exactly one shard lock, so no
            # ordering cycle exists), states read inside
            t0 = time.perf_counter()
            with contextlib.ExitStack() as stack:
                for lk in locks:
                    stack.enter_context(lk)
                states = [hub._snapshot_state_locked() for hub in self.hubs]
            if obs.enabled():
                obs.histogram("ps.snapshot_fence_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
            return states
        # native hubs lock in C++: per-shard snapshots are atomic, the
        # cross-shard cut is best-effort (documented); torn restores are
        # still detected via the recorded clock vector
        return [hub.snapshot_state() for hub in self.hubs]

    def save_set(self) -> None:
        """Write one coordinated snapshot set (all shards, one step, one
        causal cut), then advance set-level retention."""
        with self._save_lock, obs.span("ps.snapshot_set"):
            t0 = time.perf_counter()
            step = self._next_step
            set_id = f"set-{step:010d}-{random.getrandbits(32):08x}"
            states = self._cut()
            clocks = [int(state["clock"]) for _, state in states]
            for sid, (cp, (center, state)) in enumerate(
                    zip(self.checkpointers, states)):
                cp.save(step, {"center": center},
                        metadata={"kind": "ps-hub-snapshot", **state,
                                  "snapshot_set": set_id,
                                  "set_clocks": clocks,
                                  "shard_id": sid,
                                  "num_shards": len(self.hubs)},
                        apply_retention=False)
            self._next_step = step + 1
            self._prune(step)
            if obs.enabled():
                obs.histogram("ps.snapshot_set_ms").observe(
                    (time.perf_counter() - t0) * 1e3)
                obs.counter("ps_snapshot_sets_total").inc()

    def _prune(self, latest_step: int) -> None:
        doomed = sorted({s for cp in self.checkpointers
                         for s in cp.all_steps()
                         if s <= latest_step - self.keep})
        for step in doomed:  # oldest first, each step from EVERY shard
            for cp in self.checkpointers:
                cp.delete_step(step)

    def restore_latest_set(self) -> bool:
        """Restore the newest COMPLETE, same-set, clock-consistent snapshot
        set into the hubs (each shard re-arms its clock fence via
        ``restore_state``).  Returns False on a genuinely empty directory
        (first boot); raises when sets exist but every candidate is torn
        or unreadable — silently serving fresh weights would discard the
        job."""
        per_shard = [set(cp.all_steps()) for cp in self.checkpointers]
        if not any(per_shard):
            return False
        candidates = sorted(set().union(*per_shard), reverse=True)
        for step in candidates:
            if not all(step in steps for steps in per_shard):
                missing = [sid for sid, steps in enumerate(per_shard)
                           if step not in steps]
                warnings.warn(f"snapshot step {step} missing on shard(s) "
                              f"{missing}: torn set, falling back older")
                continue
            try:
                metas = [cp.metadata(step=step)["metadata"]
                         for cp in self.checkpointers]
                set_ids = {m.get("snapshot_set") for m in metas}
                if set_ids == {None}:
                    # pre-coordination (PR 6) per-shard snapshots: every
                    # shard wrote independently, so there is no set id or
                    # clock vector to check.  Still restorable — each
                    # shard's fence keeps clocks safe — but the cut is
                    # uncoordinated: say so instead of stranding the job
                    warnings.warn(
                        f"snapshot step {step} predates coordinated sets "
                        f"(no snapshot_set id): restoring per-shard "
                        f"snapshots whose center may be torn by up to one "
                        f"snapshot interval across shards (the pre-HA "
                        f"contract)")
                elif len(set_ids) != 1 or None in set_ids:
                    raise ValueError(f"mismatched snapshot_set ids "
                                     f"{sorted(map(str, set_ids))}")
                else:
                    for sid, m in enumerate(metas):
                        vec = m.get("set_clocks")
                        if vec is None or \
                                int(m.get("clock", -1)) != int(vec[sid]):
                            raise ValueError(
                                f"shard {sid} clock {m.get('clock')} does "
                                f"not match the set's recorded vector {vec}")
                trees = [cp.restore({"center": hub.get_weights()}, step=step)
                         for cp, hub in zip(self.checkpointers, self.hubs)]
            except Exception as e:
                warnings.warn(f"skipping torn/unreadable snapshot set at "
                              f"step {step}: {type(e).__name__}: {e}")
                continue
            for hub, tree, m in zip(self.hubs, trees, metas):
                hub.restore_state(tree["center"], m)
            # under the save lock: same contract as HubSnapshotter —
            # a restore racing the periodic save loop must not lose a
            # step advance (guarded-by contract, ISSUE 14)
            with self._save_lock:
                self._next_step = max(self._next_step, step + 1)
            return True
        raise RuntimeError(
            f"restore requested: snapshot sets exist under {self.directory} "
            f"but none is complete and clock-consistent across all "
            f"{len(self.hubs)} shards (see warnings)")

    # -- lifecycle -------------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.save_set()
            except Exception as e:  # a full disk must not kill the hubs
                warnings.warn(f"coordinated PS snapshot failed: "
                              f"{type(e).__name__}: {e}")

    def start(self) -> None:
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self, final_snapshot: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if final_snapshot:
            try:
                self.save_set()
            except Exception as e:
                warnings.warn(f"final coordinated PS snapshot failed: "
                              f"{type(e).__name__}: {e}")


class _ShardWorkerPool:
    """One long-lived handler thread per shard hub (ISSUE 18): a striped
    direct-transport request dispatches one closure per shard and joins —
    so a 4-shard in-process hub applies the 4 stripes on 4 cores instead
    of walking them sequentially on the caller's thread.  Safe because
    the shards are DISJOINT state (each hub has its own center, lock and
    clock — the same isolation the per-connection socket handlers rely
    on), and numpy's apply kernels release the GIL.  Results are
    bit-identical to the sequential walk: each stripe runs the exact same
    per-hub call, just concurrently with its siblings.

    Each shard's queue is strictly FIFO and single-consumer, so two
    overlapped striped commits keep their per-shard apply order.  No new
    lock is introduced (the queues synchronize internally); the pool
    holds none while running a closure, so it cannot participate in any
    lock-order cycle."""

    def __init__(self, num_shards: int):
        self._queues = [queue.SimpleQueue() for _ in range(num_shards)]
        self._threads: List[threading.Thread] = []
        self.running = False

    def start(self) -> None:
        if self.running:
            return
        self.running = True
        for i, q in enumerate(self._queues):
            t = threading.Thread(target=self._loop, args=(q,),
                                 name=f"dk-shard-worker-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    @staticmethod
    def _loop(q: "queue.SimpleQueue") -> None:
        while True:
            item = q.get()
            if item is None:
                return
            fn, box, done = item
            try:
                fn()
            except BaseException as e:
                box[0] = e
            done.set()

    def run(self, thunks: Sequence[Any]) -> None:
        """Run one thunk per shard, in parallel, and join.  The FIRST
        shard's error (in shard order) is re-raised after every shard
        finished — a failed stripe must not leave siblings mid-apply.
        Before start()/after stop() the thunks run sequentially inline,
        so lifecycle edges never drop work."""
        if not self.running:
            for fn in thunks:
                fn()
            return
        boxes = []
        events = []
        for q, fn in zip(self._queues, thunks):
            box: List[Optional[BaseException]] = [None]
            done = threading.Event()
            q.put((fn, box, done))
            boxes.append(box)
            events.append(done)
        for done in events:
            done.wait()
        for box in boxes:
            if box[0] is not None:
                raise box[0]

    def stop(self) -> None:
        if not self.running:
            return
        self.running = False
        for q in self._queues:
            q.put(None)
        for t in self._threads:
            t.join(timeout=5)
        self._threads = []


class ShardedParameterServer:
    """Facade over N per-shard hubs: one :class:`SocketParameterServer`
    subclass (or :class:`~distkeras_tpu.runtime.native.
    NativeParameterServer`) per shard, each serving its slice of the
    center on its own port, lock and commit clock.

    ``hub_factory(shard_weights, shard_id)`` builds one UNSTARTED hub per
    shard — the trainer's algorithm-specific allocator with the shard's
    weight subset and identity (so per-shard spans/metrics carry the
    shard label).  The facade owns lifecycle (``start`` is all-or-nothing:
    a shard that fails to bind tears the others down), reassembles
    ``get_weights()`` into full template order, and exposes the direct
    (inproc) transport pair — ``pull_direct`` returns the full center plus
    a per-shard clock TUPLE, and ``commit_direct`` accepts that tuple (or
    a plain int, broadcast — the unsharded client's initial 0), so
    :class:`InprocPSClient` works against the facade unchanged.

    Snapshot/fence semantics: each shard hub snapshots and restores its
    OWN slice (give each a per-shard ``snapshot_dir`` subdirectory via the
    factory); on restore every shard arms its own clock fence, so a
    snapshot set whose shards are one interval apart is still safe —
    commits against any shard's dead-incarnation clock are clamped at
    that shard's restore point.  Elastic membership is per shard
    (connection-scoped); :meth:`live_workers` reports the MIN across
    shards — a worker counts as fleet-live only while all its shard
    connections do."""

    def __init__(self, weights: Sequence[np.ndarray], plan: ShardPlan,
                 hub_factory,
                 snapshot_dir: Optional[str] = None,
                 snapshot_interval: float = 30.0,
                 snapshot_keep: int = 3,
                 restore: bool = False,
                 parallel_direct: bool = True):
        if plan.num_leaves != len(weights):
            raise ValueError(f"plan covers {plan.num_leaves} leaves, model "
                             f"has {len(weights)}")
        self.plan = plan
        self.shards: List[Any] = []
        for sid, shard_weights in enumerate(plan.split(list(weights))):
            self.shards.append(hub_factory(shard_weights, sid))
        # per-shard handler pool (ISSUE 18): striped direct pulls/commits
        # fan out to one long-lived thread per shard, so an in-process
        # multi-shard hub uses one core PER SHARD instead of serializing
        # the stripes on the caller.  parallel_direct=False keeps the
        # sequential walk (bit-identical results either way — the shards
        # are disjoint)
        self._pool = (_ShardWorkerPool(plan.num_shards)
                      if parallel_direct and plan.num_shards > 1 else None)
        # coordinated snapshot sets (ISSUE 7): when the facade owns the
        # durability story, the N per-shard snapshots are taken inside one
        # commit barrier and restored only as a complete, clock-consistent
        # set.  (Per-shard snapshotters built by hub_factory remain the
        # multi-process --shard-index topology's independent fallback —
        # don't configure both.)
        self.coordinator: Optional[SnapshotSetCoordinator] = None
        self._restore = bool(restore)
        if restore and snapshot_dir is None:
            raise ValueError("restore=True requires snapshot_dir")
        if snapshot_dir is not None:
            self.coordinator = SnapshotSetCoordinator(
                self.shards, snapshot_dir, interval=snapshot_interval,
                keep=snapshot_keep)

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> None:
        if self.coordinator is not None and self._restore:
            # load BEFORE any shard binds: the first striped pull must
            # observe the restored (fenced) set everywhere
            if not self.coordinator.restore_latest_set():
                warnings.warn("restore requested but no snapshot set "
                              "exists yet; serving initial weights")
        started = []
        try:
            for hub in self.shards:
                hub.start()
                started.append(hub)
        except BaseException:
            for hub in started:
                try:
                    hub.stop()
                except Exception:
                    pass
            raise
        if self.coordinator is not None:
            self.coordinator.start()
        if self._pool is not None:
            self._pool.start()

    def stop(self) -> None:
        if self._pool is not None:
            self._pool.stop()
        if self.coordinator is not None:
            self.coordinator.stop(final_snapshot=True)
        for hub in self.shards:
            hub.stop()

    def kill(self) -> None:
        """Crash-like teardown of every shard (see
        ``SocketParameterServer.kill``): no final snapshot set — recovery
        must come from the last periodic one."""
        if self._pool is not None:
            self._pool.stop()
        if self.coordinator is not None:
            self.coordinator.stop(final_snapshot=False)
        for hub in self.shards:
            hub.kill()

    @property
    def ports(self) -> List[int]:
        return [hub.port for hub in self.shards]

    @property
    def port(self) -> int:
        """Shard 0's port — for code paths that log or display 'the' hub
        address; striped clients must use :attr:`ports`."""
        return self.shards[0].port

    @property
    def num_updates(self) -> int:
        """Logical commits applied: every striped commit increments every
        shard once, so the max across shards is the logical count (shards
        may momentarily differ while a stripe is in flight)."""
        return max(hub.num_updates for hub in self.shards)

    def live_workers(self) -> int:
        """Fleet-live workers: the MIN across shards — a worker whose
        connection to ANY shard has lapsed no longer counts (its commits
        are only partially landing)."""
        return min(hub.live_workers() for hub in self.shards)

    def get_weights(self) -> List[np.ndarray]:
        return self.plan.assemble([hub.get_weights() for hub in self.shards])

    # -- in-process transport (transport="inproc") -----------------------------
    def pull_direct(self) -> Tuple[List[np.ndarray], Tuple[int, ...]]:
        """(full center in template order, per-shard clock tuple).  The
        tuple rides back through the matching :meth:`commit_direct` —
        opaque to :class:`InprocPSClient`, exactly like the int clock of
        an unsharded hub."""
        n = self.plan.num_shards
        shard_weights: List[Any] = [None] * n
        clocks: List[Any] = [None] * n

        def make(i: int, hub: Any):
            def fn() -> None:
                shard_weights[i], clocks[i] = hub.pull_direct()
            return fn

        thunks = [make(i, hub) for i, hub in enumerate(self.shards)]
        if self._pool is not None:
            self._pool.run(thunks)
        else:
            for fn in thunks:
                fn()
        return self.plan.assemble(shard_weights), tuple(clocks)

    def commit_direct(self, delta: Sequence[np.ndarray],
                      last_pull_clock) -> None:
        parts = self.plan.split(list(delta))
        if isinstance(last_pull_clock, (tuple, list)):
            clocks = list(last_pull_clock)
            if len(clocks) != self.plan.num_shards:
                raise ValueError(f"clock tuple has {len(clocks)} entries, "
                                 f"plan has {self.plan.num_shards} shards")
        else:
            # a plain int (the inproc client's commit-before-first-pull
            # default of 0): broadcast to every shard's clock domain
            clocks = [int(last_pull_clock)] * self.plan.num_shards

        def make(hub: Any, part: Any, clock: Any):
            def fn() -> None:
                hub.commit_direct(part, clock)
            return fn

        thunks = [make(hub, part, clock)
                  for hub, part, clock in zip(self.shards, parts, clocks)]
        if self._pool is not None:
            self._pool.run(thunks)
        else:
            for fn in thunks:
                fn()

    # -- live health plane (ISSUE 8) -------------------------------------------
    def _ingest_health(self, report: Dict[str, Any]) -> None:
        """Fold one worker report through shard 0 — mirroring the striped
        wire path, where reports travel on the shard-0 connection only (one
        LOGICAL report per worker, the ``fleet_report`` counting rule)."""
        ingest = getattr(self.shards[0], "_ingest_health", None)
        if ingest is not None:
            ingest(report)
            return
        # native shard hubs have no Python-side ingest: fold straight into
        # the process-default collector (same process by construction)
        from distkeras_tpu.observability import health as _health

        _health.collector().ingest(report, shard=0)
        _health.monitor().maybe_check()


class ShardedPSClient:
    """Striped worker-side client: the :class:`PSClient` surface over N
    per-shard connections.

    A pull fans ``pull_nowait`` out to every shard; each shard's reply
    streams — via the per-connection zero-copy ``FlatFrameCodec`` path —
    directly into that shard's slice of the double-buffered landing zone
    (each per-shard client's landing buffers ARE the slice), and
    :meth:`wait_weights` reassembles the full-order list by reference.
    Commits stripe the delta the same way, with acks coalesced per shard
    connection by the underlying pipelined clients.  ``compress="int8"``
    quantizes per shard with per-leaf residuals — the same per-leaf
    error-feedback chain as unsharded, so trajectories match.

    Reconnect/heartbeat semantics apply PER SHARD CONNECTION (each shard
    client carries its own budget and backoff state); a stripe whose
    budget runs out mid fan-out surfaces as :class:`StripeLostError`
    naming the shard (index + host:port) and emits a ``ps.stripe_lost``
    span so ``fleet_report`` can attribute the loss.  After any
    unrecovered fault the striped client as a whole is desynchronized —
    single-use, like :class:`PSClient`.  ``addresses`` is one
    ``(host, port)`` per shard, aligned with ``plan.assignments``;
    ``failover`` (optional) is one standby ``(host, port)`` — or a
    sequence of them — per shard, same alignment."""

    def __init__(self, addresses: Sequence[Tuple[str, int]],
                 templates: Sequence[np.ndarray], plan: ShardPlan,
                 timeout: Optional[float] = 60.0,
                 compress: Optional[str] = None,
                 max_inflight: int = 2,
                 max_reconnects: int = 0,
                 reconnect_backoff: float = 0.1,
                 reconnect_backoff_max: float = 5.0,
                 heartbeat_interval: Optional[float] = None,
                 trace_context: Optional["dtrace.TraceContext"] = None,
                 failover: Optional[Sequence[Any]] = None,
                 sparse_leaves: Sequence[int] = (),
                 adaptive: bool = False,
                 sparse_cache_rows: Optional[int] = None,
                 shm: bool = False):
        if sparse_cache_rows is not None:
            # the striped client's whole sparse design is row-range VIEWS
            # of one full-size cache; a bounded hot tier would need
            # per-shard LRU partitioning of the row ranges — documented
            # unsupported combination (MIGRATION.md), loud at construction
            raise ValueError(
                "sparse_cache_rows is not supported on the sharded client: "
                "hot-tier caching needs num_shards=1 (PSClient/"
                "InprocPSClient) — drop sparse_cache_rows or the sharding")
        if len(addresses) != plan.num_shards:
            raise ValueError(f"got {len(addresses)} shard addresses, plan "
                             f"has {plan.num_shards} shards")
        if failover is not None and len(failover) != plan.num_shards:
            raise ValueError(f"got {len(failover)} failover entries, plan "
                             f"has {plan.num_shards} shards (pass None for "
                             f"shards without a standby)")
        self.templates = [np.asarray(t, dtype=np.float32) for t in templates]
        if plan.num_leaves != len(self.templates):
            raise ValueError(f"plan covers {plan.num_leaves} leaves, model "
                             f"has {len(self.templates)}")
        self.plan = plan
        self.compress = compress
        # row-sparse tables (ISSUE 9): the plan splits each table across
        # ALL shards by contiguous row range; this client keeps ONE
        # full-size cache per table and hands each per-shard client its
        # row-range VIEW of it as that shard's local cache — so per-shard
        # sparse merges write straight into the full table, and
        # wait_weights reassembles with zero row copies
        self._sparse = tuple(sorted({int(i) for i in sparse_leaves}))
        if self._sparse and set(self._sparse) != set(plan.sparse_ranges):
            raise ValueError(
                f"sparse_leaves {list(self._sparse)} do not match the "
                f"plan's sparse tables {sorted(plan.sparse_ranges)}; build "
                f"the plan with shard_plan(..., sparse_leaves=...)")
        self._cache: Dict[int, np.ndarray] = {
            i: np.array(self.templates[i], np.float32) for i in self._sparse}
        self.shards: List[PSClient] = []
        try:
            local_templates = plan.split(self.templates)
            for sid, ((host, port), idxs) in enumerate(
                    zip(addresses, plan.assignments)):
                client = PSClient(
                    host, port, local_templates[sid],
                    timeout=timeout, compress=compress,
                    max_inflight=max_inflight,
                    max_reconnects=max_reconnects,
                    reconnect_backoff=reconnect_backoff,
                    reconnect_backoff_max=reconnect_backoff_max,
                    heartbeat_interval=heartbeat_interval,
                    trace_context=trace_context, shard_id=sid,
                    sparse_leaves=plan.local_sparse(sid)
                    if self._sparse else (),
                    failover=_normalize_failover(
                        failover[sid] if failover is not None else None),
                    adaptive=adaptive, shm=shm)
                # rebind the shard client's caches to row-range views of
                # the full tables (contiguous slices, so fancy-indexed
                # merges land in the full cache directly)
                if self._sparse:
                    for pos, i in zip(plan.local_sparse(sid),
                                      (j for j in idxs
                                       if j in plan.sparse_ranges)):
                        lo, hi = plan.sparse_ranges[i][sid]
                        client._cache[pos] = self._cache[i][lo:hi]
                self.shards.append(client)
        except BaseException:
            self.close()
            raise

    @property
    def transport(self) -> str:
        """Aggregate of the stripes' negotiated transports: ``"shm"``
        when every shard connection attached a ring pair, ``"tcp"`` when
        none did, ``"mixed"`` otherwise (e.g. one shard's hub declined —
        legal, each stripe negotiates independently)."""
        kinds = {getattr(c, "transport", "tcp") for c in self.shards}
        if kinds == {"shm"}:
            return "shm"
        if kinds <= {"tcp"}:
            return "tcp"
        return "mixed"

    def _stripe(self, sid: int, op):
        """Run one shard client's op, converting an unrecovered connection
        fault into the typed :class:`StripeLostError` naming the stripe
        (and recording the ``ps.stripe_lost`` span).  Catches the full
        retryable set (``PSClient._RETRYABLE``): with ``max_reconnects=0``
        the ORIGINAL fault propagates — a wedged hub surfaces as
        ``socket.timeout`` (an OSError that is not a ConnectionError) and
        a desynced stream as ``ProtocolError`` (a ValueError), and both
        are stripe deaths every bit as much as a reset is."""
        try:
            return op()
        except StripeLostError:
            raise  # already typed (nested striped clients don't exist, but)
        except PSClient._RETRYABLE as e:
            client = self.shards[sid]
            if obs.enabled():
                t_ns = time.perf_counter_ns()
                wattrs = (client.trace_context.span_attrs()
                          if client.trace_context is not None else {})
                obs.counter("ps_stripe_losses_total", shard=str(sid)).inc()
                obs.TRACER.record_span(
                    "ps.stripe_lost", t_ns, t_ns, shard=sid,
                    address=f"{client.host}:{client.port}", **wattrs)
            raise StripeLostError(sid, client.host, client.port, e) from e

    def _route_rows(self, sparse_rows: Sequence) -> List[List[np.ndarray]]:
        """Route each table's touched-row ids to the shard owning their
        row range (ids are sorted, so each shard's segment is one
        ``searchsorted`` slice), rebased to the shard's local row 0."""
        if len(sparse_rows) != len(self._sparse):
            # checked BEFORE the zip below, which would truncate
            raise ValueError(f"got {len(sparse_rows)} id arrays, client has "
                             f"{len(self._sparse)} sparse tables")
        ids_list = [net.normalize_row_ids(ids, self.templates[i].shape[0])
                    for ids, i in zip(sparse_rows, self._sparse)]
        per_shard: List[List[np.ndarray]] = []
        for sid in range(self.plan.num_shards):
            local: List[np.ndarray] = []
            for pos, i in enumerate(self._sparse):
                lo, hi = self.plan.sparse_ranges[i][sid]
                ids = ids_list[pos]
                a, b = np.searchsorted(ids, (lo, hi))
                local.append(ids[a:b] - lo)
            per_shard.append(local)
        return per_shard

    # -- pipelined API ---------------------------------------------------------
    def pull_nowait(self, sparse_rows: Optional[Sequence] = None) -> None:
        if sparse_rows is None:
            for sid, client in enumerate(self.shards):
                self._stripe(sid, client.pull_nowait)
            return
        if not self._sparse:
            raise ValueError("sparse_rows passed to a client with no "
                             "sparse_leaves configured")
        for sid, (client, local) in enumerate(
                zip(self.shards, self._route_rows(sparse_rows))):
            self._stripe(sid, lambda c=client, l=local:
                         c.pull_nowait(sparse_rows=l))

    def land_weights(self) -> None:
        """Each stripe's :meth:`PSClient.land_weights`, in shard order."""
        for sid, client in enumerate(self.shards):
            self._stripe(sid, client.land_weights)

    def wait_weights(self) -> List[np.ndarray]:
        """Full-order weight list; each dense leaf aliases its shard
        client's landing buffer (reused two pulls later — same ownership
        contract as :meth:`PSClient.wait_weights`); each sparse table is
        the client's full cache (stable storage, merged in place)."""
        parts = [self._stripe(sid, c.wait_weights)
                 for sid, c in enumerate(self.shards)]
        return self.plan.assemble(
            parts, sparse_fill=self._cache if self._sparse else None)

    def commit_nowait(self, delta: Sequence[np.ndarray],
                      sparse_rows: Optional[Sequence] = None) -> None:
        if sparse_rows is not None and not self._sparse:
            raise ValueError("sparse_rows passed to a client with no "
                             "sparse_leaves configured")
        routed = (self._route_rows(sparse_rows)
                  if sparse_rows is not None else None)
        for sid, (client, part) in enumerate(
                zip(self.shards, self.plan.split(list(delta)))):
            local = routed[sid] if routed is not None else None
            self._stripe(sid, lambda c=client, p=part, l=local:
                         c.commit_nowait(p, sparse_rows=l))

    def drain(self) -> None:
        for sid, client in enumerate(self.shards):
            self._stripe(sid, client.drain)

    # -- live health plane (ISSUE 8) -------------------------------------------
    @property
    def reconnects_used(self) -> int:
        return sum(c.reconnects_used for c in self.shards)

    @property
    def failovers_used(self) -> int:
        return sum(c.failovers_used for c in self.shards)

    @property
    def sparse_cache_hits(self) -> int:
        return sum(c.sparse_cache_hits for c in self.shards)

    @property
    def sparse_cache_misses(self) -> int:
        return sum(c.sparse_cache_misses for c in self.shards)

    def report_health(self, report: Dict[str, Any]) -> None:
        """Push one report over the SHARD-0 connection only: a striped
        worker is one logical worker, and the fleet view must count it
        once (the ``fleet_report`` shard-0 convention; shard 0 exists in
        every plan)."""
        self._stripe(0, lambda: self.shards[0].report_health(report))

    # -- blocking API ----------------------------------------------------------
    def pull(self) -> List[np.ndarray]:
        with obs.span("ps.pull", sharded=self.plan.num_shards):
            self.pull_nowait()
            return self.wait_weights()

    def commit(self, delta: Sequence[np.ndarray],
               sparse_rows: Optional[Sequence] = None) -> None:
        self.commit_nowait(delta, sparse_rows=sparse_rows)
        self.drain()

    def close(self) -> None:
        for client in self.shards:
            try:
                client.close()
            except OSError:
                pass

    def __enter__(self) -> "ShardedPSClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
