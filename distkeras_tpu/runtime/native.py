"""ctypes bindings for the C++ parameter-server hub (``native/ps_server.cpp``).

The shared library is built on demand with ``g++`` (no pybind11 in this
environment — plain ``extern "C"`` + ctypes) and cached next to this file;
it is rebuilt whenever the source's content or the build flags differ from
what the cached binary was built from (``build_shared``).  If no
toolchain is available, callers fall back to the pure-Python hub — the two
implementations speak the same wire protocol, so
:class:`distkeras_tpu.runtime.parameter_server.PSClient` works against
either.

``NativeParameterServer`` mirrors the Python ``SocketParameterServer``
surface at FEATURE PARITY (ISSUE 11): row-sparse embedding traffic
(actions ``S``/``V``/``U``/``X``), Adasum flat-combining adaptive
aggregation (``adaptive=True`` — per-worker rates still driven by the
Python :class:`~.parameter_server.AdaptiveRateController`, whose verdicts
are pushed into the C++ apply path), hot-standby replication on BOTH
sides (the ``R`` feed as primary, ``replica_of=`` as standby), reconnect
backpressure (``G``/``Y``) and health-report ingestion (``M``, drained
into the process HealthCollector by a poll thread).  The Python hub stays
the executable spec via the bit-parity matrices in ``tests/``.

The ONE remaining Python-hub-only surface is the row-sparse INPROC pair
(``pull_sparse_direct``/``commit_sparse_direct``) — see those methods.
"""

from __future__ import annotations

import ctypes
import hashlib
import json
import os
import subprocess
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from distkeras_tpu import observability as obs
from distkeras_tpu.observability import distributed as dtrace
from distkeras_tpu.runtime import networking as net

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(os.path.dirname(_HERE)), "native", "ps_server.cpp")
_LIB = os.path.join(_HERE, "_native_ps.so")

MODE_DELTA = 0   # center += d              (DOWNPOUR, elastic)
MODE_ADAG = 1    # center += d/num_workers  (ADAG)
MODE_DYNSGD = 2  # center += d/(staleness+1)

# build flags shared by every native component.  -ffp-contract=off pins
# the apply arithmetic to separate multiply-then-add (no FMA fusion), the
# exact float32 sequence numpy performs — the cross-hub bit-parity pins
# depend on it
BUILD_FLAGS = ["-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
               "-ffp-contract=off"]


def build_shared(src: str, lib: str) -> Optional[str]:
    """Compile ``src`` to the shared library ``lib`` if missing/stale.
    Returns an error string on failure, None on success.  Shared by every
    native component (PS hub, data loader).

    Stale means the sha256 of the source's CONTENT plus ``BUILD_FLAGS``
    differs from the one recorded in the ``<lib>.src-sha256`` sidecar at
    build time — not mtime: the libraries are git-ignored, so a copied or
    checked-out tree can carry an old binary whose mtime is newer than the
    source it no longer matches."""
    try:
        with open(src, "rb") as f:
            want = hashlib.sha256(
                f.read() + "\0".join(BUILD_FLAGS).encode()).hexdigest()
    except OSError:
        return f"native source not found: {src}"
    stamp = lib + ".src-sha256"
    try:
        with open(stamp) as f:
            fresh = os.path.exists(lib) and f.read().strip() == want
    except OSError:
        fresh = False
    if fresh:
        return None
    # compile to a private temp path, then atomically rename into place:
    # a concurrent process either dlopens the complete old .so or the
    # complete new one, never a half-written file
    tmp = f"{lib}.build-{os.getpid()}"
    cmd = ["g++"] + BUILD_FLAGS + [src, "-o", tmp]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"g++ invocation failed: {e}"
    if proc.returncode != 0:
        return f"g++ failed:\n{proc.stderr}"
    # library first, stamp second: a crash between the two (or a torn
    # stamp) leaves a stamp that does not match and the next load
    # rebuilds; the other order could bless an old binary
    os.replace(tmp, lib)
    with open(stamp, "w") as f:
        f.write(want)
    return None


class LazyNativeLib:
    """Build-once/load-once native library with cached failure — the shared
    state machine for every native component (PS hub, data loader, ...).

    ``bind(lib)`` is called exactly once after a successful dlopen to set
    restype/argtypes.  ``load()`` returns the CDLL or None; ``error()``
    returns the cached build failure, if any.
    """

    def __init__(self, src: str, lib_path: str, bind):
        self._src = src
        self._lib_path = lib_path
        self._bind = bind
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._error: Optional[str] = None

    def load(self) -> Optional[ctypes.CDLL]:
        with self._lock:
            if self._lib is not None:
                return self._lib
            if self._error is not None:
                return None
            err = build_shared(self._src, self._lib_path)
            if err is not None:
                self._error = err
                return None
            lib = ctypes.CDLL(self._lib_path)
            self._bind(lib)
            self._lib = lib
            return lib

    def error(self) -> Optional[str]:
        self.load()
        return self._error


def _bind_ps(lib: ctypes.CDLL) -> None:
    P = ctypes.POINTER
    lib.dk_ps_create.restype = ctypes.c_void_p
    lib.dk_ps_create.argtypes = [
        ctypes.c_int, ctypes.c_int, P(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, P(ctypes.c_int32), P(ctypes.c_int64), ctypes.c_int,
        ctypes.c_int64]
    lib.dk_ps_set_replica_of.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_int, ctypes.c_int,
                                         ctypes.c_int]
    lib.dk_ps_restore.argtypes = [ctypes.c_void_p, P(ctypes.c_float),
                                  ctypes.c_int64, ctypes.c_int64]
    lib.dk_ps_start.restype = ctypes.c_int
    lib.dk_ps_start.argtypes = [ctypes.c_void_p]
    lib.dk_ps_stop.argtypes = [ctypes.c_void_p]
    lib.dk_ps_get_weights.argtypes = [ctypes.c_void_p, P(ctypes.c_float)]
    lib.dk_ps_set_weights.argtypes = [ctypes.c_void_p, P(ctypes.c_float)]
    lib.dk_ps_num_updates.restype = ctypes.c_int64
    lib.dk_ps_num_updates.argtypes = [ctypes.c_void_p]
    lib.dk_ps_port.restype = ctypes.c_int
    lib.dk_ps_port.argtypes = [ctypes.c_void_p]
    lib.dk_ps_pull.restype = ctypes.c_int64
    lib.dk_ps_pull.argtypes = [ctypes.c_void_p, P(ctypes.c_float)]
    lib.dk_ps_snapshot.restype = ctypes.c_int64
    lib.dk_ps_snapshot.argtypes = [ctypes.c_void_p, P(ctypes.c_float)]
    lib.dk_ps_commit.restype = ctypes.c_int
    lib.dk_ps_commit.argtypes = [ctypes.c_void_p, P(ctypes.c_float),
                                 ctypes.c_int64]
    lib.dk_ps_commit_ctx.restype = ctypes.c_int
    lib.dk_ps_commit_ctx.argtypes = [ctypes.c_void_p, P(ctypes.c_float),
                                     ctypes.c_int64, ctypes.c_int64]
    lib.dk_ps_pull_sparse.restype = ctypes.c_int64
    lib.dk_ps_pull_sparse.argtypes = [ctypes.c_void_p, P(ctypes.c_int64),
                                      P(ctypes.c_int64), P(ctypes.c_float)]
    lib.dk_ps_commit_sparse.restype = ctypes.c_int
    lib.dk_ps_commit_sparse.argtypes = [ctypes.c_void_p, P(ctypes.c_float),
                                        P(ctypes.c_int64), P(ctypes.c_int64),
                                        ctypes.c_int64, ctypes.c_int64]
    lib.dk_ps_hot_rows.argtypes = [ctypes.c_void_p, P(ctypes.c_int64)]
    lib.dk_ps_stats.argtypes = [ctypes.c_void_p, P(ctypes.c_int64)]
    lib.dk_ps_staleness_hist.argtypes = [ctypes.c_void_p, P(ctypes.c_int64)]
    lib.dk_ps_merge_hist.argtypes = [ctypes.c_void_p, P(ctypes.c_int64)]
    lib.dk_ps_drain_commits.restype = ctypes.c_int64
    lib.dk_ps_drain_commits.argtypes = [ctypes.c_void_p, P(ctypes.c_int64),
                                        ctypes.c_int64]
    lib.dk_ps_next_health.restype = ctypes.c_int64
    lib.dk_ps_next_health.argtypes = [ctypes.c_void_p, P(ctypes.c_uint8),
                                      ctypes.c_int64]
    lib.dk_ps_set_rate_scale.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_double, ctypes.c_int64]
    lib.dk_ps_set_storm_params.argtypes = [ctypes.c_void_p] + [ctypes.c_int] * 5
    lib.dk_ps_arm_storm.argtypes = [ctypes.c_void_p]
    lib.dk_ps_is_standby.restype = ctypes.c_int
    lib.dk_ps_is_standby.argtypes = [ctypes.c_void_p]
    lib.dk_ps_promoted.restype = ctypes.c_int
    lib.dk_ps_promoted.argtypes = [ctypes.c_void_p]
    lib.dk_ps_promoted_at_clock.restype = ctypes.c_int64
    lib.dk_ps_promoted_at_clock.argtypes = [ctypes.c_void_p]
    lib.dk_ps_promote.restype = ctypes.c_int
    lib.dk_ps_promote.argtypes = [ctypes.c_void_p]
    lib.dk_ps_wait_synced.restype = ctypes.c_int
    lib.dk_ps_wait_synced.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.dk_ps_time_ns.restype = ctypes.c_int64
    lib.dk_ps_time_ns.argtypes = [ctypes.c_void_p]
    lib.dk_ps_destroy.argtypes = [ctypes.c_void_p]
    # shm transport (ISSUE 18): hub-side attach enable + standalone ring
    # handles (the cross-language layout pin drives these directly)
    lib.dk_ps_shm_attach.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.dk_shm_ring_create.restype = ctypes.c_void_p
    lib.dk_shm_ring_create.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                       ctypes.c_uint64]
    lib.dk_shm_ring_open.restype = ctypes.c_void_p
    lib.dk_shm_ring_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.dk_shm_ring_write.restype = ctypes.c_longlong
    lib.dk_shm_ring_write.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_longlong, ctypes.c_int]
    lib.dk_shm_ring_read.restype = ctypes.c_longlong
    lib.dk_shm_ring_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                     ctypes.c_longlong, ctypes.c_int]
    lib.dk_shm_ring_close.argtypes = [ctypes.c_void_p]
    lib.dk_shm_ring_destroy.argtypes = [ctypes.c_void_p]


_ps_lib = LazyNativeLib(_SRC, _LIB, _bind_ps)


def _load() -> Optional[ctypes.CDLL]:
    return _ps_lib.load()


def native_available() -> bool:
    return _load() is not None


def build_error() -> Optional[str]:
    return _ps_lib.error()


def _f32p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


class NativeParameterServer:
    """C++ PS hub with the Python hub's interface.  ``mode`` selects the
    commit-scaling rule (MODE_DELTA / MODE_ADAG / MODE_DYNSGD).

    Feature parity (ISSUE 11): ``sparse_leaves`` registers row-sparse
    embedding tables served over the S/V/U/X wire actions; ``adaptive``
    enables the C++ Adasum flat-combining commit merger (per-worker rates
    pushed from the Python :class:`~.parameter_server.
    AdaptiveRateController`, which this wrapper subscribes to the process
    HealthMonitor) plus G/Y reconnect backpressure; ``replica_of``
    starts this hub as a hot STANDBY of the named primary (C++ feed
    thread, promotion behind the clock fence on feed loss or first
    commit) and an ``R`` hello from a peer attaches it to this hub's own
    replication feed as a primary.  ``idle_timeout`` evicts half-open
    connections via ``SO_RCVTIMEO``; ``elastic=True`` normalizes ADAG
    commits by the live committer count; ``snapshot_dir`` attaches a
    :class:`~.parameter_server.HubSnapshotter` and ``restore=True``
    reloads the newest snapshot — with the clock fence armed in C++ —
    before serving."""

    # matches SocketParameterServer's replica-loop defaults
    _POLL_INTERVAL_S = 0.25

    def __init__(self, weights: Sequence[np.ndarray], mode: int = MODE_DELTA,
                 num_workers: int = 1, port: int = 0,
                 elastic: bool = False,
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
                 recv_batch_depth: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native PS unavailable: {build_error()}")
        self._lib = lib
        self._templates = [np.array(w, dtype=np.float32) for w in weights]
        self.sparse_leaves = tuple(sorted({int(i) for i in sparse_leaves}))
        for i in self.sparse_leaves:
            if not 0 <= i < len(self._templates):
                raise ValueError(f"sparse leaf index {i} out of range for "
                                 f"{len(self._templates)} center leaves")
            if self._templates[i].ndim != 2:
                raise ValueError(
                    f"sparse leaf {i} must be a [rows, dim] table, got "
                    f"shape {self._templates[i].shape}")
        self.adaptive = bool(adaptive)
        self.replica_of = (None if replica_of is None
                           else (str(replica_of[0]), int(replica_of[1])))
        self.replica_feed_retries = int(replica_feed_retries)
        self.replica_feed_backoff = float(replica_feed_backoff)
        sizes = (ctypes.c_int64 * len(self._templates))(
            *[t.size for t in self._templates])
        n_sp = len(self.sparse_leaves)
        sp_idx = (ctypes.c_int32 * max(1, n_sp))(*(self.sparse_leaves
                                                   or (0,)))
        sp_dim = (ctypes.c_int64 * max(1, n_sp))(
            *([self._templates[i].shape[1] for i in self.sparse_leaves]
              or [0]))
        idle_ms = 0 if idle_timeout is None else max(1, int(idle_timeout * 1000))
        # receive bound shared with the Python hub: both implementations
        # reject the exact same oversized length prefixes
        max_payload = net.max_request_payload(self._templates,
                                              self.sparse_leaves)
        self._handle = lib.dk_ps_create(int(port), len(self._templates), sizes,
                                        int(mode), int(num_workers),
                                        1 if elastic else 0, idle_ms,
                                        n_sp, sp_idx, sp_dim,
                                        1 if self.adaptive else 0,
                                        int(max_payload))
        if not self._handle:
            raise RuntimeError("dk_ps_create failed")
        # zero-copy shm transport (ISSUE 18): with a ring directory set,
        # the C++ hub answers the opt-in 'Z' attach — same-host workers'
        # frames move over mmap rings byte-identical to the socket stream.
        # None keeps the hub TCP-only (it declines nothing: the action
        # never reaches a hub whose clients were not asked to send it,
        # and an unsolicited 'Z' is declined with an empty offer).
        self.shm_dir = None if shm_dir is None else str(shm_dir)
        if self.shm_dir is not None:
            os.makedirs(self.shm_dir, exist_ok=True)
            lib.dk_ps_shm_attach(self._handle,
                                 self.shm_dir.encode("utf-8"))
        # accepted for hub-kwarg parity with SocketParameterServer: the
        # C++ receive loop already drains a pipelined client's parked
        # frames with ONE recv() per wakeup into its grow-once buffer,
        # which is what the Python hub's BatchedReceiver approximates —
        # the knob has nothing further to turn natively
        self.recv_batch_depth = max(0, int(recv_batch_depth))
        if self.replica_of is not None:
            host = self.replica_of[0]
            if host in ("", "0.0.0.0"):
                host = "127.0.0.1"
            # the C++ dialer takes numeric addresses only: resolve DNS
            # names HERE, loudly — a standby silently never syncing is
            # the one failure mode worse than refusing to construct
            import socket as _socket

            try:
                host = _socket.gethostbyname(host)
            except OSError as e:
                raise ValueError(
                    f"replica_of host {self.replica_of[0]!r} does not "
                    f"resolve: {e}") from e
            lib.dk_ps_set_replica_of(
                self._handle, host.encode("utf-8"), int(self.replica_of[1]),
                self.replica_feed_retries,
                max(1, int(self.replica_feed_backoff * 1000)))
        flat = np.concatenate([t.reshape(-1) for t in self._templates]) if self._templates \
            else np.zeros(0, np.float32)
        self._total = int(flat.size)
        lib.dk_ps_set_weights(self._handle, _f32p(flat))
        self.port = -1
        self._started = False
        # telemetry bridge state: last-seen cumulative stats/histograms so
        # sync_telemetry() can inc() registry counters by DELTAS only
        self._stats_lock = threading.Lock()
        # serializes the two C++ drains (health ring, commit log): the
        # poll thread and sync_telemetry callers (snapshotter, shutdown)
        # share the ctypes buffers below, and ctypes releases the GIL —
        # unlocked concurrent drains would tear each other's data
        self._drain_lock = threading.Lock()
        self._last_stats = [0] * len(self._STAT_KEYS)
        self._last_stale_hist = [0] * 65
        self._last_merge_hist = [0] * 65
        self._drain_buf = np.zeros(4096 * 5, np.int64)
        self._health_buf = np.zeros(
            max(net.CONTROL_PAYLOAD_MAX, int(max_payload)), np.uint8)
        # sharded-hub identity: mirrors the Python hub
        self.shard_id = None if shard_id is None else int(shard_id)
        self._mlabels = ({} if shard_id is None
                         else {"shard": str(int(shard_id))})
        # adaptive glue (bound in start(), the Python hub's eager-bind
        # convention): Python-side rate controller + monitor subscription
        # pushing verdicts into the C++ apply path
        self._rate: Optional[Any] = None
        self._health: Optional[Any] = None
        self._health_monitor: Optional[Any] = None
        self._health_unsub: Optional[Any] = None
        self._poll_stop = threading.Event()
        self._poll_thread: Optional[threading.Thread] = None
        self._restore = bool(restore)
        self.snapshotter = None
        if restore and snapshot_dir is None:
            raise ValueError("restore=True requires snapshot_dir")
        if snapshot_dir is not None:
            from distkeras_tpu.runtime.parameter_server import HubSnapshotter

            self.snapshotter = HubSnapshotter(self, snapshot_dir,
                                              interval=snapshot_interval,
                                              keep=snapshot_keep)

    def start(self) -> None:
        if self._restore and self.snapshotter is not None:
            # same contract as the Python hub: unreadable-but-present
            # snapshots are fatal (don't silently discard a job's
            # progress); a genuinely empty dir is a first boot
            if not self.snapshotter.restore_latest():
                if self.snapshotter.checkpointer.all_steps():
                    raise RuntimeError(
                        f"restore requested: snapshots exist in "
                        f"{self.snapshotter.checkpointer.directory} but none "
                        f"is readable (see warnings)")
                import warnings

                warnings.warn("restore requested but no snapshot exists "
                              "yet; serving initial weights")
        if self.adaptive:
            # bind the health plane eagerly and SUBSCRIBE (the Python
            # adaptive hub's convention): detector events drive the rate
            # controller, whose verdicts are pushed into C++ per worker
            from distkeras_tpu.observability import health as _health
            from distkeras_tpu.runtime.parameter_server import (
                AdaptiveRateController)

            if self._health is None:
                self._health = _health.collector()
            if self._health_monitor is None:
                self._health_monitor = _health.monitor()
            self._rate = AdaptiveRateController()
            self._health_unsub = self._health_monitor.subscribe(
                self._on_health_event)
        port = self._lib.dk_ps_start(self._handle)
        if port < 0:
            raise RuntimeError("native PS failed to bind")
        self.port = port
        self._started = True
        # the poll thread is the native hub's stand-in for the Python
        # hub's in-handler folds: it drains wire 'M' health reports into
        # the process collector and (adaptive) folds per-commit staleness
        # from the C++ commit log so the detectors see the same series
        self._poll_stop.clear()
        self._poll_thread = threading.Thread(target=self._poll_loop,
                                             daemon=True)
        self._poll_thread.start()
        if self.snapshotter is not None:
            self.snapshotter.start()

    def stop(self) -> None:
        self._shutdown(final_snapshot=True)

    def kill(self) -> None:
        """Crash-like teardown (no final snapshot) — the C++ twin of
        ``SocketParameterServer.kill``."""
        self._shutdown(final_snapshot=False)

    def _shutdown(self, final_snapshot: bool) -> None:
        if self._started:
            if self._health_unsub is not None and self._health_monitor is not None:
                self._health_monitor.unsubscribe(self._health_unsub)
                self._health_unsub = None
            self._poll_stop.set()
            if self._poll_thread is not None:
                self._poll_thread.join(timeout=5)
                self._poll_thread = None
            if self.snapshotter is not None:
                self.snapshotter.stop(final_snapshot=final_snapshot)
            # surface the C++ hub's final counters/commit log into the
            # registry/tracer before the serving threads go away
            try:
                self.sync_telemetry()
            except Exception:
                pass  # telemetry must never block a teardown
            self._lib.dk_ps_stop(self._handle)
            self._started = False

    # -- hot standby (replica_of surface) ---------------------------------------
    def is_standby(self) -> bool:
        """True while this hub is a replica tracking its primary (not yet
        promoted) — the C++ feed thread owns the tracking."""
        return bool(self._lib.dk_ps_is_standby(self._handle))

    @property
    def promoted(self) -> bool:
        return bool(self._lib.dk_ps_promoted(self._handle))

    @property
    def promoted_at_clock(self) -> Optional[int]:
        v = int(self._lib.dk_ps_promoted_at_clock(self._handle))
        return None if v < 0 else v

    def wait_synced(self, timeout: Optional[float] = None) -> bool:
        """Block until this replica has applied its first full sync from
        the primary (True), or ``timeout`` elapsed (False)."""
        ms = -1 if timeout is None else max(0, int(timeout * 1000))
        return bool(self._lib.dk_ps_wait_synced(self._handle, ms))

    def promote(self, reason: str = "manual") -> bool:
        """Promote the standby to primary (ops/test hook; the C++ hub also
        promotes itself on feed loss or first commit).  Arms the clock
        fence at the replicated clock, idempotent; True if this call
        performed the promotion."""
        performed = bool(self._lib.dk_ps_promote(self._handle))
        if performed:
            import warnings

            warnings.warn(f"native replica hub promoting to primary at "
                          f"clock {self.promoted_at_clock}: {reason}")
        return performed

    # -- adaptive glue ----------------------------------------------------------
    def _on_health_event(self, event: Any) -> None:
        """HealthMonitor.subscribe callback: storm events arm C++-side
        reconnect shedding; staleness/straggler events update the Python
        rate controller, whose fresh verdict for that worker is pushed
        into the C++ apply path with an expiry deadline (an expired
        verdict reads as 1.0, so a dead controller can never pin a
        worker's scale)."""
        try:
            if getattr(event, "kind", None) in ("reconnect_storm",
                                                "failover_storm"):
                self._lib.dk_ps_arm_storm(self._handle)
            rate = self._rate
            if rate is None:
                return
            rate.on_event(event)
            worker = getattr(event, "worker", None)
            if worker is None:
                return
            try:
                wid = int(str(worker))
            except ValueError:
                return  # only wire-announceable (integer) ids reach C++
            expires = self.time_ns() + int(rate.hold_s * 1e9)
            self._lib.dk_ps_set_rate_scale(self._handle, wid,
                                           float(rate.scale_for(worker)),
                                           expires)
        except Exception:
            pass  # adaptation must never take down the emitting path

    def _poll_loop(self) -> None:
        while not self._poll_stop.wait(self._POLL_INTERVAL_S):
            try:
                self._drain_health()
                if self.adaptive:
                    self._consume_commit_log()
                    mon = self._health_monitor
                    if mon is not None:
                        mon.maybe_check()
            except Exception:
                pass  # telemetry/health must never kill the hub

    def _ingest_health(self, report: Dict[str, Any]) -> None:
        """Fold one drained wire report into the process collector (lazy
        binding, the Python hub's _ingest_health)."""
        if self._health is None or self._health_monitor is None:
            from distkeras_tpu.observability import health as _health

            if self._health is None:
                self._health = _health.collector()
            if self._health_monitor is None:
                self._health_monitor = _health.monitor()
        self._health.ingest(report, shard=self.shard_id)
        self._health_monitor.maybe_check()

    def _drain_health(self) -> None:
        """Drain the C++ hub's parked action-``M`` reports into the
        process HealthCollector."""
        ptr = self._health_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        while True:
            with self._drain_lock:
                n = int(self._lib.dk_ps_next_health(self._handle, ptr,
                                                    self._health_buf.size))
                raw = bytes(self._health_buf[:n]) if n > 0 else b""
            if n == 0:
                break
            if n < 0:
                continue  # oversized report dropped (counted C++-side)
            try:
                report = json.loads(raw.decode("utf-8"))
            except Exception:
                continue  # malformed reports are ignored, never fatal
            self._ingest_health(report)

    # -- telemetry bridge (dk_ps_stats and friends) ----------------------------
    def _shard_attrs(self) -> Dict[str, int]:
        return {} if self.shard_id is None else {"shard": self.shard_id}

    # dk_ps_stats slot order (native/ps_server.cpp StatSlot) — keep in sync
    _STAT_KEYS = ("commits", "pulls", "commit_bytes", "pull_bytes",
                  "fenced_commits", "live_workers", "idle_evictions", "clock",
                  "commit_log_dropped",
                  "sparse_rows_pulled", "sparse_rows_committed",
                  "sparse_wire_bytes_saved",
                  "replicas_connected", "replicas_attached",
                  "replica_disconnects",
                  "merge_batches", "merged_commits", "max_merge_batch",
                  "backpressure_hints", "replica_frames", "promotions",
                  "health_reports_dropped", "is_standby", "promoted_flag",
                  "promoted_at_clock", "synced",
                  "repl_sparse_bytes", "repl_sparse_saved")

    # cumulative counters synced into the registry under the SAME names
    # the Python hub emits, so Prometheus/punchcard output is
    # hub-implementation-agnostic
    _COUNTER_NAMES = (("commits", "ps_commits_total"),
                      ("pulls", "ps_pulls_total"),
                      ("commit_bytes", "ps_commit_bytes_total"),
                      ("pull_bytes", "ps_pull_bytes_total"),
                      ("fenced_commits", "ps_fenced_commits_total"),
                      ("idle_evictions", "ps_idle_evictions_total"),
                      ("sparse_rows_pulled", "ps.sparse_rows_pulled"),
                      ("sparse_rows_committed", "ps.sparse_rows_committed"),
                      ("sparse_wire_bytes_saved", "ps.sparse_wire_bytes_saved"),
                      ("replicas_attached", "ps_replicas_attached_total"),
                      ("replica_disconnects", "ps_replica_disconnects_total"),
                      ("merged_commits", "ps_merged_commits_total"),
                      ("backpressure_hints", "ps_backpressure_hints_total"),
                      ("replica_frames", "ps_replica_frames_total"),
                      ("promotions", "ps_promotions_total"),
                      ("repl_sparse_saved", "ps.repl_sparse_bytes_saved"))

    def stats(self) -> Dict[str, int]:
        """The C++ hub's cumulative counters, by name (see ``dk_ps_stats``
        in ``native/ps_server.cpp``)."""
        out = (ctypes.c_int64 * len(self._STAT_KEYS))()
        self._lib.dk_ps_stats(self._handle, out)
        return dict(zip(self._STAT_KEYS, [int(v) for v in out]))

    @property
    def backpressure_hints(self) -> int:
        """Nonzero retry-after hints issued (reconnect-storm drills read
        it) — the Python adaptive hub's attribute, served from C++."""
        return self.stats()["backpressure_hints"]

    def fleet_info(self) -> Dict[str, Any]:
        """Fleet/admission snapshot in the Python hubs' ``fleet_info``
        shape.  The C++ hub does not namespace jobs (job-scoped T
        announces are a Python-hub feature; un-upgraded hubs reply with
        the plain time payload and the client treats that as a wire
        error), so the jobs block is always empty — callers see one
        uniform dict either way."""
        s = self.stats()
        return {"live_workers": int(s.get("live_workers", 0)),
                "jobs": {}, "clock": int(s.get("clock", 0)),
                "num_updates": int(s.get("commits", 0)),
                "jobs_admitted": 0, "jobs_rejected": 0}

    def time_ns(self) -> int:
        """The hub's CLOCK_MONOTONIC in ns — the same epoch Python's
        ``time.perf_counter_ns`` reads on Linux (offset sanity checks)."""
        return int(self._lib.dk_ps_time_ns(self._handle))

    def _consume_commit_log(self) -> None:
        """Drain the C++ commit log: each record becomes a hub-side span
        (telemetry on) and — when the health plane is bound — the
        announcing worker's staleness observation, the same series the
        Python hub's in-handler ``_observe_health`` folds feed."""
        telemetry = obs.enabled()
        fold = self._health is not None
        if not telemetry and not fold:
            return
        while True:
            with self._drain_lock:
                n = int(self._lib.dk_ps_drain_commits(
                    self._handle,
                    self._drain_buf.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_int64)),
                    4096))
                records = self._drain_buf[:n * 5].copy()
            for i in range(n):
                clock, worker, staleness, t_ns, dur_ns = \
                    (int(v) for v in records[i * 5:i * 5 + 5])
                if telemetry:
                    attrs = {"staleness": staleness, "clock": clock,
                             "hub": "native", **self._shard_attrs()}
                    if worker >= 0:
                        attrs["worker"] = worker
                    obs.TRACER.record_span("ps.handle_commit", t_ns,
                                           t_ns + dur_ns, tid="native-hub",
                                           **attrs)
                if fold and worker >= 0:
                    # shard-0-only convention for sharded hubs: one logical
                    # commit lands on every shard, count it once
                    if self.shard_id is None or self.shard_id == 0:
                        self._health.observe(str(worker), "staleness",
                                             float(staleness),
                                             shard=self.shard_id)
            if n < 4096:
                break

    def sync_telemetry(self) -> None:
        """Drain the C++ hub's telemetry into the process registry/tracer
        under the SAME names the Python hub emits (``ps_commits_total``,
        ``ps_commit_staleness``, ``ps.sparse_rows_pulled``, ...), so
        Prometheus/punchcard output is hub-implementation-agnostic.
        Counters advance by deltas against the last sync; the commit log
        becomes ``ps.handle_commit`` spans.  Called automatically at
        shutdown and on every hub snapshot; call it directly for an
        up-to-the-moment mid-run view."""
        self._drain_health()
        if not obs.enabled():
            return
        with self._stats_lock:
            stats = self.stats()
            vals = [stats[k] for k in self._STAT_KEYS]
            delta = {k: v - last for k, v, last
                     in zip(self._STAT_KEYS, vals, self._last_stats)}
            self._last_stats = vals
            for key, name in self._COUNTER_NAMES:
                if delta[key] > 0:
                    obs.counter(name, **self._mlabels).inc(delta[key])
            obs.gauge("ps_live_workers",
                      **self._mlabels).set(stats["live_workers"])
            obs.gauge("ps_replicas_connected",
                      **self._mlabels).set(stats["replicas_connected"])
            # exact small-integer staleness counts -> the shared log-bucket
            # histogram (value == slot; the overflow slot observes as its
            # lower bound, a documented approximation)
            hist = (ctypes.c_int64 * 65)()
            self._lib.dk_ps_staleness_hist(self._handle, hist)
            stale = obs.histogram("ps_commit_staleness", **self._mlabels)
            for slot in range(65):
                stale.observe_n(slot, int(hist[slot]) - self._last_stale_hist[slot])
                self._last_stale_hist[slot] = int(hist[slot])
            if self.adaptive:
                self._lib.dk_ps_merge_hist(self._handle, hist)
                merge = obs.histogram("ps.merge_batch", **self._mlabels)
                for slot in range(65):
                    merge.observe_n(slot,
                                    int(hist[slot]) - self._last_merge_hist[slot])
                    self._last_merge_hist[slot] = int(hist[slot])
            if self.sparse_leaves:
                # decayed hot-set estimates under the same gauge the
                # Python hub emits (ISSUE 15 row-touch telemetry)
                hot = (ctypes.c_int64 * len(self.sparse_leaves))()
                self._lib.dk_ps_hot_rows(self._handle, hot)
                for leaf, count in zip(self.sparse_leaves, hot):
                    obs.gauge("ps.sparse_hot_rows", table=str(leaf),
                              **self._mlabels).set(int(count))
        # commit log -> hub-side spans on the "native-hub" track
        self._consume_commit_log()

    # -- durability (HubSnapshotter surface) -----------------------------------
    def snapshot_state(self):
        """(center tensors, JSON-typed state dict) — one atomic view via the
        C++ snapshot path (center + clock under the hub gate; NOT counted
        as a pull — the Python hub's snapshot_state is uncounted too).
        Piggybacks a telemetry sync: a snapshotting hub surfaces its C++
        counters into the registry at least once per snapshot interval."""
        try:
            self.sync_telemetry()
        except Exception:
            pass
        flat = np.empty(self._total, np.float32)
        clock = int(self._lib.dk_ps_snapshot(self._handle, _f32p(flat)))
        center, off = [], 0
        for t in self._templates:
            center.append(flat[off:off + t.size].reshape(t.shape).copy())
            off += t.size
        return (center,
                {"clock": clock, "num_updates": int(self.num_updates)})

    def restore_state(self, center: Sequence[np.ndarray], state) -> None:
        if len(center) != len(self._templates):
            raise ValueError(f"snapshot has {len(center)} tensors, center has "
                             f"{len(self._templates)}")
        parts = [np.ascontiguousarray(c, np.float32).reshape(-1) for c in center]
        flat = np.concatenate(parts) if parts else np.zeros(0, np.float32)
        if flat.size != self._total:
            raise ValueError(f"snapshot has {flat.size} values, center has "
                             f"{self._total}")
        self._lib.dk_ps_restore(self._handle, _f32p(flat),
                                int(state.get("clock", 0)),
                                int(state.get("num_updates", 0)))

    def get_weights(self) -> List[np.ndarray]:
        out = np.zeros(self._total, np.float32)
        self._lib.dk_ps_get_weights(self._handle, _f32p(out))
        result = []
        off = 0
        for t in self._templates:
            result.append(out[off:off + t.size].reshape(t.shape).copy())
            off += t.size
        return result

    # -- in-process transport (transport="inproc") -----------------------------
    # Mirrors SocketParameterServer.pull_direct/commit_direct: co-located
    # workers exchange with the C++ center through two ctypes calls (both
    # release the GIL for the memcpy/apply), no sockets, no framing.

    def pull_direct(self):
        """(center tensors, clock at snapshot) — the clock rides back in
        with the matching :meth:`commit_direct`."""
        if self.is_standby() and not self._lib.dk_ps_wait_synced(self._handle, 0):
            # same rule as the Python hub's pull_direct: seed weights must
            # never be served as if they were the job's state
            raise RuntimeError(
                "pull_direct from a never-synced standby refused "
                "(it holds no job state yet); wait_synced() first")
        flat = np.empty(self._total, np.float32)
        clock = int(self._lib.dk_ps_pull(self._handle, _f32p(flat)))
        out, off = [], 0
        for t in self._templates:
            out.append(flat[off:off + t.size].reshape(t.shape))
            off += t.size
        return out, clock

    def commit_direct(self, delta: Sequence[np.ndarray], last_pull_clock: int) -> None:
        if len(delta) != len(self._templates):
            raise ValueError(f"commit has {len(delta)} tensors, center has "
                             f"{len(self._templates)}")
        parts = []
        for d, t in zip(delta, self._templates):
            a = np.ascontiguousarray(d, dtype=np.float32).reshape(-1)
            if a.size != t.size:
                raise ValueError(f"commit tensor size {a.size} != center "
                                 f"size {t.size}")
            parts.append(a)
        flat = np.concatenate(parts) if parts else np.zeros(0, np.float32)
        # attribute the commit to the calling worker thread's trace
        # context (inproc workers have no connection to announce T on);
        # -1 = uncontexted, matching the wire default
        ctx = dtrace.current()
        worker = int(ctx.worker_id) if ctx is not None else -1
        rc = int(self._lib.dk_ps_commit_ctx(self._handle, _f32p(flat),
                                            int(last_pull_clock), worker))
        if rc == 1:
            raise RuntimeError(
                "commit_direct into a never-synced standby refused "
                "(it has no state to take over); wait_synced() first")
        if rc == 2:
            raise net.ProtocolError(
                "commit into a standby refused (not promoted yet; verifying "
                "the primary — retry)")

    # -- sparse in-process transport (ISSUE 15) --------------------------------
    # The former last NotImplementedError pair: the C++ hub now serves
    # the sparse direct exchange too (dk_ps_pull_sparse /
    # dk_ps_commit_sparse, GIL released), so EVERY transport x hub cell
    # composes with sparse_tables.  Semantics mirror the Python hub's
    # pull_sparse_direct/commit_sparse_direct (the bit-parity matrix in
    # tests/test_hyperscale.py pins the trajectories).

    def _check_row_ids(self, ids, leaf: int) -> np.ndarray:
        """The shared :func:`networking.check_row_ids` contract over this
        hub's templates (canonicalized to a contiguous int64 array for
        the ctypes boundary)."""
        return net.check_row_ids(
            np.ascontiguousarray(np.asarray(ids).ravel(), np.int64),
            self._templates[leaf].shape[0], leaf)

    def _pack_sparse_ids(self, ids_list):
        """Validated (sorted-unique, in-bounds) id arrays -> one
        concatenated int64 buffer + per-table counts."""
        if len(ids_list) != len(self.sparse_leaves):
            raise ValueError(f"got {len(ids_list)} id arrays, hub has "
                             f"{len(self.sparse_leaves)} sparse tables")
        norm = [self._check_row_ids(ids, i)
                for ids, i in zip(ids_list, self.sparse_leaves)]
        counts = (ctypes.c_int64 * max(1, len(norm)))(
            *([ids.size for ids in norm] or [0]))
        flat = (np.concatenate(norm) if norm
                else np.zeros(0, np.int64))
        flat = np.ascontiguousarray(flat, np.int64)
        if flat.size == 0:
            flat = np.zeros(1, np.int64)  # a valid pointer for ctypes
        return norm, flat, counts

    def pull_sparse_direct(self, ids_list):
        """The S/V exchange minus the frame against the C++ center: one
        sorted-unique id array per sparse table in, ``(per-leaf values,
        clock)`` out — full copies for dense leaves, the requested
        ``[k, dim]`` row blocks for sparse leaves."""
        if not self.sparse_leaves:
            raise RuntimeError("pull_sparse_direct on a hub with no sparse "
                               "tables (pass sparse_leaves to the hub)")
        norm, flat_ids, counts = self._pack_sparse_ids(ids_list)
        total = 0
        it = iter(norm)
        for i, t in enumerate(self._templates):
            total += (next(it).size * t.shape[1]
                      if i in set(self.sparse_leaves) else t.size)
        out = np.empty(max(1, total), np.float32)
        clock = int(self._lib.dk_ps_pull_sparse(
            self._handle,
            flat_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            counts, _f32p(out)))
        if clock == -1:
            raise RuntimeError(
                "pull_sparse_direct from a never-synced standby refused "
                "(it holds no job state yet); wait_synced() first")
        if clock == -2:
            raise ValueError("sparse pull row ids rejected by the native "
                             "hub (sorted-unique, in-bounds required)")
        values, off = [], 0
        it = iter(norm)
        for i, t in enumerate(self._templates):
            if i in set(self.sparse_leaves):
                k = next(it).size
                n = k * t.shape[1]
                values.append(out[off:off + n].reshape(k, t.shape[1]).copy())
            else:
                n = t.size
                values.append(out[off:off + n].reshape(t.shape).copy())
            off += n
        return values, clock

    def commit_sparse_direct(self, parts, last_pull_clock):
        """Apply one row-sparse commit (the U exchange minus the frame):
        ``parts`` aligned with the center — full f32 delta for dense
        leaves, ``(ids, grads)`` for sparse leaves."""
        if not self.sparse_leaves:
            raise RuntimeError("commit_sparse_direct on a hub with no "
                               "sparse tables (pass sparse_leaves)")
        if len(parts) != len(self._templates):
            raise ValueError(f"commit has {len(parts)} parts, center has "
                             f"{len(self._templates)}")
        sset = set(self.sparse_leaves)
        ids_list = []
        vals = []
        for i, (p, t) in enumerate(zip(parts, self._templates)):
            if i in sset:
                ids, grads = p
                ids = self._check_row_ids(ids, i)
                grads = np.ascontiguousarray(grads, np.float32).reshape(
                    ids.size, t.shape[1])
                ids_list.append(ids)
                vals.append(grads.reshape(-1))
            else:
                vals.append(np.ascontiguousarray(p, np.float32).reshape(-1))
        counts = (ctypes.c_int64 * max(1, len(ids_list)))(
            *([ids.size for ids in ids_list] or [0]))
        flat_ids = (np.concatenate(ids_list) if ids_list
                    else np.zeros(0, np.int64))
        flat_ids = np.ascontiguousarray(flat_ids, np.int64)
        if flat_ids.size == 0:
            flat_ids = np.zeros(1, np.int64)
        flat_vals = (np.concatenate(vals) if vals
                     else np.zeros(0, np.float32))
        flat_vals = np.ascontiguousarray(flat_vals, np.float32)
        if flat_vals.size == 0:
            flat_vals = np.zeros(1, np.float32)
        ctx = dtrace.current()
        worker = int(ctx.worker_id) if ctx is not None else -1
        rc = int(self._lib.dk_ps_commit_sparse(
            self._handle, _f32p(flat_vals),
            flat_ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            counts, int(last_pull_clock), worker))
        if rc == 1:
            raise RuntimeError(
                "commit_sparse_direct into a never-synced standby refused "
                "(it has no state to take over); wait_synced() first")
        if rc == 2:
            raise net.ProtocolError(
                "commit into a standby refused (not promoted yet; verifying "
                "the primary — retry)")
        if rc == 3:
            raise ValueError("sparse commit row ids rejected by the native "
                             "hub (sorted-unique, in-bounds required)")

    @property
    def num_updates(self) -> int:
        return int(self._lib.dk_ps_num_updates(self._handle))

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                if self._started:
                    self._shutdown(final_snapshot=False)
                self._lib.dk_ps_destroy(self._handle)
                self._handle = None
        except Exception:
            pass
