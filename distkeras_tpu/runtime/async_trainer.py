"""Genuinely-asynchronous distributed trainers (host-loop + PS hub).

The mesh trainers in :mod:`distkeras_tpu.trainers` realize the reference's
async algorithms as deterministic synchronous serializations — one fused
XLA program, the right default on a TPU slice.  This module is the other
execution option from SURVEY.md §7 ("hard parts", option b): a faithful
reproduction of the reference's *actual* concurrency — N workers training
independently and racing pull/commit exchanges against a parameter-server
hub (reference call stack §3.1) — for deployments where workers are
separate host processes driving their own chips over DCN, or where true
staleness behavior is being studied.

Differences from the reference's execution (same semantics, new substrate):

- each worker's ``communication_window`` minibatches compile to ONE
  ``lax.scan`` program (no per-batch Python), so the host loop only runs
  at window boundaries — exactly where the socket exchange happens anyway;
- the PS hub may be the C++ one (``native/ps_server.cpp``) — commits then
  apply outside the GIL, so in-process worker threads genuinely overlap;
- weights travel as raw float32 frames, not pickles — through the
  zero-copy flat framing path (``recv_into`` scatter receives; a dense
  commit streamed leaf by leaf from the device to the socket, its
  copy-out issued at dispatch; ``networking.FlatFrameCodec``);
- the exchange is PIPELINED by default (``pipeline=True``): the pull for
  window k+1 is requested right after window k's program is dispatched
  and its reply is received on the worker's thread while that program
  runs (``client.land_weights()``, phase ``async.pull_land``); commit acks
  coalesce into that receive, so wall-per-window converges toward
  max(compute, wire) instead of their sum (staleness semantics:
  ARCHITECTURE.md "Async transport");
- co-located workers may skip sockets entirely with ``transport="inproc"``
  (same center logic under the hub's lock, identical trajectories;
  sockets stay the default for multi-host authenticity).

Worker threads in one process share the single JAX runtime; with multiple
devices visible each worker pins its compute to ``devices[i % n]``, giving
real device-parallel async training in one process (the test/CI shape).

Multi-host topology (exercised by ``tests/test_multihost.py``): a
standalone hub on the head node
(``runtime/launcher.py :: start_parameter_server`` / ``distkeras-ps``),
and on every worker host one Async* trainer constructed with
``ps_address=(head, port)`` — worker-only mode: it starts no hub, drives
its local shard against the remote one, and returns the center pulled at
finish.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import observability as obs
from distkeras_tpu.observability import distributed as dtrace
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.base import Model
from distkeras_tpu.parallel.engine import make_minibatch_step
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    InprocPSClient,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    _normalize_failover,
    shard_plan,
)
from distkeras_tpu.runtime.faults import WorkerPreempted
from distkeras_tpu.trainers import Trainer
from distkeras_tpu.utils import flatten_weights


class _DrainRequested(Exception):
    """Control-flow signal: the FleetController asked this worker to
    retire; unwinds the window loop into the graceful-drain handler."""

    def __init__(self, worker: int, window: int):
        super().__init__(
            f"drain requested: worker {worker} at window {window}")
        self.worker = int(worker)
        self.window = int(window)


def _make_window_fn(trainer: "AsyncDistributedTrainer", apply_fn: Callable,
                    loss: Callable, optimizer) -> Callable:
    """Jitted ``(params, opt_state, pulled, wx, wy) -> (next_params,
    opt_state, commit, mean_loss)``: one communication window of local
    steps PLUS the algorithm's window-boundary math as a single XLA
    program.

    Folding ``device_window_start`` / ``device_commit`` into the program
    keeps the worker's params and optimizer state DEVICE-RESIDENT across
    windows (round-4 verdict weak #2: the old loop round-tripped the full
    model host<->device every window and computed the commit delta in
    single-threaded host numpy).  The only per-window host<->device
    traffic left is what the PS protocol itself moves: the pulled center
    in, the commit payload out.  ``params``/``opt_state`` are donated —
    XLA reuses their buffers for the next window's state."""
    mini = make_minibatch_step(apply_fn, loss, optimizer)

    def window(params, opt_state, pulled, wx, wy):
        start = trainer.device_window_start(pulled, params)
        (after, opt_state), losses = jax.lax.scan(mini, (start, opt_state), (wx, wy))
        commit, next_params = trainer.device_commit(pulled, after)
        return next_params, opt_state, commit, jnp.mean(losses)

    return jax.jit(window, donate_argnums=(0, 1))


class AsyncDistributedTrainer(Trainer):
    """Scaffolding shared by the async family (reference §2.4's
    ``AsynchronousDistributedTrainer``): starts the PS, spawns one worker
    thread per partition, joins, returns the PS's center model."""

    def __init__(self, model, num_workers: int = 2, communication_window: int = 5,
                 native_ps: bool = False,
                 ps_address: Optional[Tuple[str, int]] = None,
                 ps_failover: Optional[Any] = None,
                 replica_of: Optional[Tuple[str, int]] = None,
                 replica_sync_timeout: float = 60.0,
                 checkpoint_interval: float = 30.0,
                 on_worker_failure: str = "raise",
                 max_worker_restarts: int = 2,
                 fault_hook: Optional[Callable[[int, int], None]] = None,
                 compress_commits: Optional[str] = None,
                 transport: str = "socket",
                 num_shards: int = 1,
                 recv_batch_depth: int = 0,
                 pipeline: bool = True,
                 max_inflight_commits: int = 2,
                 max_reconnects: Optional[int] = None,
                 reconnect_backoff: float = 0.1,
                 heartbeat_interval: Optional[float] = None,
                 elastic: bool = False,
                 ps_idle_timeout: Optional[float] = None,
                 trace_context: Optional[str] = None,
                 health_interval_s: Optional[float] = None,
                 sparse_tables: Optional[Any] = None,
                 sparse_cache_rows: Optional[int] = None,
                 adaptive: bool = False,
                 autoscale: bool = False,
                 **kwargs):
        super().__init__(model, **kwargs)
        self.num_workers = int(num_workers)
        self.communication_window = int(communication_window)
        self.native_ps = bool(native_ps)
        # transport="socket" (default): workers speak the framed wire
        # protocol — the multi-host-authentic path, also used co-located.
        # transport="inproc": co-located workers call the hub's
        # pull_direct/commit_direct under its lock — no sockets, no
        # framing; identical training trajectories (the parity property
        # tests/test_transport.py pins).  Requires owning the hub.
        # transport="shm" (ISSUE 18): the socket path plus the opt-in
        # shared-memory attach — the hub gets an shm_dir, every worker
        # client sends the action-Z capability request, and same-host
        # frames move over mmap rings instead of the kernel socket stack.
        # Byte-identical frame payloads, so trajectories match "socket"
        # exactly; a hub that declines (or a legacy hub) degrades each
        # worker independently back to plain TCP.
        if transport not in ("socket", "inproc", "shm"):
            raise ValueError(f"transport must be 'socket', 'inproc' or "
                             f"'shm', got {transport!r}")
        if transport == "inproc" and ps_address is not None:
            raise ValueError(
                "transport='inproc' requires a co-located hub (the trainer "
                "starts its own); worker-only mode with ps_address needs "
                "transport='socket'")
        self.transport = transport
        # pipeline=True (default): the pull for window k+1 is prefetched
        # while window k computes, and commit acks coalesce into later
        # receives (at most max_inflight_commits ride unacknowledged) —
        # wall-per-window converges toward max(compute, wire).  The pull
        # for k+1 then observes the center BEFORE this worker's commit k
        # (deterministic self-staleness of 1; see ARCHITECTURE.md "Async
        # transport").  pipeline=False restores the strictly serial
        # pull -> train -> commit -> ack exchange per window.
        self.pipeline = bool(pipeline)
        self.max_inflight_commits = int(max_inflight_commits)
        # "int8": workers send action-Q commits (4x fewer wire bytes,
        # error feedback client-side — see PSClient); pulls stay f32.
        # Both hubs (Python and C++) accept either commit form.
        if compress_commits not in (None, "int8"):
            raise ValueError(f"compress_commits must be None or 'int8', "
                             f"got {compress_commits!r}")
        self.compress_commits = compress_commits
        # sharded hub (ISSUE 6): num_shards > 1 partitions the center
        # across that many hubs — deterministic size-balanced leaf->shard
        # assignment (shard_plan), one hub per shard, striped pull/commit.
        # The default 1 is byte-identical to today's single-hub wire
        self.num_shards = int(num_shards)
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        # batched hub receive (ISSUE 18): > 0 makes every trainer-owned
        # hub drain its sockets recvmmsg-style, up to this many frames per
        # syscall (falling back to plain nonblocking recvs where the
        # batched syscall is unavailable).  0 (default) keeps the exact
        # one-recv_into-per-frame receive loop
        self.recv_batch_depth = int(recv_batch_depth)
        if self.recv_batch_depth < 0:
            raise ValueError(f"recv_batch_depth must be >= 0, got "
                             f"{recv_batch_depth}")
        # lazily-created run-scoped directory the shm transport's ring
        # files live in (under /dev/shm when present, so the "file" is
        # pure page cache); cleaned up when the trainer-owned hub stops
        self._shm_dir: Optional[str] = None
        # worker-only mode (multi-host): connect to an external hub at this
        # (host, port) — or, sharded, a SEQUENCE of per-shard (host, port)
        # pairs aligned with the shard plan (num_shards defaults to the
        # sequence length) — instead of starting one; see module docstring
        if ps_address is None:
            self.ps_address = None
            self._ps_addresses: Optional[List[Tuple[str, int]]] = None
        else:
            addr = list(ps_address)
            if addr and isinstance(addr[0], (str, bytes)):
                addrs = [(str(addr[0]), int(addr[1]))]
            else:
                addrs = [(str(h), int(p)) for h, p in addr]
            if len(addrs) > 1 and self.num_shards == 1:
                self.num_shards = len(addrs)
            if len(addrs) != self.num_shards:
                raise ValueError(
                    f"ps_address has {len(addrs)} shard addresses but "
                    f"num_shards={self.num_shards}; worker-only sharded mode "
                    f"needs one (host, port) per shard")
            self._ps_addresses = addrs
            self.ps_address = (addrs[0] if len(addrs) == 1
                               else tuple(addrs))
        # hot-standby failover (ISSUE 7): per-shard standby address(es)
        # every worker client rotates to when its primary stripe dies
        # inside the reconnect budget.  Unsharded: one (host, port) pair or
        # a list of pairs; sharded: one entry per shard, aligned with
        # ps_address (None for shards without a standby)
        if ps_failover is None:
            self._ps_failover: Optional[List[List[Tuple[str, int]]]] = None
        elif self.num_shards == 1:
            self._ps_failover = [_normalize_failover(ps_failover)]
        else:
            fo = list(ps_failover)
            if fo and isinstance(fo[0], (str, bytes)):
                # a bare (host, port) pair: its length can coincide with
                # num_shards (2 shards!) and would otherwise be sliced
                # into per-shard garbage instead of erroring
                raise ValueError(
                    f"ps_failover got a single (host, port) pair but "
                    f"num_shards={self.num_shards}; sharded failover needs "
                    f"one entry per shard (None for shards without a "
                    f"standby)")
            if len(fo) != self.num_shards:
                raise ValueError(
                    f"ps_failover has {len(fo)} entries but "
                    f"num_shards={self.num_shards}; sharded failover needs "
                    f"one entry per shard (None for shards without a "
                    f"standby)")
            self._ps_failover = [_normalize_failover(e) for e in fo]
        # replica_of=(host, port): the trainer-owned hub starts as a HOT
        # STANDBY of that primary (binds, tracks the primary's center,
        # promotes itself on feed loss or first commit) — the launcher's
        # --replica-of for in-process deployments.  Python hub only;
        # single-shard only (per-shard standbys are per-shard daemons)
        self.replica_of = (None if replica_of is None
                           else (str(replica_of[0]), int(replica_of[1])))
        # how long train() waits for the standby hub's first full sync
        # before refusing to train (see the wait_synced guard below)
        self.replica_sync_timeout = float(replica_sync_timeout)
        if self.replica_of is not None:
            if ps_address is not None:
                raise ValueError("replica_of configures the trainer-owned "
                                 "hub; worker-only mode (ps_address) starts "
                                 "no hub — point ps_failover at the standby "
                                 "instead")
            if self.num_shards > 1:
                raise ValueError("replica_of requires num_shards=1 (a "
                                 "sharded deployment runs one standby "
                                 "daemon per shard primary)")
            # both hubs serve replica_of (the C++ standby runs its feed
            # thread native-side; ISSUE 11) — no native guard needed
        self.checkpoint_interval = float(checkpoint_interval)
        # failure policy (SURVEY §5 "failure detection" — the reference had
        # none; Spark silently re-ran dead executors).  "raise" surfaces the
        # first worker error after all workers drain; "continue" lets the
        # survivors finish and returns the center anyway, recording errors
        # in self.worker_errors — the hub-keeps-serving recovery mode.
        # "restart" is Spark's re-run made explicit and bounded: a crashed
        # worker is restarted up to max_worker_restarts times from the
        # hub's CURRENT center (its progress up to the last applied commit
        # survives in the center; its local divergence does not), resuming
        # at the epoch it died in; once the budget is exhausted the error
        # is recorded and the survivors finish, as with "continue".
        if on_worker_failure not in ("raise", "continue", "restart"):
            raise ValueError(f"on_worker_failure must be 'raise', 'continue' "
                             f"or 'restart', got {on_worker_failure!r}")
        self.on_worker_failure = on_worker_failure
        self.max_worker_restarts = int(max_worker_restarts)
        # client resilience knobs, threaded into every worker's PSClient
        # (socket transport only — inproc workers share the hub's process
        # and die with it): bounded reconnect with exponential backoff +
        # jitter, and heartbeat-on-idle against the hub's idle eviction.
        # Default: worker-only mode (ps_address) gets a small budget —
        # remote workers face real networks AND the standalone hub's
        # default idle eviction, and a reconnect+re-pull is semantically
        # safe — while a trainer that owns its hub fails fast (the hub
        # dying means this process is dying with it)
        if max_reconnects is None:
            max_reconnects = 5 if ps_address is not None else 0
        self.max_reconnects = int(max_reconnects)
        self.reconnect_backoff = float(reconnect_backoff)
        self.heartbeat_interval = heartbeat_interval
        # elastic=True: the hub normalizes by LIVE membership instead of
        # the configured worker count (ADAG; see ADAGParameterServer) —
        # a permanently dead worker stops diluting the survivors
        self.elastic = bool(elastic)
        # half-open-connection eviction window on the trainer-owned hub.
        # Default OFF: a trainer-owned hub only serves same-process
        # workers, whose sockets always deliver FIN on death (true
        # half-open needs a dead remote host/NIC), and a default eviction
        # window would regress runs whose first-window compile outlasts
        # it.  Standalone hubs (distkeras-ps / start_parameter_server)
        # default to 300 s — they face real networks
        self.ps_idle_timeout = ps_idle_timeout
        # distributed tracing (ISSUE #5): the job id every worker's
        # TraceContext announces over the PS wire.  None = auto-generate a
        # fresh one per train() when telemetry is on; pass an explicit id
        # to join a multi-host run's workers under one job in the merged
        # trace (all hosts must pass the same string).  Only consulted
        # while telemetry is enabled — with obs off no context exists and
        # no T frame ever leaves (pre-T hubs interoperate)
        self.trace_context = trace_context
        # live fleet health plane (ISSUE 8): every health_interval_s
        # seconds each worker pushes one compact metric report (windows,
        # rolling window wall, reconnect/failover totals) to the hub —
        # wire action M on the pipelined FIFO (socket) or a direct
        # collector fold (inproc) — where the online detectors run over
        # the per-worker sliding windows.  Default None = OFF: no M frame
        # ever leaves, so pre-M hubs interoperate byte-identically.
        # Both hubs ingest M (the C++ hub parks reports in a ring its
        # wrapper drains into the collector; ISSUE 11)
        if health_interval_s is not None:
            health_interval_s = float(health_interval_s)
            if health_interval_s <= 0:
                raise ValueError(f"health_interval_s must be positive, "
                                 f"got {health_interval_s}")
            # both hubs ingest action-M reports (the C++ hub parks them
            # in a ring its Python wrapper drains into the collector)
        self.health_interval_s = health_interval_s
        # row-sparse embedding tables (ISSUE 9): None (default) = fully
        # off, every wire byte identical to the dense stack.  "auto"
        # resolves the model spec's declared EmbeddingTable leaves
        # (models.base.sparse_leaf_indices — e.g. the embedding_classifier
        # family); an explicit iterable names flat-leaf indices directly.
        # With sparse tables on, each worker pulls only the rows its next
        # window's batch touches (wire action S/V) and commits
        # (row_ids, row_grads) pairs (U, or X under int8) — idle rows cost
        # zero wire bytes; the hub applies them under the same staleness
        # clock and commit_scale rules as dense commits
        if sparse_tables is not None and sparse_tables != "auto":
            sparse_tables = tuple(sorted({int(i) for i in sparse_tables}))
        self.sparse_tables = sparse_tables
        # (the former sparse+inproc+native guard is gone: the C++ hub now
        # serves the sparse direct pair — dk_ps_pull_sparse /
        # dk_ps_commit_sparse, ISSUE 15 — so every transport x hub cell
        # composes with sparse_tables)
        # hot-tier client caching (ISSUE 15): each worker's per-table
        # host cache becomes a bounded LRU of sparse_cache_rows rows —
        # hits are served locally (zero wire), misses fetched over the
        # sparse pull, the window's compute consumes [k, dim] row blocks
        # scattered into a device-resident mirror.  None (default) keeps
        # the PR-9 full-cache path byte-identical
        self.sparse_cache_rows = (None if sparse_cache_rows is None
                                  else int(sparse_cache_rows))
        if self.sparse_cache_rows is not None:
            if sparse_tables is None:
                raise ValueError("sparse_cache_rows needs sparse_tables "
                                 "(there is no sparse exchange to cache)")
            if self.sparse_cache_rows < 1:
                raise ValueError(f"sparse_cache_rows must be >= 1, got "
                                 f"{self.sparse_cache_rows}")
            if self.num_shards > 1:
                raise ValueError(
                    "sparse_cache_rows requires num_shards=1: the striped "
                    "client's sparse design is row-range views of one "
                    "full-size cache (see MIGRATION.md)")
        # telemetry-driven adaptive aggregation (ISSUE 10), off by
        # default.  On: the trainer-owned hub merges queued commits
        # Adasum-style, scales each worker's commits by its live
        # staleness standing (DynSGD re-based on the fleet, driven by
        # HealthMonitor events), and sheds reconnect storms with
        # retry-after hints the workers' clients honor (wire action G/Y
        # — opt-in, every pre-existing frame unchanged).  Workers get
        # trace contexts even with telemetry off, so the hub can
        # attribute staleness per worker; pair with health_interval_s
        # for window-wall straggler detection too.  Python hub only
        self.adaptive = bool(adaptive)
        # both hubs serve adaptive=True: the C++ hub runs the Adasum
        # flat-combining merger and G/Y backpressure natively, with
        # per-worker rates pushed from the Python AdaptiveRateController
        # self-scaling fleet (ISSUE 19), off by default.  On: a
        # FleetController subscribes to the run's HealthMonitor and acts
        # on capacity — respawning a worker slot when fleet throughput
        # lags the frozen run-start baseline, retiring a worker the
        # staleness_drift detector names persistently (graceful drain →
        # BYE → elastic membership shrink), and authorizing the respawn
        # after a planned preemption (SpotPreemptionPlan / SIGTERM-with-
        # deadline) WITHOUT charging the restart budget.  Requires an
        # owned hub with the health plane on (health_interval_s); the
        # default False sends every wire byte identical to HEAD
        self.autoscale = bool(autoscale)
        if self.autoscale and ps_address is not None:
            raise ValueError(
                "autoscale=True requires a trainer-owned hub (the "
                "controller subscribes to the owned run's HealthMonitor); "
                "worker-only mode scales at the launcher instead "
                "(distkeras-ps --autoscale)")
        # test/chaos hook: called as fault_hook(worker_idx, window_idx) at
        # every window boundary; raise inside it to kill that worker
        self.fault_hook = fault_hook
        self.worker_errors: List[BaseException] = []
        self.worker_restarts = 0  # total supervisor restarts, last train()
        # planned-preemption records, last train(): one dict per drained
        # worker ({"worker", "window", "deadline_s", "drained_clean",
        # "outstanding_after_drain"}) — the recovery drill reads these
        self.worker_preemptions: List[Dict[str, Any]] = []
        self.fleet_controller: Optional[Any] = None  # last train()'s, if any
        self.parameter_server: Optional[Any] = None
        self._window_fn: Optional[Callable] = None  # cached per instance so a
        # second train() on the same trainer reuses the compiled program
        # (mirrors DistributedTrainer._engine)

    # -- factories (reference: allocate_worker / allocate_parameter_server) ---
    def allocate_parameter_server(self, weights: List[np.ndarray],
                                  shard_id: Optional[int] = None) -> Any:
        raise NotImplementedError  # pragma: no cover - interface

    def _hub_kwargs(self, shard_id: Optional[int] = None) -> dict:
        """Fault-tolerance + identity kwargs every trainer-owned hub
        (Python or C++) takes; subclass allocators splat this into their
        constructor.  ``shard_id`` tags a sharded hub's telemetry (None on
        the unsharded path — the exact pre-sharding series).  With sparse
        tables resolved for this run, each hub additionally learns its
        sparse leaf positions (never added otherwise — the off path
        byte-parity pins never see the kwarg)."""
        kw = {"idle_timeout": self.ps_idle_timeout, "shard_id": shard_id,
              "replica_of": self.replica_of}
        sp = getattr(self, "_hub_sparse", None)
        if sp is not None:
            kw["sparse_leaves"] = sp.get(shard_id, ())
        if self.adaptive:
            # only added when on, so the off path's zero-adaptive-
            # machinery guarantee holds for either hub implementation
            kw["adaptive"] = True
        if self.transport == "shm":
            # only added when opted in, so "socket"/"inproc" runs
            # construct hubs with byte-identical kwargs to pre-shm code
            kw["shm_dir"] = self._ensure_shm_dir()
        if self.recv_batch_depth > 0:
            kw["recv_batch_depth"] = self.recv_batch_depth
        return kw

    def _ensure_shm_dir(self) -> str:
        """The run's ring-file directory, created on first use.  Prefers
        ``/dev/shm`` (tmpfs: ring pages never touch a disk) and falls
        back to the default temp dir — mmap over any filesystem is
        correct, tmpfs is just faster under memory pressure."""
        if self._shm_dir is None:
            self._shm_dir = tempfile.mkdtemp(
                prefix="dkshm-",
                dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
        return self._shm_dir

    def _cleanup_shm_dir(self) -> None:
        """Remove the run's ring-file directory (idempotent).  The hub
        unlinks each ring file right after its attach handshake — live
        mappings keep the memory alive — so this normally removes an
        empty directory; leftovers only exist if a hub died mid-attach."""
        if self._shm_dir is not None:
            shutil.rmtree(self._shm_dir, ignore_errors=True)
            self._shm_dir = None

    def _resolve_sparse_tables(self, flat: List[np.ndarray]) -> Tuple[int, ...]:
        """The run's sparse leaf indices: () when off, the spec's declared
        EmbeddingTable leaves for "auto", or the validated explicit set."""
        declared = self.sparse_tables
        if declared is None:
            return ()
        if declared == "auto":
            from distkeras_tpu.models.base import sparse_leaf_indices

            declared = sparse_leaf_indices(self.model.spec,
                                           self.model.params)
            if not declared:
                raise ValueError(
                    f"sparse_tables='auto' but architecture "
                    f"{self.model.spec.name!r} declares no sparse embedding "
                    f"tables (sparse_param_names); name leaf indices "
                    f"explicitly or drop sparse_tables")
        # validation below covers BOTH paths: an architecture declaring
        # mismatched-vocabulary tables must fail at setup too
        for i in declared:
            if not 0 <= i < len(flat):
                raise ValueError(f"sparse_tables index {i} out of range for "
                                 f"{len(flat)} model leaves")
            if flat[i].ndim != 2:
                raise ValueError(f"sparse_tables leaf {i} must be a "
                                 f"[rows, dim] table, got {flat[i].shape}")
        # per-table vocabularies (ISSUE 15): an architecture declaring a
        # sparse_field_map gets an INDEPENDENT id set per table — each
        # table's ids come from its own feature columns and validate
        # against its own row count, so vocabularies may differ freely.
        # Without a map the PR-9 shared-vocabulary contract stands: one
        # id set per window feeds every table, so unequal row counts
        # would only surface as a mid-run ValueError on the first
        # out-of-range id — refuse at setup instead
        from distkeras_tpu.models.base import (sparse_leaf_indices,
                                               sparse_table_fields)

        fields = sparse_table_fields(self.model.spec, self.model.params)
        if fields is not None:
            by_leaf = dict(zip(sparse_leaf_indices(self.model.spec,
                                                   self.model.params),
                               fields))
            missing = [i for i in declared if i not in by_leaf]
            if missing:
                raise ValueError(
                    f"sparse_tables leaves {missing} have no "
                    f"sparse_field_map entry on architecture "
                    f"{self.model.spec.name!r} — every per-vocabulary "
                    f"table needs its column declaration")
            fields = tuple(by_leaf[i] for i in declared)
        self._sparse_fields = fields
        if fields is None:
            row_counts = {flat[i].shape[0] for i in declared}
            if len(row_counts) > 1:
                raise ValueError(
                    f"sparse_tables leaves have mismatched row counts "
                    f"{sorted(row_counts)}: tables sharing one id set must "
                    f"share one vocabulary — declare a sparse_field_map "
                    f"on the architecture for per-table vocabularies")
        return declared

    def _allocate_hub(self, weights: List[np.ndarray],
                      plan) -> Any:
        """One hub (num_shards=1) or the sharded facade — each shard built
        by the subclass's algorithm-specific allocator over its slice."""
        if plan is None:
            return self.allocate_parameter_server(weights)
        return ShardedParameterServer(
            weights, plan,
            lambda w, sid: self.allocate_parameter_server(w, shard_id=sid))

    # -- the algorithm's window-boundary math, ON DEVICE -----------------------
    # Both hooks take parameter PYTREES already resident on the worker's
    # device and trace into the jitted window program (_make_window_fn), so
    # the exchange arithmetic runs at device speed and the full model never
    # round-trips through host numpy (the commit PAYLOAD still crosses to
    # the host — that is the PS wire protocol's own traffic, not overhead).

    def device_window_start(self, pulled: Any, local: Any) -> Any:
        """What the worker trains from at window start: default = the fresh
        center (DOWNPOUR-family).  Elastic variants keep their local."""
        return pulled

    def device_commit(self, pulled: Any, local_after: Any) -> Tuple[Any, Any]:
        """Window-boundary exchange: given the center pulled at window start
        and the post-window local params (pytrees on device), return
        ``(commit_payload, params_to_continue_from)`` per the algorithm."""
        raise NotImplementedError  # pragma: no cover - interface

    # -- checkpointing ---------------------------------------------------------
    # Async runs have no synchronized epoch boundary, so the checkpoint
    # story is CENTER SNAPSHOTS: a daemon thread periodically saves the
    # hub's current center (every ``checkpoint_interval`` seconds, plus
    # once at finish), and a fresh run restores the latest center as its
    # starting weights.  Preemption loses at most one interval of commits;
    # elastic locals restart from the center (their divergence is
    # exploration state, not progress).  This was round-1 verdict weak #7
    # ("the genuinely asynchronous mode has no preemption story").

    def _maybe_restore(self, checkpointer) -> bool:
        """Load the latest center snapshot into ``self.model``; True if one
        existed."""
        step = checkpointer.latest_step()
        if step is None:
            return False
        restored = checkpointer.restore({"params": self.model.params}, step=step)
        self.model = Model(spec=self.model.spec,
                           params=jax.tree.map(jnp.asarray, restored["params"]))
        return True

    def _snapshot_loop(self, checkpointer, stop: threading.Event, get_center,
                       treedef, next_step: List[int], lock: threading.Lock) -> None:
        import warnings

        while not stop.wait(self.checkpoint_interval):
            try:
                self._snapshot(checkpointer, get_center, treedef, next_step, lock)
            except Exception as e:
                # a transient failure (hub mid-restart, disk hiccup) must
                # not silently kill the snapshot thread for the rest of
                # the run — skip this interval and try again
                warnings.warn(f"center snapshot failed (will retry): "
                              f"{type(e).__name__}: {e}")

    def _snapshot(self, checkpointer, get_center, treedef, next_step: List[int],
                  lock: threading.Lock) -> None:
        # the lock serializes the periodic loop against the final snapshot
        # (a slow save outliving the join timeout must not race the same
        # step number — Checkpointer.save rmtree's in-progress tmp dirs)
        with lock:
            weights = get_center()
            params = jax.tree.unflatten(treedef, [np.asarray(w) for w in weights])
            checkpointer.save(next_step[0], {"params": params},
                              metadata={"kind": "async-center-snapshot"})
            next_step[0] += 1

    # -- training --------------------------------------------------------------
    def train(self, dataset: Dataset, shuffle: bool = True, checkpointer=None,
              validation_data: Optional[Dataset] = None) -> Model:
        self.model.spec.reject_rng_spec(type(self).__name__ + ".train")
        self.model.spec.reject_step_hook(type(self).__name__ + ".train")
        if validation_data is not None:
            raise ValueError(
                "per-epoch validation is not supported for async trainers "
                "(workers race the hub; there is no synchronized epoch "
                "boundary to score) — evaluate the returned model, or use "
                "the sync trainer family")
        if checkpointer is not None and self.ps_address is None:
            # restore only when WE own the hub: in worker-only mode the
            # external hub's center wins (workers pull it immediately), so
            # restoring into self.model would be silently discarded —
            # multi-host resume = restart distkeras-ps from the snapshot
            # (its --save-final / the checkpointer's saved model)
            self._maybe_restore(checkpointer)
        self.record_training_start()
        flat0, treedef = flatten_weights(self.model.params)
        bad = {str(np.asarray(w).dtype) for w in flat0} - {"float32"}
        if bad:
            # the PS hubs (Python and C++) hold the center as flat float32;
            # silently retyping bf16/f64 params through pull/commit was
            # round-1 verdict weak #6 — refuse instead
            raise TypeError(
                f"async trainers require float32 parameters (PS center is "
                f"float32); found dtypes {sorted(bad)} — cast the model's "
                f"params or use the mesh trainers in distkeras_tpu.trainers")
        flat_f32 = [w.astype(np.float32) for w in flat0]
        # row-sparse tables (ISSUE 9), resolved against THIS model's leaves
        sparse_idx = self._resolve_sparse_tables(flat_f32)
        if sparse_idx and self.transport == "inproc" and self.num_shards > 1:
            raise ValueError(
                "sparse_tables with transport='inproc' requires "
                "num_shards=1 (the sharded facade has no sparse direct "
                "pair; inproc moves no wire bytes to save anyway) — use "
                "the socket transport for sharded sparse runs")
        self._sparse_idx = sparse_idx
        # leaf->shard assignment (deterministic in the model's leaf
        # layout): both ends of a sharded deployment derive the same plan,
        # so worker-only mode agrees with standalone --shard-index hubs
        plan = (shard_plan(flat_f32, self.num_shards,
                           sparse_leaves=sparse_idx)
                if self.num_shards > 1 else None)
        self._shard_plan = plan
        # per-hub sparse positions (None when sparse is off, so no hub
        # ctor ever sees an unexpected kwarg)
        if sparse_idx:
            self._hub_sparse = ({sid: plan.local_sparse(sid)
                                 for sid in range(plan.num_shards)}
                                if plan is not None else {None: sparse_idx})
        else:
            self._hub_sparse = None
        if self.ps_address is not None:
            ps = None
            addresses = list(self._ps_addresses)
        else:
            if self.health_interval_s is not None or self.adaptive:
                # we own the hub, so the process-default collector/monitor
                # serve THIS run: drop the previous run's series and frozen
                # throughput baseline, or run 2's ramp-up reads as a
                # regression against run 1's steady state (remote hubs are
                # long-lived and multi-job; only the owner resets).  An
                # adaptive hub subscribes to this monitor at start(), so
                # the reset must come first
                from distkeras_tpu.observability import health as _health
                _health.reset_default()
            ps = self._allocate_hub(flat_f32, plan)
            ps.start()
            if self.replica_of is not None:
                # the trainer's hub is a STANDBY taking over a primary's
                # job: the workers below must not race the asynchronous
                # full sync — their first commit would promote the hub
                # over its fresh init weights and silently discard the
                # primary's state.  Block until the sync landed, and fail
                # LOUDLY if it never does (an unreachable primary must not
                # silently degrade into training from seed)
                if not ps.wait_synced(timeout=self.replica_sync_timeout):
                    ps.stop()
                    self._cleanup_shm_dir()
                    raise RuntimeError(
                        f"replica_of={self.replica_of}: no full sync "
                        f"arrived from the primary within "
                        f"{self.replica_sync_timeout}s "
                        f"(replica_sync_timeout) — it is unreachable or "
                        f"not a Python hub.  Refusing to train from fresh "
                        f"weights; drop replica_of to do that deliberately")
                # this trainer IS the deliberate takeover: promote
                # explicitly (fence at the sync clock, feed severed)
                # before any worker runs — the commit-time promotion
                # trigger is for unplanned failovers and refuses commits
                # while the primary's feed is still live
                ps.promote(reason="trainer replica_of takeover (synced)")
            addresses = [("127.0.0.1", p)
                         for p in (ps.ports if plan is not None else [ps.port])]
        self.parameter_server = ps

        def control_client(**kw):
            """A fresh blocking client for control-plane reads (center
            snapshots, the worker-only final pull): striped when sharded,
            the plain PSClient otherwise.  Carries the run's failover list
            so a control read mid-failover lands on the standby too."""
            if plan is not None:
                return ShardedPSClient(addresses, flat0, plan,
                                       failover=self._ps_failover, **kw)
            return PSClient(addresses[0][0], addresses[0][1],
                            templates=flat0,
                            failover=(self._ps_failover[0]
                                      if self._ps_failover else ()), **kw)
        # distributed tracing: one job id for every worker this run spawns
        # (explicit trace_context joins multi-host workers under one job).
        # Resolved once here so a restarted worker keeps the job identity.
        # The process clock-sync estimate resets per run: an offset
        # measured against a PREVIOUS run's hub must not outlive it.
        # Adaptive runs create contexts even with telemetry off: the
        # hub's per-worker staleness series (what the rate controller
        # scales from) are keyed by the announced worker identity
        trace_job = ((self.trace_context or dtrace.new_job_id())
                     if obs.enabled() or self.adaptive else None)
        if trace_job is not None:
            dtrace.reset_clock_sync()
            if os.environ.get("DKT_TRACE_DIR"):
                # this run flushes its ring at the end under THIS job id:
                # spans surviving from a previous train() in the same
                # process must not be re-flushed (and double-counted by
                # merge_traces/fleet_report) under the new job
                obs.TRACER.clear()

        # note: chunk_windows is moot here — the async worker loop already
        # feeds one window per device transfer (stacked_epoch slices are
        # zero-copy views), so feeding is O(window) by construction
        if self._window_fn is None:
            self._window_fn = _make_window_fn(self, self.model.spec.apply_fn(),
                                              self.loss, self.optimizer)
        window_fn = self._window_fn
        devices = jax.devices()
        histories: List[List[float]] = [[] for _ in range(self.num_workers)]
        errors: List[BaseException] = []

        def unflatten(flat: Sequence[np.ndarray]):
            return jax.tree.unflatten(treedef, list(flat))

        # telemetry (near-zero when disabled): window wall vs DEVICE time
        # histograms (the benchmark's async_exchange_share reads both)
        m_wall = obs.histogram("async_window_wall_seconds")
        m_dev = obs.histogram("async_window_device_seconds")
        m_windows = obs.counter("async_windows_total")

        restart_counts = [0] * self.num_workers

        # self-scaling fleet (ISSUE 19): per-run control state shared by
        # the worker threads and the controller callbacks.  fleet_lock
        # exists even with autoscale off — the dynamic join below reads
        # `threads` under it either way
        self.worker_preemptions = []
        fleet_lock = threading.Lock()
        drain_requests: set = set()   # worker idxs asked to retire
        drained: set = set()          # worker idxs that drained clean
        exited_workers: set = set()   # idxs whose threads returned (respawn pool)
        controller = None
        if self.autoscale:
            from distkeras_tpu.observability import health as _health
            from distkeras_tpu.runtime.fleet_controller import FleetController

            def _spawn_replacement(_worker) -> None:
                # replacement capacity re-enters through an EXITED worker
                # slot (its dataset shard is otherwise orphaned); with
                # the whole fleet live there is nothing to replace, so
                # the decision stays advisory
                with fleet_lock:
                    if not exited_workers:
                        return
                    ridx = exited_workers.pop()
                t = threading.Thread(target=run_worker, args=(ridx,))
                with fleet_lock:
                    threads.append(t)
                t.start()

            def _request_drain(worker: str) -> None:
                try:
                    widx = int(worker)
                except (TypeError, ValueError):
                    return
                with fleet_lock:
                    drain_requests.add(widx)

            controller = FleetController(_health.monitor(),
                                         spawn_fn=_spawn_replacement,
                                         retire_fn=_request_drain,
                                         min_fleet=max(
                                             1, self.num_workers // 2))
        self.fleet_controller = controller

        def worker_once(idx: int, start_epoch: int, progress: List[int],
                        losses: List[Any]) -> None:
            """One attempt at a worker's epoch loop, starting at
            ``start_epoch``.  ``progress[0]`` tracks the epoch currently
            being trained so the supervisor can resume a restarted worker
            there (windows already committed within the interrupted epoch
            replay — async SGD tolerates re-applied windows far better
            than skipped data); ``progress[1]`` records ``len(losses)``
            at that epoch's start so the supervisor can drop the aborted
            attempt's partial-epoch losses before the replay re-records
            them (history must not double-count replayed windows)."""
            device = devices[idx % len(devices)]
            # per-worker trace context: announced over the PS wire (socket)
            # or read thread-locally by the hub's direct path (inproc), so
            # hub-side spans are attributable to THIS worker.  A restarted
            # worker gets a fresh span_id under the same job/worker ids
            ctx = None
            if trace_job is not None:
                ctx = dtrace.TraceContext(job_id=trace_job, worker_id=idx,
                                          span_id=dtrace.new_span_id())
                dtrace.activate(ctx)
            if self.transport == "inproc":
                client = InprocPSClient(ps, templates=flat0,
                                        compress=self.compress_commits,
                                        trace_context=ctx,
                                        sparse_leaves=sparse_idx,
                                        sparse_cache_rows=self.sparse_cache_rows)
            elif plan is not None:
                # striped worker: one pipelined connection per shard,
                # pulls/commits fan out and land per shard (the same
                # zero-copy machinery per connection)
                client = ShardedPSClient(addresses, flat0, plan,
                                         compress=self.compress_commits,
                                         max_inflight=self.max_inflight_commits,
                                         max_reconnects=self.max_reconnects,
                                         reconnect_backoff=self.reconnect_backoff,
                                         heartbeat_interval=self.heartbeat_interval,
                                         trace_context=ctx,
                                         failover=self._ps_failover,
                                         sparse_leaves=sparse_idx,
                                         adaptive=self.adaptive,
                                         shm=self.transport == "shm")
            else:
                client = PSClient(addresses[0][0], addresses[0][1],
                                  templates=flat0,
                                  compress=self.compress_commits,
                                  max_inflight=self.max_inflight_commits,
                                  max_reconnects=self.max_reconnects,
                                  reconnect_backoff=self.reconnect_backoff,
                                  heartbeat_interval=self.heartbeat_interval,
                                  trace_context=ctx,
                                  failover=(self._ps_failover[0]
                                            if self._ps_failover else ()),
                                  sparse_leaves=sparse_idx,
                                  adaptive=self.adaptive,
                                  sparse_cache_rows=self.sparse_cache_rows,
                                  shm=self.transport == "shm")
            pipeline = self.pipeline
            # row-sparse exchange (ISSUE 9): each window's pull/commit
            # carries the sorted-unique row ids its batches touch.
            # Architectures with a sparse_field_map (ISSUE 15) get an
            # INDEPENDENT id set per table from that table's own feature
            # columns; the rest keep the shared-vocabulary contract (one
            # id set for every table).  Fully inert when no sparse tables
            # are configured
            sparse_on = bool(sparse_idx)
            sparse_fields = getattr(self, "_sparse_fields", None)
            cache_on = sparse_on and self.sparse_cache_rows is not None
            # a dense float32 commit leaves the device leaf by leaf: its
            # copy-out is issued at dispatch and the client sends each leaf
            # as it lands.  Row-sparse and int8 commits are gathered or
            # quantised as whole arrays, so they are fetched whole
            commit_streams = not sparse_on and self.compress_commits is None

            def rows_of(window_x) -> List[np.ndarray]:
                x = np.asarray(window_x)
                if sparse_fields is None:
                    ids = np.unique(x.ravel().astype(np.int64))
                    return [ids] * len(sparse_idx)
                flat_x = x.reshape(-1, x.shape[-1])
                return [np.unique(flat_x[:, list(cols)].ravel()
                                  .astype(np.int64))
                        for cols in sparse_fields]
            # live health plane (ISSUE 8): periodic compact reports to the
            # hub's collector.  Wholly inert when off (health_interval is
            # None -> zero extra calls on the window path)
            health_interval = self.health_interval_s
            h_next = time.monotonic() + (health_interval or 0.0)
            h_seq = 0          # per-worker report sequence number
            h_windows = 0      # cumulative windows this worker ran
            h_wall_ms = 0.0    # window wall accumulated since last report
            h_wall_n = 0
            h_rows = 0         # cumulative sparse rows this worker committed

            def send_health() -> None:
                nonlocal h_seq, h_wall_ms, h_wall_n
                metrics = {
                    # *_total = cumulative (the collector's rate()
                    # convention); window_wall_ms = point sample (the
                    # mean since the last report)
                    "windows_total": float(h_windows),
                    "window_wall_ms": (h_wall_ms / h_wall_n
                                       if h_wall_n else None),
                    "reconnects_total": float(client.reconnects_used),
                    "failovers_total": float(client.failovers_used),
                }
                if sparse_on:
                    # the health plane sees sparse traffic too: committed
                    # rows as a cumulative series (rate = rows/s in
                    # distkeras-top and the live fleet_report)
                    metrics["sparse_rows_total"] = float(h_rows)
                if cache_on:
                    # hot-tier cache standing (ISSUE 15): cumulative hit/
                    # miss series — the HIT% column in distkeras-top and
                    # fleet_report["sparse"]["hot_tier"]
                    metrics["sparse_cache_hits_total"] = float(
                        client.sparse_cache_hits)
                    metrics["sparse_cache_misses_total"] = float(
                        client.sparse_cache_misses)
                client.report_health({
                    "job": trace_job or "local", "worker": idx,
                    "seq": h_seq, "t_wall": time.time(),
                    # which transport this worker's frames actually move
                    # over ("shm" only after a successful attach — a
                    # declined attach honestly reports "tcp"); the TRANS
                    # column in distkeras-top and fleet_report's
                    # transport block read this
                    "transport": getattr(client, "transport", None),
                    "metrics": metrics})
                h_seq += 1
                h_wall_ms, h_wall_n = 0.0, 0
            try:
                shard = dataset.shard(self.num_workers, idx)
                # worker state lives on the device for the whole run;
                # each window touches the host only for the PS wire
                # exchange (pull in, commit out) and the feed slices.
                # np.array: the socket client's pull buffers are reused
                # by later prefetches, and params must own its storage.
                # On a restart this pull IS the recovery point: the
                # worker resumes from the hub's current center
                with obs.phase("async.seed", worker=idx):
                    seed_host = [np.array(w) for w in client.pull()]
                    params = jax.device_put(unflatten(seed_host), device)
                # hot-tier mode (ISSUE 15): one full-shape DEVICE-resident
                # mirror per sparse table, seeded from the initial full
                # pull and scatter-refreshed each window with the [k, dim]
                # row block the bounded client cache hands back — the
                # full-shape host copy the PR-9 path re-uploaded per
                # window no longer exists, and per-window H2D for the
                # table drops to the touched rows
                sset = frozenset(sparse_idx)
                mirror = ({i: jax.device_put(seed_host[i], device)
                           for i in sparse_idx} if cache_on else None)
                # the seed's host copy must NOT outlive the transfer: a
                # named local would pin one full-size host array per
                # sparse table for the whole run — the exact footprint
                # sparse_cache_rows exists to eliminate
                del seed_host
                row_caps: Optional[List[int]] = None
                opt_state = jax.device_put(self.optimizer.init(params), device)
                # one pull rides ahead of the window being computed (set
                # when the previous window prefetched this window's pull)
                pull_pending = False
                for epoch in range(start_epoch, self.num_epoch):
                    progress[0] = epoch
                    progress[1] = len(losses)
                    ds = shard.shuffle(seed=self.seed + 1000 * idx + epoch) if shuffle else shard
                    stacked = ds.stacked_epoch(self.batch_size,
                                               [self.features_col, self.label_col],
                                               window=self.communication_window)
                    xs, ys = stacked[self.features_col], stacked[self.label_col]
                    n_windows = xs.shape[0]
                    if cache_on and row_caps is None:
                        # fixed scatter capacity per table: distinct ids
                        # per window are bounded by rows-per-window x the
                        # table's column count, so padding to this bound
                        # keeps the device scatter ONE compiled shape
                        per_window = int(xs.shape[1])
                        ncols = ([len(c) for c in sparse_fields]
                                 if sparse_fields is not None
                                 else [int(xs.shape[-1])] * len(sparse_idx))
                        row_caps = [min(int(flat0[i].shape[0]),
                                        per_window * nc)
                                    for i, nc in zip(sparse_idx, ncols)]
                    # rows the pending prefetched pull was issued with
                    # (sparse only): the commit for window w must carry
                    # the SAME id set its pull asked for
                    next_rows: Optional[List[np.ndarray]] = None
                    # the plain slice walk, telemetry on or off: the
                    # transfer of a window's rows is fused with the pull's
                    # below (one batched H2D per window)
                    for w in range(n_windows):
                        wx_h, wy_h = xs[w], ys[w]
                        if controller is not None:
                            with fleet_lock:
                                wants_drain = idx in drain_requests
                            if wants_drain:
                                # retire lands at a window BOUNDARY: the
                                # previous window's commit is already on
                                # the wire, no new work starts
                                raise _DrainRequested(idx, w)
                        if self.fault_hook is not None:
                            self.fault_hook(idx, w)
                        telemetry = obs.enabled()
                        t_wall = (time.perf_counter()
                                  if telemetry or health_interval is not None
                                  else 0.0)
                        rows_w: Optional[List[np.ndarray]] = None
                        if sparse_on:
                            rows_w = (next_rows if next_rows is not None
                                      else rows_of(xs[w]))
                            next_rows = None
                        # the window's leaf phases (obs.phase) follow one
                        # another on this thread and cover the window but
                        # for bookkeeping; they inherit worker/epoch/window
                        with obs.span("async.window", worker=idx,
                                      epoch=epoch, window=w):
                            with obs.phase("async.pull_wait"):
                                if not pull_pending:
                                    if sparse_on:
                                        client.pull_nowait(sparse_rows=rows_w)
                                    else:
                                        client.pull_nowait()
                                pulled_host = client.wait_weights()
                            pull_pending = False
                            # ONE batched H2D per window (center + feed
                            # slices): every transfer call costs a host
                            # dispatch, so they are fused
                            if cache_on:
                                # sparse slots of pulled_host are [k, dim]
                                # row blocks aligned with rows_w; pad each
                                # to its fixed capacity (repeating the
                                # last row — duplicate scatter indices
                                # carry identical values) and refresh the
                                # device mirrors, then assemble the full-
                                # order pulled tree from mirrors + dense
                                pads: List[Any] = []
                                for si, i in enumerate(sparse_idx):
                                    ids = rows_w[si]
                                    k = int(ids.size)
                                    if k == 0:
                                        pads.append(None)
                                        continue
                                    block = np.asarray(pulled_host[i],
                                                       np.float32)
                                    cap = row_caps[si]
                                    if k < cap:
                                        pid = np.empty(cap, np.int64)
                                        pid[:k] = ids
                                        pid[k:] = ids[k - 1]
                                        pblk = np.empty(
                                            (cap, block.shape[1]),
                                            np.float32)
                                        pblk[:k] = block
                                        pblk[k:] = block[k - 1]
                                    else:
                                        pid, pblk = ids, block
                                    pads.append((pid, pblk))
                                dense_host = [pulled_host[j]
                                              for j in range(len(pulled_host))
                                              if j not in sset]
                                with obs.phase("async.h2d"):
                                    dense_dev, pad_dev, wx, wy = \
                                        jax.device_put(
                                            (dense_host, pads, wx_h, wy_h),
                                            device)
                                flat_dev: List[Any] = []
                                di = si = 0
                                for j in range(len(pulled_host)):
                                    if j in sset:
                                        pd = pad_dev[si]
                                        if pd is not None:
                                            mirror[j] = mirror[j].at[
                                                pd[0]].set(pd[1])
                                        flat_dev.append(mirror[j])
                                        si += 1
                                    else:
                                        flat_dev.append(dense_dev[di])
                                        di += 1
                                pulled = unflatten(flat_dev)
                            else:
                                # host time in the call: the transfer is
                                # only issued here, its tail is waited for
                                # under async.device_wait
                                with obs.phase("async.h2d"):
                                    pulled, wx, wy = jax.device_put(
                                        (unflatten(pulled_host), wx_h, wy_h),
                                        device)
                            t_dev = time.perf_counter() if telemetry else 0.0
                            # host dispatch: the window program's, and
                            # the next window's pull request
                            with obs.phase("async.dispatch"):
                                params, opt_state, commit, mloss = window_fn(
                                    params, opt_state, pulled, wx, wy)
                                if commit_streams:
                                    # the runtime queues each copy behind
                                    # the program: D2H starts the moment it
                                    # ends, whatever this thread is doing
                                    for leaf in jax.tree.leaves(commit):
                                        leaf.copy_to_host_async()
                                # prefetch the NEXT window's pull while this
                                # window's program runs: the request leaves
                                # now (jax dispatch is async) and the
                                # weights stream into the other landing
                                # buffer under the compute (async.pull_land
                                # below) — the center it snapshots predates
                                # this window's commit (self-staleness 1;
                                # ARCHITECTURE.md)
                                last_window = (w == n_windows - 1
                                               and epoch == self.num_epoch - 1)
                                if pipeline and not last_window:
                                    if sparse_on:
                                        # sparse prefetch needs the NEXT
                                        # window's ids, so it stops at the
                                        # epoch tail (the next epoch's
                                        # reshuffled slices don't exist
                                        # yet); window 0 then issues its
                                        # own pull — one pipeline bubble
                                        # per epoch
                                        if w + 1 < n_windows:
                                            next_rows = rows_of(xs[w + 1])
                                            client.pull_nowait(
                                                sparse_rows=next_rows)
                                            pull_pending = True
                                    else:
                                        client.pull_nowait()
                                        pull_pending = True
                                # the previous commit's leaves, and with
                                # them a commit's worth of host copies, go
                                # here, beside the program: released where
                                # the next commit is handed over, freeing
                                # them is tens of ms on the chain
                                payload = None
                            # this thread has nothing to do until the
                            # program is done, and the hub's send of the
                            # prefetched reply moves only while this end
                            # reads: receive it now, beside the compute,
                            # not between the commit's copy-out and send
                            if pull_pending:
                                with obs.phase("async.pull_land"):
                                    client.land_weights()
                            if telemetry:
                                # the one statement only telemetry runs:
                                # it splits the wait for the window program
                                # (async_window_device_seconds, which the
                                # benchmark's async_exchange_share reads)
                                # from the commit's copy-out.  It moves no
                                # time: the commit's first leaf serialises
                                # on the program anyway
                                with obs.phase("async.device_wait"):
                                    jax.block_until_ready(mloss)
                                m_dev.observe(time.perf_counter() - t_dev)
                            # leaf order is the same tree.flatten order
                            # as the templates.  A streamed commit goes to
                            # the client as device leaves (their copies are
                            # on the way; the client's send waits for each
                            # where its bytes are due), so what is left of
                            # the phase is the hand-over; the others take
                            # one batched D2H here
                            with obs.phase("async.commit_d2h"):
                                if commit_streams:
                                    payload = jax.tree.leaves(commit)
                                else:
                                    payload = jax.tree.leaves(
                                        jax.device_get(commit))
                            # fire-and-forget when pipelined: the ack
                            # coalesces into the next window's weights
                            # receive; else it is waited for here
                            if sparse_on:
                                client.commit_nowait(payload,
                                                     sparse_rows=rows_w)
                            else:
                                client.commit_nowait(payload)
                            if not pipeline:
                                with obs.phase("async.drain"):
                                    client.drain()
                        if sparse_on:
                            h_rows += int(sum(ids.size for ids in rows_w))
                        if telemetry:
                            m_wall.observe(time.perf_counter() - t_wall)
                            m_windows.inc()
                        if health_interval is not None:
                            h_windows += 1
                            h_wall_ms += (time.perf_counter() - t_wall) * 1e3
                            h_wall_n += 1
                            if time.monotonic() >= h_next:
                                send_health()
                                h_next = time.monotonic() + health_interval
                        # loss stays a device scalar until the run ends:
                        # float() here would add one more blocking round
                        # trip per window
                        losses.append(mloss)
                if health_interval is not None:
                    # final report: a run (or epoch tail) shorter than the
                    # interval still lands at least one report per worker
                    send_health()
                # trailing acks (and nothing else: the last window never
                # prefetches) — commits must be APPLIED before the run's
                # final center read, not just queued on the wire
                with obs.phase("async.drain", worker=idx):
                    client.drain()
            except (WorkerPreempted, _DrainRequested) as stop_ev:
                # graceful drain (ISSUE 19): finish the in-flight
                # exchange — pipelined commit acks plus the unused
                # prefetched pull — then flush the int8 residual so
                # error feedback is not lost with the worker, and leave
                # through the normal BYE in the finally below.  The hub
                # sees a voluntary departure (elastic denominators
                # shrink through member_leave), never a torn stream, and
                # every acked commit is already in the center: zero
                # acked-commit loss by construction
                clean = True
                outstanding = 0
                try:
                    client.drain()
                    if self.compress_commits == "int8" and not sparse_on:
                        # the residual chain advances at quantization
                        # time, so one zero-delta commit carries exactly
                        # the accumulated residual
                        client.commit([np.zeros_like(t) for t in flat0])
                except Exception:
                    clean = False
                    pend = getattr(client, "_pending", None)
                    outstanding = len(pend) if pend is not None else -1
                if isinstance(stop_ev, WorkerPreempted):
                    with fleet_lock:
                        self.worker_preemptions.append({
                            "worker": idx, "window": stop_ev.window,
                            "deadline_s": stop_ev.deadline_s,
                            "drained_clean": clean,
                            "outstanding_after_drain": outstanding})
                    if obs.enabled():
                        obs.counter("worker.preemptions").inc()
                    if controller is not None:
                        controller.notify_drained(idx, clean=clean)
                    raise  # the supervisor respawns, budget-neutral
                # controller-requested retire: record, then exit as a
                # finished worker — the supervisor must not restart it
                with fleet_lock:
                    drain_requests.discard(idx)
                    drained.add(idx)
                if controller is not None:
                    controller.notify_drained(idx, clean=clean)
                return
            finally:
                client.close()
        def run_worker(idx: int) -> None:
            losses: List[Any] = []
            if controller is not None:
                controller.notify_worker_started(idx)
            progress = [0, 0]  # [resume epoch, losses length at its start]
            try:
                while True:
                    try:
                        worker_once(idx, progress[0], progress, losses)
                        return
                    except BaseException as e:
                        if (isinstance(e, WorkerPreempted)
                                and controller is not None
                                and controller.notify_preempted(
                                    idx, deadline_s=e.deadline_s)):
                            # planned capacity loss, already drained
                            # clean: the authorized respawn re-enters at
                            # the interrupted epoch WITHOUT burning a
                            # restart-budget slot (a preemption is not a
                            # crash), re-pulling the hub's CURRENT center
                            # like any restart
                            del losses[progress[1]:]
                            continue
                        # supervision: "restart" re-runs the worker from the
                        # hub's CURRENT center (its committed progress
                        # survives there), bounded by max_worker_restarts
                        # and resuming at the epoch it died in; any other
                        # policy records the error for the run-level
                        # raise/continue handling below
                        if (self.on_worker_failure != "restart"
                                or restart_counts[idx] >= self.max_worker_restarts):
                            errors.append(e)
                            return
                        restart_counts[idx] += 1
                        # the replay re-records the aborted epoch's
                        # windows: drop its partial losses so history
                        # counts each trained window once
                        del losses[progress[1]:]
                        # surface the swallowed cause: an operator must be
                        # able to tell two transient faults from the same
                        # deterministic bug recurring every attempt
                        import warnings

                        warnings.warn(
                            f"worker {idx} restarting "
                            f"({restart_counts[idx]}/{self.max_worker_restarts}) "
                            f"after {type(e).__name__}: {e}")
                        if obs.enabled():
                            obs.counter("worker.restarts").inc()
            finally:
                if controller is not None:
                    controller.notify_worker_exited(idx)
                    with fleet_lock:
                        # retired workers stay out of the respawn pool —
                        # re-admitting the drifting worker the controller
                        # just drained would undo the retire
                        if idx not in drained:
                            exited_workers.add(idx)
                # flush even on a mid-run crash: windows whose commits
                # already reached the center must stay in history / the
                # samples metric (the 'continue' failure policy counts on
                # this, and the old per-window float() accounting had it)
                try:
                    histories[idx].extend(float(x) for x in jax.device_get(losses))
                except Exception:
                    # a dead device can fail the final fetch; the run's
                    # primary error is already in `errors`
                    pass

        snap_stop = snap_thread = None
        if checkpointer is not None:
            def get_center():
                if ps is not None:
                    return ps.get_weights()
                with control_client() as c:
                    return c.pull()

            next_step = [(checkpointer.latest_step() or 0) + 1]
            snap_stop = threading.Event()
            snap_lock = threading.Lock()
            snap_thread = threading.Thread(
                target=self._snapshot_loop,
                args=(checkpointer, snap_stop, get_center, treedef, next_step, snap_lock),
                daemon=True)
            snap_thread.start()

        threads = [threading.Thread(target=run_worker, args=(i,)) for i in range(self.num_workers)]
        with self._profile_ctx():
            for t in threads:
                t.start()
            # spawned replacements append to `threads` mid-join (fleet
            # controller): keep joining until a pass finds no new threads
            joined = 0
            while True:
                with fleet_lock:
                    batch = threads[joined:]
                if not batch:
                    break
                for t in batch:
                    t.join()
                joined += len(batch)
        if snap_stop is not None:
            snap_stop.set()
            snap_thread.join(timeout=10)
            # final center snapshot while the hub is still up; best-effort —
            # a dead hub here must not mask the workers' root-cause errors
            # (checked right below), and with 'continue' the run's result
            # still stands even if this last save fails
            try:
                self._snapshot(checkpointer, get_center, treedef, next_step, snap_lock)
            except Exception as snap_err:
                if not errors and self.on_worker_failure == "raise":
                    raise
                errors.append(snap_err)  # recorded in worker_errors below
        if controller is not None:
            controller.stop()
        if ps is not None:
            ps.stop()
        self._cleanup_shm_dir()
        self.worker_restarts = sum(restart_counts)
        self.worker_errors = list(errors)
        if errors and self.on_worker_failure == "raise":
            # surface the workers' root cause before touching the hub again
            # (it may be gone, and that must not mask the real failure)
            raise errors[0]
        if ps is None:
            # worker-only mode: the external hub outlives us; read the center
            # (with the run's reconnect budget — a hub restart racing the
            # end of the run must not lose an otherwise-complete result)
            with control_client(
                    max_reconnects=self.max_reconnects,
                    reconnect_backoff=self.reconnect_backoff) as final_client:
                final = final_client.pull()
        else:
            final = ps.get_weights()
        # interleave per-worker histories into one trace (order is arbitrary
        # under real asynchrony; per-worker order is preserved)
        for h in histories:
            self._record_window_losses(h)
        total_windows = sum(len(h) for h in histories)
        self._record_epoch_metrics(
            epoch=self.num_epoch - 1,
            samples=total_windows * self.communication_window * self.batch_size,
            seconds=self.get_training_time(),
            chips=min(self.num_workers, len(devices)))
        # fleet-wide merge hook: when DKT_TRACE_DIR is set (and telemetry
        # on), flush this process's span ring — in worker-only mode every
        # worker host writes its own file with its PS-round-trip clock
        # offset, and merge_traces(dir) aligns them all on the hub timeline
        trace_dir = os.environ.get("DKT_TRACE_DIR")
        if trace_dir and obs.enabled():
            try:
                dtrace.flush_process_trace(
                    trace_dir, job_id=trace_job,
                    role="trainer" if ps is not None else "worker")
            except OSError as e:
                import warnings

                warnings.warn(f"trace flush to {trace_dir} failed: {e}")
        self.model = Model(spec=self.model.spec,
                           params=jax.tree.unflatten(treedef, [jnp.asarray(w) for w in final]))
        self.record_training_end()
        return self.model

class AsyncDOWNPOUR(AsyncDistributedTrainer):
    """DOWNPOUR with real asynchrony (reference §2.5): train from the fresh
    center, commit the raw accumulated delta."""

    def allocate_parameter_server(self, weights, shard_id=None):
        if self.native_ps:
            from distkeras_tpu.runtime.native import MODE_DELTA, NativeParameterServer

            return NativeParameterServer(weights, mode=MODE_DELTA,
                                         **self._hub_kwargs(shard_id))
        return DeltaParameterServer(weights, **self._hub_kwargs(shard_id))

    def device_commit(self, pulled, local_after):
        delta = jax.tree.map(lambda l, p: l - p, local_after, pulled)
        return delta, local_after


class AsyncADAG(AsyncDOWNPOUR):
    """ADAG (reference §2.6): DOWNPOUR-style worker, PS normalizes each
    delta by num_workers."""

    def allocate_parameter_server(self, weights, shard_id=None):
        if self.native_ps:
            from distkeras_tpu.runtime.native import MODE_ADAG, NativeParameterServer

            return NativeParameterServer(weights, mode=MODE_ADAG,
                                         num_workers=self.num_workers,
                                         elastic=self.elastic,
                                         **self._hub_kwargs(shard_id))
        return ADAGParameterServer(weights, num_workers=self.num_workers,
                                   elastic=self.elastic,
                                   **self._hub_kwargs(shard_id))


class AsyncDynSGD(AsyncDOWNPOUR):
    """DynSGD (reference §2.7): DOWNPOUR-style worker, PS scales each delta
    by 1/(staleness+1) from its commit clock."""

    def allocate_parameter_server(self, weights, shard_id=None):
        if self.native_ps:
            from distkeras_tpu.runtime.native import MODE_DYNSGD, NativeParameterServer

            return NativeParameterServer(weights, mode=MODE_DYNSGD,
                                         **self._hub_kwargs(shard_id))
        return DynSGDParameterServer(weights, **self._hub_kwargs(shard_id))


class AsyncAEASGD(AsyncDistributedTrainer):
    """AEASGD (reference §2.8, §3.5): locals stay divergent; each window
    commits the elastic difference ``alpha * (local - center)`` and subtracts
    it locally."""

    def __init__(self, model, rho: float = 5.0, communication_window: int = 32, **kwargs):
        super().__init__(model, communication_window=communication_window, **kwargs)
        if callable(self.learning_rate):
            # same guard (and workaround guidance) as the sync AEASGD: a
            # schedule would otherwise surface as a raw float * function
            # TypeError on the next line
            raise ValueError(
                "elastic trainers need a scalar learning_rate (the elastic "
                "coupling alpha = rho * lr is a constant); to schedule the "
                "local steps, pass an optax optimizer built with the schedule "
                "as worker_optimizer and keep learning_rate scalar")
        self.rho = float(rho)
        self.alpha = self.rho * self.learning_rate

    def allocate_parameter_server(self, weights, shard_id=None):
        if self.native_ps:
            from distkeras_tpu.runtime.native import MODE_DELTA, NativeParameterServer

            return NativeParameterServer(weights, mode=MODE_DELTA,
                                         **self._hub_kwargs(shard_id))
        return DeltaParameterServer(weights, **self._hub_kwargs(shard_id))

    def device_window_start(self, pulled, local):
        return local  # elastic workers keep their own trajectory

    def device_commit(self, pulled, local_after):
        ediff = jax.tree.map(lambda l, p: self.alpha * (l - p), local_after, pulled)
        return ediff, jax.tree.map(lambda l, e: l - e, local_after, ediff)


class AsyncEAMSGD(AsyncAEASGD):
    """EAMSGD (reference §2.9): AEASGD with Nesterov momentum on the local
    optimizer."""

    def __init__(self, model, rho: float = 5.0, momentum: float = 0.9, **kwargs):
        kwargs.setdefault("worker_optimizer", "nesterov")
        super().__init__(model, rho=rho, momentum=momentum, **kwargs)
