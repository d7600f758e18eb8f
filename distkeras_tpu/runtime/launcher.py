"""Multi-host launch helpers — the Spark-cluster replacement (SURVEY §2.14).

The reference scaled out by letting Spark place one worker per executor
and pointing them all at the driver's TCP parameter server.  This module
provides the two TPU-native equivalents:

1. **SPMD multi-host** (sync mesh trainers): every host runs the SAME
   program; :func:`initialize_multihost` wires the hosts into one JAX
   runtime (coordinator handshake, Gloo/ICI collectives), after which
   ``jax.devices()`` is the global device list and the existing mesh
   trainers work unchanged — collectives ride ICI within a slice and DCN
   across hosts.  The WindowEngine feeds the mesh with
   ``make_array_from_process_local_data`` (each process contributes the
   batch columns its devices own, preserving exact single-process
   replica-to-rows parity — proven by ``tests/test_multihost.py ::
   test_two_process_engine_adag_matches_single_process``).
   :func:`process_shard` gives each host its row-slice of a dataset (the
   reference's ``df.repartition(num_workers)``) for data planes that
   cannot hold the full set per host — e.g. async PS workers.

2. **PS multi-host** (async family): :func:`start_parameter_server` runs
   the hub standalone (CLI: ``distkeras-ps``) on a head node; worker hosts
   run Async* trainers with ``ps_address=(head, port)`` — one process per
   host, the reference's actual topology with sockets replacing Spark.

Both paths are exercised by ``tests/test_multihost.py`` with real separate
processes on CPU (2 processes x 2 virtual devices), the CI stand-in for
2 TPU hosts.
"""

from __future__ import annotations

import os
from typing import Any, List, Optional

import numpy as np


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         cpu_devices_per_process: Optional[int] = None) -> None:
    """Join this process into a multi-host JAX runtime.

    Thin, env-var-aware wrapper over ``jax.distributed.initialize``:
    arguments fall back to ``DKT_COORDINATOR`` / ``DKT_NUM_PROCESSES`` /
    ``DKT_PROCESS_ID``, and on real TPU pods everything may be ``None``
    (JAX auto-discovers from the TPU metadata).

    ``cpu_devices_per_process`` simulates a multi-host slice on CPU: it
    pins the CPU platform with that many virtual devices BEFORE the
    coordinator handshake (the 2-hosts-in-CI shape; cross-process
    collectives run over Gloo).  Must be called before any backend use.
    """
    import jax

    if cpu_devices_per_process is not None:
        # jax_num_cpu_devices wins over any inherited XLA_FLAGS device-count
        # (pin_cpu_devices' fallback path, made the primary here)
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(cpu_devices_per_process))

    coordinator_address = coordinator_address or os.environ.get("DKT_COORDINATOR")
    if num_processes is None and "DKT_NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["DKT_NUM_PROCESSES"])
    if process_id is None and "DKT_PROCESS_ID" in os.environ:
        process_id = int(os.environ["DKT_PROCESS_ID"])

    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    if cpu_devices_per_process is not None:
        local = len(jax.local_devices())
        if local != cpu_devices_per_process:
            raise RuntimeError(
                f"requested {cpu_devices_per_process} local CPU devices, got {local} "
                f"(a backend may have initialized before initialize_multihost)")


def process_shard(dataset: Any) -> Any:
    """This host's contiguous shard of the dataset — the multi-host data
    plane (reference: Spark repartition handing each worker one partition).
    Identity when running single-process.

    NOTE: the sync WindowEngine does NOT need pre-sharded data — it takes
    the global chunk on every host and slices each process's batch columns
    internally (exact single-process parity).  Use this for async PS
    workers or memory-bound hosts that must not load the full dataset."""
    import jax

    n, i = jax.process_count(), jax.process_index()
    return dataset if n == 1 else dataset.shard(n, i)


def start_parameter_server(model: Any, mode: str = "delta", num_workers: int = 1,
                           host: str = "0.0.0.0", port: int = 0,
                           native: bool = False,
                           elastic: bool = False,
                           idle_timeout: Optional[float] = 300.0,
                           snapshot_dir: Optional[str] = None,
                           snapshot_interval: float = 30.0,
                           restore: bool = False,
                           num_shards: int = 1,
                           shard_index: Optional[int] = None,
                           replica_of: Optional[Any] = None,
                           health_jsonl: Optional[str] = None,
                           sparse_tables: Optional[Any] = None,
                           adaptive: bool = False,
                           shm_dir: Optional[str] = None,
                           recv_batch_depth: int = 0) -> Any:
    """Start a standalone PS hub serving ``model``'s weights (head-node side
    of the async multi-host topology).  Returns the started server; read
    ``.port``, stop with ``.stop()``, final weights via ``.get_weights()``.

    ``mode``: ``delta`` (DOWNPOUR/elastic) | ``adag`` | ``dynsgd``.
    ``native=True`` uses the C++ hub (commits apply outside the GIL).

    Fault tolerance (both hubs): ``snapshot_dir`` makes the hub snapshot
    its center + commit clock every ``snapshot_interval`` seconds (atomic
    tmp+rename via the Checkpointer); ``restore=True`` resumes a restarted
    hub from the newest readable snapshot — with a clock fence that clamps
    pre-restart pull clocks — BEFORE serving, so workers reconnecting via
    backoff land on the recovered center.  ``idle_timeout`` evicts
    half-open connections; ``elastic`` (adag) normalizes commits by the
    live worker count instead of ``num_workers``.

    Sharded hub (``num_shards > 1``): the center is partitioned by the
    deterministic :func:`~distkeras_tpu.runtime.parameter_server.
    shard_plan` — the same plan the trainers derive from the same model,
    so no plan travels on the wire.  ``shard_index=i`` serves ONLY shard
    ``i``'s slice from this process (one ``distkeras-ps`` per shard, the
    scale-out topology); ``shard_index=None`` starts all shards in this
    process behind a :class:`~distkeras_tpu.runtime.parameter_server.
    ShardedParameterServer` facade (read ``.ports``).  When sharded,
    ``snapshot_dir`` gets a ``shard-NN`` subdirectory per shard; on the
    facade path the per-shard snapshots are COORDINATED — one commit
    barrier per set, restored only as a complete clock-consistent set
    (:class:`~distkeras_tpu.runtime.parameter_server.
    SnapshotSetCoordinator`) — while one-daemon-per-shard deployments
    keep independent per-shard snapshots (no cross-process barrier).

    High availability (``replica_of=(host, port)``): start this hub as a
    HOT STANDBY of the primary at that address — it serves pulls
    immediately, tracks the primary's applied commits over the
    replication feed (wire action ``R``), and promotes itself behind the
    clock fence when the primary dies.  Served by BOTH hubs (the C++
    standby runs its feed thread native-side); with
    ``num_shards > 1`` it requires ``shard_index`` (one standby daemon
    per shard primary, pointed at THAT shard's address).

    Live fleet health (ISSUE 8): a Python hub automatically folds worker
    health reports (wire action ``M``, sent by trainers with
    ``health_interval_s``) into this process's
    :mod:`~distkeras_tpu.observability.health` collector and runs the
    online detectors over them; ``health_jsonl`` additionally appends
    every :class:`HealthEvent` to that path as JSON lines (durable even
    if the process dies before anyone polls).

    Adaptive aggregation (ISSUE 10): ``adaptive=True`` makes the hub
    merge queued commits Adasum-style, scale each worker's commits by
    its live staleness standing (driven by the health plane's detector
    events), and answer adaptive clients' reconnect hellos with
    retry-after hints while a reconnect storm is live.  Served by BOTH
    hubs (the C++ hub runs the Adasum merger and backpressure natively);
    pair with trainers started with the matching ``adaptive=True``.

    Row-sparse embedding service (ISSUE 9): ``sparse_tables="auto"``
    registers the model's declared EmbeddingTable leaves
    (``sparse_param_names`` on the architecture) so workers started with
    the matching ``sparse_tables`` knob exchange only touched rows; an
    iterable names flat-leaf indices explicitly.  Both ends derive the
    same leaf set (and, sharded, the same row-range plan) from the same
    model — nothing travels on the wire.  Served by BOTH hubs.

    Zero-copy transport (ISSUE 18): ``shm_dir`` lets same-host workers
    that dialed with ``shm=True`` attach a pair of mmap-backed frame
    rings (wire action ``Z``) and move the SAME frame bytes without the
    kernel TCP stack; unset, every attach is declined and clients ride
    TCP unchanged.  ``recv_batch_depth=N`` drains up to N queued frames
    per receive-loop wakeup (recvmmsg where available).  Served by BOTH
    hubs (the C++ hub's wakeup loop already drains its buffer; the knob
    is accepted for parity).
    """
    from distkeras_tpu.runtime.parameter_server import (
        ShardedParameterServer, shard_plan)
    from distkeras_tpu.utils import flatten_weights

    flat, _ = flatten_weights(model.params)
    weights = [np.asarray(w, dtype=np.float32) for w in flat]
    num_shards = int(num_shards)
    if sparse_tables is None:
        sparse_idx: tuple = ()
    elif sparse_tables == "auto":
        from distkeras_tpu.models.base import sparse_leaf_indices

        sparse_idx = sparse_leaf_indices(model.spec, model.params)
        if not sparse_idx:
            raise ValueError(
                f"sparse_tables='auto' but architecture "
                f"{model.spec.name!r} declares no sparse embedding tables")
    else:
        sparse_idx = tuple(sorted({int(i) for i in sparse_tables}))
    if shard_index is not None and not (0 <= int(shard_index) < num_shards):
        raise ValueError(f"shard_index={shard_index} out of range for "
                         f"num_shards={num_shards}")
    if replica_of is not None:
        replica_of = (str(replica_of[0]), int(replica_of[1]))
        if num_shards > 1 and shard_index is None:
            raise ValueError("replica_of with num_shards > 1 requires "
                             "shard_index: run one standby daemon per "
                             "shard, each pointed at its own primary")

    def make_hub(hub_weights, shard_id, hub_port, own_snapshots=True,
                 hub_sparse=()):
        shard_snap = snapshot_dir if own_snapshots else None
        if shard_snap is not None and shard_id is not None:
            shard_snap = os.path.join(shard_snap, f"shard-{shard_id:02d}")
        common = dict(idle_timeout=idle_timeout, snapshot_dir=shard_snap,
                      snapshot_interval=snapshot_interval,
                      restore=restore if own_snapshots else False,
                      shard_id=shard_id, shm_dir=shm_dir,
                      recv_batch_depth=recv_batch_depth)
        if hub_sparse:
            common["sparse_leaves"] = hub_sparse
        if native:
            from distkeras_tpu.runtime.native import (
                MODE_ADAG, MODE_DELTA, MODE_DYNSGD, NativeParameterServer)

            native_mode = {"delta": MODE_DELTA, "adag": MODE_ADAG,
                           "dynsgd": MODE_DYNSGD}[mode]
            # the C++ hub binds all interfaces; host selection is
            # Python-hub only.  Sparse tables, adaptive aggregation and
            # hot-standby replication all run native-side (ISSUE 11)
            return NativeParameterServer(hub_weights, mode=native_mode,
                                         num_workers=num_workers,
                                         port=hub_port, elastic=elastic,
                                         replica_of=replica_of,
                                         adaptive=adaptive,
                                         **common)
        from distkeras_tpu.runtime.parameter_server import (
            ADAGParameterServer, DeltaParameterServer, DynSGDParameterServer)

        cls = {"delta": DeltaParameterServer, "adag": ADAGParameterServer,
               "dynsgd": DynSGDParameterServer}[mode]
        kwargs = ({"num_workers": num_workers, "elastic": elastic}
                  if mode == "adag" else {})
        return cls(hub_weights, host=host, port=hub_port,
                   replica_of=replica_of, adaptive=adaptive,
                   **kwargs, **common)

    if health_jsonl is not None:
        # arm the process monitor's durable sink BEFORE serving: the first
        # detector firing (possibly triggered by the very first worker
        # report) must already land on disk
        from distkeras_tpu.observability import health as _health

        _health.monitor().jsonl_path = str(health_jsonl)

    if num_shards == 1:
        ps = make_hub(weights, None, port, hub_sparse=sparse_idx)
    else:
        plan = shard_plan(weights, num_shards, sparse_leaves=sparse_idx)
        if shard_index is not None:
            sid = int(shard_index)
            # plan.split row-slices sparse tables; the pre-sparse
            # assignment indexing stays byte-identical when nothing is
            # sparse (split is then exactly the indexed selection)
            ps = make_hub(plan.split(weights)[sid],
                          sid, port, hub_sparse=plan.local_sparse(sid))
        else:
            # all shards in one process: consecutive ports from --port, or
            # all-ephemeral when port=0 (a fixed port can only bind once).
            # Durability lives in the facade's COORDINATED snapshot sets
            # (the per-hub dirs stay unset so the two mechanisms never
            # fight over the same shard-NN directories)
            ps = ShardedParameterServer(
                weights, plan,
                lambda w, sid: make_hub(w, sid, port + sid if port else 0,
                                        own_snapshots=False,
                                        hub_sparse=plan.local_sparse(sid)),
                snapshot_dir=snapshot_dir,
                snapshot_interval=snapshot_interval,
                restore=restore)
    ps.start()
    return ps


def main(argv: Optional[List[str]] = None) -> None:
    """``distkeras-ps``: serve a standalone PS hub for async multi-host runs.

    The model file is the no-pickle ``Model.serialize()`` blob:
    ``open(path, 'wb').write(Model.init(spec).serialize())``.
    """
    import argparse
    import threading

    parser = argparse.ArgumentParser(description="dist-keras-tpu parameter-server daemon")
    parser.add_argument("--model", required=True, help="serialized Model file")
    parser.add_argument("--mode", default="delta", choices=["delta", "adag", "dynsgd"])
    parser.add_argument("--num-workers", type=int, default=1,
                        help="expected worker count (adag normalization)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=5000)
    parser.add_argument("--native", action="store_true", help="use the C++ hub")
    parser.add_argument("--save-final", default=None,
                        help="on shutdown, write the final center model here")
    parser.add_argument("--snapshot-dir", default=None,
                        help="periodically snapshot center+clock here (atomic; "
                             "survives SIGKILL)")
    parser.add_argument("--snapshot-interval", type=float, default=30.0,
                        help="seconds between hub snapshots")
    parser.add_argument("--restore", action="store_true",
                        help="resume from the newest readable snapshot in "
                             "--snapshot-dir before serving (clock-fenced)")
    parser.add_argument("--idle-timeout", type=float, default=300.0,
                        help="evict connections silent for this many seconds "
                             "(half-open liveness); <= 0 disables")
    parser.add_argument("--elastic", action="store_true",
                        help="adag: normalize commits by the LIVE worker "
                             "count instead of --num-workers")
    parser.add_argument("--num-shards", type=int, default=1,
                        help="partition the center across this many hub "
                             "shards (deterministic shard_plan; trainers "
                             "pass the same num_shards)")
    parser.add_argument("--shard-index", type=int, default=None,
                        help="serve ONLY this shard from this process (one "
                             "distkeras-ps per shard); omit to serve every "
                             "shard from one process")
    parser.add_argument("--health-jsonl", default=None, metavar="PATH",
                        help="append every fleet HealthEvent (straggler, "
                             "staleness spike, reconnect/failover storm, "
                             "replication lag, throughput regression) to "
                             "this file as JSON lines; live view: "
                             "distkeras-top against a punchcard daemon")
    parser.add_argument("--sparse-tables", default=None, metavar="SPEC",
                        help="row-sparse embedding service (both hubs): "
                             "'auto' registers the model's declared "
                             "EmbeddingTable leaves, or a comma-separated "
                             "list of flat-leaf indices; workers started "
                             "with the matching sparse_tables knob then "
                             "exchange only the rows each batch touches")
    parser.add_argument("--shm-dir", default=None, metavar="DIR",
                        help="serve shared-memory frame-ring attaches (wire "
                             "action Z) to same-host clients dialed with "
                             "shm=True, creating ring files under DIR "
                             "(ideally tmpfs, e.g. /dev/shm); omit to "
                             "decline every attach (clients ride TCP "
                             "unchanged)")
    parser.add_argument("--recv-batch-depth", type=int, default=0,
                        help="drain up to N queued frames per receive-loop "
                             "wakeup (recvmmsg where available); 0 = one "
                             "recv per frame, today's loop")
    parser.add_argument("--adaptive", action="store_true",
                        help="telemetry-driven adaptive aggregation (both "
                             "hubs): merge queued commits "
                             "Adasum-style, scale each worker's commits "
                             "by its live staleness standing, and shed "
                             "reconnect storms with retry-after hints "
                             "(pair with trainers started adaptive=True)")
    parser.add_argument("--replica-of", default=None, metavar="HOST:PORT",
                        help="start as a hot standby of the primary hub at "
                             "this address: serve pulls immediately, stream "
                             "its applied commits, promote on its death "
                             "(both hubs; sharded: one standby daemon "
                             "per shard, paired with --shard-index)")
    parser.add_argument("--autoscale", action="store_true",
                        help="run an ADVISORY FleetController on this hub's "
                             "health monitor: spawn/retire/respawn "
                             "decisions are recorded and counted "
                             "(ps_fleet_* telemetry, printed at shutdown) "
                             "for an operator or supervisor to act on — "
                             "the daemon itself starts no workers")
    args = parser.parse_args(argv)
    if args.restore and not args.snapshot_dir:
        parser.error("--restore requires --snapshot-dir")
    if args.shard_index is not None and args.num_shards <= 1:
        parser.error("--shard-index requires --num-shards > 1")
    if args.save_final and args.shard_index is not None:
        parser.error("--save-final needs the full center; a single-shard "
                     "process only holds its slice")
    replica_of = None
    if args.replica_of:
        if args.num_shards > 1 and args.shard_index is None:
            parser.error("--replica-of with --num-shards > 1 requires "
                         "--shard-index (one standby daemon per shard)")
        host_part, _, port_part = args.replica_of.rpartition(":")
        if not host_part or not port_part.isdigit():
            parser.error(f"--replica-of expects HOST:PORT, got "
                         f"{args.replica_of!r}")
        replica_of = (host_part, int(port_part))
    sparse_tables: Optional[Any] = None
    if args.sparse_tables:
        if args.sparse_tables == "auto":
            sparse_tables = "auto"
        else:
            try:
                sparse_tables = tuple(
                    int(p) for p in args.sparse_tables.split(",") if p)
            except ValueError:
                parser.error(f"--sparse-tables expects 'auto' or a comma-"
                             f"separated index list, got "
                             f"{args.sparse_tables!r}")

    # a hub never needs an accelerator, and one process owns the chip:
    # Model.deserialize runs a Flax init on the default backend, which on a
    # TPU host would take the chip from the trainer started next to this
    # daemon — pin the CPU before any JAX backend comes up
    from distkeras_tpu.platform import pin_cpu_devices

    pin_cpu_devices(1)
    from distkeras_tpu.models.base import Model

    with open(args.model, "rb") as f:
        model = Model.deserialize(f.read())
    # graceful preemption drain (ISSUE 19): SIGTERM — the notice every
    # spot/preemptible scheduler sends ahead of the kill — exits the wait
    # loop below and runs the SAME shutdown as Ctrl-C.  Installed BEFORE
    # the hub starts (and before the "listening" banner): a supervisor
    # that SIGTERMs the moment the daemon reports ready must get the
    # drain, never the default-action kill
    import signal

    stop_event = threading.Event()

    def _on_sigterm(_signum, _frame):
        stop_event.set()

    prev_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    ps = start_parameter_server(model, mode=args.mode, num_workers=args.num_workers,
                                host=args.host, port=args.port, native=args.native,
                                elastic=args.elastic,
                                idle_timeout=(args.idle_timeout
                                              if args.idle_timeout > 0 else None),
                                snapshot_dir=args.snapshot_dir,
                                snapshot_interval=args.snapshot_interval,
                                restore=args.restore,
                                num_shards=args.num_shards,
                                shard_index=args.shard_index,
                                replica_of=replica_of,
                                health_jsonl=args.health_jsonl,
                                sparse_tables=sparse_tables,
                                adaptive=args.adaptive,
                                shm_dir=args.shm_dir,
                                recv_batch_depth=args.recv_batch_depth)
    if replica_of is not None:
        print(f"ps standby (replica of {replica_of[0]}:{replica_of[1]}) "
              f"listening on {args.host}:{ps.port}", flush=True)
    if args.num_shards > 1 and args.shard_index is None:
        for sid, p in enumerate(ps.ports):
            print(f"ps shard {sid}/{args.num_shards} listening on "
                  f"{args.host}:{p}", flush=True)
    elif args.shard_index is not None:
        print(f"ps shard {args.shard_index}/{args.num_shards} listening on "
              f"{args.host}:{ps.port}", flush=True)
    else:
        print(f"ps listening on {args.host}:{ps.port}", flush=True)
    controller = None
    if args.autoscale:
        from distkeras_tpu.observability import health as _health
        from distkeras_tpu.runtime.fleet_controller import FleetController

        controller = FleetController(_health.monitor())
    # the drain itself: ps.stop() takes a final snapshot, flushes and
    # severs the replication feed (a standby's stream ends with a clean
    # EOF, never a torn frame), shuts the listener down and severs worker
    # connections.  Workers reconnect to the standby/restart under their
    # own budgets; nothing acked is lost
    try:
        while not stop_event.wait(1.0):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        signal.signal(signal.SIGTERM, prev_handler)
        if stop_event.is_set():
            print("SIGTERM: draining hub (final snapshot, feed flush, "
                  "listener shutdown)", flush=True)
        if controller is not None:
            controller.stop()
            for d in controller.decisions():
                print(f"fleet decision: {d['action']} "
                      f"worker={d['worker']} reason={d['reason']}",
                      flush=True)
        ps.stop()
        # distributed tracing: the hub process is the merge's clock
        # REFERENCE (offset 0) — flush its spans (handler-side
        # ps.handle_commit/pull, snapshot, eviction; the C++ hub's drained
        # commit log lands here through stop()'s sync_telemetry) so
        # merge_traces(DKT_TRACE_DIR) can align every worker against it.
        # DKT_TELEMETRY=1 DKT_TRACE_DIR=... is the whole recipe
        trace_dir = os.environ.get("DKT_TRACE_DIR")
        if trace_dir:
            from distkeras_tpu import observability as obs

            if obs.enabled():
                from distkeras_tpu.observability.distributed import (
                    flush_process_trace,
                )

                try:
                    flush_process_trace(trace_dir, role="hub")
                except OSError as e:
                    print(f"trace flush failed: {e}", flush=True)
        if args.save_final:
            from distkeras_tpu.utils import flatten_weights, unflatten_weights

            _, treedef = flatten_weights(model.params)
            final = Model(spec=model.spec,
                          params=unflatten_weights(treedef, ps.get_weights()))
            with open(args.save_final, "wb") as f:
                f.write(final.serialize())
            print(f"final model written to {args.save_final}", flush=True)


if __name__ == "__main__":
    main()
