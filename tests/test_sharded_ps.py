"""Sharded parameter-server hub (ISSUE 6): shard plan properties, wire
compatibility, the striped client, per-shard faults/telemetry, and the
1-shard == unsharded trajectory-parity matrix.

The acceptance contract: ``num_shards=1`` is byte-identical to today's
single-hub wire, and an N-shard run at 1 worker is bit-identical to the
1-shard trajectory — partitioning the center must change WHERE the bytes
land, never what they compute.
"""

import os

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.networking import FlatFrameCodec
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    InprocPSClient,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    shard_plan,
)


def _templates():
    return [np.zeros((6, 4), np.float32), np.zeros((17,), np.float32),
            np.zeros((3, 3), np.float32), np.zeros((11,), np.float32),
            np.zeros((2,), np.float32), np.zeros((29,), np.float32)]


# -- shard plan properties -----------------------------------------------------

def test_shard_plan_deterministic_and_identity_at_one_shard():
    t = _templates()
    p1, p2 = shard_plan(t, 3), shard_plan(t, 3)
    assert p1.assignments == p2.assignments
    assert shard_plan(t, 1).assignments == (tuple(range(len(t))),)
    # every leaf assigned exactly once, each shard ascending
    seen = sorted(i for idxs in p1.assignments for i in idxs)
    assert seen == list(range(len(t)))
    for idxs in p1.assignments:
        assert list(idxs) == sorted(idxs)


def test_shard_plan_stable_under_leaf_reorder():
    """The assignment is a function of each leaf's (nbytes, dtype, shape)
    identity, not its position: permuting the template list maps every
    leaf to the same shard."""
    t = _templates()  # all layouts distinct
    base = shard_plan(t, 3)
    shard_of = {}
    for s, idxs in enumerate(base.assignments):
        for i in idxs:
            shard_of[i] = s
    rng = np.random.default_rng(7)
    for _ in range(5):
        perm = list(rng.permutation(len(t)))
        permuted = shard_plan([t[i] for i in perm], 3)
        for s, idxs in enumerate(permuted.assignments):
            for j in idxs:
                assert shard_of[perm[j]] == s, (
                    f"leaf {perm[j]} moved shard under permutation {perm}")


def test_shard_plan_balance_bound():
    """LPT guarantee: the heaviest shard exceeds the lightest by at most
    one leaf's bytes — for random size mixes, not just the fixture."""
    rng = np.random.default_rng(0)
    for trial in range(10):
        sizes = rng.integers(1, 2000, size=rng.integers(4, 40))
        t = [np.zeros(int(sz), np.float32) for sz in sizes]
        for shards in (2, 3, 4):
            if shards > len(t):
                continue
            plan = shard_plan(t, shards)
            assert sum(plan.shard_bytes) == sum(a.nbytes for a in t)
            spread = max(plan.shard_bytes) - min(plan.shard_bytes)
            assert spread <= max(a.nbytes for a in t), (
                f"trial {trial}, {shards} shards: spread {spread}")


def test_shard_plan_rejects_bad_shard_counts():
    t = _templates()
    with pytest.raises(ValueError, match="num_shards"):
        shard_plan(t, 0)
    with pytest.raises(ValueError, match="exceeds"):
        shard_plan(t, len(t) + 1)


def test_shard_plan_split_assemble_roundtrip_by_reference():
    t = _templates()
    plan = shard_plan(t, 3)
    arrays = [np.full(a.shape, i, np.float32) for i, a in enumerate(t)]
    back = plan.assemble(plan.split(arrays))
    assert all(b is a for b, a in zip(back, arrays))  # zero-copy contract


# -- wire compatibility (the num_shards=1 acceptance criterion) ----------------

def test_one_shard_codec_frames_byte_identical_to_unsharded():
    """A 1-shard plan's only shard carries all leaves in template order,
    so its codec's packed frame is byte-for-byte today's wire — against
    both the flat codec and the generic encoder."""
    t = _templates()
    plan = shard_plan(t, 1)
    payload = [np.full(a.shape, 0.25 * (i + 1), np.float32)
               for i, a in enumerate(t)]
    unsharded = FlatFrameCodec(t)
    unsharded.pack(net.ACTION_COMMIT, payload)
    shard0 = FlatFrameCodec([t[i] for i in plan.assignments[0]])
    shard0.pack(net.ACTION_COMMIT, [payload[i] for i in plan.assignments[0]])
    assert bytes(unsharded._tx) == bytes(shard0._tx)
    generic = net.encode_tensors(net.ACTION_COMMIT, payload)
    assert bytes(unsharded._tx)[8:] == generic


def test_trainer_num_shards_one_uses_plain_hub_and_client(toy_dataset):
    """num_shards=1 (the default) short-circuits the sharded machinery
    entirely: the trainer owns a plain hub, not the facade — today's code
    path, byte-identical by construction."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    tr = dk.AsyncADAG(Model.init(spec, seed=0),
                      loss="categorical_crossentropy", batch_size=16,
                      num_epoch=1, num_workers=1, communication_window=4,
                      learning_rate=0.05, seed=0)
    tr.train(toy_dataset)
    assert isinstance(tr.parameter_server, ADAGParameterServer)
    assert tr._shard_plan is None


# -- facade + striped client ---------------------------------------------------

def _start_sharded(templates, num_shards, cls=DeltaParameterServer, **hub_kw):
    plan = shard_plan(templates, num_shards)
    ps = ShardedParameterServer(
        templates, plan,
        lambda w, sid: cls(w, shard_id=sid, idle_timeout=None, **hub_kw))
    ps.start()
    return ps, plan


def test_facade_lifecycle_weights_and_direct_transport():
    t = [np.full(a.shape, 1.0, np.float32) for a in _templates()]
    ps, plan = _start_sharded(t, 3)
    try:
        assert len(ps.ports) == 3 and ps.port == ps.ports[0]
        got = ps.get_weights()
        assert [g.shape for g in got] == [a.shape for a in t]
        assert all(np.all(g == 1.0) for g in got)
        # direct pair: tuple clocks ride through opaque to the client
        weights, clocks = ps.pull_direct()
        assert isinstance(clocks, tuple) and len(clocks) == 3
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], clocks)
        assert ps.num_updates == 1
        assert all(np.allclose(g, 1.5) for g in ps.get_weights())
        # int clock broadcasts (the inproc client's pre-pull default)
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], 0)
        assert ps.num_updates == 2
        # InprocPSClient works against the facade unchanged
        client = InprocPSClient(ps, templates=t)
        pulled = client.pull()
        assert all(np.allclose(g, 2.0) for g in pulled)
        client.commit([np.full(a.shape, -1.0, np.float32) for a in t])
        assert all(np.allclose(g, 1.0) for g in ps.get_weights())
    finally:
        ps.stop()


def test_parallel_direct_pool_matches_sequential_walk():
    """The per-shard worker pool (ISSUE 18) changes WHERE each stripe
    runs, not what it computes: parallel_direct=True fans the stripes out
    to one long-lived dk-shard-worker thread per shard, and the results
    stay bit-identical to the sequential walk because the shards are
    disjoint state."""
    import threading

    t = _templates()
    rng = np.random.default_rng(7)
    deltas = [[rng.normal(size=a.shape).astype(np.float32) for a in t]
              for _ in range(3)]

    def run(parallel):
        plan = shard_plan(t, 3)
        ps = ShardedParameterServer(
            t, plan,
            lambda w, sid: DeltaParameterServer(w, shard_id=sid,
                                                idle_timeout=None),
            parallel_direct=parallel)
        ps.start()
        try:
            if parallel:
                assert ps._pool is not None and ps._pool.running
                names = {th.name for th in threading.enumerate()}
                assert {f"dk-shard-worker-{i}" for i in range(3)} <= names
            else:
                assert ps._pool is None
            for d in deltas:
                _, clocks = ps.pull_direct()
                ps.commit_direct(d, clocks)
            assert ps.num_updates == len(deltas)
            return [w.copy() for w in ps.get_weights()]
        finally:
            ps.stop()

    pooled, sequential = run(True), run(False)
    for a, b in zip(pooled, sequential):
        np.testing.assert_array_equal(a, b)
    # the pool threads are reaped on stop()
    assert not any(th.name.startswith("dk-shard-worker")
                   for th in threading.enumerate())


def test_striped_client_pull_commit_and_int8_parity():
    """The striped socket client lands values identical to an unsharded
    client over the same math — including int8 error-feedback commits,
    whose residual chain is per leaf and therefore shard-invariant."""
    t = _templates()
    rng = np.random.default_rng(3)
    deltas = [[rng.normal(size=a.shape).astype(np.float32) for a in t]
              for _ in range(4)]

    def run(num_shards, compress):
        ps, plan = _start_sharded(t, num_shards)
        try:
            if num_shards == 1:
                client = PSClient("127.0.0.1", ps.ports[0], t,
                                  compress=compress)
            else:
                client = ShardedPSClient([("127.0.0.1", p) for p in ps.ports],
                                         t, plan, compress=compress)
            with client:
                for d in deltas:
                    client.commit(d)
                final = [w.copy() for w in client.pull()]
            return final
        finally:
            ps.stop()

    for compress in (None, "int8"):
        one = run(1, compress)
        three = run(3, compress)
        for a, b in zip(one, three):
            np.testing.assert_array_equal(a, b)


def test_striped_client_rejects_address_plan_mismatch():
    t = _templates()
    plan = shard_plan(t, 3)
    with pytest.raises(ValueError, match="shard addresses"):
        ShardedPSClient([("127.0.0.1", 1)], t, plan)


def test_facade_live_workers_is_min_across_shards():
    """A worker counts as fleet-live only while ALL its shard connections
    do: membership is per shard, and the facade reports the min."""
    t = _templates()
    ps, plan = _start_sharded(t, 2)
    try:
        assert ps.live_workers() == 0
        client = ShardedPSClient([("127.0.0.1", p) for p in ps.ports], t, plan)
        with client:
            client.commit([np.zeros(a.shape, np.float32) for a in t])
            assert ps.live_workers() == 1
            # sever ONE shard connection: the worker drops out of the
            # fleet-live count even though the other shard still sees it
            import time

            client.shards[1].sock.close()
            deadline = time.monotonic() + 5.0
            while ps.live_workers() != 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert ps.live_workers() == 0
            assert ps.shards[0].live_workers() == 1
    finally:
        ps.stop()


# -- satellite: per-shard socket-buffer sizing ---------------------------------

def test_socket_buffers_sized_from_per_shard_frames():
    """Each shard hub (and each per-shard client codec) sizes its kernel
    buffers from ITS tensor subset: N shard connections cost about one
    model of buffer hint in total, not N models."""
    t = [np.zeros(65536, np.float32) for _ in range(4)]  # 256 KiB leaves
    full_frame = net.tensor_frame_len(t)
    ps, plan = _start_sharded(t, 4)
    try:
        for sid, hub in enumerate(ps.shards):
            shard_frame = net.tensor_frame_len(
                [t[i] for i in plan.assignments[sid]])
            assert hub._frame_bytes == shard_frame
            assert hub._frame_bytes < full_frame
        # the sum of per-shard hints is the full frame plus one 13-byte
        # header+count per extra shard — not 4x the model
        assert sum(h._frame_bytes for h in ps.shards) == full_frame + 3 * 13
        client = ShardedPSClient([("127.0.0.1", p) for p in ps.ports], t, plan)
        with client:
            for sid, sc in enumerate(client.shards):
                assert sc._codec.frame_len == net.tensor_frame_len(
                    [t[i] for i in plan.assignments[sid]])
    finally:
        ps.stop()


# -- per-shard telemetry + fleet attribution (satellite) -----------------------

def test_per_shard_telemetry_labels_and_fleet_report():
    t = _templates()
    obs.reset()
    # spans from earlier tests' runs would inflate the fleet report's
    # commit counts — this test owns the ring
    obs.TRACER.clear()
    obs.enable()
    try:
        ps, plan = _start_sharded(t, 2)
        try:
            client = ShardedPSClient([("127.0.0.1", p) for p in ps.ports],
                                     t, plan)
            with client:
                for _ in range(3):
                    client.commit([np.zeros(a.shape, np.float32) for a in t])
                client.pull()
            snap = obs.snapshot()
            counters = snap["counters"]
            # hub side: per-shard series, no unlabeled double count (the
            # unlabeled series may exist zeroed from earlier tests'
            # instruments — reset() zeroes, it does not unregister)
            for sid in (0, 1):
                assert counters[f'ps_commits_total{{shard="{sid}"}}'] == 3.0
            assert counters.get("ps_commits_total", 0.0) == 0.0
            # client side: per-shard commit bytes sum to the stripe total
            stripe = sum(
                counters[f'ps.commit_bytes{{shard="{sid}"}}']
                for sid in (0, 1))
            expected = 3 * sum(
                net.tensor_frame_len([t[i] for i in idxs])
                for idxs in plan.assignments)
            assert stripe == expected
            assert 'ps_commit_staleness{shard="0"}' in snap["histograms"]
        finally:
            ps.stop()
        # fleet_report: logical commits (no double count) + shard table
        from distkeras_tpu.observability.distributed import fleet_report

        report = fleet_report(events=obs.TRACER.events())
        assert report["total_commits"] == 3
        assert set(report["shards"]) == {"0", "1"}
        assert report["shards"]["0"]["commits"] == 3
        assert report["slowest_shard"] in ("0", "1")
    finally:
        obs.disable()
        obs.reset()


# -- per-shard chaos (satellite: ChaosProxy shard faults) ----------------------

def test_sharded_chaos_proxy_severs_one_stripe_and_client_recovers():
    from distkeras_tpu.runtime.faults import Fault, FaultPlan, ShardedChaosProxy

    t = _templates()
    ps, plan = _start_sharded(t, 2)
    try:
        fault_plan = FaultPlan([Fault(conn=0, frame=1, direction="s2c",
                                      kind="sever", shard=1)])
        with ShardedChaosProxy([("127.0.0.1", p) for p in ps.ports],
                               plan=fault_plan) as proxy:
            client = ShardedPSClient(
                [("127.0.0.1", p) for p in proxy.ports], t, plan,
                max_reconnects=3, reconnect_backoff=0.02)
            with client:
                for _ in range(4):
                    client.commit([np.full(a.shape, 0.5, np.float32)
                                   for a in t])
                final = [w.copy() for w in client.pull()]
            fired = proxy.faults_fired
            assert [f.shard for f in fired] == [1]
            assert proxy.proxies[0].faults_fired == []
            # shard 1's severed stripe dropped at most the in-flight
            # commit; shard 0 saw all four.  Recovery means the final
            # center is consistent per shard and the client survived
            assert client.shards[1].reconnects_used >= 1
            assert ps.shards[0].num_updates == 4
            assert ps.shards[1].num_updates >= 3
            for idxs, hub in zip(plan.assignments, ps.shards):
                n = hub.num_updates
                for i in idxs:
                    np.testing.assert_allclose(final[i], 0.5 * n, rtol=1e-6)
    finally:
        ps.stop()


@pytest.mark.parametrize("fault", [None, "sever"])
def test_striped_client_lands_every_stripe(fault, telemetry):
    """``ShardedPSClient.land_weights`` claims each stripe's prefetched
    reply (counted per shard); the commit that follows finds no stripe's
    reply pending and ``wait_weights()`` assembles the center from what
    landed.  A stripe severed mid-land reconnects inside the call and
    hands out its re-issued pull."""
    from distkeras_tpu.runtime.faults import Fault, FaultPlan, ShardedChaosProxy

    t = _templates()
    ps, plan = _start_sharded(t, 3)
    try:
        fault_plan = FaultPlan(
            [Fault(conn=0, frame=1, direction="s2c", kind="sever", shard=1)]
            if fault else [])
        with ShardedChaosProxy([("127.0.0.1", p) for p in ps.ports],
                               plan=fault_plan) as proxy:
            client = ShardedPSClient(
                [("127.0.0.1", p) for p in proxy.ports], t, plan,
                max_reconnects=3, reconnect_backoff=0.02)
            with client:
                held = client.pull()                       # s2c frame 0
                snapshot = [w.copy() for w in held]
                client.pull_nowait()                       # s2c frame 1
                client.land_weights()
                for c in client.shards:
                    assert len(c._ready) == 1
                    assert not c._has_pending(net.ACTION_WEIGHTS)
                for w, snap in zip(held, snapshot):
                    np.testing.assert_array_equal(w, snap)
                counters = obs.snapshot()["counters"]
                for sid in range(3):
                    assert counters[
                        f'ps_pulls_landed_early_total{{shard="{sid}"}}'] == 1.0
                client.commit_nowait([np.ones(a.shape, np.float32)
                                      for a in t])
                for w in client.wait_weights():            # predates the commit
                    np.testing.assert_array_equal(w, 0.0)
                client.drain()
                for w in client.pull():
                    np.testing.assert_array_equal(w, 1.0)
            assert [f.shard for f in proxy.faults_fired] == ([1] if fault
                                                             else [])
            assert client.shards[1].reconnects_used == (1 if fault else 0)
    finally:
        ps.stop()


# -- coordinated per-shard snapshots (restored as a unit) ----------------------

def test_sharded_snapshot_set_restores_as_a_unit(tmp_path):
    t = [np.full(a.shape, 1.0, np.float32) for a in _templates()]

    def factory_for(base):
        def factory(w, sid):
            return DeltaParameterServer(
                w, shard_id=sid, idle_timeout=None,
                snapshot_dir=os.path.join(base, f"shard-{sid:02d}"),
                snapshot_interval=3600.0)
        return factory

    plan = shard_plan(t, 2)
    ps = ShardedParameterServer(t, plan, factory_for(str(tmp_path)))
    ps.start()
    try:
        ps.commit_direct([np.full(a.shape, 0.5, np.float32) for a in t], 0)
        for hub in ps.shards:
            hub.snapshotter.save_now()
        expected = [w.copy() for w in ps.get_weights()]
    finally:
        ps.kill()  # crash semantics: recovery must come from the snapshots

    def restore_factory(w, sid):
        return DeltaParameterServer(
            w, shard_id=sid, idle_timeout=None,
            snapshot_dir=os.path.join(str(tmp_path), f"shard-{sid:02d}"),
            snapshot_interval=3600.0, restore=True)

    fresh = ShardedParameterServer(
        [np.zeros(a.shape, np.float32) for a in t], plan, restore_factory)
    fresh.start()
    try:
        got = fresh.get_weights()
        for a, b in zip(expected, got):
            np.testing.assert_array_equal(a, b)
        # per-shard clock fences armed at each shard's restored clock
        for hub in fresh.shards:
            assert hub._clock_fence == hub._clock == 1
    finally:
        fresh.stop()


# -- standalone per-shard hubs (launcher + worker-only striping) ---------------

def test_worker_only_mode_against_standalone_shard_hubs(toy_dataset):
    """The multi-host sharded topology end to end in one process: one
    start_parameter_server(shard_index=i) hub per shard (each derives the
    SAME deterministic plan from the same model), and a worker-only
    trainer striping against their addresses."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    model = Model.init(spec, seed=0)
    hubs = [start_parameter_server(model, mode="adag", num_workers=1,
                                   host="127.0.0.1", port=0,
                                   idle_timeout=None,
                                   num_shards=2, shard_index=i)
            for i in range(2)]
    try:
        from distkeras_tpu.utils import flatten_weights

        flat, _ = flatten_weights(model.params)
        plan = shard_plan([np.asarray(w, np.float32) for w in flat], 2)
        for sid, hub in enumerate(hubs):
            assert hub.shard_id == sid
            assert len(hub.center) == len(plan.assignments[sid])
        tr = dk.AsyncADAG(model, loss="categorical_crossentropy",
                          batch_size=16, num_epoch=1, num_workers=1,
                          communication_window=4, learning_rate=0.05, seed=0,
                          ps_address=[("127.0.0.1", h.port) for h in hubs])
        assert tr.num_shards == 2  # inferred from the address list
        trained = tr.train(toy_dataset)
        assert len(tr.history) > 0
        assert sum(h.num_updates for h in hubs) // 2 == len(tr.history)
        assert trained.predict(toy_dataset["features"][:4]).shape == (4, 2)
    finally:
        for h in hubs:
            h.stop()


def test_worker_only_address_count_must_match_num_shards():
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    with pytest.raises(ValueError, match="per shard"):
        dk.AsyncADAG(spec, ps_address=[("a", 1), ("b", 2)], num_shards=3)


# -- the 1-shard == N-shard trajectory-parity matrix ---------------------------

_ALL_TRAINERS = ["AsyncDOWNPOUR", "AsyncADAG", "AsyncDynSGD", "AsyncAEASGD",
                 "AsyncEAMSGD"]
_REFERENCE_CACHE = {}


def _parity_dataset():
    rng = np.random.default_rng(11)
    n = 128
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, size=n)]
    from distkeras_tpu.data.dataset import Dataset

    return Dataset({"features": x, "label": y})


def _parity_run(trainer_name, *, num_shards, transport, hub):
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    kwargs = dict(loss="categorical_crossentropy", batch_size=16, num_epoch=1,
                  num_workers=1, communication_window=2, learning_rate=0.05,
                  seed=0, transport=transport, native_ps=(hub == "native"),
                  num_shards=num_shards)
    if trainer_name in ("AsyncAEASGD", "AsyncEAMSGD"):
        kwargs["rho"] = 2.0
    trainer = getattr(dk, trainer_name)(Model.init(spec, seed=0), **kwargs)
    model = trainer.train(_parity_dataset(), shuffle=False)
    return trainer.history, model


def _reference(trainer_name):
    """Unsharded reference trajectory, computed once per trainer (inproc/
    python — the cheapest transport; socket/native 1-shard parity with it
    is already pinned by test_transport.py / test_native_ps.py)."""
    if trainer_name not in _REFERENCE_CACHE:
        _REFERENCE_CACHE[trainer_name] = _parity_run(
            trainer_name, num_shards=1, transport="inproc", hub="python")
    return _REFERENCE_CACHE[trainer_name]


_MATRIX = [pytest.param(_name, _transport, _hub,
                        id=f"{_name}-{_transport}-{_hub}")
           for _name in _ALL_TRAINERS
           for _transport in ("socket", "inproc")
           for _hub in ("python", "native")]


@pytest.mark.parametrize("trainer_name,transport,hub", _MATRIX)
def test_three_shard_run_bit_identical_to_unsharded(trainer_name, transport,
                                                    hub):
    """Sharding must not change the algorithm: at 1 worker, a 3-shard run
    is bit-identical to the unsharded reference trajectory for every
    Async* trainer, on both transports, against both hubs."""
    import jax

    if hub == "native":
        from distkeras_tpu.runtime.native import native_available

        if not native_available():
            pytest.skip("no C++ toolchain for the native hub")
    ref_history, ref_model = _reference(trainer_name)
    history, model = _parity_run(trainer_name, num_shards=3,
                                 transport=transport, hub=hub)
    assert history == ref_history, "window-loss trajectories diverged"
    for a, b in zip(jax.tree.leaves(ref_model.params),
                    jax.tree.leaves(model.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
