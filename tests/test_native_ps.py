"""Native (C++) parameter-server hub tests: the Python PSClient drives the
C++ server over the shared wire protocol, and results must match the
pure-Python hub bit-for-bit on deterministic schedules."""

import threading

import numpy as np
import pytest

from distkeras_tpu.runtime.native import (
    MODE_ADAG,
    MODE_DELTA,
    MODE_DYNSGD,
    NativeParameterServer,
    build_error,
    native_available,
)
from distkeras_tpu.runtime.parameter_server import PSClient

pytestmark = pytest.mark.skipif(
    not native_available(), reason=f"native PS unavailable: {build_error()}")


@pytest.fixture
def fresh_health():
    """Clean process-default collector/monitor (the native wrapper's poll
    thread folds wire reports into these)."""
    from distkeras_tpu.observability import health as health_mod

    health_mod.reset_default()
    yield health_mod
    health_mod.reset_default()


def _weights():
    return [np.zeros((2, 2), np.float32), np.zeros((3,), np.float32)]


def test_native_pull_commit_roundtrip():
    ps = NativeParameterServer(_weights(), mode=MODE_DELTA)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            w = c.pull()
            assert all(np.all(x == 0) for x in w)
            c.commit([np.ones((2, 2), np.float32), 2 * np.ones((3,), np.float32)])
            w = c.pull()
            np.testing.assert_allclose(w[0], np.ones((2, 2)))
            np.testing.assert_allclose(w[1], 2 * np.ones((3,)))
        assert ps.num_updates == 1
    finally:
        ps.stop()


def test_native_initial_weights_preserved():
    init = [np.full((2, 2), 3.0, np.float32), np.arange(3, dtype=np.float32)]
    ps = NativeParameterServer(init, mode=MODE_DELTA)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=init) as c:
            w = c.pull()
            np.testing.assert_allclose(w[0], init[0])
            np.testing.assert_allclose(w[1], init[1])
    finally:
        ps.stop()


def test_native_adag_scaling():
    ps = NativeParameterServer(_weights(), mode=MODE_ADAG, num_workers=4)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.commit([np.full((2, 2), 4.0, np.float32), np.full((3,), 8.0, np.float32)])
            w = c.pull()
            np.testing.assert_allclose(w[0], np.ones((2, 2)))
            np.testing.assert_allclose(w[1], 2 * np.ones((3,)))
    finally:
        ps.stop()


def test_native_dynsgd_staleness():
    ps = NativeParameterServer(_weights(), mode=MODE_DYNSGD)
    ps.start()
    try:
        a = PSClient("127.0.0.1", ps.port, templates=_weights())
        b = PSClient("127.0.0.1", ps.port, templates=_weights())
        a.pull()
        b.pull()
        one = [np.ones((2, 2), np.float32), np.ones((3,), np.float32)]
        a.commit(one)  # staleness 0 -> full
        b.commit(one)  # staleness 1 -> half
        w = a.pull()
        np.testing.assert_allclose(w[0], np.full((2, 2), 1.5))
        a.close()
        b.close()
    finally:
        ps.stop()


def test_native_concurrent_commits_all_land():
    ps = NativeParameterServer([np.zeros((64,), np.float32)], mode=MODE_DELTA)
    ps.start()
    n_workers, n_commits = 8, 50

    def work(i):
        with PSClient("127.0.0.1", ps.port, templates=[np.zeros((64,), np.float32)]) as c:
            for _ in range(n_commits):
                c.pull()
                c.commit([np.ones((64,), np.float32)])

    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        np.testing.assert_allclose(ps.get_weights()[0], np.full((64,), n_workers * n_commits))
        assert ps.num_updates == n_workers * n_commits
    finally:
        ps.stop()


def test_native_async_downpour_trains(toy_dataset):
    from distkeras_tpu import AsyncDOWNPOUR
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.predictors import ModelPredictor

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2}, input_shape=(8,))
    trainer = AsyncDOWNPOUR(Model.init(spec, seed=0), loss="categorical_crossentropy",
                            batch_size=16, num_epoch=2, num_workers=4,
                            communication_window=4, learning_rate=0.05, native_ps=True)
    model = trainer.train(toy_dataset)
    assert trainer.parameter_server.num_updates > 0
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index", label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"native AsyncDOWNPOUR accuracy {acc}"


def test_native_int8_commits_match_python_hub():
    """The C++ hub must dequantize action-Q commits exactly like the
    Python hub: drive BOTH hubs with the same compressed client traffic
    and compare centers element-for-element."""
    from distkeras_tpu.runtime.parameter_server import ADAGParameterServer

    rng = np.random.default_rng(5)
    deltas = [[rng.normal(size=(2, 2)).astype(np.float32),
               rng.normal(size=(3,)).astype(np.float32)] for _ in range(4)]

    def drive(ps):
        ps.start()
        try:
            with PSClient("127.0.0.1", ps.port, templates=_weights(),
                          compress="int8") as c:
                for d in deltas:
                    c.commit(d)
                return c.pull()
        finally:
            ps.stop()

    w_native = drive(NativeParameterServer(_weights(), mode=MODE_ADAG,
                                           num_workers=4))
    w_python = drive(ADAGParameterServer(_weights(), num_workers=4))
    # same client stream (error feedback included) -> identical wire
    # bytes -> both hubs apply float(q)*scale/num_workers: bit-equal
    for n, p in zip(w_native, w_python):
        np.testing.assert_array_equal(n, p)


def test_native_pull_commit_direct_matches_python_hub():
    """The C++ hub's inproc pair (dk_ps_pull/dk_ps_commit) must move the
    center exactly like the Python hub's pull_direct/commit_direct —
    same deltas, same clocks, bit-equal centers."""
    from distkeras_tpu.runtime.parameter_server import DynSGDParameterServer

    rng = np.random.default_rng(7)
    deltas = [[rng.normal(size=(2, 2)).astype(np.float32),
               rng.normal(size=(3,)).astype(np.float32)] for _ in range(5)]

    def drive(ps):
        weights, clock = ps.pull_direct()
        assert clock == 0
        for i, d in enumerate(deltas):
            # commit against a deliberately stale clock every other step so
            # the DynSGD scaling path is exercised through both hubs
            ps.commit_direct(d, clock if i % 2 == 0 else max(clock - 1, 0))
            weights, clock = ps.pull_direct()
        assert clock == len(deltas) == ps.num_updates
        return weights

    w_native = drive(NativeParameterServer(_weights(), mode=MODE_DYNSGD))
    w_python = drive(DynSGDParameterServer(_weights()))
    for n, p in zip(w_native, w_python):
        np.testing.assert_array_equal(n, p)


def test_native_inproc_trainer_matches_python_inproc(toy_dataset):
    """transport='inproc' against the C++ hub: same trajectory as the
    Python hub inproc run (single worker, deterministic schedule)."""
    import jax

    from distkeras_tpu import AsyncDOWNPOUR
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))

    def run(native):
        tr = AsyncDOWNPOUR(Model.init(spec, seed=0),
                           loss="categorical_crossentropy", batch_size=16,
                           num_epoch=1, num_workers=1, communication_window=4,
                           learning_rate=0.05, seed=0, transport="inproc",
                           native_ps=native)
        model = tr.train(toy_dataset)
        return tr, model

    t_n, m_n = run(True)
    t_p, m_p = run(False)
    assert t_n.history == t_p.history
    for a, b in zip(jax.tree.leaves(m_n.params), jax.tree.leaves(m_p.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_native_async_downpour_trains_with_int8_commits(toy_dataset):
    """End-to-end: the C++ hub + int8 commits still train the toy task."""
    import distkeras_tpu as dk
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.predictors import ModelPredictor

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    trainer = dk.AsyncDOWNPOUR(
        Model.init(spec, seed=0), loss="categorical_crossentropy",
        batch_size=16, num_epoch=2, num_workers=4, communication_window=4,
        learning_rate=0.05, seed=0, native_ps=True, compress_commits="int8")
    model = trainer.train(toy_dataset)
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index",
                            label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"native int8-commit training underperformed: {acc}"


# -- ISSUE 11: feature parity (sparse, adaptive, replication, M/G/Y) -----------

def _sparse_weights():
    return [np.zeros((6, 3), np.float32), np.zeros((4,), np.float32)]


def _native(weights=None, **kw):
    return NativeParameterServer(weights if weights is not None
                                 else _sparse_weights(), **kw)


def test_native_sparse_pull_commit_matches_python_hub():
    """S/V/U exchange against both hubs with identical client traffic:
    partial-touch row pulls and commits land bit-identical centers."""
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    rng = np.random.default_rng(3)
    ids_seq = [np.array([0, 2, 5], np.int64), np.array([1, 2], np.int64),
               np.array([3], np.int64)]

    def drive(ps):
        ps.start()
        try:
            with PSClient("127.0.0.1", ps.port,
                          templates=_sparse_weights(),
                          sparse_leaves=[0]) as c:
                c.pull()  # full seed
                for ids in ids_seq:
                    c.pull_nowait(sparse_rows=[ids])
                    c.wait_weights()
                    delta = [np.zeros((6, 3), np.float32),
                             rng.normal(size=(4,)).astype(np.float32)]
                    delta[0][ids] = rng.normal(
                        size=(ids.size, 3)).astype(np.float32)
                    c.commit(delta, sparse_rows=[ids])
                c.drain()
                return c.pull()
        finally:
            ps.stop()

    rng = np.random.default_rng(3)
    w_native = drive(_native(mode=MODE_DELTA, sparse_leaves=[0]))
    rng = np.random.default_rng(3)
    w_python = drive(DeltaParameterServer(_sparse_weights(),
                                          sparse_leaves=[0]))
    for a, b in zip(w_native, w_python):
        np.testing.assert_array_equal(a, b)


def test_native_sparse_int8_commit_matches_python_hub():
    """X (int8 row-block) commits dequantize identically on both hubs."""
    from distkeras_tpu.runtime.parameter_server import ADAGParameterServer

    ids = np.array([1, 4], np.int64)

    def drive(ps):
        ps.start()
        try:
            with PSClient("127.0.0.1", ps.port, templates=_sparse_weights(),
                          sparse_leaves=[0], compress="int8") as c:
                rng = np.random.default_rng(9)
                c.pull()
                for _ in range(3):
                    delta = [np.zeros((6, 3), np.float32),
                             rng.normal(size=(4,)).astype(np.float32)]
                    delta[0][ids] = rng.normal(size=(2, 3)).astype(np.float32)
                    c.commit(delta, sparse_rows=[ids])
                return c.pull()
        finally:
            ps.stop()

    w_native = drive(_native(mode=MODE_ADAG, num_workers=2,
                             sparse_leaves=[0]))
    w_python = drive(ADAGParameterServer(_sparse_weights(), num_workers=2,
                                         sparse_leaves=[0]))
    for a, b in zip(w_native, w_python):
        np.testing.assert_array_equal(a, b)


def test_native_sparse_rejects_bad_row_ids():
    """Out-of-bounds / unsorted id blobs drop the connection (the Python
    hub's ProtocolError semantics) and the hub survives for new peers."""
    ps = _native(mode=MODE_DELTA, sparse_leaves=[0])
    ps.start()
    try:
        from distkeras_tpu.runtime import networking as net

        for bad in (np.array([7], np.int64),      # out of range
                    np.array([3, 1], np.int64),   # unsorted
                    np.array([2, 2], np.int64)):  # duplicate
            sock = net.connect("127.0.0.1", ps.port)
            net.send_tensors(sock, net.ACTION_SPARSE_PULL, [bad])
            with pytest.raises((ConnectionError, ValueError)):
                net.recv_tensors(sock)
            sock.close()
        # hub still serves a well-formed peer
        with PSClient("127.0.0.1", ps.port, templates=_sparse_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()
    finally:
        ps.stop()


def test_native_sparse_telemetry_established_names():
    """sync_telemetry surfaces sparse counters under the SAME names the
    Python hub emits (ps.sparse_rows_pulled / _committed / wire saved)."""
    from distkeras_tpu import observability as obs

    ps = _native(mode=MODE_DELTA, sparse_leaves=[0])
    ps.start()
    obs.enable()
    obs.reset()
    try:
        ids = np.array([0, 3], np.int64)
        with PSClient("127.0.0.1", ps.port, templates=_sparse_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()
            c.pull_nowait(sparse_rows=[ids])
            c.wait_weights()
            delta = [np.zeros((6, 3), np.float32), np.ones((4,), np.float32)]
            delta[0][ids] = 1.0
            c.commit(delta, sparse_rows=[ids])
            c.drain()
        ps.sync_telemetry()
        counters = obs.snapshot()["counters"]
        assert counters.get("ps.sparse_rows_pulled") == 2.0
        assert counters.get("ps.sparse_rows_committed") == 2.0
        assert counters.get("ps.sparse_wire_bytes_saved", 0) > 0
    finally:
        obs.reset()
        obs.disable()
        ps.stop()


def test_native_adaptive_batch_of_one_bit_equal_plain():
    """Uncontended adaptive applies are bit-identical to adaptive=False —
    the C++ combiner's batch-of-one IS the plain apply (the Python hub's
    pinned property, extended to the native cell)."""
    def drive(adaptive):
        ps = _native(mode=MODE_DYNSGD, adaptive=adaptive)
        ps.start()
        try:
            rng = np.random.default_rng(11)
            with PSClient("127.0.0.1", ps.port,
                          templates=_sparse_weights()) as c:
                for i in range(6):
                    c.pull()
                    c.commit([rng.normal(size=(6, 3)).astype(np.float32),
                              rng.normal(size=(4,)).astype(np.float32)])
                return c.pull()
        finally:
            ps.stop()

    for a, b in zip(drive(True), drive(False)):
        np.testing.assert_array_equal(a, b)


def test_native_adaptive_concurrent_commits_merge_and_advance_clock():
    """Contended adaptive commits flow through the flat-combining merger:
    every commit lands (num_updates == commits), the clock advances by
    batch size, and merged batches are visible in stats."""
    ps = _native([np.zeros((64,), np.float32)], mode=MODE_DELTA,
                 adaptive=True)
    ps.start()
    n_workers, n_commits = 6, 30

    def work(_):
        with PSClient("127.0.0.1", ps.port,
                      templates=[np.zeros((64,), np.float32)]) as c:
            for _ in range(n_commits):
                c.pull()
                c.commit([np.zeros((64,), np.float32)])

    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = ps.stats()
        assert ps.num_updates == n_workers * n_commits
        assert st["clock"] == n_workers * n_commits
        assert st["commits"] == n_workers * n_commits
        assert 1 <= st["merge_batches"] <= n_workers * n_commits
        assert st["max_merge_batch"] >= 1
    finally:
        ps.stop()


def test_native_adaptive_rate_scale_applies():
    """A pushed per-worker rate scales that worker's commits in the C++
    apply path (the AdaptiveRateController -> dk_ps_set_rate_scale
    bridge), and an expired verdict reads as 1.0."""
    w = [np.zeros((4,), np.float32)]
    ps = _native(w, mode=MODE_DELTA, adaptive=True)
    ps.start()
    try:
        # worker 7 scaled to 0.5 for a generous hold
        ps._lib.dk_ps_set_rate_scale(ps._handle, 7, 0.5,
                                     ps.time_ns() + int(60e9))
        from distkeras_tpu.observability import distributed as dtrace

        ctx = dtrace.TraceContext(job_id="j", worker_id=7, span_id=1)
        with PSClient("127.0.0.1", ps.port, templates=w,
                      trace_context=ctx) as c:
            c.pull()
            c.commit([np.ones((4,), np.float32)])
        np.testing.assert_allclose(ps.get_weights()[0], np.full((4,), 0.5))
        # expired verdict: back to 1.0
        ps._lib.dk_ps_set_rate_scale(ps._handle, 7, 0.25, ps.time_ns() - 1)
        with PSClient("127.0.0.1", ps.port, templates=w,
                      trace_context=ctx) as c:
            c.pull()
            c.commit([np.ones((4,), np.float32)])
        np.testing.assert_allclose(ps.get_weights()[0], np.full((4,), 1.5))
    finally:
        ps.stop()


def test_native_answers_reconnect_hello():
    """Every native hub answers G with a Y hint: 0 outside a storm (and
    always 0 on a non-adaptive hub); an adaptive hub in a live storm
    hands out increasing slots and admits announcers that already waited
    (waits_taken > 0)."""
    from distkeras_tpu.runtime import networking as net

    def hello(port, waits=0):
        sock = net.connect("127.0.0.1", port)
        try:
            net.send_frame(sock, net.encode_reconnect_payload(waits))
            action, blobs = net.recv_tensors(sock)
            assert action == net.ACTION_RETRY
            return net.decode_retry_payload(blobs)
        finally:
            sock.close()

    plain = _native(mode=MODE_DELTA)
    plain.start()
    try:
        assert hello(plain.port) == 0
    finally:
        plain.stop()

    ps = _native(mode=MODE_DELTA, adaptive=True)
    ps.start()
    try:
        # tight storm thresholds so three hellos arm shedding
        ps._lib.dk_ps_set_storm_params(ps._handle, 3, 5000, 3000, 50, 2000)
        hints = [hello(ps.port) for _ in range(5)]
        assert hints[0] == 0 and hints[1] == 0  # below the storm threshold
        nonzero = [h for h in hints if h > 0]
        assert nonzero, hints
        assert nonzero == sorted(nonzero)  # later slots, spread in time
        assert hello(ps.port, waits=1) == 0  # waited its slot: admitted
        assert ps.backpressure_hints == len(nonzero)
    finally:
        ps.stop()


def test_native_health_reports_fold_into_collector(fresh_health):
    """Action-M reports against the native hub land in the process
    HealthCollector via the wrapper's drain (wire health reporting is
    hub-implementation-agnostic)."""
    import time

    from distkeras_tpu.observability import health as health_mod

    ps = _native(mode=MODE_DELTA)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port,
                      templates=_sparse_weights()) as c:
            c.report_health({"worker": "3", "windows": 4,
                             "window_wall_ms": {"mean": 12.0, "last": 11.0,
                                                "count": 4},
                             "reconnects_total": 0})
            c.drain()
        deadline = time.time() + 5
        while time.time() < deadline:
            if "3" in health_mod.collector().workers():
                break
            time.sleep(0.05)
        assert "3" in health_mod.collector().workers()
    finally:
        ps.stop()


def test_native_plain_client_bytes_identical_vs_python_hub():
    """THE wire-compat pin (ISSUE 11): an un-upgraded client's byte
    stream against a native sparse+adaptive hub is identical to its
    stream against the Python hub, and contains no S/V/U/X frame."""
    from distkeras_tpu.runtime import networking as net
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    t = _sparse_weights()

    def session_bytes(port):
        with PSClient("127.0.0.1", port, templates=t) as c:
            class _Rec:
                def __init__(self, sock):
                    self._sock = sock
                    self.tx = bytearray()

                def sendall(self, data):
                    self.tx += bytes(data)
                    return self._sock.sendall(data)

                def __getattr__(self, name):
                    return getattr(self._sock, name)

            rec = _Rec(c.sock)
            c.sock = rec
            c.pull()
            c.commit([np.full_like(a, 0.5) for a in t])
            c.pull()
            c.drain()
        return bytes(rec.tx)

    python_hub = DeltaParameterServer(t, idle_timeout=None)
    python_hub.start()
    native_hub = _native(mode=MODE_DELTA, sparse_leaves=[0], adaptive=True)
    native_hub.start()
    try:
        base = session_bytes(python_hub.port)
        against_native = session_bytes(native_hub.port)
    finally:
        python_hub.stop()
        native_hub.stop()
    assert base == against_native
    i = 0
    while i < len(base):
        n = int.from_bytes(base[i:i + 8], "big")
        assert base[i + 8:i + 9] not in (net.ACTION_SPARSE_PULL,
                                         net.ACTION_SPARSE_WEIGHTS,
                                         net.ACTION_SPARSE_COMMIT,
                                         net.ACTION_SPARSE_QCOMMIT)
        i += 8 + n


# -- replication (native primary / native standby) -----------------------------

def _feed_pair(primary_native, standby_native):
    from distkeras_tpu.runtime.parameter_server import DeltaParameterServer

    t = _sparse_weights()
    if primary_native:
        prim = _native(mode=MODE_DELTA)
    else:
        prim = DeltaParameterServer(t, idle_timeout=None)
    prim.start()
    if standby_native:
        stand = _native(mode=MODE_DELTA,
                        replica_of=("127.0.0.1", prim.port))
    else:
        stand = DeltaParameterServer(t, idle_timeout=None,
                                     replica_of=("127.0.0.1", prim.port))
    stand.start()
    return prim, stand


@pytest.mark.parametrize("primary_native,standby_native", [
    (True, False),
    (False, True),
    (True, True),
])
def test_native_replication_centers_track(primary_native, standby_native):
    """Hub implementations mix freely across the R feed: the standby's
    center tracks the primary bit for bit after each acked commit."""
    import time

    prim, stand = _feed_pair(primary_native, standby_native)
    t = _sparse_weights()
    try:
        assert stand.wait_synced(timeout=10)
        rng = np.random.default_rng(0)
        with PSClient("127.0.0.1", prim.port, templates=t) as c:
            for _ in range(4):
                c.pull()
                c.commit([rng.normal(size=(6, 3)).astype(np.float32),
                          rng.normal(size=(4,)).astype(np.float32)])
        deadline = time.time() + 10
        while time.time() < deadline:
            if stand.num_updates >= 4:
                break
            time.sleep(0.05)
        for a, b in zip(prim.get_weights(), stand.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        stand.stop()
        prim.stop()


def test_native_standby_promotes_on_primary_death():
    """A native standby whose primary dies promotes itself behind the
    clock fence within its retry budget, then serves commits."""
    import time

    prim, stand = _feed_pair(primary_native=False, standby_native=True)
    t = _sparse_weights()
    try:
        assert stand.wait_synced(timeout=10)
        with PSClient("127.0.0.1", prim.port, templates=t) as c:
            c.pull()
            c.commit([np.ones((6, 3), np.float32), np.ones((4,), np.float32)])
        time.sleep(0.3)
        prim.kill()
        deadline = time.time() + 20
        while time.time() < deadline and not stand.promoted:
            time.sleep(0.1)
        assert stand.promoted
        assert not stand.is_standby()
        assert stand.promoted_at_clock is not None
        # promoted standby serves commits like any hub
        with PSClient("127.0.0.1", stand.port, templates=t) as c:
            c.pull()
            c.commit([np.ones((6, 3), np.float32), np.ones((4,), np.float32)])
        np.testing.assert_allclose(stand.get_weights()[1], np.full((4,), 2.0))
    finally:
        stand.stop()
        prim.stop()


def test_native_never_synced_standby_refuses_traffic():
    """Pulls and commits against a native standby that has never synced
    drop the connection (no job state to serve or take over) — and the
    inproc pair raises the Python hub's errors."""
    # primary address that never answers: a bound-but-unserved port
    import socket as socket_mod

    placeholder = socket_mod.socket()
    placeholder.bind(("127.0.0.1", 0))
    dead_port = placeholder.getsockname()[1]
    placeholder.close()
    stand = _native(mode=MODE_DELTA, replica_of=("127.0.0.1", dead_port))
    stand.start()
    t = _sparse_weights()
    try:
        with pytest.raises((ConnectionError, ValueError, OSError)):
            with PSClient("127.0.0.1", stand.port, templates=t) as c:
                c.pull()
        with pytest.raises(RuntimeError, match="never-synced"):
            stand.pull_direct()
        with pytest.raises(RuntimeError, match="never-synced"):
            stand.commit_direct([np.zeros((6, 3), np.float32),
                                 np.zeros((4,), np.float32)], 0)
    finally:
        stand.stop()


# -- zero-copy transport (ISSUE 18) --------------------------------------------

def test_native_shm_attach_center_matches_tcp(tmp_path):
    """A shm=True PSClient negotiates rings with the C++ hub ('Z' arm,
    dk_ps_shm_attach) and the resulting center is identical to the same
    session over plain TCP; ring files are unlinked after the attach."""
    import os

    results = {}
    for shm in (False, True):
        ps = NativeParameterServer(_weights(), mode=MODE_DELTA,
                                   shm_dir=str(tmp_path))
        ps.start()
        try:
            with PSClient("127.0.0.1", ps.port, templates=_weights(),
                          shm=shm) as c:
                assert c.transport == ("shm" if shm else "tcp")
                c.pull()
                for _ in range(3):
                    c.commit([np.full((2, 2), 0.25, np.float32),
                              np.full((3,), 0.5, np.float32)])
                results[shm] = [w.copy() for w in c.pull()]
            assert ps.num_updates == 3
        finally:
            ps.stop()
    for x, y in zip(results[False], results[True]):
        np.testing.assert_array_equal(x, y)
    assert [f for f in os.listdir(str(tmp_path))
            if f.startswith("ring-")] == []


def test_cross_language_ring_byte_identical(tmp_path):
    """THE cross-language ring pin: bytes written by the C++ ring
    implementation read back identically through the Python one and vice
    versa, including EOF propagation — the two layouts are one layout."""
    import ctypes

    from distkeras_tpu.runtime import native as native_mod
    from distkeras_tpu.runtime import networking as net

    lib = native_mod._load()
    payload = bytes(range(256)) * 5  # 1280 B: wraps a 4 KiB ring

    # C++ producer -> Python consumer
    cpp_path = str(tmp_path / "cpp-ring").encode("utf-8")
    handle = lib.dk_shm_ring_create(cpp_path, 1, 4096)
    assert handle
    py_cons = net.ShmFrameRing.open(cpp_path.decode("utf-8"), "consumer")
    got = bytearray()
    buf = bytearray(512)
    for _ in range(4):
        assert lib.dk_shm_ring_write(handle, payload, len(payload),
                                     2000) == len(payload)
        want = len(got) + len(payload)
        while len(got) < want:
            n = py_cons.read_into(memoryview(buf), timeout=2.0)
            assert n > 0
            got += buf[:n]
    assert bytes(got) == payload * 4
    lib.dk_shm_ring_close(handle)  # producer EOF
    assert py_cons.read_into(memoryview(buf), timeout=2.0) == 0
    lib.dk_shm_ring_destroy(handle)
    py_cons.close()

    # Python producer -> C++ consumer
    py_path = str(tmp_path / "py-ring")
    py_prod = net.ShmFrameRing.create(py_path, "producer", capacity=4096)
    chandle = lib.dk_shm_ring_open(py_path.encode("utf-8"), 0)
    assert chandle
    writer = threading.Thread(
        target=lambda: [py_prod.write(payload, timeout=2.0)
                        for _ in range(4)] and None)
    writer.start()
    got2 = bytearray()
    cbuf = ctypes.create_string_buffer(512)
    while len(got2) < 4 * len(payload):
        n = lib.dk_shm_ring_read(chandle, cbuf, 512, 2000)
        assert n > 0
        got2 += cbuf.raw[:n]
    writer.join()
    assert bytes(got2) == payload * 4
    py_prod.close()  # EOF crosses the language boundary too
    assert lib.dk_shm_ring_read(chandle, cbuf, 512, 2000) == 0
    lib.dk_shm_ring_destroy(chandle)


# -- guidance + hygiene --------------------------------------------------------

def test_sparse_direct_pair_served_by_native_hub():
    """The FORMER last NotImplementedError combination (sparse +
    inproc + native) is served since ISSUE 15: the C++ hub's
    dk_ps_pull_sparse/dk_ps_commit_sparse round-trip row values with the
    Python hub's exact semantics, and the old guidance raises are gone."""
    ps = _native(mode=MODE_DELTA, sparse_leaves=[0])
    ps.start()
    try:
        ids = np.array([1, 4], np.int64)
        values, clock = ps.pull_sparse_direct([ids])
        assert values[0].shape == (2, 3)
        assert values[1].shape == (4,)
        grads = np.full((2, 3), 0.5, np.float32)
        ps.commit_sparse_direct([(ids, grads), np.zeros(4, np.float32)],
                                clock)
        v2, c2 = ps.pull_sparse_direct([ids])
        assert c2 == clock + 1
        np.testing.assert_array_equal(v2[0], values[0] + grads)
        # validation parity with the Python hub: bad ids are a loud
        # ValueError on BOTH directions, never a silent skip
        with pytest.raises(ValueError):
            ps.pull_sparse_direct([np.array([4, 1], np.int64)])
        with pytest.raises(ValueError):
            ps.commit_sparse_direct(
                [(np.array([99], np.int64), np.zeros((1, 3), np.float32)),
                 np.zeros(4, np.float32)], c2)
    finally:
        ps.stop()


def test_trainer_accepts_every_native_sparse_cell(toy_dataset):
    """The five Async* trainers accept EVERY native feature combination
    — the sparse+inproc guard is gone (ISSUE 15)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.base import Model, ModelSpec

    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (8,), "num_outputs": 2},
                     input_shape=(8,))
    # allowed: adaptive, health reporting, sparse over sockets, replica_of
    dk.AsyncADAG(Model.init(spec, seed=0), loss="categorical_crossentropy",
                 native_ps=True, adaptive=True, health_interval_s=1.0,
                 sparse_tables=(0,))
    dk.AsyncADAG(Model.init(spec, seed=0),
                 loss="categorical_crossentropy", native_ps=True,
                 transport="inproc", sparse_tables=(0,))


def test_native_build_is_warning_clean():
    """Build hygiene (ISSUE 11 satellite): the growing C++ surface must
    compile with -Wall -Wextra -Werror — a warning is a failed test, not
    line noise."""
    import os
    import subprocess
    import tempfile

    from conftest import require_tool

    require_tool("g++")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from distkeras_tpu.runtime.native import BUILD_FLAGS

    with tempfile.TemporaryDirectory() as td:
        for src in ("ps_server.cpp", "data_loader.cpp"):
            proc = subprocess.run(
                ["g++"] + BUILD_FLAGS + ["-Wall", "-Wextra", "-Werror",
                 os.path.join(root, "native", src),
                 "-o", os.path.join(td, src + ".so")],
                capture_output=True, text=True, timeout=300)
            assert proc.returncode == 0, f"{src}:\n{proc.stderr}"
