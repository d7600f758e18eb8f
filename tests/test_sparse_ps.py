"""Row-sparse embedding parameter service (issue 9): sparse wire framing
(actions S/V/U/X), hub row apply under the staleness clock, row-range
sharding, client caches + int8 dense-residual fallback, trainer threading,
wire-compat matrix (recording sockets), and sparse-vs-dense bit-parity."""

import time

import numpy as np
import pytest

from conftest import native_mark
from distkeras_tpu import observability as obs
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    InprocPSClient,
    PSClient,
    ShardedParameterServer,
    ShardedPSClient,
    shard_plan,
)


def _weights():
    return [np.arange(32, dtype=np.float32).reshape(8, 4),
            np.zeros((3,), np.float32)]


def _start(hub_cls=DeltaParameterServer, sparse=(0,), **kw):
    ps = hub_cls(_weights(), idle_timeout=None, sparse_leaves=sparse, **kw)
    ps.start()
    return ps


# -- wire framing --------------------------------------------------------------

def test_var_frame_encoder_bytes_identical_to_generic():
    enc = net.VarFrameEncoder(initial=8)  # force at least one grow
    for arrays in ([np.arange(5, dtype=np.int64)],
                   [np.zeros(0, np.int64), np.ones((3, 4), np.float32)],
                   [np.frombuffer(b"xy", np.uint8)]):
        frame = bytes(enc.pack(net.ACTION_SPARSE_COMMIT, arrays))
        generic = net.encode_tensors(net.ACTION_SPARSE_COMMIT, arrays)
        assert frame[8:] == generic
        assert frame[:8] == len(generic).to_bytes(8, "big")
        assert enc.frame_len == len(frame)
        action, blobs = net.decode_tensor_views(memoryview(frame)[8:])
        assert action == net.ACTION_SPARSE_COMMIT
        assert len(blobs) == len(arrays)


def test_normalize_row_ids():
    out = net.normalize_row_ids([3, 1, 3, 0], rows=8)
    np.testing.assert_array_equal(out, [0, 1, 3])
    assert out.dtype == np.int64
    assert net.normalize_row_ids([], rows=8).size == 0
    with pytest.raises(ValueError):
        net.normalize_row_ids([8], rows=8)
    with pytest.raises(ValueError):
        net.normalize_row_ids([-1], rows=8)


# -- hub validation ------------------------------------------------------------

def test_hub_rejects_bad_sparse_config():
    with pytest.raises(ValueError):
        DeltaParameterServer(_weights(), sparse_leaves=[5])
    with pytest.raises(ValueError):
        DeltaParameterServer(_weights(), sparse_leaves=[1])  # not 2-D


def test_sparse_actions_against_dense_hub_drop_connection():
    ps = DeltaParameterServer(_weights(), idle_timeout=None)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            with pytest.raises((ConnectionError, ValueError, OSError)):
                c.pull_nowait(sparse_rows=[np.array([0, 1])])
                c.wait_weights()
    finally:
        ps.stop()


def test_malformed_row_ids_drop_connection_hub_survives():
    """Unsorted / duplicate / out-of-range ids desync that connection
    (ProtocolError path) but the hub keeps serving other clients."""
    ps = _start()
    try:
        raw = net.connect("127.0.0.1", ps.port)
        try:
            net.send_tensors(raw, net.ACTION_SPARSE_PULL,
                             [np.array([3, 1], np.int64)])  # unsorted
            with pytest.raises((ConnectionError, OSError)):
                got = net.recv_frame(raw, limit=1 << 20)
                if not got:
                    raise ConnectionError("closed")
        finally:
            raw.close()
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            c.pull_nowait(sparse_rows=[np.array([0])])
            assert c.wait_weights()[1].shape == (3,)
    finally:
        ps.stop()


# -- hub apply under the staleness clock ---------------------------------------

def test_sparse_commit_applies_commit_scale():
    """An ADAG hub scales sparse row grads exactly like dense commits
    (delta / num_workers), touching ONLY the committed rows, and the
    clock/staleness bookkeeping advances once per sparse commit."""
    ps = _start(ADAGParameterServer, num_workers=4)
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()
            d = [np.zeros((8, 4), np.float32), np.ones((3,), np.float32)]
            d[0][2] = 8.0
            c.commit(d, sparse_rows=[np.array([2, 5])])
        got = ps.get_weights()
        base = _weights()
        np.testing.assert_allclose(got[0][2], base[0][2] + 2.0)  # 8/4
        np.testing.assert_allclose(got[0][5], base[0][5])  # zero grad row
        np.testing.assert_allclose(got[0][0], base[0][0])  # untouched
        np.testing.assert_allclose(got[1], 0.25)
        assert ps.num_updates == 1 and ps._clock == 1
    finally:
        ps.stop()


def test_sparse_commit_respects_clock_fence():
    """A sparse commit carrying a pre-restore pull clock is fenced exactly
    like a dense one (staleness re-based at the restore point)."""
    ps = _start(hub_cls=DeltaParameterServer)
    try:
        ids = [np.array([0])]
        values, clock = ps.pull_sparse_direct(ids)
        ps.restore_state([w + 1 for w in _weights()], {"clock": 50})
        grads = np.ones((1, 4), np.float32)
        ps.commit_sparse_direct(
            [(ids[0], grads), np.zeros(3, np.float32)], clock)
        # fence clamps: staleness 0, applied once
        assert ps._clock == 51
        np.testing.assert_allclose(ps.get_weights()[0][0],
                                   _weights()[0][0] + 2.0)
    finally:
        ps.stop()


def test_sparse_replication_feeds_row_deltas():
    """A replicated primary materializes the applied row delta into the
    existing center-shaped R feed: the standby's center tracks sparse
    commits bit for bit."""
    primary = _start()
    replica = DeltaParameterServer(
        _weights(), idle_timeout=None, sparse_leaves=[0],
        replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        assert replica.wait_synced(timeout=10)
        with PSClient("127.0.0.1", primary.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()
            d = [np.zeros((8, 4), np.float32), np.ones((3,), np.float32)]
            d[0][1] = 3.0
            c.commit(d, sparse_rows=[np.array([1, 6])])
        import time
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and replica._clock < 1:
            time.sleep(0.01)
        for a, b in zip(primary.get_weights(), replica.get_weights()):
            np.testing.assert_array_equal(a, b)
    finally:
        replica.stop()
        primary.stop()


# -- row-range shard plan ------------------------------------------------------

def test_shard_plan_sparse_row_ranges_partition_rows():
    t = [np.zeros((10, 4), np.float32), np.zeros((64,), np.float32),
         np.zeros((3, 3), np.float32)]
    plan = shard_plan(t, 3, sparse_leaves=[0])
    ranges = plan.sparse_ranges[0]
    assert len(ranges) == 3
    assert ranges[0][0] == 0 and ranges[-1][1] == 10
    for (a, b), (c, _) in zip(ranges, ranges[1:]):
        assert b == c and b > a
    # every shard lists the sparse leaf; dense leaves appear exactly once
    for sid in range(3):
        assert 0 in plan.assignments[sid]
        assert plan.local_sparse(sid) == (plan.assignments[sid].index(0),)
    dense_counts = [sum(1 for idxs in plan.assignments for i in idxs
                        if i == leaf) for leaf in (1, 2)]
    assert dense_counts == [1, 1]
    assert plan.num_leaves == 3


def test_shard_plan_sparse_split_assemble_roundtrip():
    t = [np.arange(40, dtype=np.float32).reshape(10, 4),
         np.arange(5, dtype=np.float32)]
    plan = shard_plan(t, 2, sparse_leaves=[0])
    parts = plan.split(t)
    # split returns row-range views, zero copy
    assert parts[0][0].base is t[0] or parts[0][0].base is t[0].base
    back = plan.assemble(parts)
    np.testing.assert_array_equal(back[0], t[0])
    np.testing.assert_array_equal(back[1], t[1])
    # sparse_fill substitutes the full array without concatenating
    full = np.zeros((10, 4), np.float32)
    filled = plan.assemble(parts, sparse_fill={0: full})
    assert filled[0] is full


def test_shard_plan_sparse_validation():
    t = [np.zeros((3, 4), np.float32), np.zeros((5,), np.float32)]
    with pytest.raises(ValueError):
        shard_plan(t, 4, sparse_leaves=[0])  # 3 rows < 4 shards
    with pytest.raises(ValueError):
        shard_plan(t, 2, sparse_leaves=[1])  # not 2-D
    # dense behavior unchanged: a sparse-free plan is the PR-6 plan
    plan = shard_plan(t, 2)
    assert plan.sparse_ranges == {}
    assert plan.num_leaves == 2


def test_shard_plan_dense_unchanged_by_sparse_arg_default():
    t = [np.zeros((4, 4), np.float32), np.zeros((6,), np.float32),
         np.zeros((3,), np.float32)]
    a = shard_plan(t, 2)
    b = shard_plan(t, 2, sparse_leaves=())
    assert a.assignments == b.assignments
    assert a.shard_bytes == b.shard_bytes


# -- wire compatibility (recording-socket matrix) ------------------------------

class _RecordingSock:
    def __init__(self, sock):
        self._sock = sock
        self.tx = bytearray()

    def sendall(self, data):
        self.tx += bytes(data)
        return self._sock.sendall(data)

    def __getattr__(self, name):
        return getattr(self._sock, name)


_SPARSE_ACTIONS = (net.ACTION_SPARSE_PULL, net.ACTION_SPARSE_WEIGHTS,
                   net.ACTION_SPARSE_COMMIT, net.ACTION_SPARSE_QCOMMIT)


def _assert_no_sparse_frames(stream: bytes) -> None:
    i = 0
    while i < len(stream):
        n = int.from_bytes(stream[i:i + 8], "big")
        assert stream[i + 8:i + 9] not in _SPARSE_ACTIONS
        i += 8 + n


def _plain_session_bytes(port, templates):
    with PSClient("127.0.0.1", port, templates=templates) as c:
        rec = _RecordingSock(c.sock)
        c.sock = rec
        c.pull()
        c.commit([np.full_like(t, 0.5) for t in templates])
        c.pull()
        c.drain()
    return bytes(rec.tx)


def test_plain_client_bytes_identical_against_sparse_capable_hub():
    """The zero-sparse-tables pin: an un-upgraded client's byte stream is
    identical whether the hub has sparse tables registered or not, and
    never contains an S/V/U/X frame."""
    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    plain.start()
    sparse = DeltaParameterServer(t, port=0, idle_timeout=None,
                                  sparse_leaves=[0])
    sparse.start()
    try:
        baseline = _plain_session_bytes(plain.port, t)
        against_sparse = _plain_session_bytes(sparse.port, t)
    finally:
        plain.stop()
        sparse.stop()
    assert baseline == against_sparse
    _assert_no_sparse_frames(baseline)


def test_plain_striped_client_bytes_identical_on_sparse_capable_shards():
    """The sharded cell: per-stripe byte streams of a dense striped
    session are identical whether or not the shard hubs have their sparse
    row ranges registered (same row-range plan both sides)."""
    t = [np.arange(40, dtype=np.float32).reshape(10, 4),
         np.zeros((6,), np.float32), np.zeros((3,), np.float32)]
    plan = shard_plan(t, 2, sparse_leaves=[0])

    def make(with_sparse):
        ps = ShardedParameterServer(
            t, plan, lambda w, sid: DeltaParameterServer(
                w, shard_id=sid, idle_timeout=None,
                sparse_leaves=(plan.local_sparse(sid)
                               if with_sparse else ())))
        ps.start()
        return ps

    def session(ps):
        with ShardedPSClient([("127.0.0.1", p) for p in ps.ports],
                             t, plan) as c:
            recs = []
            for sc in c.shards:
                rec = _RecordingSock(sc.sock)
                sc.sock = rec
                recs.append(rec)
            c.pull()
            c.commit([np.full_like(a, 0.5) for a in t])
            c.pull()
            c.drain()
        return [bytes(r.tx) for r in recs]

    on, off = make(True), make(False)
    try:
        streams_on = session(on)
        streams_off = session(off)
    finally:
        on.stop()
        off.stop()
    assert streams_on == streams_off
    for s in streams_on:
        _assert_no_sparse_frames(s)


def test_plain_client_bytes_identical_on_replicated_sparse_hub():
    """The replicated cell: a sparse-capable primary streaming to a hot
    standby serves an un-upgraded client the same byte conversation as a
    plain unreplicated hub."""
    t = _weights()
    plain = DeltaParameterServer(t, port=0, idle_timeout=None)
    plain.start()
    primary = DeltaParameterServer(t, port=0, idle_timeout=None,
                                   sparse_leaves=[0])
    primary.start()
    replica = DeltaParameterServer(t, port=0, idle_timeout=None,
                                   sparse_leaves=[0],
                                   replica_of=("127.0.0.1", primary.port))
    replica.start()
    try:
        assert replica.wait_synced(timeout=10)
        baseline = _plain_session_bytes(plain.port, t)
        against = _plain_session_bytes(primary.port, t)
    finally:
        replica.stop()
        primary.stop()
        plain.stop()
    assert baseline == against
    _assert_no_sparse_frames(against)


# -- client behavior -----------------------------------------------------------

def test_sparse_pull_merges_into_cache_and_full_pull_reseeds():
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as writer:
            writer.pull()
            d = [np.zeros((8, 4), np.float32), np.zeros((3,), np.float32)]
            d[0][4] = 1.0
            writer.commit(d, sparse_rows=[np.array([4])])
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()  # full pull seeds cache with the hub's center
            c.pull_nowait(sparse_rows=[np.array([0])])
            w = c.wait_weights()
            # row 4 came from the FULL pull; row 0 from the sparse merge
            np.testing.assert_allclose(w[0][4], _weights()[0][4] + 1.0)
            assert w[0] is c._cache[0]
    finally:
        ps.stop()


@pytest.mark.parametrize("claim", ["wait_weights", "land_weights"])
def test_sparse_pull_reissued_after_reconnect(claim):
    """A severed reply mid-sparse-pull reconnects and re-asks for the SAME
    rows (the _sparse_pull_ids FIFO survives the reconnect) — whichever
    call was receiving it."""
    from distkeras_tpu.runtime.faults import ChaosProxy, Fault, FaultPlan

    ps = _start()
    plan = FaultPlan([Fault(conn=0, direction="s2c", frame=1,
                            kind="sever")])
    try:
        with ChaosProxy("127.0.0.1", ps.port, plan) as proxy:
            with PSClient("127.0.0.1", proxy.port, templates=_weights(),
                          sparse_leaves=[0], max_reconnects=5,
                          reconnect_backoff=0.02) as c:
                c.pull()  # frame 0 reply: full weights (survives)
                c.pull_nowait(sparse_rows=[np.array([1, 2])])
                if claim == "land_weights":
                    c.land_weights()  # frame 1 reply severed -> re-pulled
                    assert c.reconnects_used == 1 and len(c._ready) == 1
                w = c.wait_weights()  # ... or here
                np.testing.assert_allclose(w[0][1], _weights()[0][1])
                assert c.reconnects_used == 1
                assert not c._sparse_pull_ids
    finally:
        ps.stop()


def test_int8_sparse_commit_error_feedback_converges():
    """Dense-residual fallback: repeated int8 sparse commits of the same
    delta track the true sum (error feedback over touched rows)."""
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0], compress="int8") as c:
            c.pull()
            d = [np.zeros((8, 4), np.float32), np.zeros((3,), np.float32)]
            d[0][3] = np.array([0.3, -0.7, 1.1, 0.01], np.float32)
            for _ in range(50):
                c.commit(d, sparse_rows=[np.array([3])])
        got = ps.get_weights()[0][3] - _weights()[0][3]
        np.testing.assert_allclose(got, 50 * d[0][3], rtol=0.02, atol=0.02)
    finally:
        ps.stop()


def test_inproc_sparse_matches_socket_trajectory():
    """Transport parity, extended to sparse: a deterministic single-worker
    schedule of partial-touch pulls/commits lands the identical center on
    both transports (incl. int8)."""
    for compress in (None, "int8"):
        results = []
        for transport in ("socket", "inproc"):
            ps = _start()
            try:
                if transport == "socket":
                    client = PSClient("127.0.0.1", ps.port,
                                      templates=_weights(),
                                      sparse_leaves=[0], compress=compress)
                else:
                    client = InprocPSClient(ps, templates=_weights(),
                                            sparse_leaves=[0],
                                            compress=compress)
                with client as c:
                    c.pull()
                    rng = np.random.default_rng(0)
                    for step in range(5):
                        ids = np.unique(rng.integers(0, 8, size=4))
                        c.pull_nowait(sparse_rows=[ids])
                        w = c.wait_weights()
                        d = [np.zeros((8, 4), np.float32),
                             np.full((3,), 0.1, np.float32)]
                        d[0][ids] = rng.normal(size=(ids.size, 4)) \
                            .astype(np.float32)
                        c.commit(d, sparse_rows=[ids])
                results.append([w.copy() for w in ps.get_weights()])
            finally:
                ps.stop()
        for a, b in zip(*results):
            np.testing.assert_array_equal(a, b)


def test_pipelined_sparse_commit_drains_pending_sparse_pull_first():
    """Review pin: the deadlock-avoidance drain before a large commit send
    claims pending SPARSE weights replies too (the dense rule — never
    start a big send while a reply may be in flight — applies to V
    frames, which carry the dense leaves whole)."""
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0]) as c:
            c.pull()
            ids = np.array([0, 1])
            c.pull_nowait(sparse_rows=[ids])
            d = [np.zeros((8, 4), np.float32), np.ones((3,), np.float32)]
            c.commit_nowait(d, sparse_rows=[ids])
            # the sparse reply was consumed into _ready BEFORE the commit
            # bytes left; only the commit ack remains pending
            assert not c._has_pending(net.ACTION_SPARSE_WEIGHTS)
            assert len(c._ready) == 1
            w = c.wait_weights()
            assert w[0] is c._cache[0]
            c.drain()
    finally:
        ps.stop()


@pytest.mark.parametrize("cache_rows", [None, 4])
def test_landed_sparse_pull_leaves_the_handed_out_tables_alone(cache_rows,
                                                               telemetry):
    """A sparse reply landed early (``land_weights``, beside the window
    program) must not write into what the previous ``wait_weights()`` handed
    out: in full-cache mode that is the cache table itself, which the
    running program may be reading, so the reply's rows join the cache only
    when the pull is handed out; the hot tier hands out per-pull blocks.
    Either way the commit's guard finds nothing left and ``wait_weights()``
    needs no socket."""
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0],
                      sparse_cache_rows=cache_rows) as c, \
                PSClient("127.0.0.1", ps.port, templates=_weights(),
                         sparse_leaves=[0]) as writer:
            c.pull()
            first = np.array([0, 1], np.int64)
            c.pull_nowait(sparse_rows=[first])
            held = c.wait_weights()
            writer.pull()
            d = [np.zeros((8, 4), np.float32), np.ones((3,), np.float32)]
            d[0][[1, 5]] = 1.0
            writer.commit(d, sparse_rows=[np.array([1, 5])])
            snapshot = [w.copy() for w in held]

            nxt_ids = np.array([1, 5], np.int64)
            c.pull_nowait(sparse_rows=[nxt_ids])
            c.land_weights()
            assert len(c._ready) == 1
            assert not c._has_pending(net.ACTION_SPARSE_WEIGHTS)
            for w, snap in zip(held, snapshot):
                np.testing.assert_array_equal(w, snap)
            assert obs.snapshot()["counters"][
                "ps_pulls_landed_early_total"] == 1.0
            stalls = obs.snapshot()["histograms"]["ps.pull_stall_ms"]["count"]
            c.commit_nowait([np.zeros((8, 4), np.float32),
                             np.zeros((3,), np.float32)],
                            sparse_rows=[first])
            assert obs.snapshot()["histograms"]["ps.pull_stall_ms"][
                "count"] == stalls

            sock = c.sock
            c.sock = None           # wait_weights must not need it
            try:
                nxt = c.wait_weights()
            finally:
                c.sock = sock
            center = ps.get_weights()
            np.testing.assert_array_equal(nxt[1], np.ones(3))
            if cache_rows is None:
                assert nxt[0] is c._cache[0] is held[0]
                np.testing.assert_array_equal(nxt[0][nxt_ids],
                                              center[0][nxt_ids])
            else:
                # row 1 was resident (a hit, resolved at issue from the
                # LRU), row 5 a miss filed when the reply landed
                np.testing.assert_array_equal(nxt[0][0], snapshot[0][1])
                np.testing.assert_array_equal(nxt[0][1], center[0][5])
            np.testing.assert_array_equal(held[1], snapshot[1])
            c.drain()
    finally:
        ps.stop()


def test_pull_sparse_direct_rejects_wrong_id_array_count():
    """Review pin: too many id arrays is an error, not a silent
    truncation (the zip would otherwise drop the extras)."""
    ps = _start()
    try:
        with pytest.raises(ValueError, match="id arrays"):
            ps.pull_sparse_direct([np.array([0]), np.array([1])])
    finally:
        ps.stop()


def test_mismatched_sparse_table_row_counts_refused_at_setup():
    """Review pin: explicitly-named sparse tables with unequal row counts
    are refused at train() setup (the worker sends ONE shared id set per
    window; a mid-run out-of-range id would kill the run instead)."""
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    mlp = ModelSpec(name="mlp", config={"hidden_sizes": (6,),
                                        "num_outputs": 2},
                    input_shape=(4,))
    model = Model.init(mlp, seed=0)
    import jax

    kernels = tuple(i for i, leaf in enumerate(jax.tree.leaves(model.params))
                    if np.asarray(leaf).ndim == 2)
    assert len(kernels) == 2  # (4,6) and (6,2) kernels: unequal rows
    tr = AsyncADAG(model, sparse_tables=kernels,
                   loss="categorical_crossentropy")
    rng = np.random.default_rng(0)
    ds = Dataset({
        "features": rng.normal(size=(16, 4)).astype(np.float32),
        "label": np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)],
    })
    with pytest.raises(ValueError, match="mismatched row counts"):
        tr.train(ds, shuffle=False)


# -- trainer e2e ---------------------------------------------------------------

def _full_touch_dataset(rows, fields, batch, window, n_windows):
    """Every window's batches cover ALL row ids — the full-touch shape the
    bit-parity pin needs."""
    from distkeras_tpu.data.dataset import Dataset

    n = batch * window * n_windows
    total = n * fields
    reps = -(-total // rows)
    ids = np.tile(np.arange(rows, dtype=np.int32), reps)[:total]
    labels = np.eye(2, dtype=np.float32)[
        np.arange(n) % 2]
    return Dataset({"features": ids.reshape(n, fields), "label": labels})


def _ctr_trainer(spec, sparse, **kw):
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    defaults = dict(loss="categorical_crossentropy", batch_size=4,
                    num_epoch=2, learning_rate=0.05, seed=0, num_workers=1,
                    communication_window=2,
                    sparse_tables="auto" if sparse else None)
    defaults.update(kw)
    return AsyncADAG(Model.init(spec, seed=0), **defaults)


# hub dimension (ISSUE 11): the C++ hub serves the sparse wire plane, so
# THE acceptance pin runs against both implementations
@pytest.mark.parametrize("compress,pipeline,epochs,hub", [
    (None, True, 1, "python"),
    (None, False, 2, "python"),
    ("int8", True, 1, "python"),
    ("int8", False, 2, "python"),
    pytest.param(None, True, 1, "native", marks=native_mark()),
    pytest.param("int8", True, 1, "native", marks=native_mark()),
    pytest.param(None, False, 2, "native", marks=native_mark()),
    pytest.param("int8", False, 2, "native", marks=native_mark()),
])
def test_sparse_vs_dense_full_touch_bit_parity(compress, pipeline, epochs,
                                               hub):
    """THE acceptance pin: a 1-worker run whose every window touches every
    row lands bit-identical final weights sparse vs dense (full-touch row
    gathers carry exactly the dense payload; the hub applies the same
    scaled adds; for int8 the full-row block quantizes with the same
    per-leaf scale the dense path uses).

    Pipelined parity is pinned within one epoch: across an epoch boundary
    the sparse exchange deliberately skips the cross-epoch prefetch (the
    next epoch's reshuffled row ids don't exist yet), so its boundary
    pull observes one commit more than the dense prefetch does — the
    serial exchange (pipeline=False) has no prefetch and stays
    bit-identical across any number of epochs."""
    import jax

    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec(8, dim=4, fields=2, hidden_sizes=(4,))
    ds = _full_touch_dataset(8, 2, batch=4, window=2, n_windows=2)
    finals = []
    for sparse in (True, False):
        tr = _ctr_trainer(spec, sparse, compress_commits=compress,
                          pipeline=pipeline, num_epoch=epochs,
                          native_ps=(hub == "native"))
        model = tr.train(ds, shuffle=False)
        finals.append(jax.tree.leaves(model.params))
    for a, b in zip(*finals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sparse_sharded_matches_unsharded_bit_parity():
    """Row-range striping parity: 1-shard and 3-shard sparse runs land the
    identical final center (disjoint row ranges -> per-commit adds apply
    to the same elements in the same order)."""
    import jax

    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec(9, dim=4, fields=2, hidden_sizes=(4,))
    ds = _full_touch_dataset(9, 2, batch=4, window=2, n_windows=2)
    finals = []
    for shards in (1, 3):
        tr = _ctr_trainer(spec, sparse=True, num_shards=shards)
        model = tr.train(ds, shuffle=False)
        finals.append(jax.tree.leaves(model.params))
    for a, b in zip(*finals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sparse_inproc_trainer_matches_socket():
    import jax

    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec(8, dim=4, fields=2, hidden_sizes=(4,))
    ds = _full_touch_dataset(8, 2, batch=4, window=2, n_windows=2)
    finals = []
    for transport in ("socket", "inproc"):
        tr = _ctr_trainer(spec, sparse=True, transport=transport)
        model = tr.train(ds, shuffle=False)
        finals.append(jax.tree.leaves(model.params))
    for a, b in zip(*finals):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sparse_trainer_partial_touch_trains_and_counts_rows():
    """A skewed CTR run (partial touch) trains to a finite loss while the
    hub's sparse telemetry counts rows and wire bytes saved."""
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec(64, dim=4, fields=2, hidden_sizes=(4,))
    ds = synthetic_ctr_dataset(64, 64, fields=2, seed=0)
    obs.enable()
    obs.reset()
    try:
        tr = _ctr_trainer(spec, sparse=True, num_workers=2, batch_size=4)
        tr.train(ds, shuffle=False)
        assert tr.history and np.isfinite(tr.history[-1])
        snap = obs.snapshot()
        assert snap["counters"].get("ps.sparse_rows_pulled", 0) > 0
        assert snap["counters"].get("ps.sparse_rows_committed", 0) > 0
        assert snap["counters"].get("ps.sparse_wire_bytes_saved", 0) > 0
        # fleet_report surfaces the row traffic from the commit/pull spans
        from distkeras_tpu.observability.distributed import fleet_report

        report = fleet_report(events=obs.TRACER.events())
        assert report["sparse"]["rows_committed"] > 0
        assert report["sparse"]["rows_pulled"] > 0
    finally:
        obs.reset()
        obs.disable()


def test_sparse_sharded_telemetry_is_shard_labeled():
    t = [np.zeros((10, 4), np.float32), np.zeros((3,), np.float32)]
    plan = shard_plan(t, 2, sparse_leaves=[0])
    obs.enable()
    obs.reset()
    ps = ShardedParameterServer(
        t, plan, lambda w, sid: DeltaParameterServer(
            w, shard_id=sid, idle_timeout=None,
            sparse_leaves=plan.local_sparse(sid)))
    ps.start()
    try:
        addrs = [("127.0.0.1", p) for p in ps.ports]
        with ShardedPSClient(addrs, t, plan, sparse_leaves=[0]) as c:
            c.pull()
            d = [np.ones((10, 4), np.float32), np.ones((3,), np.float32)]
            c.commit(d, sparse_rows=[np.array([1, 8])])  # one id per range
        # the hub acks a commit BEFORE its telemetry tail runs (ack
        # latency beats counter bumps by design), so an immediate
        # snapshot races the handler thread — poll briefly (the exact
        # unguarded-read-after-ack shape ISSUE 14 is about)
        keys = [f'ps.sparse_rows_committed{{shard="{sid}"}}'
                for sid in ("0", "1")]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            snap = obs.snapshot()
            if all(snap["counters"].get(k) == 1.0 for k in keys):
                break
            time.sleep(0.01)
        for key in keys:
            assert snap["counters"].get(key) == 1.0, snap["counters"]
    finally:
        ps.stop()
        obs.reset()
        obs.disable()


def test_sparse_health_reports_carry_row_rate():
    """Workers with health reporting on stream sparse_rows_total; the
    collector series and distkeras-top's ROW/S column see it."""
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.observability import health as health_mod

    spec = ctr_embedding_spec(32, dim=4, fields=2, hidden_sizes=(4,))
    ds = synthetic_ctr_dataset(64, 32, fields=2, seed=0)
    health_mod.reset_default()
    try:
        tr = _ctr_trainer(spec, sparse=True, health_interval_s=0.05,
                          batch_size=4)
        tr.train(ds, shuffle=False)
        snap = health_mod.collector().snapshot()
        worker = snap["workers"]["0"]
        series = worker["metrics"].get("sparse_rows_total")
        assert series is not None and series["last"] > 0
        frame = health_mod.render_top(
            {"fleet": snap, "events": []})
        assert "ROW/S" in frame
    finally:
        health_mod.reset_default()


def test_sparse_knob_validation():
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = ctr_embedding_spec(8, dim=4, fields=2)
    # every transport x hub cell composes with sparse_tables since
    # ISSUE 15 (the C++ hub serves the sparse direct pair too): both
    # native combinations construct cleanly now
    AsyncADAG(Model.init(spec, seed=0), sparse_tables="auto",
              native_ps=True, loss="categorical_crossentropy")
    AsyncADAG(Model.init(spec, seed=0), sparse_tables="auto",
              native_ps=True, transport="inproc",
              loss="categorical_crossentropy")
    with pytest.raises(ValueError, match="inproc"):
        tr = AsyncADAG(Model.init(spec, seed=0), sparse_tables="auto",
                       transport="inproc", num_shards=2,
                       loss="categorical_crossentropy")
        tr.train(_full_touch_dataset(8, 2, 4, 2, 2), shuffle=False)
    mlp = ModelSpec(name="mlp", config={"hidden_sizes": (4,),
                                        "num_outputs": 2},
                    input_shape=(4,))
    with pytest.raises(ValueError, match="declares no sparse"):
        tr = AsyncADAG(Model.init(mlp, seed=0), sparse_tables="auto",
                       loss="categorical_crossentropy")
        from distkeras_tpu.data.dataset import Dataset

        rng = np.random.default_rng(0)
        tr.train(Dataset({
            "features": rng.normal(size=(16, 4)).astype(np.float32),
            "label": np.eye(2, dtype=np.float32)[rng.integers(0, 2, 16)],
        }), shuffle=False)


def test_sparse_leaf_indices_resolution():
    from distkeras_tpu.models.base import Model, sparse_leaf_indices
    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec(8, dim=4, fields=2)
    model = Model.init(spec, seed=0)
    idx = sparse_leaf_indices(spec, model.params)
    assert len(idx) == 1
    import jax

    leaf = jax.tree.leaves(model.params)[idx[0]]
    assert leaf.shape == (8, 4)


def test_launcher_standalone_sparse_hub_worker_only_mode():
    """distkeras-ps-style standalone sparse hub + worker-only trainer:
    both ends derive the same sparse leaf set from the same model."""
    import jax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.launcher import start_parameter_server

    spec = ctr_embedding_spec(8, dim=4, fields=2, hidden_sizes=(4,))
    model = Model.init(spec, seed=0)
    ps = start_parameter_server(model, mode="adag", num_workers=1,
                                host="127.0.0.1", idle_timeout=None,
                                sparse_tables="auto")
    try:
        ds = _full_touch_dataset(8, 2, batch=4, window=2, n_windows=2)
        tr = _ctr_trainer(spec, sparse=True,
                          ps_address=("127.0.0.1", ps.port))
        out = tr.train(ds, shuffle=False)
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(out.params))
        assert ps.num_updates > 0
    finally:
        ps.stop()
