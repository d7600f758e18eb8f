"""Hyperscale embedding tier (issue 15): sparse row-delta replication
(REPL_SPARSE + attach-time capability), per-table vocabularies, the
hot-tier client LRU (sparse_cache_rows), row-touch telemetry, the native
sparse direct pair, and the compat/parity matrix the issue pins."""

import time

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    InprocPSClient,
    PSClient,
    _RowLRU,
    shard_plan,
)


def _weights():
    return [np.arange(40, dtype=np.float32).reshape(10, 4),
            np.zeros((3,), np.float32)]


def _start(hub_cls=DeltaParameterServer, sparse=(0,), **kw):
    ps = hub_cls(_weights(), idle_timeout=None, sparse_leaves=sparse, **kw)
    ps.start()
    return ps


# -- wire: hello capability + REPL_SPARSE framing ------------------------------

def test_repl_hello_capability_byte():
    plain = net.encode_repl_hello(7)
    sparse = net.encode_repl_hello(7, capabilities=net.REPL_CAP_SPARSE)
    _, blobs = net.decode_tensors(plain)
    assert len(blobs[0]) == 9
    assert net.decode_repl_caps(blobs[0]) == 0
    _, blobs = net.decode_tensors(sparse)
    assert len(blobs[0]) == 10
    assert net.decode_repl_caps(blobs[0]) == net.REPL_CAP_SPARSE
    # a pre-ISSUE-15 primary slices the first 9 bytes off the 10-byte
    # hello: clock + kind decode unchanged (no torn handshake either way)
    clock, kind = net.decode_repl_header(blobs[0])
    assert (clock, kind) == (7, net.REPL_HELLO)


def _raw_standby(port, capabilities):
    """A hand-rolled standby: dial, hello, return the socket."""
    sock = net.connect("127.0.0.1", port)
    net.send_frame(sock, net.encode_repl_hello(0, capabilities=capabilities))
    return sock


def _read_repl_frames(sock, n, limit=1 << 22):
    frames = []
    for _ in range(n):
        action, blobs = net.recv_tensors(sock, limit=limit)
        assert action == net.ACTION_REPL
        clock, kind = net.decode_repl_header(bytes(memoryview(blobs[0]))[:9])
        frames.append((clock, kind, blobs))
    return frames


def _sparse_commit(port, ids, value, templates=None):
    templates = templates or _weights()
    with PSClient("127.0.0.1", port, templates=templates,
                  sparse_leaves=[0]) as c:
        c.pull()
        d = [np.zeros_like(templates[0]), np.ones((3,), np.float32)]
        d[0][ids] = value
        c.commit(d, sparse_rows=[ids])


def test_sparse_primary_frames_by_attach_time_capability():
    """The never-a-torn-stream pin: one sparse primary, two hand-rolled
    standbys — the legacy (9-byte) hello receives ONLY SYNC/DELTA frames
    for the same sparse commits that reach the capable hello as
    REPL_SPARSE row deltas."""
    ps = _start()
    try:
        legacy = _raw_standby(ps.port, 0)
        capable = _raw_standby(ps.port, net.REPL_CAP_SPARSE)
        ids = np.array([2, 7], np.int64)
        _sparse_commit(ps.port, ids, 1.5)
        legacy_frames = _read_repl_frames(legacy, 2)
        capable_frames = _read_repl_frames(capable, 2)
        assert [k for _, k, _ in legacy_frames] == [net.REPL_SYNC,
                                                    net.REPL_DELTA]
        assert [k for _, k, _ in capable_frames] == [net.REPL_SYNC,
                                                     net.REPL_SPARSE]
        # the sparse frame carries exactly (header, ids, rows, dense head)
        _, _, blobs = capable_frames[1]
        assert len(blobs) == 1 + 2 + 1
        got_ids = np.frombuffer(bytes(memoryview(blobs[1])), np.int64)
        np.testing.assert_array_equal(got_ids, ids)
        rows = np.frombuffer(bytes(memoryview(blobs[2])),
                             np.float32).reshape(2, 4)
        np.testing.assert_array_equal(rows, np.full((2, 4), 1.5))
        # and it is strictly smaller than the dense-R frame next to it
        dense_size = sum(len(bytes(memoryview(b)))
                         for b in legacy_frames[1][2])
        sparse_size = sum(len(bytes(memoryview(b))) for b in blobs)
        assert sparse_size < dense_size
        legacy.close()
        capable.close()
    finally:
        ps.stop()


def test_sparse_and_dense_standbys_track_bit_identical():
    """The replication parity pin: a sparse-capable standby (row-delta
    stream) and a legacy standby (dense-R fallback) applied the SAME
    commit sequence land bit-identical to the primary and to each
    other — f32 and int8 commits, dense and sparse."""
    prim = _start()
    sb_sparse = DeltaParameterServer(_weights(), idle_timeout=None,
                                     sparse_leaves=[0],
                                     replica_of=("127.0.0.1", prim.port))
    sb_sparse.start()
    sb_dense = DeltaParameterServer(_weights(), idle_timeout=None,
                                    replica_of=("127.0.0.1", prim.port))
    sb_dense.start()
    try:
        assert sb_sparse.wait_synced(10)
        assert sb_dense.wait_synced(10)
        with PSClient("127.0.0.1", prim.port, templates=_weights(),
                      sparse_leaves=[0]) as c, \
                PSClient("127.0.0.1", prim.port, templates=_weights(),
                         sparse_leaves=[0], compress="int8") as q:
            for cl, val in ((c, 0.37), (q, -0.21)):
                cl.pull()
                d = [np.zeros((10, 4), np.float32),
                     np.full((3,), 0.11, np.float32)]
                d[0][np.array([1, 4, 8])] = val
                cl.commit(d, sparse_rows=[np.array([1, 4, 8], np.int64)])
            # one DENSE commit interleaves too (full-delta control client)
            with PSClient("127.0.0.1", prim.port,
                          templates=_weights()) as dense_client:
                dense_client.pull()
                dense_client.commit([np.full((10, 4), 0.05, np.float32),
                                     np.zeros((3,), np.float32)])
        deadline = time.time() + 10
        while time.time() < deadline and (
                sb_sparse.num_updates < 3 or sb_dense.num_updates < 3):
            time.sleep(0.02)
        pw = prim.get_weights()
        for sb in (sb_sparse, sb_dense):
            for a, b in zip(pw, sb.get_weights()):
                np.testing.assert_array_equal(a, b)
        assert prim._feed.repl_sparse_bytes > 0
    finally:
        sb_sparse.stop()
        sb_dense.stop()
        prim.stop()


def test_adaptive_merged_sparse_batch_replicates_row_union():
    """An adaptive sparse primary publishes the merged batch sparse; a
    sparse standby tracks it bit for bit."""
    prim = _start(adaptive=True)
    sb = DeltaParameterServer(_weights(), idle_timeout=None,
                              sparse_leaves=[0],
                              replica_of=("127.0.0.1", prim.port))
    sb.start()
    try:
        assert sb.wait_synced(10)
        _sparse_commit(prim.port, np.array([0, 3], np.int64), 0.5)
        _sparse_commit(prim.port, np.array([3, 9], np.int64), -0.25)
        deadline = time.time() + 10
        while time.time() < deadline and sb.num_updates < 2:
            time.sleep(0.02)
        for a, b in zip(prim.get_weights(), sb.get_weights()):
            np.testing.assert_array_equal(a, b)
        assert prim._feed.repl_sparse_bytes > 0
    finally:
        sb.stop()
        prim.stop()


# -- hot-tier client LRU -------------------------------------------------------

def test_row_lru_eviction_order_and_flush():
    lru = _RowLRU(2, 3, residual=True)
    assert lru.insert(np.array([1, 2]), np.ones((2, 3), np.float32)) == []
    # touch row 1 so row 2 becomes the LRU victim
    out = np.empty((1, 3), np.float32)
    mp, miss = lru.gather(np.array([1]), out)
    assert mp.size == 0 and lru.hits == 1
    lru.store_residuals(np.array([2]), np.full((1, 3), 0.125, np.float32))
    flushed = lru.insert(np.array([5]), np.zeros((1, 3), np.float32))
    assert [rid for rid, _ in flushed] == [2]
    np.testing.assert_array_equal(flushed[0][1], np.full(3, 0.125))
    assert lru.evictions == 1
    assert sorted(lru.slots) == [1, 5]
    # merge folds only resident rows
    lru.merge(np.array([1, 2]), np.full((2, 3), 2.0, np.float32))
    out = np.empty((1, 3), np.float32)
    lru.gather(np.array([1]), out)
    np.testing.assert_array_equal(out[0], np.full(3, 3.0))


def test_cache_knob_validation():
    t = _weights()
    with pytest.raises(ValueError, match="sparse_leaves"):
        PSClient("127.0.0.1", 1, templates=t, sparse_cache_rows=4)
    with pytest.raises(ValueError, match=">= 1"):
        InprocPSClient(object(), t, sparse_leaves=[0], sparse_cache_rows=0)
    from distkeras_tpu.runtime.parameter_server import ShardedPSClient

    plan = shard_plan(t, 1, sparse_leaves=[0])
    with pytest.raises(ValueError, match="sharded"):
        ShardedPSClient([("127.0.0.1", 1)], t, plan, sparse_leaves=[0],
                        sparse_cache_rows=4)


def test_hot_tier_pull_moves_only_misses():
    """A hit row costs zero wire: the S request of a warm pull carries
    only the ids not resident in the LRU, and the result block still
    carries fresh-or-cached values for every requested id."""
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0], sparse_cache_rows=4) as c:
            c.pull()  # seeds rows [0, 4)
            sent = []
            orig = c._sp_enc.send

            def spy(sock, action, arrays):
                sent.append([np.array(a) for a in arrays])
                return orig(sock, action, arrays)

            c._sp_enc.send = spy
            ids = np.array([1, 2, 7], np.int64)
            c.pull_nowait(sparse_rows=[ids])
            block = c.wait_weights()[0]
            np.testing.assert_array_equal(
                sent[0][0], np.array([7], np.int64))  # misses only
            center = ps.get_weights()[0]
            np.testing.assert_array_equal(block, center[ids])
            assert c.sparse_cache_hits == 2
            assert c.sparse_cache_misses == 1
    finally:
        ps.stop()


def test_hot_tier_own_commits_merge_in_place():
    """Hits merge in place: after this client commits a delta for a
    resident row, a warm (zero-wire) pull of that row reads the updated
    value — exact under a scale-1 hub."""
    ps = _start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0], sparse_cache_rows=4) as c:
            c.pull()
            ids = np.array([1], np.int64)
            c.pull_nowait(sparse_rows=[ids])
            before = c.wait_weights()[0].copy()
            d = [np.zeros((10, 4), np.float32), np.zeros((3,), np.float32)]
            d[0][1] = 2.25
            c.commit(d, sparse_rows=[ids])
            c.pull_nowait(sparse_rows=[ids])
            after = c.wait_weights()[0]
            np.testing.assert_array_equal(after, before + 2.25)
            np.testing.assert_array_equal(after, ps.get_weights()[0][ids])
    finally:
        ps.stop()


def test_evict_forces_flush_conserves_int8_residuals():
    """A tiny cache under int8: evicted rows' pending residuals ride the
    next commit (ids union), so the hub's center tracks the true delta
    sum within quantization tolerance — eviction never LOSES residuals."""
    ps = _start()
    try:
        true_sum = np.zeros((10, 4), np.float32)
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      sparse_leaves=[0], sparse_cache_rows=2,
                      compress="int8") as c:
            c.pull()
            rng = np.random.default_rng(0)
            for start in (0, 3, 6, 1, 4):
                ids = np.arange(start, start + 3, dtype=np.int64)
                c.pull_nowait(sparse_rows=[ids])
                c.wait_weights()
                d = [np.zeros((10, 4), np.float32),
                     np.zeros((3,), np.float32)]
                d[0][ids] = rng.normal(size=(3, 4)).astype(np.float32)
                true_sum += d[0]
                c.commit(d, sparse_rows=[ids])
            assert sum(l.evictions for l in c._lru.values()) > 0
        w0 = _weights()[0]
        got = ps.get_weights()[0] - w0
        # block-quantized int8 error feedback: each row's final pending
        # residual is bounded by one quantization step of its last block
        assert np.max(np.abs(got - true_sum)) < 0.1
    finally:
        ps.stop()


# -- per-table vocabularies ----------------------------------------------------

def test_multi_table_plan_reduces_to_single_table_plan():
    """The reduction pin: when every vocabulary matches, the multi-table
    row-range plan is exactly today's single-table plan per leaf."""
    t_multi = [np.zeros((12, 4), np.float32), np.zeros((12, 4), np.float32),
               np.zeros((5,), np.float32)]
    plan = shard_plan(t_multi, 3, sparse_leaves=[0, 1])
    single = shard_plan([t_multi[0], t_multi[2]], 3, sparse_leaves=[0])
    assert plan.sparse_ranges[0] == plan.sparse_ranges[1] \
        == single.sparse_ranges[0]
    # and mismatched vocabularies get INDEPENDENT per-leaf ranges
    t_mixed = [np.zeros((12, 4), np.float32), np.zeros((30, 4), np.float32)]
    p2 = shard_plan(t_mixed, 3, sparse_leaves=[0, 1])
    assert p2.sparse_ranges[0] == ((0, 4), (4, 8), (8, 12))
    assert p2.sparse_ranges[1] == ((0, 10), (10, 20), (20, 30))


def test_sparse_table_fields_resolution():
    from distkeras_tpu.models.base import (Model, sparse_leaf_indices,
                                           sparse_table_fields)
    from distkeras_tpu.models.embedding import ctr_embedding_spec

    spec = ctr_embedding_spec([16, 24, 8], dim=4)
    model = Model.init(spec, seed=0)
    idx = sparse_leaf_indices(spec, model.params)
    assert len(idx) == 3
    fields = sparse_table_fields(spec, model.params)
    assert fields == ((0,), (1,), (2,))
    # the single-table architecture declares no map (shared contract)
    spec1 = ctr_embedding_spec(16, dim=4, fields=2)
    m1 = Model.init(spec1, seed=0)
    assert sparse_table_fields(spec1, m1.params) is None


def test_multi_vocab_ids_validate_per_table():
    """Per-table validation: an id legal in the large vocabulary is
    rejected for the small one (the shared-id contract would have sent
    it everywhere)."""
    t = [np.zeros((4, 2), np.float32), np.zeros((16, 2), np.float32)]
    ps = DeltaParameterServer(t, idle_timeout=None, sparse_leaves=[0, 1])
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=t,
                      sparse_leaves=[0, 1]) as c:
            with pytest.raises(ValueError):
                c.pull_nowait(sparse_rows=[np.array([9]), np.array([9])])
            c.pull_nowait(sparse_rows=[np.array([2]), np.array([9])])
            out = c.wait_weights()
            assert out[0].shape[0] == 4  # full cache handed out
    finally:
        ps.stop()


def test_multi_vocab_trainer_end_to_end():
    """Tiny multi-table CTR run: per-field vocabularies of different
    sizes train over per-table id sets (auto-resolved field map)."""
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = ctr_embedding_spec([24, 48], dim=4, hidden_sizes=(8,))
    ds = synthetic_ctr_dataset(64, [24, 48], seed=0, hot_prob=0.5)
    tr = AsyncADAG(Model.init(spec, seed=0),
                   loss="categorical_crossentropy", batch_size=8,
                   num_epoch=1, learning_rate=0.05, seed=0, num_workers=2,
                   communication_window=2, sparse_tables="auto")
    model = tr.train(ds, shuffle=False)
    assert len(tr.history) == 4
    assert all(np.isfinite(h) for h in tr.history)
    import jax

    shapes = sorted(np.asarray(l).shape for l in jax.tree.leaves(model.params)
                    if getattr(l, "ndim", 0) == 2 and l.shape[-1] == 4)
    assert (24, 4) in shapes and (48, 4) in shapes


# -- trainer parity pins (LRU vs full cache) -----------------------------------

def _ctr_run(cache, compress=None, transport="socket", native=False):
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    spec = ctr_embedding_spec(64, dim=4, fields=2, hidden_sizes=(8,))
    ds = synthetic_ctr_dataset(96, 64, fields=2, seed=0, hot_prob=0.0)
    tr = AsyncADAG(Model.init(spec, seed=0),
                   loss="categorical_crossentropy", batch_size=8,
                   num_epoch=2, learning_rate=0.05, seed=0, num_workers=1,
                   communication_window=2, transport=transport,
                   native_ps=native, sparse_tables="auto",
                   sparse_cache_rows=cache, compress_commits=compress)
    return tr.train(ds, shuffle=False)


def _assert_params_equal(a, b):
    import jax

    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("compress", [None, "int8"])
def test_lru_cache_trajectory_identical_to_full_cache(compress):
    """The issue-15 parity pin: cache_rows >= vocabulary makes the
    hot-tier client trajectory-identical to the PR-9 full cache, f32 AND
    int8 (no evictions -> identical wire bytes, identical merges)."""
    _assert_params_equal(_ctr_run(None, compress), _ctr_run(64, compress))


@pytest.mark.parametrize("transport,native", [("inproc", False),
                                              ("inproc", True)])
def test_lru_cache_parity_other_transports(transport, native):
    ref = _ctr_run(None, None, "socket", False)
    got = _ctr_run(64, None, transport, native)
    _assert_params_equal(ref, got)


def test_native_inproc_sparse_matches_python_hub():
    """The formerly-NotImplementedError cell (sparse + inproc + native)
    is bit-identical to the Python hub."""
    _assert_params_equal(_ctr_run(None, None, "inproc", False),
                         _ctr_run(None, None, "inproc", True))


def test_replicated_sparse_trainer_standby_tracks_center():
    """E2E: a sparse-capable standby attached to the trainer-owned
    primary ends the run holding the primary's final center bit for bit
    (row-delta replication behind the ack)."""
    from distkeras_tpu.data.ctr import synthetic_ctr_dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.embedding import ctr_embedding_spec
    from distkeras_tpu.runtime.async_trainer import AsyncADAG
    from distkeras_tpu.utils import flatten_weights

    spec = ctr_embedding_spec(64, dim=4, fields=2, hidden_sizes=(8,))
    ds = synthetic_ctr_dataset(64, 64, fields=2, seed=0, hot_prob=0.0)
    model = Model.init(spec, seed=0)
    flat, _ = flatten_weights(model.params)
    flat = [np.asarray(w, np.float32) for w in flat]
    from distkeras_tpu.models.base import sparse_leaf_indices

    sparse_idx = sparse_leaf_indices(spec, model.params)
    hub = ADAGParameterServer(flat, num_workers=1, idle_timeout=None,
                              sparse_leaves=sparse_idx)
    hub.start()
    sb = ADAGParameterServer(flat, num_workers=1, idle_timeout=None,
                             sparse_leaves=sparse_idx,
                             replica_of=("127.0.0.1", hub.port))
    sb.start()
    try:
        assert sb.wait_synced(10)
        tr = AsyncADAG(model, loss="categorical_crossentropy", batch_size=8,
                       num_epoch=1, learning_rate=0.05, seed=0,
                       num_workers=1, communication_window=2,
                       sparse_tables="auto",
                       ps_address=("127.0.0.1", hub.port))
        tr.train(ds, shuffle=False)
        deadline = time.time() + 10
        while time.time() < deadline and sb.num_updates < hub.num_updates:
            time.sleep(0.02)
        for a, b in zip(hub.get_weights(), sb.get_weights()):
            np.testing.assert_array_equal(a, b)
        assert hub._feed.repl_sparse_bytes > 0
    finally:
        sb.stop()
        hub.stop()


# -- row-touch telemetry -------------------------------------------------------

def test_hub_hot_set_estimate_and_cache_counters():
    obs.enable()
    obs.reset()
    try:
        ps = _start()
        # 4 windows x (1 pull + 1 commit) = 8 folds -> exactly one decay
        # tick publishes the gauge with rows 1/2 at touch 4 -> 2 (the
        # pulls carry ZERO ids wire-side — they are warm hits)
        ps.TOUCH_DECAY_EVERY = 8
        try:
            with PSClient("127.0.0.1", ps.port, templates=_weights(),
                          sparse_leaves=[0], sparse_cache_rows=3) as c:
                c.pull()
                for _ in range(4):
                    ids = np.array([1, 2], np.int64)
                    c.pull_nowait(sparse_rows=[ids])
                    c.wait_weights()
                    d = [np.zeros((10, 4), np.float32),
                         np.zeros((3,), np.float32)]
                    d[0][ids] = 0.1
                    c.commit(d, sparse_rows=[ids])
                snap = obs.snapshot()
                # THIS hub's gauge, by its full key: obs.reset() zeroes
                # instruments in place, so a sharded hub of an earlier
                # file on this xdist worker leaves its own
                # ps.sparse_hot_rows{shard=...} behind at 0
                assert dict(snap["gauges"])[
                    'ps.sparse_hot_rows{table="0"}'] >= 2
                counters = dict(snap["counters"])
                hits = sum(v for k, v in counters.items()
                           if k.startswith("ps_sparse_cache_hits_total"))
                assert hits > 0
                assert c.sparse_cache_hits + c.sparse_cache_misses > 0
        finally:
            ps.stop()
    finally:
        obs.disable()
        obs.reset()


def test_repl_sparse_bytes_saved_counter():
    obs.enable()
    obs.reset()
    try:
        prim = _start()
        sb = DeltaParameterServer(_weights(), idle_timeout=None,
                                  sparse_leaves=[0],
                                  replica_of=("127.0.0.1", prim.port))
        sb.start()
        try:
            assert sb.wait_synced(10)
            _sparse_commit(prim.port, np.array([3], np.int64), 0.5)
            counters = dict(obs.snapshot()["counters"])
            saved = sum(v for k, v in counters.items()
                        if k.startswith("ps.repl_sparse_bytes_saved"))
            assert saved > 0
        finally:
            sb.stop()
            prim.stop()
    finally:
        obs.disable()
        obs.reset()


def test_render_top_hit_and_repl_columns():
    from distkeras_tpu.observability.health import render_top

    frame = render_top({"fleet": {"workers": {
        "0": {"meta": {"shard": None, "age_s": 1.0},
              "metrics": {
                  "sparse_cache_hits_total": {"last": 30.0, "n": 2},
                  "sparse_cache_misses_total": {"last": 10.0, "n": 2}}},
        "hub": {"meta": {"age_s": 1.0},
                "metrics": {"repl_sparse_bytes_total":
                            {"last": 4096.0, "rate": 512.0, "n": 3}}},
    }}, "events": []})
    assert "HIT%" in frame and "RΔ/S" in frame
    row0 = next(ln for ln in frame.splitlines() if ln.lstrip().startswith("0"))
    assert "75.0" in row0
    hub_row = next(ln for ln in frame.splitlines()
                   if ln.lstrip().startswith("hub"))
    assert "512" in hub_row


def test_fleet_report_hot_tier_block():
    from distkeras_tpu.observability.distributed import _hot_tier_block

    snap = {"workers": {
        "0": {"metrics": {
            "sparse_cache_hits_total": {"last": 9.0, "n": 1},
            "sparse_cache_misses_total": {"last": 3.0, "n": 1}}},
        "hub": {"metrics": {
            "repl_sparse_bytes_total": {"last": 2048.0, "n": 1}}},
    }}
    block = _hot_tier_block(snap)
    assert block["cache"]["0"]["hit_rate"] == 0.75
    assert block["repl_sparse_bytes_total"] == 2048
    assert _hot_tier_block({"workers": {}}) is None


# -- un-upgraded peers ---------------------------------------------------------

def test_plain_replicated_stream_stays_repl_sparse_free():
    """Compat: a hub with NO sparse leaves never emits a REPL_SPARSE
    frame, even to a capability-announcing standby (there is nothing
    sparse to frame) — the dense replicated byte stream is untouched."""
    t = _weights()
    prim = DeltaParameterServer(t, idle_timeout=None)
    prim.start()
    try:
        sock = _raw_standby(prim.port, net.REPL_CAP_SPARSE)
        with PSClient("127.0.0.1", prim.port, templates=t) as c:
            c.pull()
            c.commit([np.full_like(a, 0.25) for a in t])
        frames = _read_repl_frames(sock, 2)
        assert [k for _, k, _ in frames] == [net.REPL_SYNC, net.REPL_DELTA]
        sock.close()
    finally:
        prim.stop()
