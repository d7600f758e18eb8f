"""Transport-parity tests (issue 3): ``transport="inproc"`` and
``transport="socket"`` must produce IDENTICAL training trajectories.

The inproc client executes its pull/commit at the exact program points the
socket client *sends* at, and both paths run the same center arithmetic
under the same hub lock — so for a deterministic schedule (one worker) the
trajectories are bit-equal, pipelined or serial, compressed or not.  These
tests pin that property; if it breaks, the inproc fast path has silently
become a different algorithm.
"""

import numpy as np
import pytest

from conftest import native_mark
from distkeras_tpu import observability as obs
from distkeras_tpu.models.base import Model, ModelSpec


def _mlp_spec():
    return ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))


def _train(trainer_name, toy_dataset, *, transport, pipeline, num_workers=1,
           **extra):
    """``sparse_tables`` in ``extra`` swaps the dense MLP on the blobs for
    the CTR embedding model on its own ids (8 windows an epoch, partial
    touch), the shape the row-sparse exchange exists for."""
    import distkeras_tpu as dk

    spec, shuffle = _mlp_spec(), True
    if extra.get("sparse_tables"):
        from distkeras_tpu.data.ctr import synthetic_ctr_dataset
        from distkeras_tpu.models.embedding import ctr_embedding_spec

        spec = ctr_embedding_spec(64, dim=4, fields=2, hidden_sizes=(8,))
        toy_dataset = synthetic_ctr_dataset(512, 64, fields=2, seed=0,
                                            hot_prob=0.0)
        shuffle = False
    cls = getattr(dk, trainer_name)
    trainer = cls(Model.init(spec, seed=0),
                  loss="categorical_crossentropy", batch_size=16, num_epoch=2,
                  num_workers=num_workers, communication_window=4,
                  learning_rate=0.05, seed=0, transport=transport,
                  pipeline=pipeline, **extra)
    model = trainer.train(toy_dataset, shuffle=shuffle)
    return trainer, model


def _total(series, name, of=lambda v: v):
    """One telemetry series summed over its label sets (every shard)."""
    return sum(of(v) for k, v in series.items() if k.split("{", 1)[0] == name)


def _assert_bit_identical(run_a, run_b):
    import jax

    (tr_a, m_a), (tr_b, m_b) = run_a, run_b
    assert tr_a.history == tr_b.history, "window-loss trajectories diverged"
    for a, b in zip(jax.tree.leaves(m_a.params), jax.tree.leaves(m_b.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# the wire transports land the prefetched pull beside the window program
# (PSClient.land_weights); inproc copies the center when the pull is issued.
# Bit-equal trajectories say the early landing changed no float: ADAG and
# one elastic trainer, socket and shm, Python and C++ hub, dense and
# row-sparse (full cache and hot tier)
@pytest.mark.parametrize("trainer_name,pipeline,wire,extra", [
    ("AsyncADAG", True, "socket", {}),
    ("AsyncADAG", False, "socket", {}),
    ("AsyncAEASGD", True, "socket", {"rho": 2.0}),
    ("AsyncADAG", True, "shm", {}),
    ("AsyncAEASGD", True, "shm", {"rho": 2.0}),
    pytest.param("AsyncADAG", True, "socket", {"native_ps": True},
                 marks=native_mark()),
    pytest.param("AsyncAEASGD", True, "shm", {"rho": 2.0, "native_ps": True},
                 marks=native_mark()),
    ("AsyncADAG", True, "socket", {"sparse_tables": "auto"}),
    ("AsyncADAG", True, "shm", {"sparse_tables": "auto"}),
    ("AsyncAEASGD", True, "socket", {"rho": 2.0, "sparse_tables": "auto"}),
    ("AsyncADAG", True, "socket", {"sparse_tables": "auto",
                                   "sparse_cache_rows": 16}),
    pytest.param("AsyncADAG", True, "socket",
                 {"sparse_tables": "auto", "native_ps": True},
                 marks=native_mark()),
])
def test_inproc_matches_socket_bit_identical(trainer_name, pipeline, wire,
                                             extra, toy_dataset):
    """Single-worker ADAG/AEASGD trajectories are bit-equal across
    transports, with and without the pipelined overlap."""
    sock = _train(trainer_name, toy_dataset, transport=wire,
                  pipeline=pipeline, **extra)
    inproc = _train(trainer_name, toy_dataset, transport="inproc",
                    pipeline=pipeline, **extra)
    _assert_bit_identical(sock, inproc)


@pytest.mark.parametrize("extra,per_run", [
    # dense prefetch spans the epochs: every window but the run's last
    ({}, lambda windows, epochs: windows - 1),
    ({"transport": "shm"}, lambda windows, epochs: windows - 1),
    # one landing a stripe
    ({"num_shards": 2}, lambda windows, epochs: 2 * (windows - 1)),
    # the sparse prefetch stops at each epoch's tail (the next epoch's ids
    # do not exist yet)
    ({"sparse_tables": "auto"}, lambda windows, epochs: windows - epochs),
    # nothing is ever in flight: the serial exchange, the in-process client
    ({"pipeline": False}, lambda windows, epochs: 0),
    ({"transport": "inproc"}, lambda windows, epochs: 0),
])
def test_pulls_landed_early_counts_every_prefetch(extra, per_run, toy_dataset,
                                                  telemetry):
    """``ps_pulls_landed_early_total``: one for each prefetched reply the
    worker loop claimed through ``land_weights()``, and the commit's guard
    had nothing left to claim (one ``ps.pull_stall_ms`` sample a pull)."""
    kw = {"transport": "socket", "pipeline": True, **extra}
    trainer, _ = _train("AsyncADAG", toy_dataset, **kw)
    snap = telemetry.snapshot()
    landed = _total(snap["counters"], "ps_pulls_landed_early_total")
    assert landed == per_run(len(trainer.history), 2)
    if kw["transport"] != "inproc":
        # one sample a land and one a (socket-free) wait_weights, none a guard
        assert _total(snap["histograms"], "ps.pull_stall_ms",
                      lambda h: h["count"]) \
            == _total(snap["counters"], "ps_pulls_total") + landed


# -- the streamed dense commit (ISSUE 35) ---------------------------------------
# A dense float32 commit leaves without a packed frame, each leaf from its
# own buffer; the worker loop hands the client DEVICE leaves whose copies it
# issued at dispatch.  The trajectory cases above run that path on socket and
# shm and compare it, bit for bit, with inproc, which has no codec at all.

def _streamed_templates():
    """Leaves on both sides of the codec's direct-send threshold, one of
    them empty, over more bytes than a loopback socket buffers."""
    return [np.zeros(s, np.float32)
            for s in [(3,), (700, 1024), (0, 5), (17, 9), (1 << 19,), (1,)]]


@pytest.mark.parametrize("wire", ["socket", "shm", "shards2"])
def test_device_leaf_commit_lands_equal_to_numpy_commit(wire, tmp_path,
                                                        telemetry):
    """The same commit as numpy arrays and as ``jax.Array`` leaves with
    ``copy_to_host_async()`` issued: the hub's center moves by the same
    bits, and both commits (and a stripe's every part) count as streamed."""
    import jax

    from distkeras_tpu.runtime.parameter_server import (
        DeltaParameterServer, PSClient, ShardedParameterServer,
        ShardedPSClient, shard_plan)

    tmpl = _streamed_templates()
    rng = np.random.default_rng(35)
    delta = [rng.standard_normal(t.shape).astype(np.float32) for t in tmpl]
    shards = 2 if wire == "shards2" else 1
    if shards == 2:
        plan = shard_plan(tmpl, 2)
        ps = ShardedParameterServer(
            tmpl, plan, lambda w, sid: DeltaParameterServer(
                w, shard_id=sid, idle_timeout=None))
    else:
        ps = DeltaParameterServer(
            [t.copy() for t in tmpl], idle_timeout=None,
            shm_dir=str(tmp_path) if wire == "shm" else None)
    ps.start()
    try:
        if shards == 2:
            client = ShardedPSClient([("127.0.0.1", p) for p in ps.ports],
                                     tmpl, plan)
        else:
            client = PSClient("127.0.0.1", ps.port, templates=tmpl,
                              shm=wire == "shm")
            assert client.transport == ("shm" if wire == "shm" else "tcp")
        with client:
            client.commit(delta)
            after_numpy = [w.copy() for w in client.pull()]
            leaves = [jax.device_put(d) for d in delta]
            for leaf in leaves:
                leaf.copy_to_host_async()
            client.commit(leaves)
            after_device = [w.copy() for w in client.pull()]
        for d, one, two in zip(delta, after_numpy, after_device):
            np.testing.assert_array_equal(one, d)
            np.testing.assert_array_equal(two, d + d)   # exact in float32
        assert _total(telemetry.snapshot()["counters"],
                      "ps_commits_streamed_total") == 2 * shards
    finally:
        ps.stop()


@pytest.mark.parametrize("extra,streams", [
    ({}, True),
    ({"transport": "shm"}, True),
    ({"pipeline": False}, True),
    ({"num_shards": 2}, True),  # per stripe: both counters count its parts
    pytest.param({"native_ps": True}, True, marks=native_mark()),
    # whole arrays are needed first: quantised, or gathered by row
    ({"compress_commits": "int8"}, False),
    ({"sparse_tables": "auto"}, False),
    # no wire, no frame
    ({"transport": "inproc"}, False),
])
def test_commits_streamed_counts_every_dense_commit(extra, streams,
                                                    toy_dataset, telemetry):
    """``ps_commits_streamed_total`` beside the hub's ``ps_commits_total``:
    equal for a dense float32 run on a wire, zero where the commit is
    packed (int8, row-sparse) or never framed (inproc).  The C++ hub keeps
    its count to itself: there the client's equals the windows."""
    kw = {"transport": "socket", "pipeline": True, **extra}
    trainer, _ = _train("AsyncADAG", toy_dataset, **kw)
    counters = telemetry.snapshot()["counters"]
    commits = _total(counters, "ps_commits_total") or len(trainer.history)
    assert commits >= len(trainer.history) > 0
    assert _total(counters, "ps_commits_streamed_total") \
        == (commits if streams else 0)


@pytest.mark.parametrize("extra,device_leaves", [
    ({}, True),
    ({"pipeline": False}, True),
    ({"compress_commits": "int8"}, False),
    ({"sparse_tables": "auto"}, False),
])
def test_worker_loop_hands_device_leaves_only_for_a_dense_commit(
        extra, device_leaves, toy_dataset, monkeypatch):
    """The worker loop gives the client the commit's ``jax.Array`` leaves
    (copies issued at dispatch) exactly where the client streams; a commit
    that is quantised or gathered by row is fetched whole, as numpy."""
    import jax

    from distkeras_tpu.runtime.parameter_server import PSClient

    seen = []
    real = PSClient.commit_nowait

    def spy(self, delta, sparse_rows=None):
        seen.append({isinstance(d, jax.Array) for d in delta})
        return real(self, delta, sparse_rows=sparse_rows)

    monkeypatch.setattr(PSClient, "commit_nowait", spy)
    trainer, _ = _train("AsyncADAG", toy_dataset, transport="socket",
                        **{"pipeline": True, **extra})
    assert len(seen) == len(trainer.history)
    assert all(kinds == {device_leaves} for kinds in seen)


def test_inproc_matches_socket_with_int8_commits(toy_dataset):
    """The inproc client round-trips commits through the SAME quantize/
    dequantize + error-feedback math the wire uses, so compressed runs
    stay trajectory-identical too."""
    sock = _train("AsyncADAG", toy_dataset, transport="socket", pipeline=True,
                  compress_commits="int8")
    inproc = _train("AsyncADAG", toy_dataset, transport="inproc", pipeline=True,
                    compress_commits="int8")
    _assert_bit_identical(sock, inproc)


def test_inproc_multiworker_learns(toy_dataset):
    """inproc with real worker concurrency end to end: 4 workers race
    commit_direct under the hub lock and the center still learns."""
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.predictors import ModelPredictor

    trainer, model = _train("AsyncADAG", toy_dataset, transport="inproc",
                            pipeline=True, num_workers=4)
    assert trainer.parameter_server.num_updates > 0
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index",
                            label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"inproc AsyncADAG accuracy {acc}"


def test_inproc_rejects_worker_only_mode():
    import distkeras_tpu as dk

    with pytest.raises(ValueError, match="inproc"):
        dk.AsyncADAG(_mlp_spec(), transport="inproc",
                     ps_address=("head", 4242))
    with pytest.raises(ValueError, match="transport"):
        dk.AsyncADAG(_mlp_spec(), transport="carrier-pigeon")


# -- shared-memory transport (ISSUE 18) ----------------------------------------

@pytest.mark.parametrize("pipeline", [True, False])
def test_shm_matches_socket_bit_identical(pipeline, toy_dataset):
    """transport="shm" carries the SAME framed bytes over mmap rings, so
    single-worker trajectories are bit-equal to socket runs.  The counter
    assertion guards against the attach silently declining — a run that
    degraded to TCP would pass the parity check vacuously."""
    obs.reset()
    obs.enable()
    try:
        shm = _train("AsyncADAG", toy_dataset, transport="shm",
                     pipeline=pipeline)
        counters = obs.snapshot()["counters"]
        assert counters.get("ps.shm_frames_total", 0) > 0, \
            "shm run silently fell back to TCP"
    finally:
        obs.disable()
        obs.reset()
    sock = _train("AsyncADAG", toy_dataset, transport="socket",
                  pipeline=pipeline)
    _assert_bit_identical(sock, shm)


def test_shm_matches_socket_with_int8_commits(toy_dataset):
    """Quantized commits cross the rings bit-identically too, and a
    batched-receive hub (recv_batch_depth) changes syscall shape only —
    all three runs land on the same trajectory."""
    sock = _train("AsyncADAG", toy_dataset, transport="socket",
                  pipeline=True, compress_commits="int8")
    batched = _train("AsyncADAG", toy_dataset, transport="socket",
                     pipeline=True, compress_commits="int8",
                     recv_batch_depth=8)
    shm = _train("AsyncADAG", toy_dataset, transport="shm", pipeline=True,
                 compress_commits="int8")
    _assert_bit_identical(sock, batched)
    _assert_bit_identical(sock, shm)


def test_recv_batch_depth_matches_plain_socket_bit_identical(toy_dataset):
    """The hub's batched receive path (recvmmsg when available, plain
    nonblocking drains otherwise) parses the same stream — trajectories
    are bit-equal to the unbatched hub."""
    plain = _train("AsyncADAG", toy_dataset, transport="socket",
                   pipeline=True)
    batched = _train("AsyncADAG", toy_dataset, transport="socket",
                     pipeline=True, recv_batch_depth=8)
    _assert_bit_identical(plain, batched)


def test_shm_transport_validation():
    import distkeras_tpu as dk

    tr = dk.AsyncADAG(_mlp_spec(), transport="shm")
    assert tr.transport == "shm"
    with pytest.raises(ValueError, match="recv_batch_depth"):
        dk.AsyncADAG(_mlp_spec(), recv_batch_depth=-1)


def test_pipelined_prefetch_semantics_and_staleness_accounting(toy_dataset):
    """Pipelining's documented semantics (ARCHITECTURE.md "Async
    transport"): the pull for window k+1 is issued BEFORE commit k, so the
    worker trains k+1 from a center missing its own commit k — a genuinely
    staler schedule than serial (the trajectories must differ).  The hub's
    clock staleness, measured from the most recent pull REQUEST on the
    connection, still reads 0 for a lone worker in BOTH modes — it
    undercounts the delta's true base by the prefetch depth, which is why
    exact-staleness consumers (DynSGD scaling studies) use
    ``pipeline=False``."""
    obs.reset()
    obs.enable()
    try:
        piped, _ = _train("AsyncADAG", toy_dataset, transport="inproc",
                          pipeline=True)
        hist = obs.snapshot()["histograms"]["ps_commit_staleness"]
        assert hist["count"] == len(piped.history)
        # every window's prefetch re-arms the connection clock before its
        # commit -> measured 0; only the FINAL window (which has nothing
        # left to prefetch) commits against its window-start pull -> 1,
        # the one commit whose measurement equals the true delta base
        assert hist["sum"] == 1.0 and hist["max"] == 1.0

        obs.reset()
        serial, _ = _train("AsyncADAG", toy_dataset, transport="inproc",
                           pipeline=False)
        hist = obs.snapshot()["histograms"]["ps_commit_staleness"]
        assert hist["count"] == len(serial.history)
        assert hist["sum"] == 0  # serial: every pull reflects every commit
    finally:
        obs.disable()
        obs.reset()
    # same windows, different schedule: the prefetched pulls make the
    # pipelined trajectory diverge from the serial one after window 0
    assert len(piped.history) == len(serial.history)
    assert piped.history[0] == serial.history[0]
    assert piped.history != serial.history
