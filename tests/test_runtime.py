"""Runtime layer tests: framed transport, PS hub semantics, async trainers.

Covers the reference's L3 (SURVEY.md §2.11–2.12) — here pickle-free and
with the genuinely-asynchronous trainer family on top."""

import socket
import threading
import tracemalloc

import numpy as np
import pytest

from conftest import native_mark
from distkeras_tpu import observability as obs
from distkeras_tpu.runtime import networking as net
from distkeras_tpu.runtime.parameter_server import (
    ADAGParameterServer,
    DeltaParameterServer,
    DynSGDParameterServer,
    PSClient,
    _APPLY_BLOCK,
    _add_scaled_commit,
)


# -- framing ------------------------------------------------------------------

def test_tensor_frame_roundtrip():
    arrays = [np.arange(6, dtype=np.float32).reshape(2, 3), np.ones((4,), np.float32)]
    payload = net.encode_tensors(net.ACTION_COMMIT, arrays)
    action, blobs = net.decode_tensors(payload)
    assert action == net.ACTION_COMMIT
    assert len(blobs) == 2
    np.testing.assert_array_equal(np.frombuffer(blobs[0], np.float32).reshape(2, 3), arrays[0])


def test_tensor_frame_trailing_bytes_rejected():
    payload = net.encode_tensors(net.ACTION_PULL, []) + b"junk"
    with pytest.raises(ValueError, match="trailing"):
        net.decode_tensors(payload)


def test_json_frames_over_socketpair():
    a, b = socket.socketpair()
    try:
        net.send_json(a, {"action": "submit", "job": "mnist", "n": 3})
        msg = net.recv_json(b)
        assert msg == {"action": "submit", "job": "mnist", "n": 3}
    finally:
        a.close()
        b.close()


# -- zero-copy flat framing (issue 3) -----------------------------------------

def _codec_templates():
    return [np.zeros((2, 3), np.float32), np.zeros((5,), np.float32)]


def test_flat_codec_wire_bytes_match_generic_encoder():
    """The codec's frame must be byte-identical to encode_tensors' — the
    C++ hub and generic peers parse one layout."""
    arrays = [np.arange(6, dtype=np.float32).reshape(2, 3),
              np.linspace(0, 1, 5).astype(np.float32)]
    codec = net.FlatFrameCodec(_codec_templates())
    a, b = socket.socketpair()
    try:
        codec.send(a, net.ACTION_COMMIT, arrays)
        frame = net._recv_exact(b, codec.frame_len)
        generic = net.encode_tensors(net.ACTION_COMMIT, arrays)
        assert frame == len(generic).to_bytes(8, "big") + generic
    finally:
        a.close()
        b.close()


def test_flat_codec_interops_both_directions():
    """codec -> generic decode AND generic send -> codec scatter-receive."""
    tmpl = _codec_templates()
    codec = net.FlatFrameCodec(tmpl)
    arrays = [np.full((2, 3), 2.5, np.float32), np.arange(5, dtype=np.float32)]
    a, b = socket.socketpair()
    try:
        codec.send(a, net.ACTION_WEIGHTS, arrays)
        action, got = net.recv_tensors(b, templates=tmpl)
        assert action == net.ACTION_WEIGHTS
        for g, want in zip(got, arrays):
            np.testing.assert_array_equal(g, want)

        net.send_tensors(a, net.ACTION_WEIGHTS, arrays)
        out = [np.empty_like(t) for t in tmpl]
        action = codec.recv_into(b, out)
        assert action == net.ACTION_WEIGHTS
        for g, want in zip(out, arrays):
            np.testing.assert_array_equal(g, want)
    finally:
        a.close()
        b.close()


def test_flat_codec_rejects_schema_mismatch():
    tmpl = _codec_templates()
    codec = net.FlatFrameCodec(tmpl)
    a, b = socket.socketpair()
    try:
        # wrong tensor count on the wire -> frame size mismatch
        net.send_tensors(a, net.ACTION_WEIGHTS, [np.zeros((2, 3), np.float32)])
        with pytest.raises(ValueError, match="does not match"):
            codec.recv_into(b, [np.empty_like(t) for t in tmpl])
        # wrong dtype/size at pack time
        with pytest.raises(ValueError, match="does not match"):
            codec.pack(net.ACTION_COMMIT,
                       [np.zeros((2, 3), np.float64), np.zeros((5,), np.float32)])
    finally:
        a.close()
        b.close()


def test_recv_tensors_decodes_into_preallocated_out():
    """Satellite: templates/out decode straight into caller arrays — the
    returned arrays ARE the preallocated ones, no intermediate copies."""
    tmpl = _codec_templates()
    arrays = [np.full((2, 3), 4.0, np.float32), np.arange(5, dtype=np.float32)]
    a, b = socket.socketpair()
    try:
        net.send_tensors(a, net.ACTION_WEIGHTS, arrays)
        pre = [np.zeros_like(t) for t in tmpl]
        action, got = net.recv_tensors(b, out=pre)
        assert action == net.ACTION_WEIGHTS
        assert got[0] is pre[0] and got[1] is pre[1]
        for g, want in zip(pre, arrays):
            np.testing.assert_array_equal(g, want)
        # the template-less control-plane path still returns raw uint8
        net.send_tensors(a, net.ACTION_COMMIT, [np.zeros(3, np.float32)])
        action, blobs = net.recv_tensors(b)
        assert action == net.ACTION_COMMIT and blobs[0].dtype == np.uint8
    finally:
        a.close()
        b.close()


def test_recv_frame_into_reuses_buffer_and_views():
    a, b = socket.socketpair()
    try:
        buf = bytearray()
        net.send_frame(a, b"x" * 32)
        mv = net.recv_frame_into(b, buf)
        assert bytes(mv) == b"x" * 32 and len(buf) == 32
        net.send_frame(a, b"y" * 8)  # smaller frame: buffer NOT shrunk
        mv = net.recv_frame_into(b, buf)
        assert bytes(mv) == b"y" * 8 and len(buf) == 32
    finally:
        a.close()
        b.close()


# -- parameter servers --------------------------------------------------------

def _weights():
    return [np.zeros((2, 2), np.float32), np.zeros((3,), np.float32)]


def test_delta_ps_pull_commit():
    ps = DeltaParameterServer(_weights())
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


def test_pull_lands_row_major_whatever_the_templates_layout():
    """A template carries shape and dtype, not strides: ``np.asarray`` of a
    TPU device array can come back in a non-C layout (first seen on a v5e
    for a [256, 10] leaf — the landing buffer inherited it through
    ``empty_like`` and every pull died as a ProtocolError)."""
    center = np.arange(6, dtype=np.float32).reshape(2, 3)
    ps = DeltaParameterServer([np.asfortranarray(center)])
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port,
                      templates=[np.asfortranarray(center)]) as c:
            (w,) = c.pull()
            assert w.flags.c_contiguous
            np.testing.assert_array_equal(w, center)
    finally:
        ps.stop()


def test_adag_ps_normalizes_by_num_workers():
    ps = ADAGParameterServer(_weights(), num_workers=4)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights()) as c:
            c.commit([np.full((2, 2), 4.0, np.float32), np.full((3,), 8.0, np.float32)])
            w = c.pull()
            np.testing.assert_allclose(w[0], np.ones((2, 2)))
            np.testing.assert_allclose(w[1], 2 * np.ones((3,)))
    finally:
        ps.stop()


def test_dynsgd_staleness_scaling():
    """Worker B pulls, then A's commit lands first: B's commit has
    staleness 1 and is scaled by 1/2 (reference DynSGD rule)."""
    ps = DynSGDParameterServer(_weights())
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


def _delta_as(form, values):
    """``values`` (float32, 1-D) as the delta leaf a commit path can hand
    the hub: the array itself, the misaligned view the socket path decodes
    out of its receive buffer, ``commit_direct``'s float64 or a strided
    slice."""
    if form == "aligned":
        return values
    if form == "misaligned":
        buf = bytearray(13 + values.nbytes)
        buf[13:] = values.tobytes()
        d = np.frombuffer(buf, np.float32, values.size, 13)
        assert values.size == 0 or not d.flags.aligned
        return d
    if form == "float64":
        return values.astype(np.float64)
    wide = np.zeros((values.size, 2), np.float32)
    wide[:, 0] = values
    d = wide[:, 0]
    assert values.size < 2 or not d.flags.c_contiguous
    return d


@pytest.mark.parametrize("form", ["aligned", "misaligned", "float64",
                                  "noncontiguous"])
@pytest.mark.parametrize("scale", [1.0, 0.25, 1.0 / 3.0])
def test_add_scaled_commit_bit_identical_to_expression(scale, form):
    """The in-place blocked apply performs the expression's own two float32
    roundings: every leaf equals ``c + d * float32(scale)`` bit for bit,
    whatever its size against the block and however the delta is laid
    out."""
    rng = np.random.default_rng(26)
    sizes = [0, 1, _APPLY_BLOCK - 1, _APPLY_BLOCK, _APPLY_BLOCK + 1,
             3 * _APPLY_BLOCK + 7]
    center = [rng.standard_normal(n).astype(np.float32) for n in sizes]
    values = [(rng.standard_normal(n) * 1e-3).astype(np.float32)
              for n in sizes]
    expected = [c + v * np.float32(scale) for c, v in zip(center, values)]
    delta = [_delta_as(form, v) for v in values]
    kept = [np.array(d) for d in delta]
    _add_scaled_commit(center, delta, scale,
                       np.empty(_APPLY_BLOCK, np.float32))
    for c, e, d, k in zip(center, expected, delta, kept):
        assert c.dtype == np.float32 and e.dtype == np.float32
        assert np.array_equal(c.view(np.uint32), e.view(np.uint32))
        assert np.array_equal(d, k)  # the delta is read, never written


def test_add_scaled_commit_keeps_leaf_shapes_and_fortran_center():
    """Leaves keep their shapes (the flat blocks are views), a center leaf
    with no flat view (Fortran order) still gets the same bits, and a
    scalar leaf stays a scalar."""
    for scale in (1.0, 0.25):
        scalar = [np.array(2.0, np.float32)]
        _add_scaled_commit(scalar, [np.array(4.0, np.float64)], scale,
                           np.empty(_APPLY_BLOCK, np.float32))
        assert scalar[0].shape == () and scalar[0] == 2.0 + 4.0 * scale
    rng = np.random.default_rng(27)
    shape = (3, _APPLY_BLOCK // 2 + 5)
    c0 = rng.standard_normal(shape).astype(np.float32)
    d = rng.standard_normal(shape).astype(np.float32)
    expected = c0 + d * np.float32(0.25)
    for center in ([c0.copy()], [np.asfortranarray(c0)]):
        _add_scaled_commit(center, [d], 0.25,
                           np.empty(_APPLY_BLOCK, np.float32))
        assert center[0].shape == shape
        assert np.array_equal(center[0].view(np.uint32),
                              expected.view(np.uint32))


def test_adag_socket_commit_applies_without_a_leaf_temporary():
    """The dense apply is in place: over a socket commit at a leaf of 8
    blocks (scale 1/4, the general branch) the hub's ``apply_commit``
    allocates less than one leaf — ``d * scale`` would be a whole one."""
    leaf = np.zeros(8 * _APPLY_BLOCK, np.float32)
    ps = ADAGParameterServer([leaf], num_workers=4)
    apply_commit, grown = ps.apply_commit, []

    def traced_apply(delta, staleness):
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        apply_commit(delta, staleness)
        grown.append(tracemalloc.get_traced_memory()[1] - before)

    ps.apply_commit = traced_apply
    ps.start()
    tracemalloc.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=[leaf]) as c:
            c.commit([np.full(leaf.shape, 4.0, np.float32)])
            w = c.pull()
    finally:
        tracemalloc.stop()
        ps.stop()
    assert np.array_equal(w[0], np.ones(leaf.shape, np.float32))
    assert len(grown) == 1 and grown[0] < leaf.nbytes // 8


def test_concurrent_commits_all_land():
    ps = DeltaParameterServer([np.zeros((16,), np.float32)])
    ps.start()
    n_workers, n_commits = 8, 20

    def work(i):
        with PSClient("127.0.0.1", ps.port, templates=[np.zeros((16,), np.float32)]) as c:
            for _ in range(n_commits):
                c.pull()
                c.commit([np.ones((16,), np.float32)])

    try:
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        np.testing.assert_allclose(ps.get_weights()[0], np.full((16,), n_workers * n_commits))
        assert ps.num_updates == n_workers * n_commits
    finally:
        ps.stop()


def test_client_size_mismatch_raises():
    ps = DeltaParameterServer(_weights())
    ps.start()
    try:
        c = PSClient("127.0.0.1", ps.port, templates=[np.zeros((5,), np.float32)])
        with pytest.raises((ValueError, ConnectionError)):
            c.pull()
        c.sock.close()
    finally:
        ps.stop()


def test_ps_stop_wakes_accept_thread_immediately():
    """stop() must shutdown() the listener (close() alone does not wake a
    blocked accept() on Linux): before the fix every hub stop burned the
    full 5s join timeout and leaked its accept thread."""
    import time as _time

    ps = DeltaParameterServer(_weights())
    ps.start()
    t0 = _time.monotonic()
    ps.stop()
    assert _time.monotonic() - t0 < 2.0, "stop() waited on the accept thread"
    assert not ps._accept_thread.is_alive()


def test_pipelined_client_coalesces_acks_and_prefetches():
    """The issue-3 hot-path schedule, driven by hand: prefetch pull k+1
    BEFORE commit k, consume replies lazily — every commit still lands,
    every prefetched pull observes the center WITHOUT the commit sent
    after it (self-staleness 1), and drain() leaves nothing in flight."""
    ps = DeltaParameterServer([np.zeros((4,), np.float32)])
    ps.start()
    tmpl = [np.zeros((4,), np.float32)]
    one = [np.ones((4,), np.float32)]
    try:
        with PSClient("127.0.0.1", ps.port, templates=tmpl) as c:
            w0 = c.pull()
            np.testing.assert_array_equal(w0[0], 0)
            for k in range(4):
                c.pull_nowait()        # prefetch (k+1) — predates commit k
                c.commit_nowait(one)   # fire-and-forget
                # deadlock-avoidance contract: the commit send claimed the
                # in-flight weights reply FIRST (the hub must be parked in
                # recv while the commit bytes travel), so no weights reply
                # remains pending once commit_nowait returns
                assert all(kind != net.ACTION_WEIGHTS
                           for kind, _ in c._pending)
                w = c.wait_weights()   # hands out the claimed pull
                # the prefetched snapshot misses THIS window's commit
                np.testing.assert_array_equal(w[0], np.full(4, float(k)))
            c.drain()
            assert len(c._pending) == 0
            np.testing.assert_array_equal(c.pull()[0], np.full(4, 4.0))
        assert ps.num_updates == 4
    finally:
        ps.stop()


def test_pipelined_pull_buffers_double_buffer():
    """wait_weights alternates between two landing buffers, so the pull
    being consumed survives the next prefetched receive (and exactly one
    more)."""
    ps = DeltaParameterServer([np.zeros((4,), np.float32)])
    ps.start()
    tmpl = [np.zeros((4,), np.float32)]
    try:
        with PSClient("127.0.0.1", ps.port, templates=tmpl) as c:
            w1 = c.pull()
            c.commit([np.ones((4,), np.float32)])
            w2 = c.pull()
            assert w1[0] is not w2[0]  # different landing buffers
            np.testing.assert_array_equal(w1[0], 0)  # older pull intact
            np.testing.assert_array_equal(w2[0], 1)
            c.commit([np.ones((4,), np.float32)])
            w3 = c.pull()  # reuses w1's buffer
            assert w3[0] is w1[0]
            np.testing.assert_array_equal(w3[0], 2)
    finally:
        ps.stop()


def _landed_counter():
    return obs.snapshot()["counters"].get("ps_pulls_landed_early_total", 0.0)


def _stall_samples():
    h = obs.snapshot()["histograms"].get("ps.pull_stall_ms")
    return h["count"] if h else 0


@pytest.mark.parametrize("hub", [
    "python", pytest.param("native", marks=native_mark())])
def test_land_weights_claims_the_prefetched_reply(hub, telemetry):
    """The worker loop's schedule by hand: ``pull_nowait(); land_weights()``
    puts the reply (and the ack ahead of it) into the OTHER landing buffer
    and ``_ready``; the commit's guard then finds nothing to claim and
    ``wait_weights()`` hands the pull out without touching the socket.  The
    buffer the previous ``wait_weights()`` handed out — the one a running
    window program may still be reading — keeps its bytes."""
    tmpl = [np.zeros((1 << 14,), np.float32)]
    one = [np.ones((1 << 14,), np.float32)]
    if hub == "native":
        from distkeras_tpu.runtime import native

        ps = native.NativeParameterServer(tmpl, mode=native.MODE_DELTA)
    else:
        ps = DeltaParameterServer(tmpl)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=tmpl) as c:
            held = c.pull()
            for k in range(4):
                snapshot = held[0].copy()
                c.pull_nowait()             # prefetch: predates commit k
                c.land_weights()            # ...and lands beside "the program"
                assert _landed_counter() == k + 1
                assert len(c._ready) == 1
                assert not any(kind in (net.ACTION_WEIGHTS,
                                        net.ACTION_SPARSE_WEIGHTS)
                               for kind, _ in c._pending)
                np.testing.assert_array_equal(held[0], snapshot)
                stalls = _stall_samples()
                c.commit_nowait(one)
                assert _stall_samples() == stalls   # the guard claimed nothing
                sock, c.sock = c.sock, None    # any use of it raises
                try:
                    nxt = c.wait_weights()
                finally:
                    c.sock = sock
                assert nxt[0] is not held[0]
                # self-staleness 1: the snapshot misses THIS window's commit
                np.testing.assert_array_equal(nxt[0], np.full(1 << 14, float(k)))
                np.testing.assert_array_equal(held[0], snapshot)
                held = nxt
            c.drain()
            np.testing.assert_array_equal(c.pull()[0], np.full(1 << 14, 4.0))
        assert ps.num_updates == 4
    finally:
        ps.stop()


def test_land_weights_with_nothing_pending_and_the_guard_stands(telemetry):
    """With no pull in flight ``land_weights()`` returns at once, reads
    nothing and counts nothing (acks stay queued: it is not a drain).  A
    caller that never lands is still protected: ``commit_nowait`` claims the
    pending reply BEFORE any commit byte leaves — and that is not an early
    landing."""
    tmpl = [np.zeros((4,), np.float32)]
    one = [np.ones((4,), np.float32)]
    ps = DeltaParameterServer(tmpl)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=tmpl) as c:
            c.pull()
            c.commit_nowait(one)            # one ack in flight, no pull
            sock, c.sock = c.sock, None    # any use of it raises
            try:
                c.land_weights()
            finally:
                c.sock = sock
            assert [kind for kind, _ in c._pending] == [net.ACTION_ACK]
            assert not c._ready and _landed_counter() == 0

            c.pull_nowait()
            sent = []
            real = c._codec.send_streamed
            c._codec.send_streamed = lambda *a: (
                sent.append([kind for kind, _ in c._pending]), real(*a))
            stalls = _stall_samples()
            c.commit_nowait(one)
            # when the commit's bytes left, the weights reply had been claimed
            assert sent == [[]] and len(c._ready) == 1
            assert _stall_samples() == stalls + 1
            assert _landed_counter() == 0
            np.testing.assert_array_equal(c.wait_weights()[0], np.ones(4))
            c.drain()
    finally:
        ps.stop()


def test_ps_killed_mid_run_surfaces_clean_error_no_hang():
    """Fault-injection satellite: the hub dies while a worker is mid
    pull/commit traffic — PSClient must surface ConnectionError/OSError
    promptly (no hang on a half-read frame, no silent corruption)."""
    import time as _time

    ps = DeltaParameterServer([np.zeros((1 << 16,), np.float32)])
    ps.start()
    tmpl = [np.zeros((1 << 16,), np.float32)]
    c = PSClient("127.0.0.1", ps.port, templates=tmpl, timeout=10.0)
    c.pull()
    c.commit([np.ones((1 << 16,), np.float32)])  # connection is known-good
    stopper = threading.Thread(target=ps.stop)
    deadline = _time.monotonic() + 30.0
    stopper.start()
    try:
        with pytest.raises((ConnectionError, OSError, ValueError)):
            while _time.monotonic() < deadline:
                c.pull_nowait()
                c.commit_nowait([np.ones((1 << 16,), np.float32)])
                c.wait_weights()
        assert _time.monotonic() < deadline, "client hung on a dead hub"
    finally:
        stopper.join()
        c.sock.close()
    # the center survived to the last APPLIED commit — an interrupted
    # frame must never half-apply
    applied = ps.get_weights()[0]
    assert float(applied[0]) == float(applied[-1])
    assert float(applied[0]) == ps.num_updates


# -- async trainers -----------------------------------------------------------

@pytest.mark.parametrize("trainer_name", ["AsyncDOWNPOUR", "AsyncADAG", "AsyncAEASGD", "AsyncDynSGD"])
def test_async_trainers_learn(trainer_name, toy_dataset):
    import distkeras_tpu as dk
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.predictors import ModelPredictor
    from distkeras_tpu.data.transformers import LabelIndexTransformer

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2}, input_shape=(8,))
    cls = getattr(dk, trainer_name)
    kwargs = dict(loss="categorical_crossentropy", batch_size=16, num_epoch=2,
                  num_workers=4, communication_window=4, learning_rate=0.05, seed=0)
    if trainer_name in ("AsyncAEASGD",):
        kwargs["rho"] = 2.0
    trainer = cls(Model.init(spec, seed=0), **kwargs)
    model = trainer.train(toy_dataset)
    assert trainer.parameter_server.num_updates > 0
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index", label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"{trainer_name} accuracy {acc}"
    assert len(trainer.history) > 0


def test_async_checkpoint_snapshots_and_resume(toy_dataset, tmp_path):
    """Async checkpoint story (round-1 weak #7): periodic center snapshots
    + resume-from-latest-center."""
    import numpy as np

    from distkeras_tpu.checkpoint import Checkpointer
    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.runtime.async_trainer import AsyncDOWNPOUR

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    ck = Checkpointer(str(tmp_path / "async-ck"), keep=3)
    t1 = AsyncDOWNPOUR(spec, num_workers=2, communication_window=2,
                       batch_size=16, num_epoch=2, learning_rate=0.05,
                       checkpoint_interval=0.2)
    m1 = t1.train(toy_dataset, checkpointer=ck)
    # at least the final snapshot exists, and it equals the returned center
    assert ck.latest_step() is not None
    restored = ck.restore({"params": m1.params})
    for a, b in zip(jax_leaves(restored["params"]), jax_leaves(m1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # resume: a fresh trainer with the same checkpointer starts FROM the
    # snapshot center, not from init
    t2 = AsyncDOWNPOUR(spec, num_workers=2, communication_window=2,
                       batch_size=16, num_epoch=1, seed=123)
    assert t2._maybe_restore(ck) is True
    for a, b in zip(jax_leaves(t2.model.params), jax_leaves(m1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # and training from the restored center still runs end to end
    m2 = t2.train(toy_dataset, checkpointer=ck)
    assert len(t2.history) > 0


def jax_leaves(tree):
    import jax

    return jax.tree.leaves(tree)


def test_fault_injection_continue_and_raise(toy_dataset):
    """Failure-policy test (SURVEY §5 failure detection): a deterministically
    killed worker either fails the run (default) or is tolerated while the
    survivors finish ('continue')."""
    import pytest as _pytest

    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.runtime.async_trainer import AsyncDOWNPOUR

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))

    def kill_worker_1(idx, window):
        if idx == 1 and window == 1:
            raise RuntimeError("injected fault: worker 1 dies at window 1")

    common = dict(num_workers=2, communication_window=2, batch_size=16,
                  num_epoch=2, learning_rate=0.05, fault_hook=kill_worker_1)

    t = AsyncDOWNPOUR(spec, **common)
    with _pytest.raises(RuntimeError, match="injected fault"):
        t.train(toy_dataset)

    t2 = AsyncDOWNPOUR(spec, on_worker_failure="continue", **common)
    model = t2.train(toy_dataset)  # survivors finish, center returned
    assert len(t2.worker_errors) == 1
    assert "injected fault" in str(t2.worker_errors[0])
    assert len(t2.history) > 0  # worker 0 trained through both epochs
    assert model.predict(toy_dataset["features"][:8]).shape == (8, 2)


def test_q_blob_roundtrip_and_error_feedback():
    """quantize/dequantize inverts within scale/2 per element, and the
    client-side error-feedback accumulator makes the SUM of dequantized
    commits track the sum of true deltas (compression is unbiased over
    time — the property that lets int8 commits train)."""
    from distkeras_tpu.runtime.networking import (dequantize_q_blob,
                                                  quantize_q_blob)

    rng = np.random.default_rng(0)
    d = rng.normal(size=(64,)).astype(np.float32)
    blob, residual = quantize_q_blob(d)
    back = dequantize_q_blob(blob, 64)
    scale = np.frombuffer(blob[:4], ">f4")[0]
    assert np.abs(back - d).max() <= scale / 2 + 1e-7
    np.testing.assert_allclose(back + residual, d, rtol=0, atol=1e-6)

    # zero delta: scale stays 1.0, nothing divides by zero
    zb, zr = quantize_q_blob(np.zeros(8, np.float32))
    assert np.all(dequantize_q_blob(zb, 8) == 0) and np.all(zr == 0)

    # error feedback across a stream of commits
    true_sum = np.zeros(64, np.float32)
    wire_sum = np.zeros(64, np.float32)
    carry = np.zeros(64, np.float32)
    for step in range(50):
        d = rng.normal(size=(64,)).astype(np.float32) * 0.01
        true_sum += d
        blob, carry = quantize_q_blob(d + carry)
        wire_sum += dequantize_q_blob(blob, 64)
    # the residual is all that separates the sums, and it is bounded by
    # one quantum — NOT growing with the number of commits
    np.testing.assert_allclose(wire_sum, true_sum, atol=5e-3)


def test_int8_commits_land_like_f32_commits():
    """An int8-compressed commit of exactly-representable deltas must move
    the Python hub's center exactly like the f32 commit (ADAG scaling
    applies AFTER dequantization, on the hub)."""
    ps = ADAGParameterServer(_weights(), num_workers=4)
    ps.start()
    try:
        with PSClient("127.0.0.1", ps.port, templates=_weights(),
                      compress="int8") as c:
            # max|d| = 127 makes the scale exactly 1.0: quantization is
            # lossless here, isolating the wire path from rounding
            c.commit([np.full((2, 2), 127.0, np.float32),
                      np.full((3,), 127.0, np.float32)])
            w = c.pull()
            np.testing.assert_allclose(w[0], np.full((2, 2), 127.0 / 4))
            np.testing.assert_allclose(w[1], np.full((3,), 127.0 / 4))
        assert ps.num_updates == 1
    finally:
        ps.stop()


def test_compressed_async_trainer_learns(toy_dataset):
    """AsyncDOWNPOUR with compress_commits='int8' reaches the same toy
    accuracy as uncompressed — error feedback keeps training unbiased."""
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
        learning_rate=0.05, seed=0, compress_commits="int8")
    model = trainer.train(toy_dataset)
    assert trainer.parameter_server.num_updates > 0
    ds = ModelPredictor(model, features_col="features").predict(toy_dataset)
    ds = LabelIndexTransformer().transform(ds)
    acc = AccuracyEvaluator(prediction_col="prediction_index",
                            label_col="label_index").evaluate(ds)
    assert acc > 0.9, f"int8-commit training underperformed: {acc}"
