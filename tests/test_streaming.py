"""Streaming inference service (reference: the Kafka pipeline, SURVEY §2.21)."""

import numpy as np
import pytest

from conftest import join_all
from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.runtime.streaming import (
    StreamingClient, StreamingInferenceServer, stream_predict)


@pytest.fixture(scope="module")
def served_model():
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (8,), "num_outputs": 3},
                     input_shape=(6,))
    model = Model.init(spec, seed=0)
    server = StreamingInferenceServer(model, max_batch=32).start()
    yield model, server
    server.stop()


def test_stream_matches_direct_predict(served_model):
    model, server = served_model
    rng = np.random.default_rng(0)
    x = rng.normal(size=(20, 6)).astype(np.float32)  # < max_batch: padding path
    with StreamingClient("127.0.0.1", server.port) as client:
        assert client.max_batch == 32
        streamed = client.predict(x)
    direct = model.predict(x)
    np.testing.assert_allclose(streamed, direct, rtol=1e-5, atol=1e-6)


def test_many_micro_batches_one_connection(served_model):
    model, server = served_model
    rng = np.random.default_rng(1)
    with StreamingClient("127.0.0.1", server.port) as client:
        for b in (1, 7, 32, 5):  # varying sizes, no recompiles server-side
            x = rng.normal(size=(b, 6)).astype(np.float32)
            out = client.predict(x)
            assert out.shape == (b, 3)
            np.testing.assert_allclose(out, model.predict(x), rtol=1e-5, atol=1e-6)


def test_stream_predict_pipeline(served_model):
    model, server = served_model
    rng = np.random.default_rng(2)
    rows = rng.normal(size=(50, 6)).astype(np.float32)
    got_rows, got_preds = [], []
    for r, p in stream_predict("127.0.0.1", server.port, iter(rows), micro_batch=16):
        got_rows.append(r)
        got_preds.append(p)
    # 50 events at micro_batch 16 -> 16+16+16+2 (tail flushed)
    assert [len(r) for r in got_rows] == [16, 16, 16, 2]
    np.testing.assert_allclose(np.concatenate(got_rows), rows)
    np.testing.assert_allclose(np.concatenate(got_preds), model.predict(rows),
                               rtol=1e-5, atol=1e-6)


def test_oversized_batch_rejected(served_model):
    _, server = served_model
    with StreamingClient("127.0.0.1", server.port) as client:
        with pytest.raises(ValueError, match="outside"):
            client.predict(np.zeros((33, 6), np.float32))


def test_wrong_row_shape_rejected(served_model):
    _, server = served_model
    with StreamingClient("127.0.0.1", server.port) as client:
        with pytest.raises(ValueError, match="server expects"):
            client.predict(np.zeros((4, 5), np.float32))


def test_concurrent_clients(served_model):
    import threading

    model, server = served_model
    rng = np.random.default_rng(3)
    xs = [rng.normal(size=(8, 6)).astype(np.float32) for _ in range(4)]
    outs = [None] * 4
    errs = []

    def go(i):
        try:
            with StreamingClient("127.0.0.1", server.port) as c:
                outs[i] = c.predict(xs[i])
        except BaseException as e:
            errs.append(e)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    join_all(threads, "the streaming clients")
    assert not errs
    for i in range(4):
        np.testing.assert_allclose(outs[i], model.predict(xs[i]), rtol=1e-5, atol=1e-6)
