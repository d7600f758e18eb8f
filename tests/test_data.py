"""Dataset + transformer tests (reference behaviors from SURVEY §2.16)."""

import numpy as np
import pytest

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.data.transformers import (
    DenseTransformer,
    LabelIndexTransformer,
    MinMaxTransformer,
    OneHotTransformer,
    ReshapeTransformer,
)


def make_ds(n=20):
    return Dataset({
        "features": np.arange(n * 4, dtype=np.float32).reshape(n, 4),
        "label": np.arange(n, dtype=np.int32) % 3,
    })


def test_dataset_basics():
    ds = make_ds()
    assert len(ds) == 20
    assert set(ds.columns) == {"features", "label"}
    taken = ds.take(5)
    assert len(taken) == 5


def test_dataset_rejects_ragged_columns():
    with pytest.raises(ValueError):
        Dataset({"a": np.zeros(3), "b": np.zeros(4)})


def test_batches_drop_remainder():
    ds = make_ds(n=10)
    batches = list(ds.batches(3))
    assert len(batches) == 3
    assert all(b["features"].shape == (3, 4) for b in batches)


def test_stacked_epoch_shapes():
    ds = make_ds(n=20)
    stacked = ds.stacked_epoch(batch_size=2, columns=["features"], window=2)
    assert stacked["features"].shape == (5, 2, 2, 4)


def test_split():
    ds = make_ds(n=20)
    train, test = ds.split(0.75, seed=0)
    assert len(train) == 15 and len(test) == 5


def test_onehot_transformer():
    ds = make_ds()
    out = OneHotTransformer(3, input_col="label", output_col="onehot").transform(ds)
    onehot = out["onehot"]
    assert onehot.shape == (20, 3)
    np.testing.assert_array_equal(np.argmax(onehot, axis=1), ds["label"])
    np.testing.assert_allclose(onehot.sum(axis=1), 1.0)


def test_minmax_transformer():
    ds = Dataset({"features": np.array([[0.0], [127.5], [255.0]], dtype=np.float32)})
    out = MinMaxTransformer(0.0, 1.0, 0.0, 255.0, "features", "scaled").transform(ds)
    np.testing.assert_allclose(out["scaled"], [[0.0], [0.5], [1.0]], atol=1e-6)


def test_reshape_transformer():
    ds = Dataset({"flat": np.zeros((6, 12), dtype=np.float32)})
    out = ReshapeTransformer("flat", "img", (2, 3, 2)).transform(ds)
    assert out["img"].shape == (6, 2, 3, 2)


def test_dense_transformer():
    indices = np.array([[0, 2, -1], [1, -1, -1]], dtype=np.int32)
    values = np.array([[1.0, 3.0, 0.0], [5.0, 0.0, 0.0]], dtype=np.float32)
    ds = Dataset({"indices": indices, "values": values})
    out = DenseTransformer(size=4).transform(ds)
    np.testing.assert_allclose(out["features"], [[1, 0, 3, 0], [0, 5, 0, 0]])


def test_label_index_transformer():
    preds = np.array([[0.1, 0.8, 0.1], [0.9, 0.05, 0.05]], dtype=np.float32)
    ds = Dataset({"prediction": preds})
    out = LabelIndexTransformer(3).transform(ds)
    np.testing.assert_array_equal(out["prediction_index"], [1, 0])


def test_chunk_windows_for_budget():
    """Budget helper: chunks sized near the
    byte budget, floored at one window, loud on nonsense inputs."""
    from distkeras_tpu.data.dataset import (DEFAULT_CHUNK_BUDGET_BYTES,
                                            chunk_windows_for_budget)

    # 1 KB rows, batch 32, window 1 -> budget//32KB windows
    assert chunk_windows_for_budget(1024, 32, 1) == \
        DEFAULT_CHUNK_BUDGET_BYTES // (1024 * 32)
    # explicit budget override
    assert chunk_windows_for_budget(1024, 32, 1, budget_bytes=64 * 1024) == 2
    # a single window can exceed the budget; never returns 0
    assert chunk_windows_for_budget(10**9, 32, 1) == 1
    with pytest.raises(ValueError):
        chunk_windows_for_budget(0, 32, 1)
    with pytest.raises(ValueError):
        chunk_windows_for_budget(1024, 0, 1)


def test_trainer_auto_chunk_windows(tmp_path):
    """chunk_windows="auto" resolves per dataset via the budget helper and
    the trainer still learns through the chunked feed."""
    from distkeras_tpu.data.dataset import DEFAULT_CHUNK_BUDGET_BYTES
    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.trainers import SingleTrainer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 8)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(axis=1) > 0).astype(np.int32)]
    ds = Dataset({"features": x, "label": y})
    spec = ModelSpec(name="mlp",
                     config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))
    tr = SingleTrainer(spec, loss="categorical_crossentropy", batch_size=32,
                       num_epoch=3, learning_rate=0.1, chunk_windows="auto")
    # resolution: 32-byte rows x batch 32 = 1KB/window; budget >> dataset,
    # so auto resolves to a large step and chunked_epoch caps it at the
    # epoch — the small-data case degrades to the fast path by arithmetic
    resolved = tr._resolve_chunk_windows(ds, 32, 1)
    assert resolved == DEFAULT_CHUNK_BUDGET_BYTES // (8 * 4 * 32)
    model = tr.train(ds)
    assert tr.history[-1] < tr.history[0]
    assert model.predict(x[:4]).shape == (4, 2)
