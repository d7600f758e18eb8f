"""Dataset loaders + chunked data plane."""

import numpy as np
import pytest

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.data.loaders import load_cifar10, load_cifar100, load_mnist


def test_mnist_synthetic_fallback_shapes():
    train, test, info = load_mnist()
    assert info["synthetic"] is True  # offline environment
    assert train["features"].shape == (60000, 28, 28, 1)
    assert train["features"].dtype == np.float32
    assert 0.0 <= train["features"].min() and train["features"].max() <= 1.0
    assert train["label"].shape == (60000, 10)
    assert test["label_index"].shape == (10000,)


def test_mnist_flatten():
    train, _, _ = load_mnist(flatten=True)
    assert train["features"].shape == (60000, 784)


@pytest.mark.slow  # 55-63 s alone: two 50,000-image synthetic sets are generated
def test_cifar_shapes():
    train, test, info = load_cifar10()
    assert train["features"].shape == (50000, 32, 32, 3)
    train100, _, info100 = load_cifar100()
    assert train100["label"].shape == (50000, 100)


def test_synthetic_is_deterministic_and_learnable():
    a, _, _ = load_mnist()
    b, _, _ = load_mnist()
    np.testing.assert_array_equal(a["features"][:16], b["features"][:16])
    # nearest-class-mean separability on the TRAINING means: must clearly
    # beat chance (the signal is real) but stay well below ceiling (the
    # round-3 hardening intentionally makes one-shot separation impossible
    # so wall-to-target measures training, not compile time)
    x = a["features"][:4000].reshape(4000, -1)
    y = a["label_index"][:4000]
    centers = np.stack([x[y == c].mean(axis=0) for c in range(10)])
    pred = np.argmin(((x[:, None, :] - centers[None]) ** 2).sum(-1), axis=1)
    acc = (pred == y).mean()
    assert 0.2 < acc < 0.995, acc


def test_real_npz_cache_wins(tmp_path):
    x_train = np.zeros((32, 28, 28), np.uint8)
    y_train = np.arange(32) % 10
    np.savez(tmp_path / "mnist.npz", x_train=x_train, y_train=y_train,
             x_test=x_train[:8], y_test=y_train[:8])
    train, test, info = load_mnist(cache_dir=str(tmp_path))
    assert info["synthetic"] is False
    assert train["features"].shape == (32, 28, 28, 1)
    assert len(test) == 8


def test_no_fallback_raises():
    with pytest.raises(FileNotFoundError):
        load_mnist(cache_dir="/nonexistent", synthetic_fallback=False)


# -- chunked epoch -------------------------------------------------------------

def _ds(n=100):
    return Dataset({"features": np.arange(n * 3, dtype=np.float32).reshape(n, 3),
                    "label": np.arange(n, dtype=np.int32)})


def test_chunked_epoch_covers_same_rows_as_stacked():
    ds = _ds(100)
    stacked = ds.stacked_epoch(4, ["features", "label"], window=2)
    chunks = list(ds.chunked_epoch(4, ["features", "label"], window=2, chunk_windows=5))
    assert len(chunks) == 3  # 12 windows -> 5 + 5 + 2
    assert [c["features"].shape[0] for c in chunks] == [5, 5, 2]
    rejoined = np.concatenate([c["features"] for c in chunks])
    np.testing.assert_array_equal(rejoined, stacked["features"])


def test_chunked_epoch_default_is_one_chunk():
    ds = _ds(64)
    chunks = list(ds.chunked_epoch(8, ["features"], window=1))
    assert len(chunks) == 1
    assert chunks[0]["features"].shape == (8, 1, 8, 3)


def test_chunked_epoch_chunks_are_views():
    ds = _ds(64)
    (chunk,) = ds.chunked_epoch(8, ["features"], window=1, chunk_windows=8)
    assert chunk["features"].base is not None  # zero-copy reshape of a slice


def test_chunked_training_matches_unchunked():
    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.trainers import ADAG, SingleTrainer

    rng = np.random.default_rng(0)
    n = 256
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(int)
    ds = Dataset({"features": x, "label": np.eye(2, dtype=np.float32)[y]})
    spec = ModelSpec(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 2},
                     input_shape=(8,))

    def run(cls, chunk_windows, **kw):
        t = cls(spec, loss="categorical_crossentropy", worker_optimizer="sgd",
                learning_rate=0.05, batch_size=8, num_epoch=2, seed=0,
                chunk_windows=chunk_windows, **kw)
        m = t.train(ds)
        return t, m

    for cls, kw in ((SingleTrainer, {}), (ADAG, {"communication_window": 2, "num_workers": 2})):
        t_full, m_full = run(cls, None, **kw)
        t_chunk, m_chunk = run(cls, 3, **kw)
        assert t_full.history == pytest.approx(t_chunk.history, rel=1e-5)
        for a, b in zip(np.asarray(list(m_full.params.values())[0]["kernel"]).ravel(),
                        np.asarray(list(m_chunk.params.values())[0]["kernel"]).ravel()):
            assert a == pytest.approx(b, rel=1e-5)


def test_raw_idx_mnist_files_load(tmp_path):
    """The four raw (gzipped) IDX files work as dropped in — no npz
    conversion step."""
    import gzip
    import struct

    rng = np.random.default_rng(0)

    def write_idx(name, arr):
        arr = np.asarray(arr, np.uint8)
        magic = 0x0800 | arr.ndim
        payload = struct.pack(">I", magic) + b"".join(
            struct.pack(">I", d) for d in arr.shape) + arr.tobytes()
        with gzip.open(tmp_path / (name + ".gz"), "wb") as f:
            f.write(payload)

    write_idx("train-images-idx3-ubyte", rng.integers(0, 256, (32, 28, 28)))
    write_idx("train-labels-idx1-ubyte", rng.integers(0, 10, (32,)))
    write_idx("t10k-images-idx3-ubyte", rng.integers(0, 256, (8, 28, 28)))
    write_idx("t10k-labels-idx1-ubyte", rng.integers(0, 10, (8,)))

    train, test, info = load_mnist(cache_dir=str(tmp_path), synthetic_fallback=False)
    assert not info["synthetic"]
    assert train["features"].shape == (32, 28, 28, 1)
    assert test["features"].shape == (8, 28, 28, 1)
    assert train["label"].shape == (32, 10)


def test_raw_cifar_pickle_batches_load(tmp_path):
    """The upstream pickled cifar-10-batches-py directory works as
    extracted — no conversion step."""
    import pickle

    rng = np.random.default_rng(1)
    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()
    for i in range(1, 6):
        batch = {b"data": rng.integers(0, 256, (4, 3072), dtype=np.uint8),
                 b"labels": rng.integers(0, 10, 4).tolist()}
        (d / f"data_batch_{i}").write_bytes(pickle.dumps(batch))
    test_batch = {b"data": rng.integers(0, 256, (6, 3072), dtype=np.uint8),
                  b"labels": rng.integers(0, 10, 6).tolist()}
    (d / "test_batch").write_bytes(pickle.dumps(test_batch))

    from distkeras_tpu.data.loaders import load_cifar10

    train, test, info = load_cifar10(cache_dir=str(tmp_path), synthetic_fallback=False)
    assert not info["synthetic"]
    assert train["features"].shape == (20, 32, 32, 3)
    assert test["features"].shape == (6, 32, 32, 3)


def test_raw_cifar_targz_loads(tmp_path):
    """The literal downloaded cifar-100-python.tar.gz works unextracted."""
    import io
    import pickle
    import tarfile

    rng = np.random.default_rng(2)

    def member(labels_key, n):
        return pickle.dumps({b"data": rng.integers(0, 256, (n, 3072), dtype=np.uint8),
                             labels_key: rng.integers(0, 100, n).tolist()})

    tar_path = tmp_path / "cifar-100-python.tar.gz"
    with tarfile.open(tar_path, "w:gz") as tf:
        for name, blob in (("train", member(b"fine_labels", 10)),
                           ("test", member(b"fine_labels", 4))):
            ti = tarfile.TarInfo(f"cifar-100-python/{name}")
            ti.size = len(blob)
            tf.addfile(ti, io.BytesIO(blob))

    from distkeras_tpu.data.loaders import load_cifar100

    train, test, info = load_cifar100(cache_dir=str(tmp_path), synthetic_fallback=False)
    assert not info["synthetic"]
    assert train["features"].shape == (10, 32, 32, 3)
    assert test["features"].shape == (4, 32, 32, 3)


def test_synthetic_has_label_noise_and_overlap(tmp_path, monkeypatch):
    """The stand-ins must be HARD: train labels carry noise (test clean),
    and per-pixel class signal is small against the pixel noise, so
    targets take real training instead of measuring compile time."""
    # isolate from the machine's real caches (~/.keras etc.): a dev box
    # with a cached mnist.npz must not turn this into a real-data test
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.delenv("DKT_DATA_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    train, test, info = load_mnist(cache_dir=str(tmp_path))
    assert info["synthetic"]
    x = train["features"].reshape(len(train), -1)
    y = train["label_index"]
    # per-pixel SNR: class-delta std is far below the noise std
    class_means = np.stack([x[y == c].mean(0) for c in range(10)])
    signal = class_means.std(0).mean()
    noise = np.mean([x[y == c].std(0).mean() for c in range(10)])
    assert signal < 0.35 * noise, (signal, noise)
