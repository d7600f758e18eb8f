"""Trainer integration tests on the simulated 8-chip slice (SURVEY §4.2/4.3)."""

import jax
import numpy as np
import pytest

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.evaluators import AccuracyEvaluator
from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.predictors import ModelPredictor
from distkeras_tpu.trainers import (
    ADAG,
    AEASGD,
    DOWNPOUR,
    AveragingTrainer,
    DynSGD,
    EAMSGD,
    EnsembleTrainer,
    SingleTrainer,
)


def tiny_mlp_spec():
    return ModelSpec(name="mlp", config={"hidden_sizes": (32,), "num_outputs": 2}, input_shape=(8,))


def accuracy_of(model, dataset):
    ds = ModelPredictor(model, features_col="features").predict(dataset)
    return AccuracyEvaluator(prediction_col="prediction", label_col="label_index").evaluate(ds)


def test_single_trainer_learns(toy_dataset):
    trainer = SingleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                            worker_optimizer="sgd", learning_rate=0.1,
                            batch_size=64, num_epoch=5)
    model = trainer.train(toy_dataset)
    assert trainer.history[-1] < trainer.history[0]
    assert accuracy_of(model, toy_dataset) > 0.95
    assert trainer.get_training_time() > 0


@pytest.mark.parametrize("trainer_cls,kwargs", [
    (ADAG, {"communication_window": 2}),
    (DOWNPOUR, {"communication_window": 4, "learning_rate": 0.01}),
    (AEASGD, {"communication_window": 4, "rho": 1.0}),
    (EAMSGD, {"communication_window": 4, "rho": 1.0, "momentum": 0.9}),
    (DynSGD, {"communication_window": 2}),
])
def test_distributed_trainers_learn(toy_dataset, trainer_cls, kwargs):
    kwargs = dict(kwargs)
    kwargs.setdefault("learning_rate", 0.05)
    trainer = trainer_cls(tiny_mlp_spec(), loss="categorical_crossentropy",
                          worker_optimizer=kwargs.pop("worker_optimizer", "sgd"),
                          num_workers=8, batch_size=8, num_epoch=4, **kwargs)
    model = trainer.train(toy_dataset)
    assert accuracy_of(model, toy_dataset) > 0.9, f"{trainer_cls.__name__} failed to learn"


def test_adag_window1_matches_large_batch_sgd(toy_dataset):
    """ADAG with window=1 is exactly large-batch SGD: center' =
    center − lr · mean_r grad_r — must match a single-device run on the
    same global batches (the sync-equivalence anchor for the collectives)."""
    lr, bs, workers = 0.1, 16, 8
    single = SingleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                           worker_optimizer="sgd", learning_rate=lr,
                           batch_size=bs * workers, num_epoch=1, seed=0)
    m_single = single.train(toy_dataset, shuffle=False)

    adag = ADAG(tiny_mlp_spec(), loss="categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=lr, num_workers=workers,
                batch_size=bs, communication_window=1, num_epoch=1, seed=0)
    m_adag = adag.train(toy_dataset, shuffle=False)

    for a, b in zip(jax.tree.leaves(m_single.params), jax.tree.leaves(m_adag.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_averaging_trainer(toy_dataset):
    trainer = AveragingTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                               learning_rate=0.1, num_workers=8, batch_size=8, num_epoch=3)
    model = trainer.train(toy_dataset)
    assert accuracy_of(model, toy_dataset) > 0.9


def test_ensemble_trainer_returns_n_distinct_models(toy_dataset):
    trainer = EnsembleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                              learning_rate=0.1, num_workers=8, batch_size=8, num_epoch=2)
    models = trainer.train(toy_dataset)
    assert len(models) == 8
    p0 = jax.tree.leaves(models[0].params)[0]
    p1 = jax.tree.leaves(models[1].params)[0]
    assert not np.allclose(np.asarray(p0), np.asarray(p1))
    assert accuracy_of(models[0], toy_dataset) > 0.85


def test_determinism_same_seed_same_result(toy_dataset):
    """Sync path determinism (SURVEY §5 race-detection replacement)."""
    def run():
        t = ADAG(tiny_mlp_spec(), loss="categorical_crossentropy", learning_rate=0.05,
                 num_workers=8, batch_size=8, communication_window=2, num_epoch=1, seed=123)
        return t.train(toy_dataset)

    m1, m2 = run(), run()
    for a, b in zip(jax.tree.leaves(m1.params), jax.tree.leaves(m2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_metrics_recorded_single_and_distributed(toy_dataset):
    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.trainers import ADAG, SingleTrainer

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (8,), "num_outputs": 2},
                     input_shape=(8,))
    for cls, kw in ((SingleTrainer, {}), (ADAG, {"num_workers": 2, "communication_window": 2})):
        t = cls(spec, loss="categorical_crossentropy", batch_size=16, num_epoch=2, **kw)
        t.train(toy_dataset)
        assert len(t.metrics) == 2
        for rec in t.metrics:
            assert rec["samples"] > 0 and rec["seconds"] > 0
            assert rec["samples_per_sec_per_chip"] > 0
        # every sample fed is accounted for exactly once per epoch
        assert t.metrics[0]["samples"] <= len(toy_dataset)


def test_profile_dir_writes_trace(toy_dataset, tmp_path):
    import os

    from distkeras_tpu.models.base import ModelSpec
    from distkeras_tpu.trainers import SingleTrainer

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (8,), "num_outputs": 2},
                     input_shape=(8,))
    t = SingleTrainer(spec, loss="categorical_crossentropy", batch_size=16,
                      num_epoch=1, profile_dir=str(tmp_path / "prof"))
    t.train(toy_dataset)
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(tmp_path / "prof") for f in fs]
    assert files, "profiler trace directory is empty"


def test_async_rejects_non_float32_params():
    import jax
    import numpy as np
    import pytest as _pytest

    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model, ModelSpec
    from distkeras_tpu.runtime.async_trainer import AsyncDOWNPOUR

    spec = ModelSpec(name="mlp", config={"hidden_sizes": (8,), "num_outputs": 2},
                     input_shape=(4,))
    m = Model.init(spec, seed=0)
    m = Model(spec=spec, params=jax.tree.map(lambda x: x.astype("bfloat16"), m.params))
    ds = Dataset({"features": np.zeros((64, 4), np.float32),
                  "label": np.eye(2, dtype=np.float32)[np.zeros(64, int)]})
    t = AsyncDOWNPOUR(m, num_workers=1, batch_size=16, num_epoch=1)
    with _pytest.raises(TypeError, match="float32"):
        t.train(ds)


def test_validation_data_records_per_epoch_metrics():
    import numpy as _np

    from distkeras_tpu.data.dataset import Dataset as _DS
    from distkeras_tpu.models.base import ModelSpec as _MS
    from distkeras_tpu.trainers import ADAG as _ADAG, SingleTrainer as _ST

    rng = _np.random.default_rng(0)
    x = rng.normal(size=(128, 8)).astype(_np.float32)
    w = rng.normal(size=(8, 3)).astype(_np.float32)
    labels = _np.argmax(x @ w, axis=1)
    onehot = _np.eye(3, dtype=_np.float32)[labels]
    train = _DS({"features": x[:96], "label": onehot[:96]})
    val = _DS({"features": x[96:], "label": onehot[96:]})
    spec = _MS(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 3},
               input_shape=(8,))

    tr = _ST(spec, batch_size=32, num_epoch=3, learning_rate=0.1)
    tr.train(train, validation_data=val)
    assert len(tr.metrics) == 3
    assert all("val_loss" in m and "val_accuracy" in m for m in tr.metrics)
    # training on a separable task: val accuracy must improve over random
    assert tr.metrics[-1]["val_accuracy"] > 0.5
    assert tr.metrics[-1]["val_loss"] < tr.metrics[0]["val_loss"]

    tr2 = _ADAG(spec, num_workers=8, batch_size=4, num_epoch=2,
                communication_window=2, learning_rate=0.1)
    tr2.train(train, validation_data=val)
    assert all("val_loss" in m for m in tr2.metrics)

    # regression labels (float vector targets): loss only, no accuracy
    reg = _DS({"features": x[:96], "label": (x[:96] @ w).astype(_np.float32)})
    regval = _DS({"features": x[96:], "label": (x[96:] @ w).astype(_np.float32)})
    spec_r = _MS(name="mlp", config={"hidden_sizes": (16,), "num_outputs": 3},
                 input_shape=(8,))
    tr3 = _ST(spec_r, loss="mse", batch_size=32, num_epoch=1, learning_rate=0.01)
    tr3.train(reg, validation_data=regval)
    assert "val_loss" in tr3.metrics[-1]
    assert "val_accuracy" not in tr3.metrics[-1]

    # (N, 1) integer index labels must not argmax-collapse to class 0
    idx = _DS({"features": x[:96], "label": labels[:96].reshape(-1, 1)})
    idxval = _DS({"features": x[96:], "label": labels[96:].reshape(-1, 1)})
    tr4 = _ST(spec, loss="sparse_categorical_crossentropy",
              batch_size=32, num_epoch=3, learning_rate=0.1)
    # sparse CE wants [N] int labels; reshape col inside a wrapper loss
    import jax.numpy as _jnp
    from distkeras_tpu.ops.losses import get_loss as _gl
    sce = _gl("sparse_categorical_crossentropy")
    tr4.loss = lambda logits, y: sce(logits, y.reshape(-1))
    tr4.train(idx, validation_data=idxval)
    assert tr4.metrics[-1]["val_accuracy"] > 0.5

    # averaging trainer validates the averaged model; ensemble refuses
    from distkeras_tpu.trainers import AveragingTrainer as _AT, EnsembleTrainer as _ET
    tr5 = _AT(spec, num_workers=8, batch_size=4, num_epoch=1, learning_rate=0.1)
    tr5.train(train, validation_data=val)
    assert "val_accuracy" in tr5.metrics[-1]
    with pytest.raises(ValueError, match="ambiguous"):
        _ET(spec, num_workers=8, batch_size=4, num_epoch=1).train(
            train, validation_data=val)

    # token-level (B, T) int labels: accuracy counts tokens, not rows
    from distkeras_tpu.models.transformer import small_lm_spec as _lm
    lm_spec = _lm(vocab_size=16, model_dim=16, num_heads=2, num_layers=1,
                  max_seq_len=8)
    lm_spec.config["compute_dtype"] = "float32"
    toks = rng.integers(0, 16, (32, 8)).astype(_np.int32)
    tgts = _np.roll(toks, -1, axis=1).astype(_np.int32)
    lm_ds = _DS({"features": toks, "label": tgts})
    tr6 = _ST(lm_spec, loss=lambda logits, y: _optax_sce(logits, y),
              batch_size=8, num_epoch=1, learning_rate=0.01)
    tr6.train(lm_ds, validation_data=lm_ds)
    assert 0.0 <= tr6.metrics[-1]["val_accuracy"] <= 1.0

    # empty validation set is a loud error, not a fake perfect score
    with pytest.raises(ValueError, match="empty"):
        _ST(spec, batch_size=32, num_epoch=1).train(
            train, validation_data=_DS({"features": x[:0], "label": onehot[:0]}))


def _optax_sce(logits, y):
    import jax.numpy as _jnp
    import optax as _optax

    return _optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(_jnp.float32), y).mean()


def test_single_trainer_early_stopping_stops_and_restores(toy_dataset):
    # an impossible min_delta means epoch 0 sets the best and every later
    # epoch is "no improvement": patience=1 stops at epoch 2 of 10
    trainer = SingleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                            worker_optimizer="sgd", learning_rate=0.1,
                            batch_size=64, num_epoch=10)
    model = trainer.train(toy_dataset, validation_data=toy_dataset,
                          early_stopping={"patience": 2, "min_delta": 1e9,
                                          "monitor": "val_loss"})
    assert len(trainer.metrics) == 3  # epoch 0 best + 2 stale (Keras >=)
    # restore_best hands back the epoch-0 weights: retraining one epoch
    # from them must reproduce epoch 1's val_loss trajectory start
    assert model is not None


def test_single_trainer_early_stopping_needs_validation(toy_dataset):
    trainer = SingleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                            worker_optimizer="sgd", learning_rate=0.1,
                            batch_size=64, num_epoch=3)
    with pytest.raises(ValueError, match="validation_data"):
        # pre-flight: must fail BEFORE any epoch trains
        trainer.train(toy_dataset, early_stopping={"patience": 0})
    assert len(trainer.metrics) == 0


def test_distributed_trainer_early_stopping(toy_dataset):
    trainer = ADAG(tiny_mlp_spec(), loss="categorical_crossentropy",
                   worker_optimizer="sgd", learning_rate=0.05,
                   num_workers=8, batch_size=8, num_epoch=10,
                   communication_window=2)
    model = trainer.train(toy_dataset, validation_data=toy_dataset,
                          early_stopping={"patience": 0, "min_delta": 1e9,
                                          "monitor": "val_loss"})
    assert len(trainer.metrics) == 2  # epoch 0 best, epoch 1 stops
    # restore_best: returned model is the epoch-0 center snapshot
    assert model.params is not None


def test_ensemble_rejects_early_stopping(toy_dataset):
    trainer = EnsembleTrainer(tiny_mlp_spec(), loss="categorical_crossentropy",
                              worker_optimizer="sgd", learning_rate=0.05,
                              num_workers=4, batch_size=8, num_epoch=2)
    with pytest.raises(ValueError, match="ambiguous for an ensemble"):
        trainer.train(toy_dataset, early_stopping={"patience": 1})


def test_accuracy_evaluator_rejects_integer_onehot():
    # integer arrays are always class indices; an int one-hot column must
    # raise with guidance, not broadcast into a wrong accuracy
    ds = Dataset({"prediction_index": np.array([0, 1, 1, 0]),
                  "label": np.eye(2, dtype=np.int64)[[0, 1, 0, 1]]})
    ev = AccuracyEvaluator(prediction_col="prediction_index", label_col="label")
    with pytest.raises(ValueError, match="Integer label"):
        ev.evaluate(ds)


def test_async_elastic_rejects_schedule_learning_rate():
    import optax

    from distkeras_tpu.runtime.async_trainer import AsyncAEASGD, AsyncEAMSGD

    sched = optax.exponential_decay(0.1, 10, 0.9)
    for cls in (AsyncAEASGD, AsyncEAMSGD):
        with pytest.raises(ValueError, match="scalar learning_rate"):
            cls(tiny_mlp_spec(), loss="categorical_crossentropy",
                num_workers=2, learning_rate=sched)


def test_engine_steady_state_rate_preserves_state(toy_dataset):
    """steady_state_rate compiles a multi-epoch program, reports a positive
    rate, and must NOT consume the caller's state (the epoch program
    donates its inputs; the method copies internally)."""
    trainer = ADAG(tiny_mlp_spec(), loss="categorical_crossentropy",
                   worker_optimizer="sgd", learning_rate=0.05,
                   num_workers=8, batch_size=8, num_epoch=1,
                   communication_window=2)
    trainer.train(toy_dataset)
    engine = trainer.engine
    state = engine.init_state(trainer.model)
    chunk = next(iter(toy_dataset.chunked_epoch(
        64, ["features", "label"], window=2, chunk_windows=2)))
    rate = engine.steady_state_rate(state, chunk["features"], chunk["label"],
                                    reps=2, repeat=2)
    assert rate > 0
    # the caller's state is still alive and usable afterwards
    state2, losses = engine.run_epoch(state, chunk["features"], chunk["label"])
    assert np.isfinite(losses).all()
