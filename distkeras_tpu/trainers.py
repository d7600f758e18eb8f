"""Trainer API (reference parity: ``distkeras/trainers.py``).

The reference exposed ``Trainer.train(dataframe) -> keras model`` with
concrete classes ``SingleTrainer``, ``ADAG``, ``DOWNPOUR``, ``AEASGD``,
``EAMSGD``, ``DynSGD``, ``AveragingTrainer``, ``EnsembleTrainer``
(SURVEY.md §2.1–2.9).  Constructor surfaces are kept kwargs-compatible
(``num_workers``, ``batch_size``, ``communication_window``, ``rho``,
``learning_rate``, ``momentum``, ``num_epoch``, ``features_col``,
``label_col``) so reference users can switch with minimal edits; Spark
DataFrames become :class:`distkeras_tpu.data.Dataset`, "workers" become
mesh replicas, and the parameter server becomes the window engine's
collectives (see ``parallel/engine.py``).
"""

from __future__ import annotations

import time
from typing import Any, Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import observability as obs
from distkeras_tpu.checkpoint import Checkpointer
from distkeras_tpu.data.dataset import Dataset, prefetch_to_device
from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.ops.losses import get_loss
from distkeras_tpu.ops.optimizers import get_optimizer
from distkeras_tpu.parallel.algorithms import (
    AdagAlgorithm,
    Algorithm,
    DownpourAlgorithm,
    DynSGDAlgorithm,
    ElasticAlgorithm,
    NoCommitAlgorithm,
)
from distkeras_tpu.parallel.engine import WindowEngine, scan_epoch_fn
from distkeras_tpu.parallel.mesh import create_mesh


class Trainer:
    """Base trainer: holds the model, loss, worker optimizer, data columns,
    and wall-clock accounting (reference ``record_training_start/end``)."""

    def __init__(self, model: Union[Model, ModelSpec], loss: Union[str, Callable] = "categorical_crossentropy",
                 worker_optimizer: str = "sgd", learning_rate: float = 0.01,
                 momentum: Optional[float] = None,
                 features_col: str = "features", label_col: str = "label",
                 batch_size: int = 32, num_epoch: int = 1, seed: int = 0,
                 chunk_windows: Optional[Union[int, str]] = None,
                 profile_dir: Optional[str] = None):
        if isinstance(model, ModelSpec):
            model = Model.init(model, seed=seed)
        model.spec.reject_silent_aux(type(self).__name__)
        self.model = model
        self.loss = get_loss(loss)
        self.optimizer = get_optimizer(worker_optimizer, learning_rate=learning_rate, momentum=momentum)
        self.learning_rate = learning_rate
        self.features_col = features_col
        self.label_col = label_col
        self.batch_size = int(batch_size)
        self.num_epoch = int(num_epoch)
        self.seed = seed
        # bound host->device feeding to this many windows per transfer
        # (None = whole epoch in one transfer, the small-data fast path;
        # "auto" = size chunks near DEFAULT_CHUNK_BUDGET_BYTES, resolved
        # per dataset at train time)
        if chunk_windows is None or chunk_windows == "auto":
            self.chunk_windows = chunk_windows
        else:
            self.chunk_windows = int(chunk_windows)
        # observability (SURVEY §5 rows 1/5): per-epoch throughput records
        # in self.metrics; profile_dir writes a jax.profiler trace of train()
        self.profile_dir = profile_dir
        self.metrics: List[dict] = []
        self.history: List[float] = []  # per-window (or per-batch) mean loss
        self._t_start: Optional[float] = None
        self._t_end: Optional[float] = None

    def _resolve_chunk_windows(self, dataset, batch_size: int, window: int):
        """``chunk_windows`` for this dataset: passthrough unless "auto",
        which sizes chunks near the feed budget (one row's feature bytes x
        batch x window per window — ``chunk_windows_for_budget``)."""
        if self.chunk_windows != "auto":
            return self.chunk_windows
        from distkeras_tpu.data.dataset import chunk_windows_for_budget

        row_bytes = int(np.asarray(dataset[self.features_col][0]).nbytes)
        return chunk_windows_for_budget(row_bytes, batch_size, window)

    # reference API: record_training_start/record_training_end/get_training_time
    def record_training_start(self) -> None:
        self._t_start = time.time()
        self._t_end = None

    def record_training_end(self) -> None:
        self._t_end = time.time()

    def get_training_time(self) -> float:
        if self._t_start is None:
            return 0.0
        end = self._t_end if self._t_end is not None else time.time()
        return end - self._t_start

    def train(self, dataset: Dataset, shuffle: bool = True,
              checkpointer: Optional[Checkpointer] = None,
              validation_data: Optional[Dataset] = None,
              early_stopping=None) -> Model:  # pragma: no cover - interface
        raise NotImplementedError

    def _profile_ctx(self):
        """``jax.profiler.trace`` over train() when ``profile_dir`` is set
        (view with TensorBoard / xprof); no-op otherwise."""
        if self.profile_dir is None:
            import contextlib

            return contextlib.nullcontext()
        return jax.profiler.trace(self.profile_dir)

    _VAL_BATCH = 1024  # validation chunk rows: bounds device residency for
                       # big validation sets (two static shapes per run: the
                       # full chunk and one remainder)

    def _validate(self, params, validation_data: Optional[Dataset]) -> Optional[dict]:
        """Per-epoch validation: loss (always) + accuracy (classification
        labels only).  Evaluated in bounded chunks; the jitted evaluator is
        cached per classification-mode, so reusing one trainer across
        classification and regression validation sets stays correct."""
        if validation_data is None:
            return None
        y_host = validation_data[self.label_col]
        # accuracy only for classification labels: integer class indices, or
        # float rows that are actually one-hot (a float vector target that
        # isn't one-hot is regression — argmax "accuracy" would be noise).
        # A trailing size-1 axis is an index column, not a one-class one-hot.
        y_probe = y_host[..., 0] if (y_host.ndim > 1 and y_host.shape[-1] == 1) else y_host
        if np.issubdtype(y_probe.dtype, np.integer):
            classify = True
        elif y_probe.ndim > 1:
            sample = np.asarray(y_probe[:256])
            classify = bool(np.all((sample == 0) | (sample == 1))
                            and np.allclose(sample.sum(axis=-1), 1))
        else:
            classify = False
        fns = getattr(self, "_val_fns", None)
        if fns is None:
            fns = {}
            self._val_fns = fns
        if classify not in fns:
            apply = self.model.spec.apply_fn()
            loss = self.loss
            want_acc = classify

            @jax.jit
            def val(params, x, y):
                from distkeras_tpu.evaluators import _to_index

                logits = apply(params, x)
                out = {"loss_sum": loss(logits, y) * x.shape[0]}
                if want_acc:
                    if logits.ndim > 1 and logits.shape[-1] == 1:
                        pred = (logits[..., 0] > 0).astype(jnp.int32)  # single-logit binary
                    elif logits.ndim == 1:
                        pred = (logits > 0).astype(jnp.int32)
                    else:
                        pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    idx = _to_index(y)
                    # shapes are static at trace time: token-level labels
                    # ((B, T) ints vs (B, T) preds) count every element;
                    # incompatible label/logit shapes drop accuracy rather
                    # than report a broadcasting accident
                    if pred.shape == idx.shape:
                        out["correct"] = jnp.sum((pred == idx).astype(jnp.float32))
                        out["acc_denom"] = jnp.asarray(float(pred.size), jnp.float32)
                return out

            fns[classify] = val
        fn = fns[classify]
        x_host = validation_data[self.features_col]
        n = len(x_host)
        if n == 0:
            raise ValueError("validation_data is empty — 0-row validation "
                             "would silently report val_loss 0.0")
        loss_sum = correct = denom = 0.0
        have_acc = classify
        for i in range(0, n, self._VAL_BATCH):
            out = fn(params, jnp.asarray(x_host[i:i + self._VAL_BATCH]),
                     jnp.asarray(y_host[i:i + self._VAL_BATCH]))
            loss_sum += float(out["loss_sum"])
            if "correct" in out:
                correct += float(out["correct"])
                denom += float(out["acc_denom"])
            else:
                have_acc = False
        result = {"val_loss": loss_sum / n}
        if have_acc and denom > 0:
            result["val_accuracy"] = correct / denom
        return result

    class _EarlyStopping:
        """Keras-``EarlyStopping`` semantics over the per-epoch validation
        metrics: stop once ``patience`` consecutive epochs pass without a
        ``min_delta`` improvement on ``monitor`` (val_loss: lower is
        better; val_accuracy: higher; ``patience=0`` behaves like 1, as in
        Keras).  ``restore_best=True`` (default) hands the best-epoch
        weights back instead of the last ones."""

        def __init__(self, patience: int = 3, min_delta: float = 0.0,
                     monitor: str = "val_loss", restore_best: bool = True):
            if monitor not in ("val_loss", "val_accuracy"):
                raise ValueError(f"monitor must be val_loss or val_accuracy, "
                                 f"got {monitor!r}")
            self.patience = int(patience)
            self.min_delta = float(min_delta)
            self.monitor = monitor
            self.restore_best = bool(restore_best)
            self.best: Optional[float] = None
            self.best_params = None
            self.stale = 0
            self.stopped_epoch: Optional[int] = None

        def update(self, epoch: int, metrics: dict, params) -> bool:
            """Record this epoch; True = stop now."""
            if self.monitor not in metrics:
                raise ValueError(
                    f"early stopping monitors {self.monitor!r} but the epoch "
                    f"metrics lack it (keys: {sorted(metrics)}); pass "
                    "validation_data=")
            value = metrics[self.monitor]
            better = (self.best is None
                      or (value < self.best - self.min_delta
                          if self.monitor == "val_loss"
                          else value > self.best + self.min_delta))
            if better:
                self.best = value
                self.stale = 0
                if self.restore_best:
                    self.best_params = jax.tree.map(np.asarray, params)
            else:
                self.stale += 1
                if self.stale >= max(self.patience, 1):
                    self.stopped_epoch = epoch
                    return True
            return False

    @staticmethod
    def _early_stopper(early_stopping) -> Optional["Trainer._EarlyStopping"]:
        if early_stopping is None:
            return None
        if isinstance(early_stopping, Trainer._EarlyStopping):
            return early_stopping
        return Trainer._EarlyStopping(**dict(early_stopping))

    def _batch_keys(self, epoch: int, chunk_idx: int, shape) -> np.ndarray:
        """Deterministic per-(seed, epoch, chunk, batch) dropout keys —
        raw uint32 threefry pairs, one per minibatch slot in ``shape``.
        One definition for single and distributed trainers so the
        determinism contract can't silently diverge between them."""
        krng = np.random.default_rng([self.seed, epoch, chunk_idx])
        return krng.integers(0, 2**32, size=tuple(shape) + (2,), dtype=np.uint32)

    def _record_epoch_metrics(self, epoch: int, samples: int, seconds: float,
                              chips: int = 1) -> None:
        """``chips`` = devices this trainer actually engaged — NOT
        ``jax.device_count()``, which would under-report per-chip rate when
        fewer replicas than visible devices are in use.

        The per-epoch numbers stay in ``self.metrics``; the process
        telemetry registry (when enabled) counts the epochs under the
        ``trainer`` label."""
        rate = round(samples / max(seconds, 1e-9) / max(chips, 1), 1)
        self.metrics.append({
            "epoch": epoch,
            "samples": int(samples),
            "seconds": round(seconds, 4),
            "chips": int(chips),
            "samples_per_sec_per_chip": rate,
        })
        if obs.enabled():
            obs.counter("trainer_epochs_total",
                        trainer=type(self).__name__).inc()

    def _record_window_losses(self, losses) -> None:
        """Append per-window mean losses to ``history`` and (when telemetry
        is on) the ``trainer_window_loss`` histogram — the loss trace's
        queryable form."""
        values = [float(x) for x in np.asarray(losses).ravel()]
        self.history.extend(values)
        if obs.enabled() and values:
            hist = obs.histogram("trainer_window_loss",
                                 trainer=type(self).__name__)
            for v in values:
                hist.observe(v)


class SingleTrainer(Trainer):
    """Single-device training — the reference's minimal path (SURVEY §3.2):
    one coalesced partition, one worker, plain SGD.  Here: one chip, the
    epoch compiled to a single ``lax.scan`` program.

    ``checkpointer`` (no reference counterpart — SURVEY §5 "Checkpoint:
    none in-library") persists (params, opt_state) after every epoch and
    resumes from the latest checkpoint if one exists.
    """

    def train(self, dataset: Dataset, shuffle: bool = True,
              checkpointer: Optional[Checkpointer] = None,
              validation_data: Optional[Dataset] = None,
              early_stopping=None) -> Model:
        """``early_stopping``: None, a ``Trainer._EarlyStopping``, or a dict
        of its kwargs (``patience``/``min_delta``/``monitor``/
        ``restore_best``) — Keras-EarlyStopping semantics over the per-epoch
        validation metrics (requires ``validation_data=``)."""
        self.model.spec.reject_step_hook("SingleTrainer.train")
        self.record_training_start()
        stopper = self._early_stopper(early_stopping)
        if stopper is not None and validation_data is None:
            raise ValueError(
                "early_stopping monitors validation metrics; pass "
                "validation_data= (failing now beats training a full epoch "
                "before the missing metric is noticed)")
        # cached across train() calls: scan_epoch_fn returns a fresh jit
        # closure each time, which would defeat the jit cache and recompile
        # on every call (callers like the baseline runner call train() once
        # per epoch to evaluate in between)
        epoch_fn = getattr(self, "_epoch_fn", None)
        needs_rng = self.model.spec.needs_rng
        if epoch_fn is None:
            apply = (self.model.spec.train_apply_fn() if needs_rng
                     else self.model.spec.apply_fn())
            epoch_fn = scan_epoch_fn(apply, self.loss, self.optimizer,
                                     with_rng=needs_rng)
            self._epoch_fn = epoch_fn
        # epoch_fn donates its (params, opt_state) buffers; work on a copy so
        # the caller's Model object stays valid
        params = jax.tree.map(jnp.array, self.model.params)
        opt_state = self.optimizer.init(params)
        start_epoch = 0
        if checkpointer is not None:
            # resolve the step once: restore() and metadata() must read the
            # SAME checkpoint even if a concurrent writer lands a new one
            ckpt_step = checkpointer.latest_step()
            if ckpt_step is not None:
                restored = checkpointer.restore({"params": params, "opt_state": opt_state},
                                                step=ckpt_step)
                params = jax.tree.map(jnp.asarray, restored["params"])
                opt_state = jax.tree.map(jnp.asarray, restored["opt_state"])
                start_epoch = int(checkpointer.metadata(step=ckpt_step)["metadata"]["epochs_done"])
        with self._profile_ctx():
            for epoch in range(start_epoch, self.num_epoch):
                t_epoch = time.time()
                samples = 0
                ds = dataset.shuffle(seed=self.seed + epoch) if shuffle else dataset

                def place(chunk):
                    # async H2D issue only — prefetch_to_device overlaps the
                    # next chunk's copy-in with this chunk's training
                    return (jnp.asarray(chunk[self.features_col].squeeze(1)),
                            jnp.asarray(chunk[self.label_col].squeeze(1)))

                placed = prefetch_to_device(
                    ds.chunked_epoch(self.batch_size,
                                     [self.features_col, self.label_col],
                                     window=1,
                                     chunk_windows=self._resolve_chunk_windows(
                                         ds, self.batch_size, 1)),
                    place)
                with obs.span("trainer.epoch", trainer=type(self).__name__,
                              epoch=epoch):
                    for chunk_idx, (xs, ys) in enumerate(placed):
                        if needs_rng:
                            keys = self._batch_keys(epoch, chunk_idx, (xs.shape[0],))
                            params, opt_state, losses = epoch_fn(
                                params, opt_state, xs, ys, jnp.asarray(keys))
                        else:
                            params, opt_state, losses = epoch_fn(params, opt_state,
                                                                 xs, ys)
                        self._record_window_losses(losses)
                        samples += xs.shape[0] * xs.shape[1]
                self._record_epoch_metrics(epoch, samples, time.time() - t_epoch, chips=1)
                val = self._validate(params, validation_data)
                if val:
                    self.metrics[-1].update(val)
                if checkpointer is not None:
                    checkpointer.save(epoch + 1, {"params": params, "opt_state": opt_state},
                                      metadata={"epochs_done": epoch + 1})
                if stopper is not None and stopper.update(epoch, self.metrics[-1], params):
                    if stopper.restore_best and stopper.best_params is not None:
                        params = jax.tree.map(jnp.asarray, stopper.best_params)
                    break
        self.model = Model(spec=self.model.spec, params=params)
        self.record_training_end()
        return self.model


class DistributedTrainer(Trainer):
    """Common scaffolding for mesh-replica training (reference §2.4).

    ``num_workers`` defaults to every visible device.  Subclasses provide
    ``allocate_algorithm()`` — the analogue of the reference's
    ``allocate_worker``/``allocate_parameter_server`` factory pair, now a
    single collective update rule.
    """

    def __init__(self, model, num_workers: Optional[int] = None, communication_window: int = 5,
                 mesh=None, **kwargs):
        super().__init__(model, **kwargs)
        self.communication_window = int(communication_window)
        self.mesh = mesh if mesh is not None else create_mesh(num_workers)
        self.num_workers = self.mesh.shape["replica"]
        self._engine: Optional[WindowEngine] = None

    def allocate_algorithm(self) -> Algorithm:  # pragma: no cover - interface
        raise NotImplementedError

    def _divergent_seeds(self) -> Optional[Sequence[int]]:
        return None

    @property
    def engine(self) -> WindowEngine:
        if self._engine is None:
            self._engine = WindowEngine(
                spec=self.model.spec,
                loss=self.loss,
                optimizer=self.optimizer,
                algorithm=self.allocate_algorithm(),
                mesh=self.mesh,
                window=self.communication_window,
            )
        return self._engine

    def _validation_params(self, state):
        """Params the per-epoch validation should score — the center for
        PS-style trainers; overridden where the center is not the artifact
        (AveragingTrainer scores the average of the replicas)."""
        return self.engine.center_model(state).params

    def _restore_best(self, model: Model) -> Model:
        """Swap in the early-stopping best-epoch weights when a stop
        recorded them; shared by every train() so subclasses overriding
        train() cannot silently drop restoration."""
        if getattr(self, "_es_best_params", None) is not None:
            return Model(spec=self.model.spec,
                         params=jax.tree.map(jnp.asarray, self._es_best_params))
        return model

    def _run_epochs(self, dataset: Dataset, shuffle: bool,
                    checkpointer: Optional[Checkpointer] = None,
                    validation_data: Optional[Dataset] = None,
                    early_stopping=None) -> Any:
        stopper = self._early_stopper(early_stopping)
        if stopper is not None and validation_data is None:
            raise ValueError(
                "early_stopping monitors validation metrics; pass "
                "validation_data= (failing now beats training a full epoch "
                "before the missing metric is noticed)")
        self._es_best_params = None  # set when early stopping restores best
        engine = self.engine
        state = engine.init_state(self.model, divergent_seeds=self._divergent_seeds())
        start_epoch = 0
        if checkpointer is not None:
            ckpt_step = checkpointer.latest_step()
            if jax.process_count() > 1:
                # every process MUST resume from the same step or they
                # issue different numbers of collectives and the job
                # hangs: process 0's view of the spool is authoritative
                # (it is the writer).  A process that then can't READ
                # that step fails loudly — the checkpoint dir must be a
                # shared filesystem.
                from jax.experimental import multihost_utils

                step = multihost_utils.broadcast_one_to_all(
                    np.int64(-1 if ckpt_step is None else ckpt_step))
                ckpt_step = None if int(step) < 0 else int(step)
            if ckpt_step is not None:
                restored = checkpointer.restore({"state": state}, step=ckpt_step)["state"]
                state = engine.shard_state(restored)
                start_epoch = int(checkpointer.metadata(step=ckpt_step)["metadata"]["epochs_done"])
        global_batch = self.batch_size * self.num_workers
        with self._profile_ctx():
            for epoch in range(start_epoch, self.num_epoch):
                t_epoch = time.time()
                samples = 0
                ds = dataset.shuffle(seed=self.seed + epoch) if shuffle else dataset
                placed = prefetch_to_device(
                    ds.chunked_epoch(global_batch,
                                     [self.features_col, self.label_col],
                                     window=self.communication_window,
                                     chunk_windows=self._resolve_chunk_windows(
                                         ds, global_batch,
                                         self.communication_window)),
                    lambda ch: engine.place_data(ch[self.features_col],
                                                 ch[self.label_col]))
                with obs.span("trainer.epoch", trainer=type(self).__name__,
                              epoch=epoch):
                    for chunk_idx, (xs_d, ys_d) in enumerate(placed):
                        keys = None
                        if engine.needs_rng:
                            keys = self._batch_keys(epoch, chunk_idx, xs_d.shape[:2])
                        state, losses = engine.run_epoch(state, xs_d, ys_d, keys=keys)
                        self._record_window_losses(losses)
                        samples += (xs_d.shape[0]
                                    * self.communication_window * global_batch)
                self._record_epoch_metrics(epoch, samples, time.time() - t_epoch,
                                           chips=self.num_workers)
                if validation_data is not None:
                    vparams = self._validation_params(state)
                    val = self._validate(vparams, validation_data)
                    self.metrics[-1].update(val)
                if checkpointer is not None:
                    if jax.process_count() > 1:
                        # replicas live on other hosts: ALL processes run
                        # the row-gather collectives, only process 0
                        # materializes the host copy and writes.  The
                        # barrier after the write is what makes the spool
                        # consistent: without it another process can
                        # finish train(), start a resumed run, and read
                        # latest_step() BEFORE process 0's atomic rename
                        # lands — divergent start_epochs then issue
                        # mismatched collectives and the job hangs.  (If
                        # process 0 dies mid-save the others block here
                        # until the distributed runtime declares it dead
                        # — a visible failure, not silent divergence.)
                        from jax.experimental import multihost_utils

                        writer = jax.process_index() == 0
                        host_state = engine.gather_state(state, to_host=writer)
                        if writer:
                            checkpointer.save(epoch + 1, {"state": host_state},
                                              metadata={"epochs_done": epoch + 1})
                        multihost_utils.sync_global_devices(
                            f"distkeras-ckpt-{epoch + 1}")
                    else:
                        checkpointer.save(epoch + 1, {"state": state},
                                          metadata={"epochs_done": epoch + 1})
                if stopper is not None and stopper.update(
                        epoch, self.metrics[-1], vparams):
                    if stopper.restore_best and stopper.best_params is not None:
                        self._es_best_params = stopper.best_params
                    break
        return state

    def train(self, dataset: Dataset, shuffle: bool = True,
              checkpointer: Optional[Checkpointer] = None,
              validation_data: Optional[Dataset] = None,
              early_stopping=None) -> Model:
        """``early_stopping``: see ``SingleTrainer.train`` — monitored on
        the center/average params the trainer would hand back."""
        self.record_training_start()
        state = self._run_epochs(dataset, shuffle, checkpointer, validation_data,
                                 early_stopping=early_stopping)
        self.model = self._restore_best(self.engine.center_model(state))
        self.record_training_end()
        return self.model


class ADAG(DistributedTrainer):
    """Asynchronous Distributed Adaptive Gradients (reference §2.6):
    windowed delta commits, normalized on the center."""

    def allocate_algorithm(self) -> Algorithm:
        return AdagAlgorithm()


class DOWNPOUR(DistributedTrainer):
    """Downpour SGD (reference §2.5): raw accumulated-delta commits."""

    def allocate_algorithm(self) -> Algorithm:
        return DownpourAlgorithm()


class AEASGD(DistributedTrainer):
    """Asynchronous elastic averaging SGD (reference §2.8)."""

    def __init__(self, model, rho: float = 5.0, communication_window: int = 32, **kwargs):
        super().__init__(model, communication_window=communication_window, **kwargs)
        if callable(self.learning_rate):
            raise ValueError(
                "elastic trainers need a scalar learning_rate (the elastic "
                "coupling alpha = rho * lr is a constant); to schedule the "
                "local steps, pass an optax optimizer built with the schedule "
                "as worker_optimizer and keep learning_rate scalar")
        self.rho = float(rho)

    def allocate_algorithm(self) -> Algorithm:
        return ElasticAlgorithm(rho=self.rho, learning_rate=self.learning_rate)


class EAMSGD(AEASGD):
    """Elastic averaging with momentum on the local step (reference §2.9).
    Same elastic commit as AEASGD; the momentum lives in the local optax
    optimizer (Nesterov by default, per the EAMSGD paper)."""

    def __init__(self, model, rho: float = 5.0, momentum: float = 0.9, **kwargs):
        kwargs.setdefault("worker_optimizer", "nesterov")
        super().__init__(model, rho=rho, momentum=momentum, **kwargs)


class DynSGD(DistributedTrainer):
    """Staleness-aware dynamic learning rate (reference §2.7):
    commit r scaled by 1/(staleness_r + 1)."""

    def allocate_algorithm(self) -> Algorithm:
        return DynSGDAlgorithm()


class AveragingTrainer(DistributedTrainer):
    """Train N independent replicas, then average weights (reference §2.2)."""

    def __init__(self, model, **kwargs):
        kwargs.setdefault("communication_window", 1)
        super().__init__(model, **kwargs)

    def allocate_algorithm(self) -> Algorithm:
        return NoCommitAlgorithm()

    def _validation_params(self, state):
        # NoCommit leaves the center at init; the meaningful per-epoch
        # artifact is the average of the replicas
        return self.engine.averaged_model(state).params

    def train(self, dataset: Dataset, shuffle: bool = True,
              checkpointer: Optional[Checkpointer] = None,
              validation_data: Optional[Dataset] = None,
              early_stopping=None) -> Model:
        self.record_training_start()
        state = self._run_epochs(dataset, shuffle, checkpointer, validation_data,
                                 early_stopping=early_stopping)
        self.model = self._restore_best(self.engine.averaged_model(state))
        self.record_training_end()
        return self.model


class EnsembleTrainer(DistributedTrainer):
    """Train N independent models and return all of them (reference §2.3).

    ``decorrelate=True`` re-initializes each member from its own seed
    (reference used ``utils.uniform_weights`` for this).
    """

    def __init__(self, model, decorrelate: bool = True, **kwargs):
        kwargs.setdefault("communication_window", 1)
        super().__init__(model, **kwargs)
        self.decorrelate = decorrelate

    def allocate_algorithm(self) -> Algorithm:
        return NoCommitAlgorithm()

    def _divergent_seeds(self) -> Optional[Sequence[int]]:
        if not self.decorrelate:
            return None
        return [self.seed + 1000 + i for i in range(self.num_workers)]

    def train(self, dataset: Dataset, shuffle: bool = True,
              checkpointer: Optional[Checkpointer] = None,
              validation_data: Optional[Dataset] = None,
              early_stopping=None) -> List[Model]:  # type: ignore[override]
        if validation_data is not None or early_stopping is not None:
            raise ValueError(
                "per-epoch validation (and early stopping on it) is "
                "ambiguous for an ensemble (N independent members, no "
                "single center); evaluate the returned models with "
                "ModelPredictor/AccuracyEvaluator")
        self.record_training_start()
        state = self._run_epochs(dataset, shuffle, checkpointer)
        models = self.engine.local_models(state)
        self.record_training_end()
        return models
