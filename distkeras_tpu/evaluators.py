"""Evaluators (reference parity: ``distkeras/evaluators.py``).

Reference: ``AccuracyEvaluator(prediction_col, label_col).evaluate(df)``
computed classification accuracy by comparing two DataFrame columns.
Here the comparison is one jit'd reduction over whole columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.data.dataset import Dataset


class Evaluator:
    def evaluate(self, dataset: Dataset) -> float:  # pragma: no cover - interface
        raise NotImplementedError


class AccuracyEvaluator(Evaluator):
    """Fraction of rows where prediction matches label.

    Accepts class-index columns, one-hot/probability-vector columns, or a
    mix (vectors are argmax'd) — covering both the reference usage
    (``LabelIndexTransformer`` output vs integer label) and direct logits.
    """

    def __init__(self, prediction_col: str = "prediction_index", label_col: str = "label"):
        self.prediction_col = prediction_col
        self.label_col = label_col

        def acc(pred, label):
            p, l = _pred_to_index(pred), _to_index(label)
            if p.shape != l.shape:
                # e.g. an INTEGER-dtype one-hot label column: integer arrays
                # are always treated as class indices (so (B, T) token
                # labels survive), which would otherwise broadcast into a
                # silently wrong accuracy whenever shapes happen to align
                raise ValueError(
                    f"prediction indices {p.shape} vs label indices {l.shape}: "
                    "shapes must match after index conversion. Integer label "
                    "columns are taken as class indices whatever their rank — "
                    "convert one-hot labels to float, or argmax them first")
            return jnp.mean((p == l).astype(jnp.float32))

        self._fn = jax.jit(acc)

    def evaluate(self, dataset: Dataset) -> float:
        return float(self._fn(jnp.asarray(dataset[self.prediction_col]), jnp.asarray(dataset[self.label_col])))


def _to_index(col: jnp.ndarray) -> jnp.ndarray:
    """Class-index or one-hot/probability column -> int32 class indices.

    A trailing size-1 axis is an index column wearing a column shape
    ((N, 1) from dataframe-style sources), NOT a one-class one-hot —
    argmax over it would collapse every row to 0.  Integer arrays are
    ALWAYS indices whatever their rank ((B, T) token labels stay (B, T));
    only float arrays argmax over the class axis."""
    if col.ndim > 1 and col.shape[-1] == 1:
        col = col[..., 0]
    if col.ndim > 1 and not jnp.issubdtype(col.dtype, jnp.integer):
        col = jnp.argmax(col, axis=-1)
    return col.astype(jnp.int32)


def _pred_to_index(col: jnp.ndarray) -> jnp.ndarray:
    """Model-output column -> int32 class indices.

    Differs from ``_to_index`` on 1-D (or (N, 1)) FLOAT columns: a model's
    scalar output is a single-logit binary score (class = logit > 0, the
    raw-logit convention the trainers' validation path also uses), not a
    float-coded class id — truncating 2.7 to class 2 would be noise."""
    if col.ndim > 1 and col.shape[-1] == 1:
        col = col[..., 0]
    if col.ndim > 1:
        col = jnp.argmax(col, axis=-1)
    elif not jnp.issubdtype(col.dtype, jnp.integer):
        col = col > 0
    return col.astype(jnp.int32)


class TopKAccuracyEvaluator(Evaluator):
    """Fraction of rows whose true class is in the top-k predictions.

    Needs a vector prediction column (logits/probabilities); beyond the
    reference surface (which had accuracy only), standard for the CIFAR/
    ImageNet-style configs of ``BASELINE.json``.
    """

    def __init__(self, k: int = 5, prediction_col: str = "prediction",
                 label_col: str = "label"):
        self.k = int(k)
        self.prediction_col = prediction_col
        self.label_col = label_col

        def topk(pred, label):
            if pred.ndim < 2:
                raise ValueError("TopKAccuracyEvaluator needs a vector "
                                 "prediction column (logits/probabilities)")
            label = _to_index(label)
            _, idx = jax.lax.top_k(pred, self.k)
            return jnp.mean(jnp.any(idx == label[:, None], axis=-1).astype(jnp.float32))

        self._fn = jax.jit(topk)

    def evaluate(self, dataset: Dataset) -> float:
        return float(self._fn(jnp.asarray(dataset[self.prediction_col]),
                              jnp.asarray(dataset[self.label_col])))


class ConfusionMatrixEvaluator(Evaluator):
    """num_classes x num_classes counts: rows = true class, cols = predicted.

    ``evaluate`` returns the matrix as a numpy int array (not a float) —
    the building block for any derived metric.
    """

    def __init__(self, num_classes: int, prediction_col: str = "prediction_index",
                 label_col: str = "label"):
        self.num_classes = int(num_classes)
        self.prediction_col = prediction_col
        self.label_col = label_col

        def confusion(pred, label):
            pred, label = _pred_to_index(pred), _to_index(label)
            c = self.num_classes
            # out-of-range indices (e.g. the common -1 "ignore" sentinel, or
            # an index >= num_classes) must not clamp into bin 0 / vanish —
            # route them to an overflow bin that is sliced off
            valid = (pred >= 0) & (pred < c) & (label >= 0) & (label < c)
            flat = jnp.where(valid, label * c + pred, c * c)
            counts = jnp.bincount(flat, length=c * c + 1)
            return counts[: c * c].reshape(c, c)

        self._fn = jax.jit(confusion)

    def evaluate(self, dataset: Dataset) -> np.ndarray:
        return np.asarray(self._fn(jnp.asarray(dataset[self.prediction_col]),
                                   jnp.asarray(dataset[self.label_col])))


class PrecisionRecallF1Evaluator(Evaluator):
    """Per-class precision/recall/F1 plus macro averages, from the
    confusion matrix.  ``evaluate`` returns a dict:
    ``{"precision": [C], "recall": [C], "f1": [C], "macro_precision": x,
    "macro_recall": x, "macro_f1": x}`` (zero-division yields 0, the
    sklearn ``zero_division=0`` convention).
    """

    def __init__(self, num_classes: int, prediction_col: str = "prediction_index",
                 label_col: str = "label"):
        self._confusion = ConfusionMatrixEvaluator(num_classes, prediction_col, label_col)

    def evaluate(self, dataset: Dataset) -> dict:
        cm = self._confusion.evaluate(dataset).astype(np.float64)
        tp = np.diag(cm)
        pred_tot = cm.sum(axis=0)
        true_tot = cm.sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            precision = np.where(pred_tot > 0, tp / pred_tot, 0.0)
            recall = np.where(true_tot > 0, tp / true_tot, 0.0)
            denom = precision + recall
            f1 = np.where(denom > 0, 2 * precision * recall / denom, 0.0)
        return {
            "precision": precision, "recall": recall, "f1": f1,
            "macro_precision": float(precision.mean()),
            "macro_recall": float(recall.mean()),
            "macro_f1": float(f1.mean()),
        }
