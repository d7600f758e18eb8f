"""Synthetic CTR impressions — the row-sparse embedding workload's data
(ISSUE 9).

Real CTR logs have two properties the row-sparse PS path is built around:

- each impression names only ``fields`` ids out of a vocabulary of
  ``rows`` — a batch touches a tiny row subset of the embedding table;
- id traffic is heavily skewed (a small hot set takes most impressions),
  so the touched-row set per communication window is far below
  ``batch x window x fields`` distinct ids.

This generator reproduces both with a two-tier draw: a ``hot_fraction``
of the vocabulary receives ``hot_prob`` of the traffic, the cold tail is
uniform.  Labels are LEARNABLE, not noise: each id carries a fixed random
propensity weight and the click probability is the sigmoid of the
impression's summed weights — so a trained embedding model's loss
actually falls, and end-to-end runs exercise real gradients over real row
subsets.
"""

from __future__ import annotations

import numpy as np

from distkeras_tpu.data.dataset import Dataset


def synthetic_ctr_dataset(n: int, rows, fields: int = 4, seed: int = 0,
                          hot_fraction: float = 0.01,
                          hot_prob: float = 0.9) -> Dataset:
    """``n`` impressions over a ``rows``-id vocabulary: int32 ``features``
    ``[n, fields]`` and one-hot float32 ``label`` ``[n, 2]``
    (click / no-click).

    ``rows`` as an int draws every field from ONE shared vocabulary (the
    PR-9 contract, unchanged); a SEQUENCE gives each field its own
    independent vocabulary size (``fields`` is then implied) — the
    multi-table shape ``ctr_embedding_spec(rows=[...])`` trains on, with
    the same two-tier hot/cold skew applied per field."""
    if not 0.0 < hot_fraction <= 1.0:
        raise ValueError(f"hot_fraction must be in (0, 1], got {hot_fraction}")
    if not 0.0 <= hot_prob <= 1.0:
        raise ValueError(f"hot_prob must be in [0, 1], got {hot_prob}")
    rng = np.random.default_rng(seed)
    if isinstance(rows, (list, tuple)):
        # multi-vocabulary draw: per-field id streams and per-field
        # propensity tables (a fresh code path — the scalar branch below
        # stays stream-for-stream identical to PR 9's generator)
        per_field = [int(r) for r in rows]
        fields = len(per_field)
        shape = (int(n), int(fields))
        is_hot = rng.random(shape) < hot_prob
        cols = []
        for f, r in enumerate(per_field):
            hot = max(1, min(r, int(round(r * hot_fraction))))
            cols.append(np.where(is_hot[:, f],
                                 rng.integers(0, hot, size=int(n)),
                                 rng.integers(0, r, size=int(n))))
        ids = np.stack(cols, axis=1).astype(np.int32)
        logits = np.zeros(int(n), np.float32)
        for f, r in enumerate(per_field):
            propensity = rng.normal(scale=1.0 / np.sqrt(fields),
                                    size=r).astype(np.float32)
            logits += propensity[ids[:, f]]
        p_click = 1.0 / (1.0 + np.exp(-logits))
        clicks = (rng.random(int(n)) < p_click).astype(np.int64)
        label = np.eye(2, dtype=np.float32)[clicks]
        return Dataset({"features": ids, "label": label})
    rows = int(rows)
    hot = max(1, min(int(rows), int(round(rows * hot_fraction))))
    shape = (int(n), int(fields))
    is_hot = rng.random(shape) < hot_prob
    ids = np.where(is_hot,
                   rng.integers(0, hot, size=shape),
                   rng.integers(0, rows, size=shape)).astype(np.int32)
    # per-id click propensity: fixed for the dataset, so the label is a
    # function of the ids and an embedding model can actually learn it
    propensity = rng.normal(scale=1.0 / np.sqrt(fields),
                            size=int(rows)).astype(np.float32)
    logits = propensity[ids].sum(axis=1)
    p_click = 1.0 / (1.0 + np.exp(-logits))
    clicks = (rng.random(int(n)) < p_click).astype(np.int64)
    label = np.eye(2, dtype=np.float32)[clicks]
    return Dataset({"features": ids, "label": label})


def touched_row_fraction(ids: np.ndarray, rows: int, batch_size: int,
                         window: int) -> float:
    """Mean fraction of the vocabulary one communication window's batches
    touch — the number the sparse wire-savings tripwire is phrased in."""
    ids = np.asarray(ids)
    per_window = int(batch_size) * int(window)
    n_windows = len(ids) // per_window
    if n_windows == 0 or rows <= 0:
        return 1.0
    fracs = [np.unique(ids[w * per_window:(w + 1) * per_window]).size / rows
             for w in range(n_windows)]
    return float(np.mean(fracs))
