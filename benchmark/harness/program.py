"""The one place that touches the system under test.

Builds the program's model from a configuration file and the reference's
seeded weights (what the program's model and its parameter tree look like
is the family's to say: ``families/<family>.py``, see ``harness/spec.py``),
looks the trainer class up by name, and hands it a
``Dataset`` that stamps the host clock where the trainer touches it — the
benchmark's own spans around the calls into the input layer.  Nothing here
reaches below the public entry ``Trainer(...).train(Dataset)``.
"""

from __future__ import annotations

import importlib
import threading
import time
from typing import Any, Dict, List

import numpy as np

from distkeras_tpu.data.dataset import Dataset


def build_model(cfg: Dict[str, Any], family, reference, seed: int):
    """The program's ``Model`` on the reference's seeded weights: made on the
    device in one jitted call, handed over as host arrays so that no
    parameter-sized device array of the benchmark's outlives set-up."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.base import Model

    make = jax.jit(lambda s: family.to_program_tree(reference.init_params(cfg, s), cfg))
    host = jax.tree.map(np.array, make(jnp.uint32(seed % 2**32)))
    return Model(spec=family.model_spec(cfg), params=host)


def change_norms(cfg: Dict[str, Any], family, reference, params, seed: int,
                 rare_rows=None) -> Dict[str, Any]:
    """Per-leaf norms of (the program's center - the seed's weights), in the
    reference's leaf naming, reduced on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda t, s, rare: reference._leaf_norms({
        k: v - reference.init_params(cfg, s)[k]
        for k, v in family.from_program_tree(t, cfg).items()}, rare))
    return jax.device_get(fn(params, jnp.uint32(seed % 2**32), rare_rows))


def trainer_class(traffic: Dict[str, Any]):
    return getattr(importlib.import_module(traffic["module"]), traffic["trainer"])


def make_trainer(traffic: Dict[str, Any], model, chips: int):
    """``cls(model, **constructor)`` with exactly the traffic file's
    arguments; ``"chips"`` stands for the cell's chip count."""
    kw = {k: (chips if v == "chips" else v)
          for k, v in traffic["constructor"].items()}
    return trainer_class(traffic)(model, **kw)


class _StampedRows(np.ndarray):
    """A stacked epoch ``[windows, ...]`` that stamps the clock when the
    trainer takes window ``w``'s rows (``xs[w]``) and hands back a plain
    array; anything else is numpy's."""

    def __getitem__(self, index):
        out = np.asarray(super().__getitem__(index))
        if isinstance(index, (int, np.integer)) and self.ndim > 1:
            self.stamp(int(index))
        return out


class TimedDataset(Dataset):
    """A ``Dataset`` that records when the trainer touches it.

    ``marks`` collects ``(event, perf_counter, value, thread)``: ``shard``
    (an asynchronous worker starts), ``rows`` (that worker takes window
    ``value``'s rows: its window loop reaches that window), ``worker_end``,
    ``epoch`` (a synchronous epoch starts: ``chunked_epoch`` is called) and
    ``feed`` spans (seconds spent producing each chunk or the stacked
    epoch).  Rows are served exactly as a plain ``Dataset`` serves them."""

    def __init__(self, columns, marks: List[tuple] = None):
        super().__init__(columns)
        self.marks = [] if marks is None else marks

    def _mark(self, event: str, value: float = 0.0) -> None:
        self.marks.append((event, time.perf_counter(), value,
                           threading.current_thread()))

    def shard(self, num_shards: int, index: int) -> "TimedDataset":
        self._mark("shard")
        # a watcher that only blocks in join(): it stamps the moment this
        # worker thread ends (last commit acknowledged, losses on the host)
        worker = threading.current_thread()

        def watch():
            worker.join()
            self._mark("worker_end")

        joiner = threading.Thread(target=watch, name="bench-watch")
        joiner.start()
        self.marks.append(("joiner", 0.0, 0.0, joiner))
        part = super().shard(num_shards, index)
        return TimedDataset(part._columns, self.marks)

    def stacked_epoch(self, batch_size, columns, window=1):
        out = super().stacked_epoch(batch_size, columns, window=window)
        first = True
        for name in list(out):
            rows = out[name].view(_StampedRows)
            # one column stamps: the worker takes xs[w] and ys[w] together
            rows.stamp = (lambda w: self._mark("rows", w)) if first else (lambda w: None)
            out[name], first = rows, False
        return out

    def shuffle(self, seed: int = 0) -> "TimedDataset":
        return TimedDataset(super().shuffle(seed)._columns, self.marks)

    def chunked_epoch(self, batch_size, columns, window=1, chunk_windows=None):
        self._mark("epoch")
        it = super().chunked_epoch(batch_size, columns, window=window,
                                   chunk_windows=chunk_windows)
        while True:
            t0 = time.perf_counter()
            try:
                chunk = next(it)
            except StopIteration:
                return
            self._mark("feed", time.perf_counter() - t0)
            yield chunk


def run_call(trainer, traffic: Dict[str, Any], columns: Dict[str, np.ndarray]
             ) -> Dict[str, Any]:
    """One ``trainer.train(Dataset)`` call and the host-clock record of it.

    The window opens when the call's first window starts — the first time a
    worker's window loop takes a window's rows on the asynchronous plane
    (the pull, H2D, program, D2H and commit of that window follow; the
    worker's seed pull and its replica's first H2D come before and happen
    once a job), the epoch's ``chunked_epoch`` on the synchronous one — and
    closes when the last commit is applied and the last loss is on the host:
    the last worker thread's end, or ``train()``'s return (after the last
    chunk ``train()`` only wraps the center, which is on the device already).
    """
    ds = TimedDataset(columns)
    n_before = len(trainer.history)
    t_call = time.perf_counter()
    model = trainer.train(ds, **traffic.get("train", {}))
    t_ret = time.perf_counter()
    for m in list(ds.marks):
        if m[0] == "joiner":
            m[3].join()
    times = lambda ev: [m[1] for m in ds.marks if m[0] == ev]
    if traffic["plane"] == "async":
        t_open, t_close = min(times("rows")), max(times("worker_end"))
    else:
        t_open, t_close = min(times("epoch")), t_ret
    feed = [m[2] for m in ds.marks if m[0] == "feed"]
    return {"model": model, "losses": list(trainer.history[n_before:]),
            "t_call": t_call, "t_open": t_open, "t_close": t_close,
            "t_return": t_ret, "feed_s": float(sum(feed)),
            "window_starts": sorted(times("rows")),
            "hub_updates": getattr(getattr(trainer, "parameter_server", None),
                                   "num_updates", None)}
