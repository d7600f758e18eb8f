"""Columnar in-memory Dataset — the Spark-DataFrame stand-in.

Reference parity: trainers consumed a Spark ``DataFrame`` with
``features_col``/``label_col`` string-named columns, repartitioned it over
workers, and iterated partitions row-by-row inside executors
(``distkeras/workers.py``).  TPU-native design: columns are contiguous
host numpy arrays (no row objects, no JVM), batching is a zero-copy slice,
and "repartitioning over workers" becomes device-sharding the leading batch
axis over a mesh axis — the data plane feeds the chips directly.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from distkeras_tpu import observability as obs
from distkeras_tpu import utils

# Out-of-core chunk-size budget (bytes of feature data per chunk) for the
# double-buffered feed.  25 MB balances transfer granularity (enough
# batches per chunk to amortize the per-transfer host cost) against
# double-buffer residency (2 chunks in flight).  No benchmark cell feeds
# out of core yet (PERF.md section 7, ``lm590m_sync_feed``): re-measure
# before moving it.
DEFAULT_CHUNK_BUDGET_BYTES = 25 * 2**20


def chunk_windows_for_budget(row_bytes: int, batch_size: int, window: int = 1,
                             budget_bytes: Optional[int] = None) -> int:
    """``chunk_windows`` value sizing each chunk near the feed budget.

    ``row_bytes`` is one sample's feature bytes (``features[0].nbytes``).
    Returns at least 1 (a single window may exceed the budget; chunking
    cannot split below one window)."""
    if row_bytes <= 0 or batch_size <= 0 or window <= 0:
        raise ValueError(f"row_bytes, batch_size and window must be positive, "
                         f"got {row_bytes}, {batch_size}, {window}")
    budget = DEFAULT_CHUNK_BUDGET_BYTES if budget_bytes is None else budget_bytes
    return max(1, budget // (row_bytes * batch_size * window))


def prefetch_to_device(chunks: Iterator, place: Callable,
                       produce_ahead: bool = True) -> Iterator:
    """Double-buffered feed: yield ``place(chunk)`` with the NEXT chunk's
    host->device transfer already issued before the caller consumes the
    current one.

    ``place`` must only ISSUE the transfer (``jax.device_put`` /
    ``jnp.asarray`` — both asynchronous), never block on it; the caller's
    loss read for chunk N then overlaps chunk N+1's copy-in.  With
    ``produce_ahead`` (default) chunk PRODUCTION — disk page faults and
    the chunk-local shuffle copy for ``ColumnFile`` datasets — runs on a
    background thread with a one-chunk queue, so host-side IO overlaps
    training too, not just the transfer.  At most two chunks are in
    flight either way, so feeding stays O(chunk) memory — the out-of-core
    epoch's IO/H2D/compute overlap (SURVEY §7 step 3).

    Telemetry: the consumer's wait for its next chunk is the leaf phase
    ``feed.wait`` (the trainer's thread blocked on input, once a chunk;
    the producer's side is ``feed_chunk_load_seconds``)."""
    if produce_ahead:
        import queue
        import threading

        q: "queue.Queue" = queue.Queue(maxsize=1)
        stop = threading.Event()
        # the producer must NOT capture the `chunks` cell: it is rebound to
        # the produced() generator below, and a closure reference from the
        # live thread would keep that generator (and so its stop-setting
        # finalizer) alive exactly until stop is set — a reference deadlock
        # that leaked the thread on abandoned consumers
        source = chunks

        def put(item) -> bool:
            # bounded-wait put so an abandoned consumer (exception mid-
            # epoch, early break) cannot strand this thread in q.put
            # forever — it notices `stop` within 0.1s, drops its chunk,
            # and exits instead of leaking a thread + a chunk per retry
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        # telemetry (no-op unless observability is enabled): producer-side
        # chunk production latency (disk page faults + shuffle copies) and
        # the handoff queue's occupancy — the feed path's two signals
        m_load = obs.histogram("feed_chunk_load_seconds")
        m_depth = obs.gauge("feed_queue_depth")
        m_chunks = obs.counter("feed_chunks_total")

        def producer():
            try:
                it_src = iter(source)
                while True:
                    telemetry = obs.enabled()
                    t0 = time.perf_counter() if telemetry else 0.0
                    try:
                        c = next(it_src)
                    except StopIteration:
                        break
                    if telemetry:
                        m_load.observe(time.perf_counter() - t0)
                        m_chunks.inc()
                    if not put(("chunk", c)):
                        return
                    m_depth.set(q.qsize())
            except BaseException as exc:  # surfaced on the consumer side
                put(("error", exc))
            else:
                put(("done", None))

        producer_thread = threading.Thread(target=producer, daemon=True)
        producer_thread.start()

        def produced():
            try:
                while True:
                    # bounded wait + liveness check (ADVICE round 5): a
                    # producer killed WITHOUT its sentinel (interpreter
                    # teardown, an exception inside the sentinel put
                    # itself) must surface as an error, not a silent
                    # forever-hang in q.get()
                    try:
                        kind, val = q.get(timeout=1.0)
                    except queue.Empty:
                        if not producer_thread.is_alive():
                            # one last non-blocking drain: the producer may
                            # have enqueued its sentinel between our timeout
                            # and the liveness check
                            try:
                                kind, val = q.get_nowait()
                            except queue.Empty:
                                raise RuntimeError(
                                    "prefetch producer thread died without "
                                    "delivering its chunk or end-of-epoch "
                                    "sentinel; the feed cannot make progress"
                                ) from None
                        else:
                            continue
                    m_depth.set(q.qsize())
                    if kind == "error":
                        raise val
                    if kind == "done":
                        return
                    yield val
            finally:
                stop.set()  # runs on normal exhaustion AND GeneratorExit

        chunks = produced()
    it = iter(chunks)
    end = cur = object()
    while True:
        with obs.phase("feed.wait"):
            nxt = next(it, end)
        if nxt is end:
            break
        placed = place(nxt)
        if cur is not end:
            yield cur
        cur = placed
    if cur is not end:
        yield cur


class Dataset:
    """A dict of equal-length numpy columns with DataFrame-ish helpers."""

    def __init__(self, columns: Dict[str, np.ndarray]):
        lengths = {k: len(v) for k, v in columns.items()}
        if len(set(lengths.values())) > 1:
            raise ValueError(f"column length mismatch: {lengths}")
        self._columns = {k: np.asarray(v) for k, v in columns.items()}

    # -- DataFrame-ish surface -------------------------------------------------
    @property
    def columns(self) -> List[str]:
        return list(self._columns)

    def __len__(self) -> int:
        return len(next(iter(self._columns.values()))) if self._columns else 0

    def __getitem__(self, col: str) -> np.ndarray:
        return self._columns[col]

    def with_column(self, name: str, values: np.ndarray) -> "Dataset":
        if len(values) != len(self):
            raise ValueError(f"new column {name!r} has {len(values)} rows, dataset has {len(self)}")
        cols = dict(self._columns)
        cols[name] = np.asarray(values)
        return Dataset(cols)

    def select(self, names: Sequence[str]) -> "Dataset":
        return Dataset({n: self._columns[n] for n in names})

    def take(self, n: int) -> "Dataset":
        return Dataset({k: v[:n] for k, v in self._columns.items()})

    def shuffle(self, seed: int = 0) -> "Dataset":
        """Row shuffle (reference: ``utils.shuffle`` before repartitioning)."""
        return Dataset(utils.shuffle_arrays(self._columns, seed=seed))

    def shard(self, num_shards: int, index: int) -> "Dataset":
        """Contiguous row shard ``index`` of ``num_shards`` (reference:
        ``df.repartition(num_workers)`` handing each worker one partition).
        Equal-size shards; the tail remainder is dropped so every worker
        sees the same number of rows."""
        if not 0 <= index < num_shards:
            raise ValueError(f"shard index {index} out of range for {num_shards} shards")
        per = len(self) // num_shards
        if per == 0:
            raise ValueError(f"dataset of {len(self)} rows cannot be split into {num_shards} shards")
        return Dataset({k: v[index * per:(index + 1) * per] for k, v in self._columns.items()})

    def split(self, fraction: float, seed: Optional[int] = None) -> Sequence["Dataset"]:
        """Random (train, test)-style split; reference: ``df.randomSplit``."""
        ds = self.shuffle(seed) if seed is not None else self
        cut = int(len(ds) * fraction)
        left = Dataset({k: v[:cut] for k, v in ds._columns.items()})
        right = Dataset({k: v[cut:] for k, v in ds._columns.items()})
        return left, right

    # -- batch plane -----------------------------------------------------------
    def batches(self, batch_size: int, columns: Optional[Sequence[str]] = None,
                drop_remainder: bool = True) -> Iterator[Dict[str, np.ndarray]]:
        """Yield batch dicts of the requested columns."""
        names = list(columns) if columns is not None else self.columns
        n = len(self)
        end = (n // batch_size) * batch_size if drop_remainder else n
        for i in range(0, end, batch_size):
            yield {c: self._columns[c][i : i + batch_size] for c in names}

    def chunked_epoch(self, batch_size: int, columns: Sequence[str],
                      window: int = 1, chunk_windows: Optional[int] = None
                      ) -> Iterator[Dict[str, np.ndarray]]:
        """Yield the epoch in bounded chunks of ``[n, window, batch, ...]``.

        The memory-bounded form of :meth:`stacked_epoch`: at most
        ``chunk_windows`` windows are materialized per yield (each chunk is
        a zero-copy reshape of a column slice), so epoch feeding is
        O(chunk), not O(dataset) — the host-sharded-feeding story for data
        that doesn't fit the single-transfer fast path.  ``None`` yields
        the whole epoch as one chunk.  The final chunk may be smaller
        (possible one-off recompile of the epoch program for that shape).
        """
        per_window = batch_size * window
        num_windows = len(self) // per_window
        if num_windows == 0:
            raise ValueError(
                f"dataset of {len(self)} rows too small for batch_size={batch_size} window={window}")
        step = num_windows if chunk_windows is None else int(chunk_windows)
        if step <= 0:
            raise ValueError(f"chunk_windows must be positive, got {chunk_windows}")
        for start in range(0, num_windows, step):
            n = min(step, num_windows - start)
            out = {}
            for c in columns:
                v = self._columns[c][start * per_window:(start + n) * per_window]
                out[c] = v.reshape((n, window, batch_size) + v.shape[1:])
            yield out

    def stacked_epoch(self, batch_size: int, columns: Sequence[str],
                      window: int = 1) -> Dict[str, np.ndarray]:
        """Materialize one epoch as [num_windows, window, batch, ...] arrays.

        This is the TPU-friendly feed shape: a whole epoch (or a large chunk)
        becomes one device transfer and the train loop runs as a compiled
        ``lax.scan`` over windows instead of a Python batch loop — the
        replacement for the reference's per-row partition iterators.
        (Exactly the single-chunk case of :meth:`chunked_epoch`.)
        """
        return next(self.chunked_epoch(batch_size, columns, window=window))
