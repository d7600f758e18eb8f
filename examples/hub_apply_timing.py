#!/usr/bin/env python
"""Host timing of the hub's dense apply — a tool, not a benchmark metric.

Times ``parameter_server._add_scaled_commit`` (in place, block by block
through one scratch) against the expression it replaced (``c += d *
scale``, a temporary per leaf) at the 148 leaf shapes of cerebras-gpt-590m
(2.36 GB), the delta as misaligned float32 views into one bytearray laid
out as the socket path's receive buffer.  ``--threads`` also times the
helper with every leaf's flat span split over that many threads, each with
a scratch of its own (numpy releases the GIL; elementwise, so the bits do
not depend on the split).  No JAX, no device: run it on the machine whose
hub is in question, e.g. ``chiprun -- python3 examples/hub_apply_timing.py``
for the chip's host.  One JSON line per reading, then a summary line."""

import argparse
import json
import statistics
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from distkeras_tpu.runtime.parameter_server import (  # noqa: E402
    _APPLY_BLOCK,
    _add_scaled_commit,
)

# https://huggingface.co/cerebras/Cerebras-GPT-590M (config.json), as
# TransformerLM(positional="learned") lays it out: tied embedding, no bias
# in the dense layers, LayerNorm with scale and bias
VOCAB, POSITIONS, D_MODEL, D_FFN, LAYERS = 50257, 2048, 1536, 6144, 18


def leaf_shapes():
    block = [(D_MODEL,), (D_MODEL,), (D_MODEL, 3 * D_MODEL),
             (D_MODEL, D_MODEL), (D_MODEL,), (D_MODEL,),
             (D_MODEL, D_FFN), (D_FFN, D_MODEL)]
    return ([(VOCAB, D_MODEL), (POSITIONS, D_MODEL)] + block * LAYERS
            + [(D_MODEL,), (D_MODEL,)])


def wire_frame_views(shapes, rng):
    """One bytearray laid out as the receive buffer of a dense commit — 8
    bytes of frame length, action and count (5), then an 8-byte prefix
    before every tensor — filled with a delta, and its per-leaf views as
    ``net.decode_tensor_views`` hands them to the hub: float32 at byte
    offset 13 + 8k + (bytes before), never 4-aligned."""
    sizes = [int(np.prod(s)) for s in shapes]
    frame = bytearray(13 + sum(8 + 4 * n for n in sizes))
    views, off = [], 13
    for shape, n in zip(shapes, sizes):
        off += 8
        v = np.frombuffer(frame, np.float32, n, off).reshape(shape)
        v[...] = rng.standard_normal(shape, np.float32) * np.float32(1e-3)
        views.append(v)
        off += 4 * n
    return frame, views


def old_expression(center, delta, scale):
    for c, d in zip(center, delta):
        c += d * scale


def split_spans(center, delta, k):
    """Thread j's share: the j-th of k contiguous pieces of every leaf's
    flat span (center and delta alike)."""
    shares = [([], []) for _ in range(k)]
    for c, d in zip(center, delta):
        cf, df = c.reshape(-1), d.reshape(-1)
        edges = np.linspace(0, cf.size, k + 1).astype(np.int64)
        for (cs, ds), lo, hi in zip(shares, edges[:-1], edges[1:]):
            cs.append(cf[lo:hi])
            ds.append(df[lo:hi])
    return shares


def apply_split(pool, shares, scratches, scale):
    futures = [pool.submit(_add_scaled_commit, cs, ds, scale, scratch)
               for (cs, ds), scratch in zip(shares, scratches)]
    for f in futures:
        f.result()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--repeats", type=int, default=4)
    ap.add_argument("--threads", type=int, nargs="*", default=[2, 4],
                    help="thread counts to time the split helper at")
    ap.add_argument("--seed", type=int, default=26)
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    shapes = leaf_shapes()
    frame, delta = wire_frame_views(shapes, rng)
    center = [rng.standard_normal(s, np.float32) * np.float32(0.02)
              for s in shapes]
    twin = [c.copy() for c in center]
    nbytes = sum(c.nbytes for c in center)
    scratch = np.empty(_APPLY_BLOCK, np.float32)
    print(json.dumps({"leaves": len(shapes), "center_bytes": nbytes,
                      "block_bytes": scratch.nbytes,
                      "misaligned": not any(d.flags.aligned for d in delta),
                      "numpy": np.__version__}), flush=True)

    # the two forms give the same bits (old on the twin, helper on the
    # center, same start), at both scales
    for scale in (1.0, 0.25):
        old_expression(twin, delta, scale)
        _add_scaled_commit(center, delta, scale, scratch)
        if not all(np.array_equal(a.view(np.uint32), b.view(np.uint32))
                   for a, b in zip(center, twin)):
            raise SystemExit(f"helper and expression differ at scale {scale}")
    del twin

    forms = {"old_expression": partial(old_expression, center, delta),
             "helper": lambda s: _add_scaled_commit(center, delta, s, scratch)}
    readings = {}
    with ThreadPoolExecutor(max(args.threads, default=1)) as pool:
        for k in args.threads:
            forms[f"helper_{k}_threads"] = partial(
                apply_split, pool, split_spans(center, delta, k),
                [np.empty(_APPLY_BLOCK, np.float32) for _ in range(k)])
        for rep in range(args.repeats):
            for scale in (1.0, 0.25):
                for name, fn in forms.items():
                    t0 = time.perf_counter()
                    fn(scale)
                    ms = (time.perf_counter() - t0) * 1e3
                    readings.setdefault((name, scale), []).append(ms)
                    print(json.dumps({"form": name, "scale": scale,
                                      "repeat": rep, "ms": ms}), flush=True)
    print(json.dumps({"summary_ms_min_median_max": {
        f"{name}@{scale}": [min(v), statistics.median(v), max(v)]
        for (name, scale), v in readings.items()}}))


if __name__ == "__main__":
    main()
