"""Seeded synthetic tokens with learnable structure.

A uniform draw has none: its loss can only sit at ln(vocab), blind to a
broken exchange.  Here a row is a Zipf unigram draw (rank r has weight
r^-exponent, the ranks laid over the vocabulary by a fixed stride so that
frequent tokens are not all neighbours) in which each position, with
probability ``repeat_prob``, repeats the token ``1..repeat_span`` places
back.  The distribution is the traffic file's and the same for every seed;
the seed only picks the sample, so every seed sees the same kind of work.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


STRIDE = 7919     # coprime with the vocabulary: rank r is token (r * STRIDE) % vocab


def rare_token_ids(vocab: int, min_rank: int) -> np.ndarray:
    """Ids of the tokens of Zipf rank ``min_rank`` (0-based) and beyond."""
    return ((np.arange(min_rank, vocab, dtype=np.int64) * STRIDE) % vocab).astype(np.int32)


def make_rows(rows: int, seq_len: int, vocab: int, seed: int,
              zipf_exponent: float = 1.1, repeat_prob: float = 0.3,
              repeat_span: int = 8, stride: int = STRIDE) -> Dict[str, np.ndarray]:
    """``features`` [rows, seq_len] int32 and ``label`` = the next token
    (rows are drawn one position longer, so no target is padding)."""
    rng = np.random.default_rng([int(seed) % 2**63, rows, seq_len])
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -float(zipf_exponent)
    cdf = np.cumsum(weights / weights.sum())
    ranks = np.searchsorted(cdf, rng.random((rows, seq_len + 1)))
    toks = ((np.minimum(ranks, vocab - 1) * stride) % vocab).astype(np.int32)
    rep = rng.random((rows, seq_len + 1)) < repeat_prob
    back = rng.integers(1, repeat_span + 1, size=(rows, seq_len + 1))
    for t in range(1, seq_len + 1):   # a repeat may copy a repeat: in order
        src = np.maximum(t - back[:, t], 0)
        toks[:, t] = np.where(rep[:, t], toks[np.arange(rows), src], toks[:, t])
    return {"features": np.ascontiguousarray(toks[:, :-1]),
            "label": np.ascontiguousarray(toks[:, 1:])}
