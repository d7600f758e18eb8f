"""Rotary position embeddings (RoPE, Su et al. 2021).

No reference counterpart (the reference predates transformers) — the
modern positional default for the flagship LM, next to the learned table:
instead of adding a position vector to the residual stream, each
query/key head vector is ROTATED by an angle proportional to its absolute
position, so the attention score <R(p_q)q, R(p_k)k> depends only on the
relative offset p_q - p_k.  TPU-friendly by construction: pure elementwise
cos/sin math that XLA fuses into the projection epilogues, no table in
HBM, and nothing length-bound — the same weights serve any sequence
length (``max_seq_len`` remains only a cache-sizing bound for decoding).

Two conventions, one set of frequencies base^(-2i/D): NeoX split-half (the
default) — the head dim splits into two halves that rotate as (x1, x2) ->
(x1 cos - x2 sin, x2 cos + x1 sin), channel i paired with channel i + D/2 —
and adjacent pairs (``interleaved=True``; GPT-J, the DeepSeek-V3 family's
``rope_interleave``): channels (2i, 2i+1) rotate together, in place.  One
is the other under a fixed permutation of the channels, so a score
<R(p_q)q, R(p_k)k> is the same in both when q and k share the convention.
Rotation runs in float32 (angle precision at large positions) and casts
back to the input dtype.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_rotate(x: jnp.ndarray, positions: jnp.ndarray,
                base: float = 10000.0, interleaved: bool = False) -> jnp.ndarray:
    """Rotate ``x`` [B, L, H, D] by absolute ``positions`` [L]; ``interleaved``
    pairs channels (2i, 2i+1) instead of (i, i + D/2).

    Works for any head count (queries and grouped GQA keys alike) and any
    even D.  Position 0 is the identity rotation, so un-offset prefixes
    are unchanged and cached K rows (stored rotated) stay valid forever —
    rotation depends only on the row's own absolute position.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    half = d // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freq[None, :]   # [L, half]
    cos = jnp.cos(ang)[None, :, None, :]                           # [1, L, 1, half]
    sin = jnp.sin(ang)[None, :, None, :]
    if interleaved:
        # pairs on an axis of their own, not x[..., 0::2]: XLA:TPU makes
        # gathers and copies of strided slices of the lanes (PERF.md, PR 34)
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
    r1, r2 = x1 * cos - x2 * sin, x2 * cos + x1 * sin
    if interleaved:
        return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)
