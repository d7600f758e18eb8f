"""Loss registry with Keras-style string names.

Reference parity: the reference passed Keras loss names (strings) into
``Trainer(model, loss='categorical_crossentropy', ...)`` and compiled them
into the worker's model (``workers.py :: Worker.prepare_model``).  Here each
name maps to a pure ``loss(logits_or_preds, labels) -> scalar`` function
that jit-compiles and differentiates cleanly on TPU.

All losses reduce with a mean over the batch so gradient magnitudes are
batch-size invariant (required for the window/commit algebra of the
distributed trainers to match the reference's per-batch semantics).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

LossFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]


# logits-size ceiling for the UNchunked CE path: below this the [rows, V]
# f32 logits (plus cotangent) fit HBM comfortably and the dense form is
# chosen over the chunked lax.map: the map's sequential DUS accumulation
# and the checkpoint's extra forward matmul are work the dense form does
# not do, against the HBM traffic of materializing the logits once.
# Above the ceiling chunking is chosen — it exists for memory.
_DENSE_CE_BYTES = 640 * 1024 * 1024
_DEFAULT_CHUNK_ROWS = 2048  # chunk size target when the policy must chunk


def _pick_chunks(rows: int, vocab: int, target_rows: Optional[int]) -> int:
    """Chunk count with the largest chunk size that divides ``rows`` and
    stays <= ``target_rows``.  One dense chunk when the full [rows, V] f32
    logits stay under ``_DENSE_CE_BYTES`` (see above;
    the ceiling applies only on the DEFAULT policy ``target_rows=None`` —
    an explicit ``chunk_rows`` is a caller's memory bound and is honored
    strictly) or when ``rows`` factorizes awkwardly (e.g. prime ``rows``,
    where the only fitting divisor would mean near-per-row chunks and a
    long sequential ``lax.map``) — materializing the logits once beats
    serializing thousands of tiny matmuls."""
    if target_rows is None:
        if rows * vocab * 4 <= _DENSE_CE_BYTES:
            return 1
        target_rows = _DEFAULT_CHUNK_ROWS
    if rows <= target_rows:
        return 1
    for n in range(2, rows + 1):
        if rows % n == 0 and rows // n <= target_rows:
            if rows // n >= max(8, target_rows // 8):
                return n
            break  # divisors only get smaller from here
    return 1


def unembed_cross_entropy(hidden: jnp.ndarray, table: jnp.ndarray,
                          targets: jnp.ndarray, chunk_rows: Optional[int] = None,
                          compute_dtype: Optional[jnp.dtype] = jnp.bfloat16) -> jnp.ndarray:
    """Fused unembed + softmax CE whose logits stay bounded: chunked when
    they would be large, dense when they fit.

    ``hidden`` [B, L, E] (final-norm output), ``table`` [V, E] (the tied
    embedding matrix), ``targets`` [B, L] int.  Returns per-position CE
    [B, L] in float32.  ``chunk_rows=None`` (default) picks the
    policy below; an EXPLICIT ``chunk_rows`` is treated as a hard memory
    bound — the dense fast path is never taken over it.

    Two wins over ``head() -> optax CE`` on TPU:

    - the unembed matmul runs in ``compute_dtype`` (default bfloat16 — full
      MXU rate) with float32 accumulation via ``preferred_element_type``,
      instead of the float32 x float32 matmul ``embed.attend`` issues;
    - when the [B*L, V] float32 logits would exceed ``_DENSE_CE_BYTES``
      they are computed ``chunk_rows`` rows at a time inside a ``lax.map``
      whose body is ``jax.checkpoint``'d, so the backward recomputes each
      chunk instead of keeping ~1 GB of logits (+ another in the
      cotangent) live across the whole backward.  Peak logit memory drops
      from O(B*L*V) to O(chunk_rows * V).  Below the ceiling the dense
      single-matmul form runs (see ``_pick_chunks``).

    ``compute_dtype=None`` keeps the inputs' dtype (exact-parity testing).
    """
    b, l, e = hidden.shape
    rows = b * l
    h2 = hidden.reshape(rows, e)
    t2 = targets.reshape(rows).astype(jnp.int32)
    if compute_dtype is not None:
        h2 = h2.astype(compute_dtype)
        table = table.astype(compute_dtype)

    def chunk_ce(hc, tc):
        logits = lax.dot_general(hc, table, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [rows_c, V]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return lse - tgt

    n_chunks = _pick_chunks(rows, table.shape[0], chunk_rows)
    if n_chunks == 1:
        ce = chunk_ce(h2, t2)
    else:
        body = jax.checkpoint(chunk_ce, prevent_cse=False)
        ce = lax.map(lambda args: body(*args),
                     (h2.reshape(n_chunks, rows // n_chunks, e),
                      t2.reshape(n_chunks, rows // n_chunks)))
    return ce.reshape(b, l)


def lm_token_cross_entropy(module, params, tokens: jnp.ndarray, targets: jnp.ndarray,
                           pos_offset=0, chunk_rows: Optional[int] = None,
                           compute_dtype: Optional[jnp.dtype] = jnp.bfloat16) -> jnp.ndarray:
    """Per-position next-token CE [B, L] for a tied-embedding LM.

    The single home of the fused-loss wiring contract: ``module`` must
    expose a ``hidden`` method (forward up to and including the final norm,
    no unembed) and keep its tied unembedding table at
    ``params['embed']['embedding']`` — i.e. ``models.transformer
    .TransformerLM``.  Used by ``parallel/lm.py`` and the parity tests
    so the pairing lives in exactly one place.
    """
    h = module.apply({"params": params}, tokens, pos_offset=pos_offset,
                     method="hidden")
    return unembed_cross_entropy(h, params["embed"]["embedding"],
                                 targets.astype(jnp.int32),
                                 chunk_rows=chunk_rows, compute_dtype=compute_dtype)


def categorical_crossentropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Softmax CE with one-hot labels (labels shape [..., num_classes])."""
    return optax.softmax_cross_entropy(logits, labels).mean()


def sparse_categorical_crossentropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Softmax CE with integer labels (labels shape [...])."""
    labels = labels.astype(jnp.int32)
    if labels.ndim == logits.ndim:  # tolerate a trailing singleton label dim
        labels = labels.squeeze(-1)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def binary_crossentropy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Sigmoid CE on logits (numerically stable; do NOT pre-sigmoid)."""
    return optax.sigmoid_binary_cross_entropy(logits, labels.astype(logits.dtype)).mean()


def mean_squared_error(preds: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.square(preds - targets.astype(preds.dtype)))


def mean_absolute_error(preds: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(jnp.abs(preds - targets.astype(preds.dtype)))


_LOSSES: Dict[str, LossFn] = {
    "categorical_crossentropy": categorical_crossentropy,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "binary_crossentropy": binary_crossentropy,
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
}


def get_loss(name_or_fn) -> LossFn:
    """Resolve a Keras-style loss name (or pass a callable through)."""
    if callable(name_or_fn):
        return name_or_fn
    try:
        return _LOSSES[name_or_fn]
    except KeyError:
        raise ValueError(f"unknown loss {name_or_fn!r}; known: {sorted(_LOSSES)}") from None


def register_loss(name: str, fn: LossFn) -> None:
    _LOSSES[name] = fn
