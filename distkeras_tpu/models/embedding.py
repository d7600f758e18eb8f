"""Embedding-bag CTR classifier — the row-sparse PS workload (ISSUE 9).

dist-keras's heritage is Spark-ML tabular pipelines; the modern version of
that workload is CTR/recommender training, where one embedding table
dwarfs the dense model and every batch touches only the few hundred rows
its categorical ids name.  This module is the minimal faithful shape of
that family: ``fields`` categorical id columns over ONE shared vocabulary,
an embedding-bag reduce (sum over fields), and a small dense head.

The ``EmbeddingTable`` leaf kind is declared DECLARATIVELY: the module
class lists the param-path names of its row-sparse ``[rows, dim]`` tables
in ``sparse_param_names``, and :func:`sparse_leaf_indices` (models/base)
resolves them to flat-leaf indices — the metadata the async trainers
thread into the PS stack (``sparse_tables="auto"``) so pull/commit traffic
moves only the rows a batch touches.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu.models.base import ModelSpec, register_model


@register_model("embedding_classifier")
class EmbeddingBagClassifier(nn.Module):
    """Shared-vocabulary embedding bag + MLP head (logits out).

    Input: int ids ``[batch, fields]`` in ``[0, rows)``.  Each field's id
    indexes the ONE ``[rows, dim]`` table (flax ``nn.Embed``; its param is
    named ``embedding`` — the name ``sparse_param_names`` declares); the
    field vectors are mean-reduced (an "embedding bag"), then a small
    dense stack emits class logits.  Under any gradient step only the
    rows present in the batch receive nonzero gradient — the property the
    row-sparse PS commit path is built on."""

    rows: int
    dim: int = 16
    hidden_sizes: Sequence[int] = (32,)
    num_outputs: int = 2

    # param-path leaf names that are row-sparse [rows, dim] tables — the
    # EmbeddingTable declaration sparse_leaf_indices() resolves
    sparse_param_names = ("embedding",)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        emb = nn.Embed(self.rows, self.dim, name="table")(x.astype(jnp.int32))
        h = emb.mean(axis=1)  # [batch, dim] — the bag reduce
        for hsz in self.hidden_sizes:
            h = nn.relu(nn.Dense(hsz)(h))
        return nn.Dense(self.num_outputs, dtype=jnp.float32)(h)


@register_model("multi_embedding_classifier")
class MultiTableCTRClassifier(nn.Module):
    """Per-field embedding tables with INDEPENDENT vocabularies (the
    hyperscale tier's multi-table shape, ISSUE 15).

    Input: int ids ``[batch, fields]`` where column ``f`` indexes its own
    ``[vocab_sizes[f], dim]`` table — user ids, item ids and context ids
    are different id spaces with different sizes and different hot
    shapes, exactly what one shared vocabulary cannot express.  The field
    vectors are mean-reduced and fed to the same dense head as the
    single-table classifier.

    Each table is a separate flax submodule ``table_<f>`` whose param is
    named ``embedding`` (``sparse_param_names``); ``sparse_field_map``
    (built lazily per instance — the map depends only on ``fields``)
    tells the async trainers which feature column feeds which table, so
    every table's pull/commit id set is computed — and validated —
    against ITS vocabulary."""

    vocab_sizes: Sequence[int]
    dim: int = 16
    hidden_sizes: Sequence[int] = (32,)
    num_outputs: int = 2

    sparse_param_names = ("embedding",)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        xi = x.astype(jnp.int32)
        vecs = [
            nn.Embed(int(rows), self.dim, name=f"table_{f}")(xi[:, f])
            for f, rows in enumerate(self.vocab_sizes)]
        h = jnp.stack(vecs, axis=1).mean(axis=1)
        for hsz in self.hidden_sizes:
            h = nn.relu(nn.Dense(hsz)(h))
        return nn.Dense(self.num_outputs, dtype=jnp.float32)(h)


# column f feeds table_f — the declaration models.base.sparse_table_fields
# resolves.  A plain class attribute keyed by module name: the map is a
# function of the field ORDINALS only, so one generous upper bound serves
# every fields count (unknown names are simply never matched)
MultiTableCTRClassifier.sparse_field_map = {
    f"table_{f}": (f,) for f in range(64)}


def ctr_embedding_spec(rows, dim: int = 16, fields: int = 4,
                       hidden_sizes: Sequence[int] = (32,),
                       num_outputs: int = 2) -> ModelSpec:
    """Spec for the synthetic-CTR example: ``fields`` int32 id
    columns in, click/no-click logits out.

    ``rows`` as an int keeps the PR-9 single-shared-vocabulary
    architecture byte-identical; a SEQUENCE of ints declares one
    independent vocabulary per field (``multi_embedding_classifier`` —
    ``fields`` is then implied by the sequence length)."""
    if isinstance(rows, (list, tuple)):
        return ModelSpec(name="multi_embedding_classifier",
                         config={"vocab_sizes": tuple(int(r) for r in rows),
                                 "dim": int(dim),
                                 "hidden_sizes": tuple(hidden_sizes),
                                 "num_outputs": int(num_outputs)},
                         input_shape=(len(rows),),
                         input_dtype="int32")
    return ModelSpec(name="embedding_classifier",
                     config={"rows": int(rows), "dim": int(dim),
                             "hidden_sizes": tuple(hidden_sizes),
                             "num_outputs": int(num_outputs)},
                     input_shape=(int(fields),),
                     input_dtype="int32")
