"""LM training: dp × sp × tp shard_map step (ring attention + Megatron TP).

No reference counterpart (the reference predates transformers; SURVEY §5
"long-context: absent") — this is the TPU-native long-context path:

- batch sharded over ``dp``;
- sequence sharded over ``sp`` with ring attention streaming KV blocks over
  ICI (``ops/attention.py``);
- heads / FFN sharded over ``tp`` (Megatron column/row split) with the two
  per-block psums inside the model (``models/transformer.py``);
- gradients of replicated params arrive via collective adjoints, gradients
  of tp-sharded params stay local to their shard.

Any of the axes may be absent from the mesh (or size 1): the same step
builder covers pure-dp, dp×sp, dp×tp and the full 3-D mesh.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.models.base import ModelSpec, build_module
from distkeras_tpu.ops.losses import lm_token_cross_entropy


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        key = getattr(k, "key", None)
        if key is None:
            key = getattr(k, "name", None)
        if key is not None:
            names.append(str(key))
    return tuple(names)


def _tp_leaf_spec(path, leaf, tp_axis: Optional[str]) -> P:
    """Megatron placement rule, keyed on the flax param path.

    Matches both the raw param tree and optimizer-state trees (whose paths
    carry the same ``block_i/<layer>/kernel`` suffix); everything else —
    layernorms, embeddings, scalar optimizer counters — is replicated.
    """
    if tp_axis is None:
        return P()
    names = _path_names(path)
    ndim = len(getattr(leaf, "shape", ()))
    if "kernel" in names:
        if "qkv" in names and ndim == 4:
            return P(None, None, tp_axis, None)
        # GQA split layout: q [E, H, Dh] and kv [E, 2, Hkv, Dh] are both
        # column-parallel over their head axis (num_kv_heads % tp_size is
        # validated by TransformerBlock)
        if "q" in names and ndim == 3:
            return P(None, tp_axis, None)
        if "kv" in names and ndim == 4:
            return P(None, None, tp_axis, None)
        if "proj" in names and ndim == 3:
            return P(tp_axis, None, None)
        if "up" in names and ndim == 2:
            return P(None, tp_axis)
        if "down" in names and ndim == 2:
            return P(tp_axis, None)
    return P()


# leaves only the config-driven block builds: no Megatron split is defined
_UNSHARDABLE = frozenset({"gate", "q_norm", "k_norm", "attn_post_norm", "ffn_post_norm",
                          "gate_proj", "experts", "lm_head",
                          # the linear-attention mixer
                          "lin_q", "lin_k", "lin_v", "lin_gate", "lin_a", "lin_b", "lin_norm",
                          "lin_out", "conv_q", "conv_k", "conv_v", "A_log", "dt_bias",
                          # the latent-attention mixer
                          "kv_down", "kv_norm", "kv_up"})


def lm_param_specs(params: Any, tp_axis: Optional[str]) -> Any:
    """PartitionSpec pytree for a TransformerLM param (or optimizer-state,
    or gradient) tree under Megatron tensor parallelism."""
    if tp_axis is not None:
        found = sorted({getattr(k, "key", None) for path, _ in
                        jax.tree_util.tree_flatten_with_path(params)[0] for k in path}
                       & _UNSHARDABLE)
        if found:
            raise ValueError(f"tensor parallelism splits the GPT-2-style block's leaves "
                             f"only; this tree has {found}")
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _tp_leaf_spec(path, leaf, tp_axis), params)


def lm_opt_specs(optimizer: optax.GradientTransformation, params: Any,
                 tp_axis: Optional[str]) -> Any:
    opt_shapes = jax.eval_shape(optimizer.init, params)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _tp_leaf_spec(path, leaf, tp_axis), opt_shapes)


def lm_state_shardings(mesh: Mesh, optimizer: optax.GradientTransformation,
                       params: Any, tp_axis: Optional[str] = None):
    """(param shardings, opt-state shardings) for placing host state on the
    mesh — feed to ``jax.device_put`` before the first step."""
    pspecs = lm_param_specs(params, tp_axis)
    ospecs = lm_opt_specs(optimizer, params, tp_axis)
    to_sharding = lambda spec: NamedSharding(mesh, spec)
    return (jax.tree.map(to_sharding, pspecs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(to_sharding, ospecs, is_leaf=lambda x: isinstance(x, P)))


def make_lm_train_step(spec: ModelSpec, optimizer: optax.GradientTransformation,
                       mesh: Mesh, dp_axis: str = "dp", sp_axis: Optional[str] = "sp",
                       tp_axis: Optional[str] = None) -> Callable:
    """Build a jitted (params, opt_state, tokens, targets) -> (params,
    opt_state, loss) step over the mesh.

    ``spec`` is the FULL-model spec (init produces the full param tree);
    when ``tp_axis`` names a mesh axis, the step internally applies a module
    configured for the local shard sizes (``tp_size = mesh.shape[tp_axis]``)
    and expects params placed with ``lm_state_shardings``.  ``sp_axis=None``
    (or absent from the mesh) disables sequence parallelism; the spec's
    ``seq_axis`` must agree.
    """
    spec.reject_silent_aux("make_lm_train_step")
    # the tp/sp step shards the GPT-2-style block's leaves and ties the head
    from distkeras_tpu.models.transformer import reject_block_features

    reject_block_features(spec.config, "make_lm_train_step (tensor / sequence parallelism)")
    sp_active = sp_axis is not None and sp_axis in mesh.shape and mesh.shape[sp_axis] > 1
    if sp_active and spec.config.get("seq_axis") != sp_axis:
        raise ValueError(
            f"spec.config['seq_axis'] = {spec.config.get('seq_axis')!r} must equal "
            f"sp_axis = {sp_axis!r} or ring attention would not ride this mesh axis")
    tp_size = mesh.shape[tp_axis] if (tp_axis is not None and tp_axis in mesh.shape) else 1
    if tp_axis is not None and tp_axis not in mesh.shape:
        raise ValueError(f"tp_axis {tp_axis!r} is not a mesh axis of {mesh}")
    cfg = dict(spec.config)
    cfg.update(tp_axis=tp_axis if tp_size > 1 else None, tp_size=tp_size)
    module = build_module(spec.name, cfg)
    loss_axes = (dp_axis, sp_axis) if sp_active else (dp_axis,)

    def local_loss(params, tokens, targets, offset):
        # fused unembed+CE: the [B, L, V] f32 logits tensor is never
        # materialized and the unembed matmul runs at bf16 MXU rate
        # (ops/losses.py) — the embed table is replicated under tp, so the
        # fused path is tp-invariant like head()
        ce = lm_token_cross_entropy(module, params, tokens, targets,
                                    pos_offset=offset)
        # mask the GLOBAL final position: its target is shift_targets'
        # padding, not a real next token.  Global position = offset + local
        # index; only the last sp shard holds the padded column.
        l_local = tokens.shape[1]
        global_len = l_local * (lax.axis_size(sp_axis) if sp_active else 1)
        pos = offset + jnp.arange(l_local)
        weights = (pos < global_len - 1).astype(jnp.float32)[None, :]
        wsum = jnp.sum(ce * weights)
        wcount = jnp.sum(weights) * tokens.shape[0]
        return wsum, wcount

    def shard_fn(params, opt_state, tokens, targets):
        offset = (lax.axis_index(sp_axis) * tokens.shape[1]) if sp_active else 0

        # Differentiate the GLOBAL (psum'd) loss and use the result as-is.
        # Replicated params enter mesh-invariant (P()); their use in varying
        # computation is an implicit broadcast whose transpose is a psum, so
        # ``jax.grad`` of the global loss returns the cross-shard-summed
        # gradient directly — adding a manual pmean/psum would double-count.
        # tp-sharded params enter tp-varying; their grads stay local to the
        # shard (Megatron semantics).  The loss itself is tp-INVARIANT —
        # the in-model psums already merged the partial sums — so it is
        # reduced over (dp, sp) only.
        def global_loss(p):
            wsum, wcount = local_loss(p, tokens, targets, offset)
            # wsum derives from the (dp/sp-sharded) data so it already varies
            # over every loss axis; wcount depends only on the sp position and
            # genuinely lacks dp — widen it for the uniform-vma psum
            missing = tuple(a for a in loss_axes if a not in jax.typeof(wcount).vma)
            if missing:
                wcount = lax.pcast(wcount, missing, to="varying")
            return lax.psum(wsum, loss_axes) / lax.psum(wcount, loss_axes)

        loss, grads = jax.value_and_grad(global_loss)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    param_template = jax.eval_shape(lambda: spec.init_params(seed=0))
    pspecs = lm_param_specs(param_template, tp_axis if tp_size > 1 else None)
    ospecs = lm_opt_specs(optimizer, param_template, tp_axis if tp_size > 1 else None)
    data_spec = P(dp_axis, sp_axis) if sp_active else P(dp_axis)
    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(pspecs, ospecs, data_spec, data_spec),
        out_specs=(pspecs, ospecs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def lm_data_shardings(mesh: Mesh, dp_axis: str = "dp", sp_axis: Optional[str] = "sp"):
    # same activation predicate as make_lm_train_step (size-1 sp is inactive)
    if sp_axis is not None and sp_axis in mesh.shape and mesh.shape[sp_axis] > 1:
        return NamedSharding(mesh, P(dp_axis, sp_axis))
    return NamedSharding(mesh, P(dp_axis))


def shift_targets(tokens) -> Any:
    """Host-side next-token targets: targets[t] = tokens[t+1], last = pad(0).

    Done on the host because the shift crosses sp shard boundaries; the
    cost is one roll over an int array per batch.  The padded final position
    is excluded from the training loss by ``make_lm_train_step``'s mask.
    """
    import numpy as np

    targets = np.roll(np.asarray(tokens), -1, axis=-1)
    targets[..., -1] = 0
    return targets
