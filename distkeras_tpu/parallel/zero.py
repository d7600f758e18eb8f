"""ZeRO-1 sharded optimizer state over the data-parallel axis.

Absent from the reference (SURVEY §2.13 lists ZeRO/FSDP-style sharding as
beyond-parity headroom) — on TPU it is the natural next step once data
parallelism exists: optimizer state is the largest training tensor after
the params (2x params for Adam), and replicating it across every replica
wastes exactly (N-1)/N of that HBM.

TPU-native formulation (the collectives ride ICI):

- params stay REPLICATED (this is ZeRO stage 1, not FSDP);
- the whole parameter pytree is raveled into one flat vector, padded to a
  multiple of the axis size, and each replica owns one contiguous shard
  of optimizer state (``1/N`` of Adam's moments);
- per step: each replica computes full gradients on its batch shard, a
  single ``psum_scatter`` both averages them AND hands each replica only
  its gradient shard (half the bytes of a full allreduce), the optimizer
  update runs on the local shard, and one ``all_gather`` rebuilds the
  replicated updated params.

Exactness: every optax transform used here (sgd, momentum, adam, ...) is
ELEMENTWISE over parameters, so updating disjoint shards on different
replicas is bit-identical to the replicated update — pinned by the
parity test against the plain DP step.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.models.base import ModelSpec


def _state_specs(optimizer: optax.GradientTransformation, shard_size: int,
                 axis: str) -> Any:
    """Per-leaf specs for the sharded optimizer state: vector leaves (adam
    moments etc.) shard over ``axis``; 0-d leaves (step counters) are
    identical on every replica and stay replicated."""
    shape = jax.eval_shape(optimizer.init, jnp.zeros((shard_size,), jnp.float32))
    return jax.tree.map(lambda l: P(axis) if l.ndim else P(), shape)


def make_zero_train_step(spec: ModelSpec, loss: Callable,
                         optimizer: optax.GradientTransformation, mesh: Mesh,
                         axis: str = "replica") -> Callable:
    """Build ``(params, opt_shard, x, y) -> (params, opt_shard, loss)``.

    ``params`` replicated; ``opt_shard`` is this step's sharded optimizer
    state — create it with :func:`zero_init_state`, place it with
    :func:`zero_state_sharding`.  ``x``/``y`` batch-sharded over ``axis``.

    .. warning:: ``optimizer`` must be ELEMENTWISE over parameters (sgd,
       momentum, adam, adamw, rmsprop ...).  Transforms that couple
       parameters globally — ``clip_by_global_norm``, LARS/LAMB trust
       ratios — would compute their statistic over only the local 1/N
       shard inside ``shard_map`` and silently diverge from replicated
       DP.  Apply such transforms to the full gradient BEFORE this step
       (or use the replicated trainers).
    """
    spec.reject_silent_aux("make_zero_train_step")
    spec.reject_step_hook("make_zero_train_step")
    spec.reject_rng_spec("make_zero_train_step")
    apply_fn = spec.apply_fn()
    n = mesh.shape[axis]
    template = jax.eval_shape(lambda: spec.init_params(seed=0))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(template))
    padded = -(-total // n) * n
    shard_size = padded // n

    def shard_fn(params, opt_shard, x, y):
        flat0, unravel = ravel_pytree(params)

        def loss_fn(p):
            return loss(apply_fn(p, x), y)

        step_loss, grads = jax.value_and_grad(lambda p: loss_fn(p))(params)
        gflat, _ = ravel_pytree(grads)
        gflat = jnp.pad(gflat, (0, padded - total))
        # one collective: mean-reduce AND scatter — each replica receives
        # only its shard of the averaged gradient (allreduce would move 2x)
        gshard = lax.psum_scatter(gflat, axis, scatter_dimension=0, tiled=True) / n

        my = lax.axis_index(axis)
        pflat = jnp.pad(flat0, (0, padded - total))
        pshard = lax.dynamic_slice_in_dim(pflat, my * shard_size, shard_size)
        updates, opt_shard = optimizer.update(gshard, opt_shard, pshard)
        new_pshard = optax.apply_updates(pshard, updates)

        # rebuild replicated params: each replica contributes its updated
        # shard at its offset, psum concatenates AND yields the invariant
        # type the replicated out_spec needs (all_gather's result stays
        # device-varying under the vma system)
        contrib = lax.dynamic_update_slice_in_dim(
            jnp.zeros((padded,), new_pshard.dtype), new_pshard, my * shard_size, 0)
        new_flat = lax.psum(contrib, axis)[:total]
        new_params = unravel(new_flat)
        mean_loss = lax.psum(step_loss, axis) / n
        return new_params, opt_shard, mean_loss

    ospecs = _state_specs(optimizer, shard_size, axis)
    sharded = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), ospecs, P(axis), P(axis)),
        out_specs=(P(), ospecs, P()))
    return jax.jit(sharded, donate_argnums=(0, 1))


def zero_init_state(params: Any, optimizer: optax.GradientTransformation,
                    mesh: Mesh, axis: str = "replica") -> Any:
    """Sharded optimizer state: each replica holds only its shard of the
    vector leaves (1/N of the replicated state's memory).

    For the elementwise transforms this module supports, init over the
    padded flat params equals the concatenation of per-shard inits — so we
    jit the init with sharded OUT shardings and XLA allocates the state
    already distributed (the full replicated state, which for Adam is the
    2x-params tensor ZeRO exists to avoid, never materializes anywhere).
    """
    n = mesh.shape[axis]
    flat, _ = ravel_pytree(params)
    total = int(flat.size)
    padded = -(-total // n) * n
    shardings = zero_state_sharding(optimizer, params, mesh, axis)
    init = jax.jit(lambda f: optimizer.init(jnp.pad(f, (0, padded - total))),
                   out_shardings=shardings)
    return init(flat)


def zero_state_sharding(optimizer: optax.GradientTransformation, params: Any,
                        mesh: Mesh, axis: str = "replica"):
    """Per-leaf shardings for the opt-state pytree from zero_init_state."""
    n = mesh.shape[axis]
    flat, _ = ravel_pytree(params)
    shard_size = -(-int(flat.size) // n)
    specs = _state_specs(optimizer, shard_size, axis)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda v: isinstance(v, P))


def zero_data_sharding(mesh: Mesh, axis: str = "replica"):
    return NamedSharding(mesh, P(axis))
