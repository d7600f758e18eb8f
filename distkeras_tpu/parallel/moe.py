"""Expert parallelism: Switch-style mixture-of-experts over an ``ep`` axis.

No reference counterpart (data-parallel only, SURVEY §2.13) — this
completes the framework's parallelism suite (dp/sp/tp/pp/ep).  The design
is the standard TPU MoE shape (Switch Transformer / Mesh-TF lineage),
built for the MXU and ICI:

- **Top-k routing with static capacity** (``router_top_k``: 1 = Switch,
  2 = GShard-style gating with renormalized pair weights and rank
  priority — every token's first choice seats before any second
  choice).  Each expert accepts at most ``capacity`` tokens per shard
  (the rest fall through on the residual path).
- **Two dispatch implementations, one seating rule**
  (``dispatch_impl``): ``"dense"`` builds the classic [T, E, C] one-hot
  dispatch/combine tensors and einsums through them — no gathers, no
  dynamic shapes, everything MXU-tiled, but the einsums cost
  ``4·T·E·C·D`` matmul FLOPs of pure routing plumbing per layer, a
  share that grows with ``T·E·C``.  ``"sorted"`` computes
  the SAME seating (expert id + queue position per assignment) and then
  moves rows by index: a static-shape scatter builds the slot->token
  map, one gather fills the [E, C, D] slot tensor, one gather + a
  k-term weighted sum combines — zero dispatch matmuls, O((kT + EC)·D)
  memory traffic, still static shapes for XLA.  Both paths seat the
  same assignments and agree (parity-tested): bit for bit under top-1,
  within a few float32 ulps under top-2, where the two-term combine is
  added in another order than the dense contraction over all slots;
  ``"auto"`` picks dense only
  below a small-shape threshold where a single fused einsum beats
  gather launch overhead (see :func:`resolve_dispatch_impl`).
- **Experts live sharded over ``ep``.**  Dispatch is two
  ``lax.all_to_all``s over the mesh axis: token slots [E, C, D] travel to
  the shard owning their expert, come back as expert outputs — the
  all-to-all rides ICI, exactly like the sequence-parallel ring.
- **Router determinism.**  Routing depends only on (params, tokens), so
  ep=1 and ep=N produce bit-comparable results for the same inputs — the
  parity property the tests pin down.

The load-balancing auxiliary loss is the Switch one:
``E * sum_e f_e * p_e`` (token fraction times mean router prob).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import observability as obs
from distkeras_tpu.models.base import ModelSpec, register_model

import flax.linen as nn

# auto dispatch threshold: below this many [T, E, C] one-hot elements the
# dense einsum pair is a single fused MXU kernel over <= 1 MB of f32 and
# has less to launch than the sorted path's scatter + gathers; above it
# the dense tax grows as 4·T·E·C·D matmul FLOPs while sorted stays
# O((kT + EC)·D) bytes moved.  No benchmark cell runs MoEMLP, so the
# crossover itself is not on the ledger (PERF.md).
_DENSE_DISPATCH_MAX_TEC = 1 << 18


def resolve_dispatch_impl(impl: str, t: int, e: int, c: int) -> str:
    """Resolve ``dispatch_impl`` ("dense" | "sorted" | "auto") for a
    routing shape: tokens ``t``, experts ``e``, per-expert capacity ``c``.

    ``auto`` keys on the dense one-hot tensor size ``t*e*c`` — the
    quantity whose growth makes the dense einsums' 2·T MACs per slot
    element intolerable — with the threshold documented above."""
    if impl in ("dense", "sorted"):
        return impl
    if impl != "auto":
        raise ValueError(f"dispatch_impl must be 'dense', 'sorted' or "
                         f"'auto', got {impl!r}")
    return "dense" if t * e * c <= _DENSE_DISPATCH_MAX_TEC else "sorted"


def dispatch_matmul_flops(t: int, e: int, c: int, d: int, impl: str) -> int:
    """FORWARD matmul FLOPs one MoE layer spends on dispatch + combine.

    Dense: the [T,E,C] one-hot einsums cost ``2·T·E·C·D`` on each side.
    Sorted: zero — rows move by gather/scatter, not contraction.  The
    layer sows its ``dispatch_flops_pct`` stat from this (multiply by 3
    for fwd+bwd accounting)."""
    if impl == "sorted":
        return 0
    if impl != "dense":
        raise ValueError(f"impl must be 'dense' or 'sorted', got {impl!r}")
    return 4 * t * e * c * d


class MoEMLP(nn.Module):
    """Router + E experts (each a 2-layer gelu MLP), top-k dispatch.

    Call with tokens [T, D] -> (out [T, D], aux_loss scalar).  ``ep_axis``
    set (and bound by an enclosing shard_map) runs expert-parallel: this
    shard computes routing for its T tokens, all_to_all's token slots so
    each shard runs only its E_local = E/ep experts, and reverses the
    exchange.  Unbound (init / single device): all experts local, same
    math, no collectives.

    Expert-parameter sharding follows the TP pattern (models/transformer.py):
    init always builds the FULL tree (``ep_size=1`` semantics, w_up
    [E, D, F]); the train step device_puts w_up/w_down with a leading-axis
    ``P(ep)`` sharding and applies a module configured with ``ep_size=ep``,
    whose declared param shapes are the LOCAL slabs [E/ep, D, F] — each
    device holds (and optimizes) only its own experts' weights.  The
    router stays replicated: routing needs all E logits.
    """

    num_experts: int
    model_dim: int
    hidden_dim: int
    capacity: int  # per-expert slots PER SHARD
    ep_axis: Optional[str] = None
    ep_size: int = 1
    router_top_k: int = 1  # 1 = Switch; 2 = GShard-style top-2 gating
    dispatch_impl: str = "auto"  # "dense" | "sorted" | "auto" — see
                                 # resolve_dispatch_impl; same seating
                                 # either way (parity tested)
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        t, d = x.shape
        if d != self.model_dim:
            raise ValueError(f"tokens have dim {d}, module declares model_dim={self.model_dim}")
        e, c, f = self.num_experts, self.capacity, self.hidden_dim
        k_r = self.router_top_k
        if k_r not in (1, 2):
            raise ValueError(f"router_top_k must be 1 or 2, got {k_r}")
        if k_r > e:
            raise ValueError(f"router_top_k {k_r} exceeds num_experts {e}")
        if e % self.ep_size:
            raise ValueError(f"num_experts {e} not divisible by ep_size {self.ep_size}")
        impl = resolve_dispatch_impl(self.dispatch_impl, t, e, c)
        e_local = e // self.ep_size
        router = self.param("router", nn.initializers.normal(0.02), (d, e))
        w_up_l = self.param("w_up", nn.initializers.lecun_normal(), (e_local, d, f))
        w_down_l = self.param("w_down", nn.initializers.lecun_normal(), (e_local, f, d))

        xc = x.astype(self.compute_dtype)
        # -- routing (float32 for a stable softmax/top-k) ----------------------
        scores = jax.nn.softmax((x.astype(jnp.float32) @ router.astype(jnp.float32)),
                                axis=-1)  # [T, E]
        # gate weights: Switch (k=1) uses the raw top prob; top-2 uses the
        # GShard form — the pair's probs renormalized to sum to 1
        gate_probs, choice = lax.top_k(scores, k_r)            # [T, k]
        if k_r > 1:
            gate_probs = gate_probs / jnp.sum(gate_probs, axis=-1, keepdims=True)
        onehots = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [T, k, E]
        # queue positions with RANK priority (GShard): every token's first
        # choice is seated before any token's second choice, so adding a
        # second choice never evicts someone's first.  The rank-major
        # [k*T, E] cumsum implements exactly that order; beyond-capacity
        # assignments drop (residual path, standard Switch behavior).
        # This seating is shared by BOTH dispatch impls — parity by
        # construction, the einsum-vs-gather choice only moves the rows
        oh_rank = jnp.swapaxes(onehots, 0, 1)                   # [k, T, E], rank-major
        rank_major = oh_rank.reshape(k_r * t, e)                # [k*T, E]
        pos_flat = jnp.cumsum(rank_major, axis=0) * rank_major - 1.0
        pos_rank = jnp.sum(pos_flat.reshape(k_r, t, e) * oh_rank,
                           axis=-1).astype(jnp.int32)           # [k, T]
        keep = pos_rank < c
        gates_rank = jnp.swapaxes(gate_probs, 0, 1)             # [k, T]

        # Switch load-balance aux: E * sum_e (fraction routed) * (mean prob)
        # — computed on FIRST choices for both k (the standard Switch form;
        # GShard's variant likewise uses the top-1 assignment fraction)
        frac = jnp.mean(onehots[:, 0], axis=0)
        mean_prob = jnp.mean(scores, axis=0)
        aux = e * jnp.sum(frac * mean_prob)

        # router observability (surfaced by the train steps into their
        # stats output): what fraction of routed assignments fell off the
        # capacity cliff, how hot the hottest expert ran relative to its
        # capacity, and what share of this layer's matmul FLOPs the
        # RESOLVED dispatch impl spends on routing plumbing (analytic,
        # layer-local: dispatch over dispatch + experts + router).
        # Scalars, so the sow costs nothing
        assigned = jnp.sum(rank_major, axis=0)                  # [E]
        self.sow("router_stats", "dropped_fraction",
                 1.0 - jnp.sum(keep.astype(jnp.float32)) / (k_r * t))
        self.sow("router_stats", "max_expert_load",
                 jnp.max(assigned) / c)
        disp_fl = dispatch_matmul_flops(t, e, c, d, impl)
        layer_fl = 4 * e * c * d * f + 2 * t * d * e  # experts + router, fwd
        # NOTE the denominator: LAYER-local (dispatch + experts + router —
        # the module cannot see attention/unembed), so under dense
        # dispatch this reads higher than a model-wide share would;
        # exactly 0 on the sorted path
        self.sow("router_stats", "dispatch_flops_pct",
                 jnp.float32(100.0 * disp_fl / (disp_fl + layer_fl)))

        # -- dispatch to experts ----------------------------------------------
        if impl == "dense":
            slot = jax.nn.one_hot(jnp.where(keep, pos_rank, -1), c,
                                  dtype=jnp.float32)            # [k, T, C]; dropped -> 0
            per_rank = oh_rank[:, :, :, None] * slot[:, :, None, :]
            dispatch = jnp.sum(per_rank, axis=0)                # [T, E, C]
            slots = jnp.einsum("tec,td->ecd",
                               dispatch.astype(self.compute_dtype), xc)
        else:
            # sorted: each kept (rank, token) assignment owns a unique flat
            # slot expert*C + queue_pos (queue positions are unique per
            # expert across the rank-major order); dropped assignments park
            # on a dummy slot E*C that is sliced away.  Scatter the TOKEN
            # INDEX per slot (ints — no gradient surface), then one gather
            # fills the slot tensor; unoccupied slots multiply to zero so
            # the expert compute sees exactly the dense path's operand
            choice_rank = jnp.swapaxes(choice, 0, 1)            # [k, T]
            dest = jnp.where(keep, choice_rank * c + pos_rank, e * c)
            flat_dest = dest.reshape(-1)                        # [k*T]
            src_tok = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None, :],
                                       (k_r, t)).reshape(-1)
            slot_tok = jnp.zeros((e * c + 1,), jnp.int32).at[flat_dest].set(src_tok)
            occupied = jnp.zeros((e * c + 1,), self.compute_dtype
                                 ).at[flat_dest].set(1)
            slots = (jnp.take(xc, slot_tok[:e * c], axis=0)
                     * occupied[:e * c, None]).reshape(e, c, d)
        ep = 1
        if self.ep_axis is not None and self.ep_axis in jax.typeof(x).vma:
            ep = lax.axis_size(self.ep_axis)
            if ep != self.ep_size:
                raise ValueError(f"mesh axis {self.ep_axis!r} has size {ep}, module "
                                 f"was configured with ep_size={self.ep_size}")
        if ep > 1:
            # tiled all_to_all: [E, C, D] -> [E_local, ep*C, D] — shard s
            # keeps its E_local experts' slot block from EVERY peer (the
            # expert dim splits, the slot dim concatenates); rides ICI
            slots = lax.all_to_all(slots, self.ep_axis, split_axis=0, concat_axis=1,
                                   tiled=True)

        h = jnp.einsum("ecd,edf->ecf", slots, w_up_l.astype(self.compute_dtype))
        h = nn.gelu(h)
        out_slots = jnp.einsum("ecf,efd->ecd", h, w_down_l.astype(self.compute_dtype))

        if ep > 1:
            # reverse exchange: [E_local, ep*C, D] -> [E, C, D]
            out_slots = lax.all_to_all(out_slots, self.ep_axis, split_axis=1,
                                       concat_axis=0, tiled=True)

        if impl == "dense":
            combine = jnp.sum(per_rank * gates_rank[:, :, None, None], axis=0)
            out = jnp.einsum("tec,ecd->td",
                             combine.astype(self.compute_dtype), out_slots)
        else:
            # gather each assignment's expert output back by its flat slot
            # (dropped -> the appended zero row), then gate-weight and sum
            # over the k ranks with the same precision as the dense
            # combine (compute-dtype operands, dot accumulation, one
            # downcast)
            padded = jnp.concatenate(
                [out_slots.reshape(e * c, d),
                 jnp.zeros((1, d), out_slots.dtype)], axis=0)
            y_tok = jnp.take(padded, dest, axis=0)              # [k, T, D]
            gates_c = gates_rank.astype(self.compute_dtype)     # [k, T]
            # the k-term sum as a contraction (not an explicit mul+add):
            # XLA lowers it through the same dot machinery as the dense
            # combine einsum; under top-2 the two paths still end an ulp
            # or two apart (the order of the two-term sum)
            out = jnp.einsum("kt,ktd->td", gates_c, y_tok)
        return out.astype(x.dtype), aux


# -- the sigmoid-routed expert layer with a shared expert ---------------------
#
# One replica's SHARE of an expert-parallel layer: the router scores every
# expert of the deployment (``num_experts``), this replica holds experts
# ``[lo, hi)`` and computes their part; what the experts held elsewhere
# would add is left out.  No capacity, no drops: every assignment to a held
# expert is computed, whatever the imbalance.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_tokens(x, order, inverse, k: int):
    """Rows of ``x`` [T, D] in sorted-assignment order: row ``r`` is token
    ``order[r] // k`` (assignment ``a = t * k + j`` is token ``t``'s ``j``-th
    choice).  Its transpose is a gather too (by the inverse permutation, then
    a sum over each token's ``k`` rows), not a scatter-add."""
    return jnp.take(x, order // k, axis=0)


def _gather_tokens_fwd(x, order, inverse, k):
    return _gather_tokens(x, order, inverse, k), inverse


def _gather_tokens_bwd(k, inverse, g):
    rows = jnp.take(g, inverse, axis=0).reshape(-1, k, g.shape[-1])
    return jnp.sum(rows.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_gather_tokens.defvjp(_gather_tokens_fwd, _gather_tokens_bwd)


@jax.custom_vjp
def _unsort_rows(y, order, inverse):
    """Sorted rows back in assignment order (the inverse permutation)."""
    return jnp.take(y, inverse, axis=0)


def _unsort_rows_fwd(y, order, inverse):
    return _unsort_rows(y, order, inverse), order


def _unsort_rows_bwd(order, g):
    return jnp.take(g, order, axis=0), None, None


_unsort_rows.defvjp(_unsort_rows_fwd, _unsort_rows_bwd)


def _up(rows, w_gate, w_up, group_sizes):
    """Each held expert's two up-projections over its group of sorted rows."""
    return (lax.ragged_dot(rows, w_gate, group_sizes),
            lax.ragged_dot(rows, w_up, group_sizes))


def _down(g, u, w_down, live, group_sizes):
    """SwiGLU's product and each held expert's down-projection.  Rows past
    the last group belong to no held expert: ``ragged_dot`` does not compute
    them, and what it leaves there must not reach a sum."""
    act = jnp.where(live, nn.silu(g) * u, 0)
    return jnp.where(live, lax.ragged_dot(act, w_down, group_sizes), 0)


def _routed_full(k, xc, w, w_gate, w_up, w_down, order, inverse, group_sizes):
    """The held experts' part of the output over ALL ``T * k`` sorted rows:
    the only static bound no step can overflow.  ``w`` [T, k] float32 is
    each choice's weight, 0 where the expert is held elsewhere."""
    t, d = xc.shape
    with jax.named_scope("moe.dispatch"):
        live = (jnp.arange(t * k) < jnp.sum(group_sizes))[:, None]
        rows = jnp.where(live, _gather_tokens(xc, order, inverse, k), 0)
    with jax.named_scope("moe.experts"):
        y = _down(*_up(rows, w_gate, w_up, group_sizes), w_down, live, group_sizes)
    with jax.named_scope("moe.combine"):
        per_choice = _unsort_rows(y, order, inverse).reshape(t, k, d)
        routed = jnp.sum(per_choice.astype(jnp.float32) * w[:, :, None], axis=1)
    return routed.astype(xc.dtype)


# -- the same over a bounded number of rows ------------------------------------
#
# The stable sort puts the held assignments first, so while a step's held
# assignments number at most ``R`` they are all among ``order[:R]``: the
# gather, the experts and the masks run on [R, .] buffers.  Bringing R sorted
# rows back to T tokens is a sum over each token's held choices (0 to k of
# them), and a scatter-add of rows is slow on the chip; here it is one grouped
# matmul.  The R rows are put in token order (a sort of R keys and one gather
# of R rows), tokens are cut into tiles of ``_TOKEN_TILE``, and a tile's
# output is ``onehot^T @ rows`` over that tile's rows alone: ``onehot`` [rows,
# _TOKEN_TILE] places a row at its token's slot in the tile, at the row's gate
# weight.  The same product without weights transposes the gather, so no
# gradient is a scatter-add either.

# How far the bounded path's buffers reach above the balanced share of a
# step's assignments, ``T * k * held / num_experts``.  Measured held shares
# at 16 of 128 experts were 10.5-13.2% against 12.5% balanced (PERF.md,
# PR 28), so twice the balanced share leaves a whole share of room; a step
# over it takes the full-size path and loses nothing but time.
_ROW_BOUND_OVER_BALANCED = 2
_ROW_BOUND_MULTIPLE = 512       # the bound is rounded up to whole row tiles
_TOKEN_TILE = 256


def held_row_bound(t: int, k: int, held_n: int, num_experts: int) -> int:
    """Rows the bounded path holds for ``t`` tokens of ``k`` choices where
    ``held_n`` of ``num_experts`` experts are held; ``t * k`` where the
    bound reaches it (half the experts or more held): no bounded path then."""
    rows = -(-_ROW_BOUND_OVER_BALANCED * t * k * held_n // num_experts)
    return min(-(-rows // _ROW_BOUND_MULTIPLE) * _ROW_BOUND_MULTIPLE, t * k)


def _take(table, index):
    """``table[index]`` along the first axis for indices that are in range by
    construction: clamped, where ``jnp.take`` would follow its gather with a
    pass that fills what was out of range."""
    return jnp.take(table, index, axis=0, mode="clip")


class _RowPlan(NamedTuple):
    """Where the first ``R`` sorted rows come from and go to; integers only."""

    token: jnp.ndarray       # [R] the token of sorted row r
    live: jnp.ndarray        # [R, 1] whether row r is a held assignment
    by_token: jnp.ndarray    # [R] the sorted rows' indices in token order, live ones first
    slot: jnp.ndarray        # [R] in that order, the token's place in its tile
    tile_sizes: jnp.ndarray  # [tiles] live rows of each tile of _TOKEN_TILE tokens


def _row_plan(order, group_sizes, bound: int, t: int, k: int) -> _RowPlan:
    head = order[:bound]
    live = jnp.arange(bound) < jnp.sum(group_sizes)
    # assignment ids ascend with the token: sorting them is sorting by token
    in_order, by_token = lax.sort_key_val(jnp.where(live, head, t * k),
                                          jnp.arange(bound, dtype=head.dtype))
    tiles = -(-t // _TOKEN_TILE)
    tile = jnp.where(live, head // (k * _TOKEN_TILE), tiles)
    tile_sizes = jnp.sum(tile[:, None] == jnp.arange(tiles, dtype=tile.dtype),
                         axis=0, dtype=jnp.int32)
    return _RowPlan(head // k, live[:, None], by_token, in_order // k % _TOKEN_TILE,
                    tile_sizes)


def _rows_to_tokens(rows, weight, plan: _RowPlan, t: int):
    """``out[token] = sum over the token's live rows of weight * row``, the
    products and sums in float32: [R, D] -> [T, D] in ``rows.dtype``.
    ``weight`` [R] float32, or ``None`` for 1.  A float32 weight is split
    into as many addends of ``rows.dtype`` as hold its mantissa, one block of
    slots each, so that the matmul's operands stay in the compute dtype and
    the products are exact."""
    dtype, tiles = rows.dtype, plan.tile_sizes.shape[0]
    ordered = _take(rows, plan.by_token)
    onehot = plan.slot[:, None] == jnp.arange(_TOKEN_TILE, dtype=plan.slot.dtype)
    if weight is None:
        places = [onehot.astype(dtype)]
    else:
        rest, places = _take(weight, plan.by_token), []
        for _ in range(1 if dtype == jnp.float32 else 3):
            part = rest.astype(dtype)
            places.append(jnp.where(onehot, part[:, None], 0))
            rest = rest - part.astype(jnp.float32)
    dims = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
    out = lax.ragged_dot_general(jnp.concatenate(places, axis=1), ordered, plan.tile_sizes,
                                 dims, preferred_element_type=jnp.float32)
    out = jnp.sum(out.reshape(tiles, len(places), _TOKEN_TILE, -1), axis=1)
    return out.reshape(tiles * _TOKEN_TILE, -1)[:t].astype(dtype)


def _bounded(xc, w, w_gate, w_up, w_down, order, inverse, group_sizes, plan: _RowPlan):
    """:func:`_routed_full` for a step whose held assignments number at most
    ``R``: the same groups through the same ``ragged_dot``s, on [R, .].
    Beside the output, what :func:`_bounded_pull` needs of it.  A row that is
    not live holds its token's row all the same, which no group reads, and
    belongs to a choice held elsewhere: its weight is 0."""
    with jax.named_scope("moe.dispatch"):
        rows = _take(xc, plan.token)
    with jax.named_scope("moe.experts"):
        g, u = _up(rows, w_gate, w_up, group_sizes)
        y = _down(g, u, w_down, plan.live, group_sizes)
    with jax.named_scope("moe.combine"):
        weight = _take(w.reshape(-1), order[:plan.token.shape[0]])
        out = _rows_to_tokens(y, weight, plan, xc.shape[0])
    return out, (rows, g, u, y, weight)


def _bounded_pull(kept, xc, w, w_gate, w_up, w_down, order, inverse, group_sizes,
                  plan: _RowPlan, g_out):
    """The gradients of :func:`_bounded`'s output into its first five
    arguments.  Every transpose of a gather is a gather: a row's gradient is
    its token's, and the tokens' is :func:`_rows_to_tokens` again."""
    rows, g, u, y, weight = kept
    bound = plan.token.shape[0]
    with jax.named_scope("moe.combine"):
        g_rows = _take(g_out, plan.token).astype(jnp.float32)
        d_y = (g_rows * weight[:, None]).astype(y.dtype)
        d_weight = jnp.sum(g_rows * y.astype(jnp.float32), axis=-1)
        d_w = jnp.where(inverse < bound, _take(d_weight, inverse), 0).reshape(w.shape)
    with jax.named_scope("moe.experts"):
        # the stages' outputs are in ``kept``: of these two only the pullbacks run
        d_g, d_u, d_down = jax.vjp(
            lambda g, u, w_down: _down(g, u, w_down, plan.live, group_sizes),
            g, u, w_down)[1](d_y)
        d_rows, d_gate, d_up = jax.vjp(
            lambda rows, w_gate, w_up: _up(rows, w_gate, w_up, group_sizes),
            rows, w_gate, w_up)[1]((d_g, d_u))
    with jax.named_scope("moe.dispatch"):
        d_x = _rows_to_tokens(jnp.where(plan.live, d_rows, 0), None, plan, xc.shape[0])
    return d_x, d_w, d_gate, d_up, d_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed(k, over, xc, w, w_gate, w_up, w_down, order, inverse, group_sizes, plan):
    """The bounded path, or the full-size one where ``over`` says that this
    step's held assignments exceed the bound.  One conditional forward and
    one backward, each written out: differentiating THROUGH a ``lax.cond``
    would make it hand back every branch's residuals, zero-filled [T * k, .]
    buffers for the branch not taken, which is the traffic the bound removes.
    Here the residuals are the inputs and the bounded path's [R, .] buffers
    (blank after a full-size forward, whose backward branch recomputes its
    forward): a recomputed block runs the experts once more, not twice."""
    return lax.cond(over,
                    lambda *a: _routed_full(k, *a[:-1]),
                    lambda *a: _bounded(*a)[0],
                    xc, w, w_gate, w_up, w_down, order, inverse, group_sizes, plan)


def _routed_fwd(k, over, *inputs):
    def blank_like(a):       # under shard_map as varying as what the other branch keeps
        zeros = jnp.zeros(a.shape, a.dtype)
        return lax.pcast(zeros, tuple(a.vma), to="varying") if a.vma else zeros

    blank = jax.tree.map(blank_like, jax.eval_shape(_bounded, *inputs)[1])
    out, kept = lax.cond(over,
                         lambda *a: (_routed_full(k, *a[:-1]), blank),
                         _bounded,
                         *inputs)
    return out, (over, inputs, kept)


def _routed_bwd(k, res, g_out):
    over, (*diff, order, inverse, group_sizes, plan), kept = res
    ints = (order, inverse, group_sizes)
    grads = lax.cond(
        over,
        lambda *d: jax.vjp(lambda *d: _routed_full(k, *d, *ints), *d)[1](g_out),
        lambda *d: _bounded_pull(kept, *d, *ints, plan, g_out),
        *diff)
    return (None, *grads, None, None, None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


class HeldExpertsMLP(nn.Module):
    """Sigmoid top-k router over ``num_experts``, a shared expert, and the
    experts ``experts_held = [lo, hi)`` this replica holds; tokens [T, D] ->
    [T, D].

    ``s = sigmoid(float32(x @ W_r))``; ``S = top_k(s + b)`` (the bias ``b``
    selects only and has no gradient); ``w_e = route_scale * s_e / (sum of
    the k selected scores + 1e-20)``; ``out = Shared(x) + sum over e in S and
    held of w_e * Expert_e(x)``, each routed expert a SwiGLU of width
    ``hidden_dim``, the shared one ONE SwiGLU of width ``shared_dim``
    (``None``: the routed width; a model with n shared experts has n times
    it), added unweighted.  The normaliser runs over all k selected scores,
    held or not.  The router's matmul, the sigmoid and the selection run in float32
    (``Precision.HIGHEST``: a bfloat16 pass seats near-ties differently).

    No token is dropped and there is no capacity: all ``T * k`` assignments
    are sorted by held expert (the ones held elsewhere last), their rows
    gathered into one buffer, and ``lax.ragged_dot`` multiplies each held
    expert's group of rows — the groups are as uneven as the router makes
    them, and the rows past the last group are not computed.  The buffer has
    ``R = held_row_bound(...)`` rows: twice the balanced share of the
    assignments, ``2 * T * k * (hi - lo) / num_experts`` (32,768 of 131,072
    at 16,384 tokens, top-8, 16 of 128 held), which the layer computes from
    its own shapes.  A step whose held assignments exceed ``R`` takes the
    same arithmetic over all ``T * k`` rows, the one static bound that
    cannot overflow (537 MB a buffer in bfloat16 at those sizes), behind one
    conditional; a replica that holds half the experts or more has ``R = T *
    k`` and no conditional.  Both paths compute every held assignment.

    The layer sows (collection ``moe_counts``) its assignment counts over
    ALL experts (``assignments``, int32 [num_experts]) and ``calls``, int32
    [2]: 1, and whether this call ran over all ``T * k`` rows.  The training
    step moves the bias with the counts (:func:`bias_update`, through
    ``models/transformer.py::routed_step_hook``); no loss term.  It runs one
    replica's share without its exchange, and that is the only way it runs:
    the exchange between the replicas of a layer is not built, so the layer
    has no expert-parallel axis to be given.
    """

    num_experts: int
    experts_held: Tuple[int, int]
    model_dim: int
    hidden_dim: int
    top_k: int = 8
    route_scale: float = 1.0
    compute_dtype: jnp.dtype = jnp.bfloat16
    shared_dim: Optional[int] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        t, d = x.shape
        e, k, f = self.num_experts, self.top_k, self.hidden_dim
        fs = f if self.shared_dim is None else self.shared_dim
        if fs < 1:
            raise ValueError(f"shared_dim {self.shared_dim}: the layer always has its "
                             "shared expert; a model without one is not built here")
        lo, hi = (int(v) for v in self.experts_held)
        if not 0 <= lo < hi <= e:
            raise ValueError(f"experts_held {self.experts_held} is no range of "
                             f"the {e} experts")
        if k > e:
            raise ValueError(f"top_k {k} exceeds num_experts {e}")
        held_n, cd = hi - lo, self.compute_dtype
        router = self.param("router", nn.initializers.normal(0.02), (d, e))
        bias = self.param("router_bias", nn.initializers.zeros, (e,))
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", init, (held_n, d, f))
        w_up = self.param("w_up", init, (held_n, d, f))
        w_down = self.param("w_down", init, (held_n, f, d))
        xc = x.astype(cd)

        with jax.named_scope("moe.route"):
            scores = jax.nn.sigmoid(jnp.dot(
                x.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))                   # [T, E]
            _, choice = lax.top_k(scores + lax.stop_gradient(bias), k)   # [T, k]
            gates = jnp.take_along_axis(scores, choice, axis=-1)
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
            gates = gates * self.route_scale
            counts = jnp.sum(choice[:, :, None] == jnp.arange(e, dtype=choice.dtype),
                             axis=(0, 1), dtype=jnp.int32)           # [E]
        self.sow("moe_counts", "assignments", counts)

        with jax.named_scope("moe.shared"):
            g = nn.Dense(fs, use_bias=False, dtype=cd, name="shared_gate")(xc)
            u = nn.Dense(fs, use_bias=False, dtype=cd, name="shared_up")(xc)
            shared = nn.Dense(d, use_bias=False, dtype=cd, name="shared_down")(nn.silu(g) * u)

        bound = held_row_bound(t, k, held_n, e)
        with jax.named_scope("moe.dispatch"):
            local = choice.reshape(-1) - lo                           # [T * k]
            held = (local >= 0) & (local < held_n)
            order = jnp.argsort(jnp.where(held, local, held_n), stable=True)
            inverse = jnp.argsort(order)
            group_sizes = counts[lo:hi]
            w = jnp.where(held.reshape(t, k), gates, 0.0)
            plan = _row_plan(order, group_sizes, bound, t, k) if bound < t * k else None
        inputs = (xc, w, w_gate.astype(cd), w_up.astype(cd), w_down.astype(cd),
                  order, inverse, group_sizes)
        if plan is None:
            over, routed = True, _routed_full(k, *inputs)
        else:
            over = jnp.sum(group_sizes) > bound
            routed = _routed(k, over, *inputs, plan)
        self.sow("moe_counts", "calls", jnp.stack([jnp.int32(1), jnp.asarray(over, jnp.int32)]))
        return (shared + routed).astype(x.dtype)


def bias_update(bias, counts, coeff: float):
    """The selection bias after one optimizer step: ``delta = coeff *
    sign(mean(c) - c)``, ``b <- b + delta - mean(delta)`` with ``c`` the
    step's assignment counts over all experts."""
    c = counts.astype(jnp.float32)
    delta = coeff * jnp.sign(jnp.mean(c) - c)
    return bias + (delta - jnp.mean(delta)).astype(bias.dtype)


@register_model("moe_mlp_classifier")
class MoEClassifier(nn.Module):
    """Small MoE classifier: embed -> MoE layer (+residual) -> head.

    The minimal end-to-end carrier for expert parallelism (the MoE analogue
    of the reference's MLP example family).
    """

    input_dim: int = 32
    model_dim: int = 64
    num_experts: int = 4
    hidden_dim: int = 128
    capacity: int = 64
    num_outputs: int = 10
    ep_axis: Optional[str] = None
    ep_size: int = 1
    router_top_k: int = 1
    dispatch_impl: str = "auto"

    @staticmethod
    def sown_collections(config) -> tuple:
        return ("aux_loss", "router_stats")

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        h = nn.Dense(self.model_dim, name="embed")(x)
        moe_out, aux = MoEMLP(num_experts=self.num_experts, model_dim=self.model_dim,
                              hidden_dim=self.hidden_dim, capacity=self.capacity,
                              ep_axis=self.ep_axis, ep_size=self.ep_size,
                              router_top_k=self.router_top_k,
                              dispatch_impl=self.dispatch_impl, name="moe")(h)
        h = h + moe_out
        self.sow("aux_loss", "load_balance", aux)
        return nn.Dense(self.num_outputs, name="head")(h)


def moe_classifier_spec(input_dim: int = 32, num_experts: int = 4, capacity: int = 64,
                        num_outputs: int = 10, ep_axis: Optional[str] = None,
                        router_top_k: int = 1,
                        dispatch_impl: str = "auto") -> ModelSpec:
    return ModelSpec(
        name="moe_mlp_classifier",
        config={"input_dim": input_dim, "num_experts": num_experts,
                "capacity": capacity, "num_outputs": num_outputs, "ep_axis": ep_axis,
                "router_top_k": router_top_k, "dispatch_impl": dispatch_impl},
        input_shape=(input_dim,),
    )


def _moe_param_specs(params: Any, ep_axis: str):
    """w_up/w_down leaves shard over ep on the leading (expert) axis; the
    router and every non-MoE leaf stay replicated."""

    def spec_for(path, _leaf):
        names = {getattr(k, "key", None) for k in path}
        return P(ep_axis) if names & {"w_up", "w_down"} else P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def _collect_router_stats(tree) -> Dict[str, list]:
    """Walk a sown ``router_stats`` collection — nested {module_path:
    {stat_name: (values...)}} dicts, one entry per MoE layer — and group
    the leaf values by STAT NAME across layers."""
    stats: Dict[str, list] = {}

    def visit(node):
        for key, val in dict(node).items():
            if hasattr(val, "items"):
                visit(val)
            else:
                vals = val if isinstance(val, (tuple, list)) else (val,)
                stats.setdefault(key, []).extend(vals)

    visit(tree)
    return stats


def _make_moe_step(spec: ModelSpec, optimizer: optax.GradientTransformation,
                   mesh: Mesh, dp_axis: str, ep_axis: str, aux_weight: float,
                   num_experts: int, per_example_loss: Callable) -> Callable:
    """Shared (dp x ep) step machinery: batch sharded over both axes,
    expert weights sharded over ep, aux losses collected from every sown
    ``aux_loss`` leaf, gradients synced per-leaf down to each param's
    sharding."""
    from distkeras_tpu.models.base import build_module

    ep = mesh.shape[ep_axis]
    if num_experts % ep:
        raise ValueError(f"num_experts {num_experts} not divisible by "
                         f"ep mesh axis size {ep}")
    module_local = build_module(spec.name, dict(spec.config, ep_axis=ep_axis, ep_size=ep))

    def shard_fn(params, opt_state, x, y):
        def loss_fn(p):
            logits, variables = module_local.apply(
                {"params": p}, x, mutable=["aux_loss", "router_stats"])
            ce = per_example_loss(logits, y)
            aux_leaves = jax.tree.leaves(variables.get("aux_loss", {}))
            aux = sum(aux_leaves) / len(aux_leaves) if aux_leaves else 0.0
            loss = ce + aux_weight * aux
            n = lax.psum(1, (dp_axis, ep_axis))
            return lax.psum(loss, (dp_axis, ep_axis)) / n, variables

        (loss, variables), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # router observability: every sown counter, averaged over layers
        # and shards (each shard routes its own tokens) — returned so the
        # caller's training loop can watch drops/overflow without a second
        # forward.  stats names follow the sow names in MoEMLP
        n = lax.psum(1, (dp_axis, ep_axis))
        stats = {
            name: lax.psum(sum(vals) / len(vals), (dp_axis, ep_axis)) / n
            for name, vals in _collect_router_stats(
                variables.get("router_stats", {})).items()
        }
        # sync each grad leaf down to its param's sharding: replicated
        # params need the cross-shard psum; expert slabs keep their ep
        # variance but still sum over dp (the same slab serves every dp row)
        grads = jax.tree.map(
            lambda g, p: lax.psum(g, extra) if (extra := tuple(
                a for a in jax.typeof(g).vma if a not in jax.typeof(p).vma)) else g,
            grads, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, stats

    def wrapped(params, opt_state, x, y):
        # specs resolved at trace time from the actual tree structures
        pspecs = _moe_param_specs(params, ep_axis)
        ospecs = _moe_param_specs(opt_state, ep_axis)
        data_spec = P((dp_axis, ep_axis))  # batch split over all devices
        sharded = jax.shard_map(shard_fn, mesh=mesh,
                                in_specs=(pspecs, ospecs, data_spec, data_spec),
                                out_specs=(pspecs, ospecs, P(), P()))
        return sharded(params, opt_state, x, y)

    jitted = jax.jit(wrapped, donate_argnums=(0, 1))

    def step_with_telemetry(params, opt_state, x, y):
        out = jitted(params, opt_state, x, y)
        if obs.enabled():
            # the stats the router always computed and the train loops
            # used to discard: surfaced as gauges.  float() blocks on the
            # step — only paid when telemetry is on
            stats = out[3]
            for stat_name in ("dropped_fraction", "max_expert_load",
                              "dispatch_flops_pct"):
                if stat_name in stats:
                    obs.gauge(f"moe_{stat_name}").set(float(stats[stat_name]))
        return out

    return step_with_telemetry


def make_moe_train_step(spec: ModelSpec, optimizer: optax.GradientTransformation,
                        mesh: Mesh, dp_axis: str = "dp", ep_axis: str = "ep",
                        aux_weight: float = 0.01) -> Callable:
    """Jitted ``(params, opt_state, x, y) -> (params, opt_state, loss,
    router_stats)`` over a (dp, ep) mesh for classifier-shaped models:
    ``y`` one-hot.  Expert weights sharded over ep (place state with
    ``moe_state_shardings``), everything else replicated.  ``router_stats``
    is a dict of scalars averaged over MoE layers and shards —
    ``dropped_fraction`` (routed assignments lost to the capacity cliff),
    ``max_expert_load`` (hottest expert's assignments / capacity) and
    ``dispatch_flops_pct`` (share of the MoE LAYER's matmul FLOPs —
    dispatch + experts + router — spent on routing plumbing; exactly 0
    for sorted) — for the training loop's metrics.
    """
    return _make_moe_step(
        spec, optimizer, mesh, dp_axis, ep_axis, aux_weight,
        num_experts=spec.config["num_experts"],
        per_example_loss=lambda logits, y: optax.softmax_cross_entropy(
            logits.astype(jnp.float32), y).mean())


def make_moe_lm_train_step(spec: ModelSpec, optimizer: optax.GradientTransformation,
                           mesh: Mesh, dp_axis: str = "dp", ep_axis: str = "ep",
                           aux_weight: float = 0.01) -> Callable:
    """(dp x ep) training step for a MoE TransformerLM (``moe_experts`` set
    in the spec): tokens/targets [B, L] int32 with B sharded over both
    axes, Switch FFN experts sharded over ep, per-block load-balance aux
    losses averaged into the objective.  Returns ``(params, opt_state,
    loss, router_stats)`` — see :func:`make_moe_train_step` for the stats
    dict.  v1 scope: MoE composes with dp/ep here (tp/sp belong to the
    dense lm step in parallel/lm.py).
    """
    return _make_moe_step(
        spec, optimizer, mesh, dp_axis, ep_axis, aux_weight,
        num_experts=spec.config["moe_experts"],
        per_example_loss=lambda logits, tgt: optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tgt.astype(jnp.int32))[:, :-1].mean())


def moe_state_shardings(mesh: Mesh, optimizer: optax.GradientTransformation,
                        params: Any, ep_axis: str = "ep"):
    """(param shardings, opt-state shardings) for ``device_put`` before the
    step: expert slabs over ep, the rest replicated (mirrors
    ``lm_state_shardings`` for the tp path)."""
    pspecs = _moe_param_specs(params, ep_axis)
    ospecs = _moe_param_specs(jax.eval_shape(optimizer.init, params), ep_axis)
    to_sh = lambda s: NamedSharding(mesh, s)
    return (jax.tree.map(to_sh, pspecs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(to_sh, ospecs, is_leaf=lambda x: isinstance(x, P)))


def moe_data_sharding(mesh: Mesh, dp_axis: str = "dp", ep_axis: str = "ep"):
    return NamedSharding(mesh, P((dp_axis, ep_axis)))
