"""Pipeline parallelism: microbatch schedules over a ``pp`` axis.

No reference counterpart (the reference is data-parallel only, SURVEY.md
§2.13) — TPU-native headroom.  Two schedules share one substrate (the
rotation: each rank applies its resident stage of ``num_layers / pp``
transformer blocks to its current buffer, then ``lax.ppermute``s
activations one hop):

1. **GPipe** — all-forward-then-all-backward.  Rank 0 feeds a fresh
   microbatch each tick; the last rank collects finished microbatches;
   ``M + pp - 1`` ticks drain ``M``.  The backward schedule is NOT
   hand-written: differentiating through the tick scan reverses every
   ppermute (collective adjoints), which IS the backward pipeline.
   ``jax.checkpoint`` around the stage keeps per-tick residuals
   O(microbatch), but the scan's residuals grow O(M) overall.
2. **1F1B** (``schedule="1f1b"``) — hand-scheduled: each cycle runs one
   forward AND one backward unit per rank, cotangents hop up a reverse
   ppermute ring, and backward units re-derive their stage vjp from a
   ``2*pp - 1``-slot input ring — resident activations O(pp) regardless
   of M.  Same gradients (parity-tested), same 2(pp-1)-unit bubble.

Layout: block params are stacked to [num_layers, ...] and sharded over pp
on the leading axis (each rank holds its stage's slab); embedding/unembed/
final-norm params are replicated — only rank 0's embedding output enters
the pipeline, so its gradient routes exclusively through rank 0's path.

Composes with data parallelism over a (dp, pp) mesh; tensor/sequence axes
compose at the block level and are left out of the v1 pipeline step.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.models.base import ModelSpec, build_module
from distkeras_tpu.models.transformer import TransformerBlock


def split_block_params(params: Dict[str, Any]) -> Tuple[Dict[str, Any], Any]:
    """Full TransformerLM params -> (outer params, blocks stacked on axis 0).

    ``outer`` keeps the embedding / positional / final-norm leaves under
    their original names; ``blocks`` stacks ``block_0..block_{n-1}`` (all
    structurally identical) into one pytree with a leading layer axis.
    """
    names = sorted((k for k in params if k.startswith("block_")),
                   key=lambda k: int(k.split("_")[1]))
    if not names:
        raise ValueError("params contain no block_i subtrees; not a TransformerLM tree")
    blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *[params[k] for k in names])
    outer = {k: v for k, v in params.items() if not k.startswith("block_")}
    return outer, blocks


def merge_block_params(outer: Dict[str, Any], blocks: Any) -> Dict[str, Any]:
    """Inverse of ``split_block_params`` (for checkpointing / serialization)."""
    num_layers = jax.tree.leaves(blocks)[0].shape[0]
    params = dict(outer)
    for i in range(num_layers):
        params[f"block_{i}"] = jax.tree.map(lambda a, i=i: a[i], blocks)
    return params


def pp_param_specs(outer: Dict[str, Any], blocks: Any, pp_axis: str):
    outer_specs = jax.tree.map(lambda _: P(), outer)
    block_specs = jax.tree.map(lambda _: P(pp_axis), blocks)
    return outer_specs, block_specs


def make_pp_train_step(spec: ModelSpec, optimizer: optax.GradientTransformation,
                       mesh: Mesh, num_microbatches: int,
                       dp_axis: str = "dp", pp_axis: str = "pp",
                       schedule: str = "gpipe") -> Callable:
    """Build a jitted ((outer, blocks), opt_state, tokens, targets) ->
    ((outer, blocks), opt_state, loss) pipeline-parallel training step.

    ``tokens``/``targets`` are [B, L] with B sharded over dp (and B a
    multiple of ``num_microbatches`` per dp shard); block params must be
    placed with ``pp_state_shardings``.

    ``schedule``:

    - ``"gpipe"`` — all-forward-then-all-backward; the backward pipeline
      comes free from differentiating the tick scan (collective
      adjoints).  Activation residuals grow with the number of
      microbatches M: O(M) stage boundaries live across the backward.
    - ``"1f1b"`` — hand-scheduled one-forward-one-backward: each cycle
      every rank runs one forward unit AND one backward unit (the
      backward re-derives its stage vjp from a stored stage INPUT), so
      at most ``2*pp - 1`` microbatch activations are ever resident —
      O(pp), independent of M.  The gradient math is identical (parity
      tested); the BUBBLE is also identical (2(pp-1) idle units either
      way — non-interleaved 1F1B trades nothing for its memory bound).
      Pick it when M must grow (long sequences / small microbatches)
      and GPipe's O(M) residuals would not fit HBM.

      **Head cost:** ``unit_scalar`` runs the final-norm + unembed
      matmul and the vocab-wide softmax-CE inside a ``lax.cond`` whose
      predicate is (last rank AND valid backward unit) — XLA
      conditionals execute one branch per device at runtime, so only
      the last rank's M valid units ever pay the vocab-sized matmul;
      every other rank (and fill/drain cycles) runs the cheap cotangent
      chain term: the same M head evaluations a step as GPipe.  (A
      ``jnp.where`` mask would compute the head on every rank every
      cycle, ``pp * (1 + 2(pp-1)/M)`` times GPipe's unembed FLOPs.)
    """
    if spec.config.get("moe_experts"):
        raise ValueError("MoE FFN does not compose with pipeline parallelism "
                         "(v1); use make_moe_lm_train_step or a dense spec")
    # the stages apply ONE block module to a stacked slab of layers
    from distkeras_tpu.models.transformer import reject_block_features

    reject_block_features(spec.config, "pipeline parallelism (make_pp_train_step)")
    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"schedule must be 'gpipe' or '1f1b', got {schedule!r}")
    pp = mesh.shape[pp_axis]
    num_layers = spec.config["num_layers"]
    if num_layers % pp:
        raise ValueError(f"num_layers {num_layers} not divisible by pp {pp}")
    layers_per_stage = num_layers // pp
    cfg = spec.config
    cdtype = cfg.get("compute_dtype", jnp.bfloat16)
    block = TransformerBlock(
        model_dim=cfg["model_dim"], num_heads=cfg["num_heads"],
        num_kv_heads=cfg.get("num_kv_heads"),
        mlp_ratio=cfg.get("mlp_ratio", 4), seq_axis=None,
        positional=cfg.get("positional") or "learned",
        attn_impl=cfg.get("attn_impl"), compute_dtype=cdtype)
    module = build_module(spec.name, dict(cfg, seq_axis=None))

    @jax.checkpoint
    def stage_apply(stage_params, x):
        """Apply this rank's ``layers_per_stage`` blocks (scan over the slab)."""

        def one(x, layer_params):
            return block.apply({"params": layer_params}, x), None

        x, _ = lax.scan(one, x, stage_params)
        return x

    def vary(z):
        """Promote to varying over (dp, pp) — both schedules' buffers need
        the full vma before mixing with per-shard data."""
        missing = tuple(a for a in (dp_axis, pp_axis)
                        if a not in jax.typeof(z).vma)
        return lax.pcast(z, missing, to="varying") if missing else z

    down_perm = [(i, (i + 1) % pp) for i in range(pp)]
    up_perm = [(i, (i - 1) % pp) for i in range(pp)]

    def shard_fn_1f1b(params, opt_state, tokens, targets):
        """One-forward-one-backward: cycle c runs the forward of
        microbatch ``c - rank`` and the backward of microbatch
        ``c - 2(pp-1) + rank`` on every rank, with activations hopping
        down (ppermute) and cotangents hopping up each cycle.

        No autodiff crosses the cycle scan: backward units recompute
        their stage vjp from the stage INPUT stored in a ``2*pp - 1``
        slot ring (an input stored at cycle ``b + r`` is consumed at
        ``b + 2(pp-1) - r``, span <= 2(pp-1) < ring), and parameter
        gradients accumulate explicitly.  The last rank's backward unit
        folds the head + CE vjp into the same grad call via a
        ``lax.cond``-selected scalar (the cond's vjp is the cond of the
        branch vjps, so non-head units contribute exactly the cotangent
        chain and zero head gradient — and, unlike a ``jnp.where``
        mask, never EXECUTE the vocab-sized head matmul).

        Resident activations really are O(pp): the embedding runs PER
        CYCLE on the current microbatch's tokens (the full-epoch token
        ids are the only O(M) array — int32, model_dim-times smaller
        than activations), and rank 0's embedding cotangent folds into
        the gradient accumulator in the same cycle via an inline vjp
        instead of being collected into an O(M) buffer.

        Params enter the cycle computation pcast to (dp, pp)-VARYING, so
        every unit grad is shard-local (no per-cycle implicit psum from
        the unvarying->varying adjoint); the single demotion to each
        param's sharding happens once after the scan — where the psum
        over pp neatly SUMS the outer tree's two owners (rank 0's
        embedding part, the last rank's head part).
        """
        outer, blocks = params
        my = lax.axis_index(pp_axis)
        is_last = my == pp - 1
        b, l = tokens.shape
        m = num_microbatches
        mb = b // m
        e = cfg["model_dim"]
        edtype = jnp.dtype(cdtype)
        tok_mb = vary(tokens.reshape(m, mb, l))
        tgt_mb = vary(targets.reshape(m, mb, l))
        outer_v = jax.tree.map(vary, outer)
        blocks_v = jax.tree.map(vary, blocks)

        def embed(outer_, tok_1mb):
            return module.apply({"params": outer_}, tok_1mb,
                                method="embed_tokens")

        def unit_scalar(blocks_, outer_, x_in, cot_in, tgt_1mb, head_flag):
            """``head_flag`` = (last rank AND valid backward unit): the
            vocab-sized head + CE runs inside a ``lax.cond`` branch, so
            every other rank (and the last rank's fill/drain cycles)
            executes only the cheap chain term at RUNTIME — XLA
            conditionals evaluate one branch per device, which is how a
            per-rank branch lives inside one SPMD program without every
            rank paying the unembed matmul (a ``jnp.where`` mask would
            compute it and throw it away on pp ranks in every cycle).
            Autodiff through cond yields the cond of the branch vjps, so
            non-head units contribute exactly the cotangent chain and
            zero head gradient."""
            y = stage_apply(blocks_, x_in)

            def ce_term(y_):
                logits = module.apply({"params": outer_}, y_, method="head")
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), tgt_1mb.astype(jnp.int32))
                return jnp.sum(ce[:, :-1])

            def chain_term(y_):
                return jnp.sum((y_ * cot_in).astype(jnp.float32))

            return lax.cond(head_flag, ce_term, chain_term, y)

        unit_grad = jax.value_and_grad(unit_scalar, argnums=(0, 1, 2))

        ring = 2 * pp - 1
        cycles = m + 2 * (pp - 1)
        zeros_f32 = lambda tree: jax.tree.map(
            lambda a: vary(jnp.zeros(a.shape, jnp.float32)), tree)
        carry0 = (
            vary(jnp.zeros((mb, l, e), edtype)),               # fwd_buf
            vary(jnp.zeros((mb, l, e), edtype)),               # cot_buf
            vary(jnp.zeros((ring, mb, l, e), edtype)),         # act ring
            zeros_f32(blocks),                                 # grad accum
            zeros_f32(outer),                                  # outer grad accum
            vary(jnp.zeros((), jnp.float32)),                  # loss accum
        )

        def cycle(carry, c):
            fwd_buf, cot_buf, acts, g_blocks, g_outer, loss = carry
            # ---- forward unit: microbatch c - my -------------------------
            feed = embed(outer_v, lax.dynamic_index_in_dim(
                tok_mb, jnp.clip(c, 0, m - 1), 0, keepdims=False))
            x_in_f = jnp.where(my == 0, feed.astype(edtype), fwd_buf)
            y_f = stage_apply(blocks_v, x_in_f)
            acts = lax.dynamic_update_index_in_dim(acts, x_in_f, c % ring, 0)
            # ---- backward unit: microbatch c - 2(pp-1) + my --------------
            b_idx = c - 2 * (pp - 1) + my
            b_valid = jnp.logical_and(b_idx >= 0, b_idx < m)
            stored_at = b_idx + my  # its forward cycle on this rank
            x_in_b = lax.dynamic_index_in_dim(
                acts, jnp.clip(stored_at, 0, cycles) % ring, 0, keepdims=False)
            tgt_b = lax.dynamic_index_in_dim(tgt_mb, jnp.clip(b_idx, 0, m - 1),
                                             0, keepdims=False)
            # head branch only where it counts: the last rank's VALID
            # units (b_valid also gates it so fill/drain cycles skip the
            # unembed too — the head now runs exactly M times per step,
            # matching GPipe's count)
            val, (gb, go, gx) = unit_grad(blocks_v, outer_v, x_in_b, cot_buf,
                                          tgt_b,
                                          jnp.logical_and(is_last, b_valid))
            mask = b_valid.astype(jnp.float32)
            # rank 0's input cotangent is the embedding cotangent for mb b:
            # fold it into the outer grads NOW (inline vjp over one
            # microbatch) instead of collecting an O(M) cotangent buffer
            tok_b = lax.dynamic_index_in_dim(tok_mb, jnp.clip(b_idx, 0, m - 1),
                                             0, keepdims=False)
            keep0 = jnp.logical_and(b_valid, my == 0)
            ggx = jnp.where(keep0, gx, jnp.zeros_like(gx))
            _, vjp_embed = jax.vjp(lambda o: embed(o, tok_b), outer_v)
            (ge,) = vjp_embed(ggx.astype(feed.dtype))
            g_blocks = jax.tree.map(lambda acc, g: acc + mask * g, g_blocks, gb)
            g_outer = jax.tree.map(
                lambda acc, g1, g2: acc + mask * g1 + g2.astype(jnp.float32),
                g_outer, go, ge)
            loss = loss + jnp.where(jnp.logical_and(b_valid, is_last), val, 0.0)
            # ---- communication: activations down, cotangents up ----------
            fwd_buf = lax.ppermute(y_f, pp_axis, down_perm)
            cot_buf = lax.ppermute(gx.astype(edtype), pp_axis, up_perm)
            return (fwd_buf, cot_buf, acts, g_blocks, g_outer, loss), None

        (carry_out, _) = lax.scan(cycle, carry0, jnp.arange(cycles))
        _, _, _, g_blocks, g_outer_acc, loss_sum = carry_out

        # normalization matching the GPipe loss: global token count over dp
        wcount = lax.pcast(jnp.float32(b * (l - 1)), (dp_axis,), to="varying")
        denom = lax.psum(wcount, (dp_axis,))
        # grads accumulated SHARD-LOCALLY (params entered varying): one
        # explicit demotion to each param's sharding.  blocks are
        # pp-sharded dp-replicated -> sum over dp only; the outer tree's
        # two contributions live on different ranks (embedding on rank 0,
        # head on the last rank, zero elsewhere by masking), so the psum
        # over pp both combines them and replicates the result
        g_blocks = jax.tree.map(lambda g: lax.psum(g, (dp_axis,)) / denom,
                                g_blocks)
        g_outer = jax.tree.map(
            lambda g: lax.psum(g, (dp_axis, pp_axis)) / denom, g_outer_acc)
        loss = lax.psum(loss_sum, (dp_axis, pp_axis)) / denom

        grads = (g_outer, g_blocks)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    def shard_fn(params, opt_state, tokens, targets):
        outer, blocks = params
        my = lax.axis_index(pp_axis)

        def global_loss(p):
            outer, blocks = p
            # stage slab arrives as [layers_per_stage, ...] (leading pp axis
            # stripped by shard_map); embedding is computed identically on
            # every rank but only rank 0's copy enters the pipeline
            b, l = tokens.shape
            mb = b // num_microbatches
            toks_mb = tokens.reshape(num_microbatches, mb, l)

            # Embed/head run outside the pipeline via TransformerLM's own
            # bound methods, so they share one source of truth (and the
            # exact param leaves) with the single-device __call__ path.
            # The block params are absent from `outer`, which is fine:
            # embed_tokens/head never touch them.
            x_emb = module.apply({"params": outer}, toks_mb.reshape(b, l),
                                 method="embed_tokens")
            x_emb = vary(x_emb.reshape(num_microbatches, mb, l, -1))
            e = x_emb.shape[-1]
            ticks = num_microbatches + pp - 1
            buf0 = vary(jnp.zeros((mb, l, e), x_emb.dtype))
            outs0 = vary(jnp.zeros_like(x_emb))

            def tick(carry, t):
                buf, outs = carry
                feed = lax.dynamic_index_in_dim(
                    x_emb, jnp.clip(t, 0, num_microbatches - 1), 0, keepdims=False)
                x_in = jnp.where(my == 0, feed, buf)
                # idle ranks/ticks compute on garbage; results are never
                # collected (GPipe bubble) — predication would not save
                # wall-clock on a SPMD schedule
                y = stage_apply(blocks, x_in)
                done_idx = t - (pp - 1)
                valid = jnp.logical_and(my == pp - 1, done_idx >= 0)
                new_outs = lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(done_idx, 0, num_microbatches - 1), 0)
                outs = jnp.where(valid, new_outs, outs)
                buf = lax.ppermute(y, pp_axis, down_perm)
                return (buf, outs), None

            (buf, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
            # finished activations live on the last rank only; mask + psum
            # replicates them (making the rest of the loss pp-invariant)
            outs = lax.psum(jnp.where(my == pp - 1, outs, 0.0), pp_axis)

            logits = module.apply({"params": outer}, outs.reshape(b, l, e),
                                  method="head")
            ce = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), targets.astype(jnp.int32))
            wsum = jnp.sum(ce[:, :-1])
            wcount = jnp.float32(b * (l - 1))
            wcount = lax.pcast(wcount, (dp_axis,), to="varying")
            return lax.psum(wsum, (dp_axis,)) / lax.psum(wcount, (dp_axis,))

        loss, grads = jax.value_and_grad(global_loss)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    outer_t, blocks_t = jax.eval_shape(
        lambda: split_block_params(spec.init_params(seed=0)))
    pspecs = pp_param_specs(outer_t, blocks_t, pp_axis)
    ospecs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _opt_leaf_spec(path, pp_axis),
        jax.eval_shape(optimizer.init, (outer_t, blocks_t)))
    data_spec = P(dp_axis)
    sharded = jax.shard_map(
        shard_fn_1f1b if schedule == "1f1b" else shard_fn,
        mesh=mesh,
        in_specs=(pspecs, ospecs, data_spec, data_spec),
        out_specs=(pspecs, ospecs, P()),
    )
    return jax.jit(sharded, donate_argnums=(0, 1))


def _opt_leaf_spec(path, pp_axis: str) -> P:
    """Optimizer-state leaves mirroring the (outer, blocks) params tuple.

    Optax states nest that tuple under namedtuple/tuple wrappers whose keys
    are also SequenceKeys, so walk from the leaf upward: the innermost
    SequenceKey (the params-tuple position, since everything below it is
    the flax dict tree) decides — index 1 is the pp-sharded block slab.
    Pure-scalar leaves (step counters) sit directly under state tuples and
    resolve to index 0 -> replicated, which is correct for them too.
    """
    for k in reversed(path):
        idx = getattr(k, "idx", None)
        if idx == 1:
            return P(pp_axis)
        if idx is not None:
            return P()
    return P()


def pp_state_shardings(mesh: Mesh, optimizer: optax.GradientTransformation,
                       outer: Dict[str, Any], blocks: Any,
                       pp_axis: str = "pp"):
    pspecs = pp_param_specs(outer, blocks, pp_axis)
    ospecs = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _opt_leaf_spec(path, pp_axis),
        jax.eval_shape(optimizer.init, (outer, blocks)))
    to_sh = lambda s: NamedSharding(mesh, s)
    return (jax.tree.map(to_sh, pspecs, is_leaf=lambda x: isinstance(x, P)),
            jax.tree.map(to_sh, ospecs, is_leaf=lambda x: isinstance(x, P)))
