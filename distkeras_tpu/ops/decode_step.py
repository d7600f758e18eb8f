"""Fused single-token decode step: one Pallas kernel per transformer block.

Why this exists: the XLA decode step at batch 1 lowers to ~15 ops per
block (LN, qkv, two cache updates, scores, mask, softmax, pv, proj,
residual, LN, up, gelu, down, residual), and at one token a step each op
is so small that the per-token cost follows the op count (a fixed cost
to sequence each op) rather than the matmul sizes.  Collapsing each
block into ONE Mosaic kernel removes that per-op floor and leaves the
step bounded by what it must be bounded by: HBM traffic for weights and
cache.  Decoding has no benchmark cell, so none of this is on the ledger
(ROADMAP, "Never on the ledger").

Design (single kernel, grid over layers — Mosaic grids run sequentially,
so the hidden-state carry lives in VMEM scratch across grid steps):

- Per-layer weights are stacked to ``[L, ...]`` slabs outside the kernel
  (a one-time, loop-invariant transform that XLA hoists out of the decode
  scan) and streamed per layer through ``BlockSpec`` index maps — Pallas
  double-buffers the fetches, overlapping layer ``l+1``'s weight DMA with
  layer ``l``'s compute.
- The KV cache stays in HBM (``pl.ANY``): the kernel DMAs the layer's
  K/V slabs into VMEM scratch (attention must read them anyway).  The
  NEW K/V rows leave the kernel as ordinary [L, B, HD] outputs and land
  in the cache via one XLA ``dynamic_update_slice`` per cache outside it
  (in place under the decode scan's donation) — Mosaic rejects both a
  dynamic single-row VMEM insert and a sub-tile-aligned HBM DMA write,
  and a blocked-output cache would write the whole slab back per layer
  per token, doubling cache traffic.  The new token's own attention
  contribution is merged analytically as a second online-softmax term,
  so the slab never needs the row at all.
- The K cache is stored TRANSPOSED for this path — [L, B, HD, S] — and
  V row-major [L, B, S, H, D].  This makes both attention contractions
  canonical MXU matmuls with NO [S, HD]-sized elementwise pass and no
  lane<->sublane transposes (Mosaic supports neither a cheap [1, HD] ->
  [HD, 1] reshape nor fast big elementwise f32 passes, so a kernel
  built on them scales badly with the cache's rows):

      scores^T [H, S] = (sel^T ⊙ q_row) [H, HD]  @  k_slab^T [HD, S]
      mix      [H, HD] =          p^T [H, S]     @  v_slab   [S, HD]
      o        [1, HD] = masked row-sum of mix (block-diagonal strip)

  where ``sel^T[h, hd] = (hd // D == h)`` is the 0/1 head selector:
  broadcasting the [1, HD] q row down H sublanes is free, softmax runs
  over lanes, and head count only changes the selector height.

The kernel is decode-phase only (L = 1): prefill keeps the XLA path,
whose big [P, E] matmuls are already MXU-shaped (the K cache is
transposed once after prefill).  Reference parity note: the reference
has no decode path at all (SURVEY.md §2.21 serves independent
``model.predict`` calls); this is TPU-native headroom on the framework's
own serving story.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.quantize import QTensor
from distkeras_tpu.platform import on_tpu

_NEG_INF = float("-inf")

# a batch-8 working set (two KV slabs + double-buffered weight blocks +
# attention temps) runs to tens of MB; v5e VMEM fits it comfortably but
# Mosaic's 16MB default does not
_VMEM_LIMIT = 96 * 1024 * 1024


class DecodeWeights(NamedTuple):
    """Per-layer weight slabs stacked on a leading layer axis.

    ``ln`` packs all four norm vectors (ln0 scale/bias, ln1 scale/bias)
    as rows of one [L, 8, E] f32 slab — Mosaic wants the last two block
    dims tileable, and four [L, E] arrays would each carry a sublane-1
    block; padding to 8 rows costs nothing and keeps one fetch."""

    ln: jnp.ndarray     # [L, 8, E] f32
    wqkv: jnp.ndarray   # [L, E, 3*H*D] compute dtype
    wproj: jnp.ndarray  # [L, H*D, E]
    wup: jnp.ndarray    # [L, E, F]
    wdown: jnp.ndarray  # [L, F, E]


def stack_decode_weights(params: Any, num_layers: int,
                         dtype=jnp.bfloat16) -> DecodeWeights:
    """Restack ``block_{i}`` param subtrees into layer-major slabs.

    Inside a jitted generate fn this is loop-invariant w.r.t. the decode
    scan, so XLA materializes the slabs once per call, not per token.
    int8 ``QTensor`` leaves are dequantized here (the fused kernel
    streams weights in the compute dtype: at batch 1 the per-op floor,
    not weight bytes, is what the fused path removes).
    """
    def deq(w):
        return w.dequantize(dtype) if isinstance(w, QTensor) else w.astype(dtype)

    lns, qkvs, projs, ups, downs = [], [], [], [], []
    for i in range(num_layers):
        pb = params[f"block_{i}"]
        e = pb["LayerNorm_0"]["scale"].shape[0]
        ln = jnp.zeros((8, e), jnp.float32)
        ln = ln.at[0].set(pb["LayerNorm_0"]["scale"].astype(jnp.float32))
        ln = ln.at[1].set(pb["LayerNorm_0"]["bias"].astype(jnp.float32))
        ln = ln.at[2].set(pb["LayerNorm_1"]["scale"].astype(jnp.float32))
        ln = ln.at[3].set(pb["LayerNorm_1"]["bias"].astype(jnp.float32))
        lns.append(ln)
        qkvs.append(deq(pb["qkv"]["kernel"]).reshape(e, -1))      # [E, 3HD]
        projs.append(deq(pb["proj"]["kernel"]).reshape(-1, e))    # [HD, E]
        ups.append(deq(pb["up"]["kernel"]))                       # [E, F]
        downs.append(deq(pb["down"]["kernel"]))                   # [F, E]
    return DecodeWeights(jnp.stack(lns), jnp.stack(qkvs), jnp.stack(projs),
                         jnp.stack(ups), jnp.stack(downs))


def round_cache_len(n: int) -> int:
    """The transposed K slab puts the sequence on LANES: multiple of 128."""
    return -(-n // 128) * 128


# what the working set may claim of the 96MB grant, leaving headroom for
# Mosaic's own temporaries and pipelining copies
_VMEM_BUDGET = 72 * 1024 * 1024


def _kernel_vmem_bytes(config: dict, batch: int, cache_len: int) -> int:
    """Rough VMEM working set: both KV slabs + double-buffered weight
    blocks + the [B*H, B*S] f32 score block and its exp/mask copies +
    the per-layer activation slabs (sublane-padded qkv output [B8, 3E]
    and MLP up-projection [B8, F] — near the budget these are what
    pushes a shape past the grant, so omitting them would let
    ``fused_step_supported`` pass a shape that dies at Mosaic compile
    time, the exact failure the gate exists to prevent)."""
    e = config["model_dim"]
    h = config["num_heads"]
    f = config.get("mlp_ratio", 4) * e
    import numpy as np

    dsize = np.dtype(config.get("compute_dtype", jnp.bfloat16)).itemsize
    b8 = -(-batch // 8) * 8  # rows are sublane-padded to 8
    slabs = 2 * batch * cache_len * e * dsize
    weight_block = (e * 3 * e + e * e + e * f + f * e) * dsize * 2
    scores = 3 * (batch * h) * (batch * cache_len) * 4
    # the matmuls producing these run at preferred_element_type=f32, so the
    # live buffer is f32 plus its compute-dtype downcast copy
    acts = (b8 * 3 * e + b8 * f) * (4 + dsize)
    return slabs + weight_block + scores + acts


def fused_step_supported(config: dict, batch: int, cache_len: int) -> bool:
    """Shapes the kernel handles: lane-tiled dims, a lane-tiled cache
    length (see ``round_cache_len``), and a working set the VMEM grant
    can hold (a shape passing the tiling checks but blowing the grant
    would die at Mosaic compile time, not fall back).  Callers use the
    XLA step when this is False."""
    e = config["model_dim"]
    h = config["num_heads"]
    f = config.get("mlp_ratio", 4) * e
    # batch cap: the kernel's [B*H, B*S] f32 score block grows
    # quadratically with batch (6MB at b16/s768); past 16 rows plain
    # batched decode amortizes fine anyway
    kv_heads = config.get("num_kv_heads") or h
    return (e % 128 == 0 and f % 128 == 0 and h <= 128
            and kv_heads == h  # GQA's split q/kv layout: XLA step only (v1)
            # rope rotates q/k per step; the kernel bakes learned-table
            # embedding math only (v1) — auto falls back to the XLA step
            and (config.get("positional") or "learned") == "learned"
            and not config.get("moe_experts")
            and cache_len % 128 == 0 and 1 <= batch <= 16
            and _kernel_vmem_bytes(config, batch, cache_len) <= _VMEM_BUDGET)


# auto-select crossover.  The kernel's edge is the fixed ~15-op-per-layer
# sequencing cost it removes, which stops mattering once per-layer weight
# streaming dominates (XLA's step already overlaps that streaming) — so
# auto-select keys on total block-weight bytes.  The bound was set inside
# the winning region of a July 2026 sweep (batch 1, 768-row cache, 2 to 8
# layers) that no benchmark cell has repeated: re-measure before moving it.
_AUTO_MAX_BLOCK_BYTES = 24 * 1024 * 1024


def fused_step_auto(config: dict, batch: int, cache_len: int) -> bool:
    """Should the fused kernel be auto-selected?  True only in the regime
    where it measured FASTER than the XLA step: batch 1 (the batched
    kernel's lockstep score block loses to XLA's amortization) and a
    small-to-mid model (see the crossover note above).  ``step_impl='fused'``
    overrides this for A/B measurement; ``fused_step_supported`` is the
    hard shape gate."""
    e = config["model_dim"]
    # qkv 3e² + proj e² + up/down 2·mlp_ratio·e² per layer, bf16 stream
    # (= 12e² at the measured mlp_ratio-4 crossover configs)
    per_layer = (4 + 2 * config.get("mlp_ratio", 4)) * e * e
    block_bytes = per_layer * config["num_layers"] * 2
    return (batch == 1 and block_bytes <= _AUTO_MAX_BLOCK_BYTES
            and fused_step_supported(config, batch, cache_len))


def resolve_step_impl(config: dict, batch: int, cache_len: int,
                      requested, *, what: str = "step_impl") -> str:
    """The ONE selection policy shared by ``make_generate_fn``,
    ``make_speculative_generate_fn`` (draft side): ``None`` -> fused iff
    on TPU and ``fused_step_auto``;
    explicit ``"fused"`` -> hard-validated against
    ``fused_step_supported``; anything else must be ``"xla"``."""
    cache_len = round_cache_len(cache_len)
    if requested is None:
        return ("fused" if (on_tpu()
                            and fused_step_auto(config, batch, cache_len))
                else "xla")
    if requested == "fused":
        if not fused_step_supported(config, batch, cache_len):
            raise ValueError(
                f"{what}='fused' does not support this config/shape "
                f"(model_dim {config['model_dim']}, batch {batch}, cache "
                f"{cache_len}); see ops.decode_step.fused_step_supported")
        return "fused"
    if requested != "xla":
        raise ValueError(f"unknown {what} {requested!r}; use None, 'fused' "
                         "or 'xla'")
    return "xla"


def _ln(x32, scale, bias):
    """LayerNorm matching models/decode.py::_layer_norm (f32 stats, eps 1e-6)."""
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias


def _decode_kernel(pos_ref, x_ref, ln_ref, wqkv_ref, wproj_ref, wup_ref,
                   wdown_ref, kc_hbm, vc_hbm, x_out, k_rows, v_rows,
                   xc, k_slab, v_slab, sem_k, sem_v, *, batch: int,
                   heads: int, pos_dim: int, s_len: int, dtype):
    """One transformer block over the [B8, E] hidden state at position
    ``pos``; grid dimension 0 is the layer index."""
    l = pl.program_id(0)
    pos = pos_ref[0]
    head_dim = pos_dim
    del pos_dim

    # slab reads first: the LN + qkv matmul below runs under the DMA
    cp_k = pltpu.make_async_copy(kc_hbm.at[l], k_slab, sem_k)
    cp_v = pltpu.make_async_copy(vc_hbm.at[l], v_slab, sem_v)
    cp_k.start()
    cp_v.start()

    @pl.when(l == 0)
    def _seed():
        xc[...] = x_ref[...]

    x = xc[...]  # [B8, E] compute dtype (bf16 residual stream, as XLA path)
    x32 = x.astype(jnp.float32)

    y = _ln(x32, ln_ref[0, 0], ln_ref[0, 1]).astype(dtype)
    qkv = jax.lax.dot_general(y, wqkv_ref[0], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    qkv = qkv.astype(dtype)  # XLA path rounds q/k/v to bf16 before use
    hd = heads * head_dim
    q = qkv[:batch, :hd]
    k_new = qkv[:batch, hd:2 * hd]
    v_new = qkv[:batch, 2 * hd:3 * hd]

    k_rows[...] = k_new[None]
    v_rows[...] = v_new[None]
    cp_k.wait()
    cp_v.wait()

    # --- attention over the slab (batch-interleaved transposed-K scheme) --
    # One scores matmul and one mix matmul for the WHOLE batch: rows are
    # (b, h) pairs, columns (b', s) pairs, and the block-diagonal mask
    # kills the b != b' cross terms.  The B-fold FLOP redundancy is ~2us
    # of MXU time at batch 8; the per-b matmul loop it replaced cost
    # ~8us of issue latency per batch row per layer.
    bh, bs = batch * heads, batch * s_len
    kmat = k_slab[...]                                     # [HD, B*S]
    vmat = v_slab[...]                                     # [B*S, HD]

    row_h = jax.lax.broadcasted_iota(jnp.int32, (bh, hd), 0) % heads
    hd_col = jax.lax.broadcasted_iota(jnp.int32, (bh, hd), 1)
    sel_t = hd_col // head_dim == row_h                    # [BH, HD] 0/1
    sel_f32 = sel_t.astype(jnp.float32)
    scale = 1.0 / head_dim ** 0.5
    # selB[b, r] = (r // heads == b): folds the H rows of batch b back to
    # one output row; selBT is its transpose (built from iota, not
    # transposed — Mosaic transposes are not free) replicating each batch
    # row across its H head-rows
    selB = (jax.lax.broadcasted_iota(jnp.int32, (batch, bh), 1) // heads
            == jax.lax.broadcasted_iota(jnp.int32, (batch, bh), 0))
    selBT = (jax.lax.broadcasted_iota(jnp.int32, (bh, batch), 0) // heads
             == jax.lax.broadcasted_iota(jnp.int32, (bh, batch), 1))

    def rows_per_head(a):                                  # [B, HD] -> [BH, HD]
        if batch == 1:
            return jnp.broadcast_to(a, (bh, hd))
        out = jax.lax.dot_general(selBT.astype(a.dtype), a,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return out.astype(a.dtype)  # 0/1 replication: exact in any dtype

    q_bdt = sel_t.astype(dtype) * rows_per_head(q)         # [BH, HD]
    scores = jax.lax.dot_general(
        q_bdt, kmat, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale        # [BH, BS]
    row_b = jax.lax.broadcasted_iota(jnp.int32, (bh, bs), 0) // heads
    col = jax.lax.broadcasted_iota(jnp.int32, (bh, bs), 1)
    mask = (row_b == col // s_len) & (col % s_len < pos)
    scores = jnp.where(mask, scores, _NEG_INF)

    qk_new = q.astype(jnp.float32) * k_new.astype(jnp.float32)   # [B, HD]
    s_new = jnp.sum(sel_f32 * rows_per_head(qk_new), axis=1,
                    keepdims=True) * scale                 # [BH, 1]

    m = jnp.maximum(jnp.max(scores, axis=1, keepdims=True), s_new)
    p = jnp.exp(scores - m)                                # [BH, BS]
    p_new = jnp.exp(s_new - m)                             # [BH, 1]
    denom = jnp.sum(p, axis=1, keepdims=True) + p_new
    # jax.nn.softmax(f32) then .astype(bf16) in the XLA path: divide
    # first, round to bf16, THEN weight V — same op order here
    p = (p / denom).astype(dtype)
    p_new = (p_new / denom).astype(dtype).astype(jnp.float32)
    mix = jax.lax.dot_general(p, vmat, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [BH, HD]
    selB_f32 = selB.astype(jnp.float32)
    o = jax.lax.dot_general(selB_f32, mix * sel_f32, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # [B, HD]
    pn_wide = jax.lax.dot_general(selB_f32, sel_f32 * p_new,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o = o + pn_wide * v_new.astype(jnp.float32)

    pad_rows = x.shape[0] - batch
    o8 = (o.astype(dtype) if pad_rows == 0 else
          jnp.concatenate([o.astype(dtype), jnp.zeros((pad_rows, hd), dtype)]))
    proj = jax.lax.dot_general(o8, wproj_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    x = x + proj.astype(dtype)

    x32 = x.astype(jnp.float32)
    y = _ln(x32, ln_ref[0, 2], ln_ref[0, 3]).astype(dtype)
    up = jax.lax.dot_general(y, wup_ref[0], (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    up = jax.nn.gelu(up.astype(dtype))
    down = jax.lax.dot_general(up, wdown_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    x = x + down.astype(dtype)

    xc[...] = x
    # write the (valid partial) output every visit: last write wins, and
    # no emitted block ever depends on stale revisited-buffer contents
    x_out[...] = x


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _fused_call(weights: DecodeWeights, x8, k_t, v_all, pos_arr, *,
                heads: int, interpret: bool):
    num_layers, hd, b, s_len = k_t.shape
    # 2D per-layer HBM slices for the kernel's DMAs (Mosaic rejects
    # memref slicing that keeps 1 of an inner dim on 4D tiled refs)
    kc = k_t.reshape(num_layers, hd, b * s_len)
    vc = v_all.reshape(num_layers, b * s_len, hd)
    e = x8.shape[1]
    f = weights.wup.shape[2]
    dtype = x8.dtype
    b8 = x8.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_layers,),
        in_specs=[
            pl.BlockSpec((b8, e), lambda l, p: (0, 0)),
            pl.BlockSpec((1, 8, e), lambda l, p: (l, 0, 0)),
            pl.BlockSpec((1, e, 3 * hd), lambda l, p: (l, 0, 0)),
            pl.BlockSpec((1, hd, e), lambda l, p: (l, 0, 0)),
            pl.BlockSpec((1, e, f), lambda l, p: (l, 0, 0)),
            pl.BlockSpec((1, f, e), lambda l, p: (l, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((b8, e), lambda l, p: (0, 0)),
            pl.BlockSpec((1, b, hd), lambda l, p: (l, 0, 0)),
            pl.BlockSpec((1, b, hd), lambda l, p: (l, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((b8, e), dtype),                  # xc carry
            pltpu.VMEM((hd, b * s_len), k_t.dtype),      # k slab (transposed)
            pltpu.VMEM((b * s_len, hd), vc.dtype),       # v slab
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    head_dim = hd // heads
    kernel = functools.partial(_decode_kernel, batch=b, heads=heads,
                               pos_dim=head_dim, s_len=s_len, dtype=dtype)
    x_out, k_rows, v_rows = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b8, e), dtype),
                   jax.ShapeDtypeStruct((num_layers, b, hd), k_t.dtype),
                   jax.ShapeDtypeStruct((num_layers, b, hd), vc.dtype)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(pos_arr, x8, weights.ln, weights.wqkv, weights.wproj, weights.wup,
      weights.wdown, kc, vc)
    # the new rows land via ONE dynamic_update_slice per cache — in place
    # under the decode scan's buffer donation, like any XLA KV cache.
    # K is lane-major: its rows form a [.., HD, B, 1] column at lane ``pos``
    pos = pos_arr[0]
    k_t = jax.lax.dynamic_update_slice(
        k_t, jnp.transpose(k_rows, (0, 2, 1))[..., None], (0, 0, 0, pos))
    v_all = jax.lax.dynamic_update_slice(
        v_all, v_rows.reshape(num_layers, b, 1, *v_all.shape[3:]),
        (0, 0, pos, 0, 0))
    return (x_out, k_t, v_all)


def transpose_k_cache(k_all: jnp.ndarray) -> jnp.ndarray:
    """[L, B, S, H, D] (prefill layout) -> [L, H*D, B, S] (fused-step
    layout: keys lane-major, batch interleaved ahead of the sequence so
    the kernel reads one [HD, B*S] slab); one XLA transpose after
    prefill."""
    num_layers, b, s_len = k_all.shape[:3]
    return jnp.transpose(k_all.reshape(num_layers, b, s_len, -1), (0, 3, 1, 2))


def fused_decode_step(weights: DecodeWeights, x, k_t, v_all, pos, *,
                      heads: int, interpret: bool = False):
    """One fused decode step over all layers.

    ``x`` [B, E] is the embedded token at position ``pos``; ``k_t`` is
    the TRANSPOSED [L, HD, B, S] key cache (``transpose_k_cache``),
    ``v_all`` the [L, B, S, H, D] value cache.  Returns (hidden [B, E]
    before final norm, k_t, v_all) with the new rows landed.
    """
    b, e = x.shape
    b8 = max(8, -(-b // 8) * 8)
    x8 = jnp.zeros((b8, e), x.dtype).at[:b].set(x) if b8 != b else x
    pos_arr = jnp.full((1,), pos, jnp.int32)
    x_out, k_t, v_all = _fused_call(weights, x8, k_t, v_all, pos_arr,
                                    heads=heads, interpret=interpret)
    return x_out[:b], k_t, v_all
