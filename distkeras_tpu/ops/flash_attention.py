"""Pallas TPU flash attention: the framework's hot-op kernel.

The reference delegates all compute to the Keras backend and ships no
kernels of its own (SURVEY.md §2 "Native components: none"); this module is
the TPU-native replacement for that compute path's attention hot op, written
directly against the MXU/VMEM model:

- O(block) VMEM: the [Lq, Lk] probability matrix is never materialized and
  no full-sequence tensor is ever resident — the kv (resp. q) position is
  an innermost grid dimension, so Pallas streams [block, D] tiles through
  VMEM while float32 scratch accumulators carry the online-softmax state
  (running max / denominator / output) across grid steps.  Sequence length
  is bounded by HBM, not VMEM.
- MXU-shaped: matmuls run in the input dtype (bf16 x bf16 at full MXU rate)
  with ``preferred_element_type=float32`` accumulation; only the softmax
  statistics live in float32.
- Causal skipping: key blocks entirely in the masked future contribute no
  FLOPs — the per-block compute is predicated on the block's global
  position, which also makes sharded callers (ring attention holds only a
  sequence shard) pay only for the keys they can see.

Backward pass is the standard flash recomputation: store per-row logsumexp
in the forward; recompute block probabilities in the backward and
accumulate dQ (grid streams kv blocks) and dK/dV (grid streams q blocks)
in float32 scratch.

Interpret mode (``interpret=True``, auto-selected on the CPU backend) runs
the same kernels through the Pallas interpreter so CPU tests exercise
identical code.

Layout note: kernels grid over (batch, head, outer block, inner block) on a
[B, H, L, D] layout — Mosaic requires the last two block dims to be
(8, 128)-tiled or equal to the array dims, so the head axis must sit
outside them (same scheme as jax.experimental.pallas.ops.tpu
.flash_attention).  The public entry transposes from the framework's
[B, L, H, D]; per-row softmax stats (logsumexp, delta) are stored with a
trailing 8-lane dim for the same tiling reason.

Two head sizes: queries and keys are [.., D], values [.., Dv] and so are
the output, its cotangent and dv (latent attention: 192 and 128); q, k,
dq and dk tiles, the dk scratch and the fused backward's [Lq, D] dq
scratch are D wide, v, o, do and dv tiles, the forward's accumulator and
the dv scratch Dv wide, and the scale is D^-1/2.  A block's last dim
equals the array's, so D need not be a multiple of the 128 lanes (Mosaic
pads a [block, 192] tile to 256 in VMEM).  With D == Dv the kernels trace
to the program they always were.  The backward's tiers
(``_fused_bwd_ok``) reckon the dq scratch at D.

Fully-masked query rows (possible only when ``q_offset < k_offset``) output
exactly 0 with 0 gradient, matching ``ring_attention``'s convention.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.platform import on_tpu

_NEG_INF = float("-inf")
_STAT_LANES = 8  # trailing lanes for per-row stats (min f32 tile lane count
                 # that can equal the array dim; avoids 128x padding waste)

# Mosaic's default scoped-vmem budget is 16M, which the dkv kernel's working
# set at (1024, 1024) blocks overflows by 8K inside full transformer backward
# programs.  24M is enough for the large-block dkv pass; a generous grant
# is not free — Mosaic folds the budget into its pipelining decisions —
# so grant the minimum that fits.
_VMEM_LIMIT = 24 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


class _Config(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff argument).

    Three block pairs: forward, dq, and dkv.  The backward normally runs as
    ONE fused kernel (``_bwd_fused_kernel``) using the dkv pair; the dq
    pair only matters on the two-kernel fallback taken when the fused
    kernel's [Lq, D] dq scratch would overflow scoped vmem
    (``_fused_bwd_ok``)."""

    causal: bool
    q_offset: int
    k_offset: int
    block_q: int
    block_k: int
    block_q_dq: int
    block_k_dq: int
    block_q_bwd: int
    block_k_bwd: int
    interpret: bool
    window: int = 0  # > 0: sliding window, key j visible iff 0 <= i - j < window


def _block_visible(cfg: _Config, qi, kj, bq, bk):
    """True unless key block ``kj`` is entirely in query block ``qi``'s
    masked future (then its FLOPs are predicated away).  Block sizes are
    explicit because forward and backward kernels may use different ones."""
    if not cfg.causal:
        return True
    last_q_pos = cfg.q_offset + (qi + 1) * bq - 1
    first_k_pos = cfg.k_offset + kj * bk
    if not cfg.window:
        return last_q_pos >= first_k_pos
    # sliding window: also not entirely behind the window's far edge
    first_q_pos = cfg.q_offset + qi * bq
    last_k_pos = cfg.k_offset + (kj + 1) * bk - 1
    return (last_q_pos >= first_k_pos) & (first_q_pos - last_k_pos < cfg.window)


def _apply_causal_mask(s, cfg: _Config, qi, kj, bq, bk):
    """Mask ``s`` [bq, bk] where q_pos < k_pos — but only blocks that
    straddle the diagonal pay for the iota+where; blocks fully below it
    (first q row sees the last k column) pass through untouched."""
    def masked(s):
        q_pos = cfg.q_offset + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = cfg.k_offset + kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        if cfg.window:
            return jnp.where((q_pos >= k_pos) & (q_pos - k_pos < cfg.window), s, _NEG_INF)
        return jnp.where(q_pos >= k_pos, s, _NEG_INF)

    first_q_pos = cfg.q_offset + qi * bq
    last_k_pos = cfg.k_offset + (kj + 1) * bk - 1
    inside = first_q_pos >= last_k_pos
    if cfg.window:
        # ... and the block's farthest pair (last query, first key) is
        # still inside the window: only the diagonal and the window's edge
        # pay for the mask
        inside &= (first_q_pos + bq - 1) - (cfg.k_offset + kj * bk) < cfg.window
    return jax.lax.cond(inside, lambda s: s, masked, s)


def _k_span(cfg: _Config, qi, bq, bk, nkb):
    """(first, last) key block a query block can see under the window;
    ``qi`` a Python int or a traced scalar.  ``last < first`` where it sees
    none.  A windowed grid visits only these (``_forward``)."""
    first = (cfg.q_offset + qi * bq - (cfg.window - 1) - cfg.k_offset) // bk
    last = (cfg.q_offset + (qi + 1) * bq - 1 - cfg.k_offset) // bk
    if isinstance(qi, int):
        return max(first, 0), min(last, nkb - 1)
    return jnp.maximum(first, 0), jnp.minimum(last, nkb - 1)


def _q_span(cfg: _Config, kj, bq, bk, nqb):
    """(first, last) query block that can see key block ``kj`` under the
    window (``_fused_backward_call``'s inner grid)."""
    first = (cfg.k_offset + kj * bk - cfg.q_offset) // bq
    last = (cfg.k_offset + (kj + 1) * bk - 1 + cfg.window - 1 - cfg.q_offset) // bq
    if isinstance(kj, int):
        return max(first, 0), min(last, nqb - 1)
    return jnp.maximum(first, 0), jnp.minimum(last, nqb - 1)


def _span_steps(span, cfg: _Config, n_outer: int, bq, bk, n_inner: int) -> int:
    """The inner grid's length: the widest span over the outer blocks."""
    return max(1, max(hi - lo + 1 for lo, hi in
                      (span(cfg, o, bq, bk, n_inner) for o in range(n_outer))))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                cfg: _Config, scale: float, k_blocks: int):
    qi, step = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = cfg.block_q, cfg.block_k
    kj, visible = step, None
    if cfg.window:
        # the inner grid walks the window's key blocks only: step 0 is the
        # first block this query block sees (``_forward``'s index map)
        first, last = _k_span(cfg, qi, bq, bk, k_blocks)
        kj = first + step
        visible = kj <= last

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_block_visible(cfg, qi, kj, bq, bk) if visible is None
             else visible & _block_visible(cfg, qi, kj, bq, bk))
    def _compute():
        q = q_ref[0, 0]  # [bq, d] — native dtype: bf16 x bf16 at full MXU rate
        k_blk = k_ref[0, 0]
        v_blk = v_ref[0, 0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if cfg.causal:
            s = _apply_causal_mask(s, cfg, qi, kj, bq, bk)
        m = m_scr[:, 0]
        blk_max = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), _NEG_INF, m - safe_m))
        p = jnp.exp(s - safe_m[:, None])
        pv = jax.lax.dot_general(p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(new_m[:, None], m_scr.shape)
        l_scr[...] = l_scr[...] * corr[:, None] + jnp.broadcast_to(
            jnp.sum(p, axis=-1)[:, None], l_scr.shape)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(step == nk - 1)
    def _flush():
        m = m_scr[:, 0]
        l_sum = l_scr[:, 0]
        # fully-masked rows (l == 0): output exactly 0, lse 0 (a finite
        # sentinel; the backward recomputes p = exp(-inf - 0) = 0 so grads
        # are exactly 0)
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_sum, 1e-30)[:, None]).astype(o_ref.dtype)
        lse = jnp.where(l_sum > 0.0,
                        jnp.where(jnp.isneginf(m), 0.0, m) + jnp.log(jnp.maximum(l_sum, 1e-30)),
                        0.0)
        lse_ref[0, 0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[2:])


def _recompute_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    cfg: _Config, scale: float, qi, kj, bq, bk):
    """Shared backward recompute: (p, ds, refs' blocks) for one
    [bq, bk] tile.  p = softmax probabilities rebuilt from the stored
    logsumexp (masked entries exactly 0), ds = p * (dp - delta) in float32.
    Used by all three backward kernels so the score/probability algebra
    lives in one place."""
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    lse = lse_ref[0, 0, :, 0:1]      # [bq, 1]
    delta = delta_ref[0, 0, :, 0:1]  # [bq, 1]
    k_blk = k_ref[0, 0]
    v_blk = v_ref[0, 0]
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cfg.causal:
        s = _apply_causal_mask(s, cfg, qi, kj, bq, bk)
    p = jnp.exp(s - lse)  # masked/-inf entries -> exactly 0
    dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return p, ds, q, do, k_blk, v_blk


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, cfg: _Config, scale: float):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, bk = cfg.block_q_dq, cfg.block_k_dq

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_block_visible(cfg, qi, kj, bq, bk))
    def _compute():
        _, ds, _, _, k_blk, _ = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cfg, scale, qi, kj, bq, bk)
        dq_scr[...] += jax.lax.dot_general(ds.astype(k_blk.dtype), k_blk,
                                           (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _flush():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, cfg: _Config, scale: float):
    kj, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = cfg.block_q_bwd, cfg.block_k_bwd

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_block_visible(cfg, qi, kj, bq, bk))
    def _compute():
        p, ds, q, do, _, _ = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cfg, scale, qi, kj, bq, bk)
        dv_scr[...] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr, *,
                      cfg: _Config, scale: float, q_blocks: int):
    """One-pass backward: dK, dV and dQ from a single s/p recomputation.

    The separate dq kernel re-derives the identical [bq, bk] score and
    probability blocks the dkv kernel just computed — at small head dims
    that recompute IS the kernel cost, so fusing the two backward passes
    cuts backward time by about the dq kernel's.

    The catch is accumulation order: dK/dV accumulate over the inner qi
    steps (scratch flushed per kv block, as before) while dQ accumulates
    over the OUTER kj steps.  A [Lq, D] float32 scratch holds every dq row
    for the (b, h) pair; row block qi is updated in place via a dynamic
    slice and the dq output block is flushed on the final kj pass.  The
    scratch makes VMEM O(Lq * D) rather than O(block) — ``_backward``
    falls back to the two-kernel path when that does not fit.
    """
    kj, step = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    bq, bk = cfg.block_q_bwd, cfg.block_k_bwd
    qi, visible = step, None
    if cfg.window:
        # the inner grid walks only the query blocks that see this key
        # block (``_fused_backward_call``'s index map); past the last one
        # the step stays on it with its compute predicated off
        first, last = _q_span(cfg, kj, bq, bk, q_blocks)
        visible = first + step <= last
        qi = jnp.maximum(jnp.minimum(first + step, last), 0)

    @pl.when(step == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when((kj == 0) & (step == 0))
    def _init_q():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_block_visible(cfg, qi, kj, bq, bk) if visible is None
             else visible & _block_visible(cfg, qi, kj, bq, bk))
    def _compute():
        p, ds, q, do, k_blk, _ = _recompute_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, cfg, scale, qi, kj, bq, bk)
        ds = ds.astype(q.dtype)
        dv_scr[...] += jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dq_scr[pl.ds(qi * bq, bq), :] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(step == nq - 1)
    def _flush_kv():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)

    # dq row block qi accumulates across the OUTER kj steps, so its output
    # window is revisited once per kj.  Emit the current accumulated prefix
    # on EVERY visit: each window Pallas flushes then holds kernel-written
    # data and the final, ordered revisit carries the complete sum —
    # correctness rests on last-write-wins, not on revisited output
    # windows preserving stale buffer contents (unstated semantics under
    # double-buffering).  The extra [bq, d] VMEM store per step is noise
    # next to the three matmuls above.
    dq_ref[0, 0] = (dq_scr[pl.ds(qi * bq, bq), :] * scale).astype(dq_ref.dtype)


def _out_struct(shape, dtype, *like):
    """ShapeDtypeStruct whose ``vma`` (varying-mesh-axes set) is the union
    of the inputs' — required for pallas_call outputs under ``shard_map``
    with vma checking (e.g. the dp-sharded LM step); plain jit traces have
    no vma and take the unannotated branch."""
    vma = frozenset().union(*(getattr(jax.typeof(x), "vma", frozenset()) or frozenset()
                              for x in like))
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _forward(q, k, v, cfg: _Config):
    """q [B, H, Lq, D], k [B, H, Lk, D], v [B, H, Lk, Dv] -> (o [B, H, Lq,
    Dv], lse [B, H, Lq, 8]); the scale is the query/key size's."""
    b, h, lq, d = q.shape
    lk, d_v = k.shape[2], v.shape[3]
    bq, bk = cfg.block_q, cfg.block_k
    scale = 1.0 / (d ** 0.5)
    nkb = lk // bk
    kernel = functools.partial(_fwd_kernel, cfg=cfg, scale=scale, k_blocks=nkb)
    steps, kv_index = nkb, lambda b, h, i, j: (b, h, j, 0)
    if cfg.window:
        # key blocks wholly outside the window are not visited: the inner
        # grid is as long as the widest span, and a step past a query
        # block's last visible key block stays on that block (no new DMA;
        # its compute is predicated off in the kernel)
        steps = _span_steps(_k_span, cfg, lq // bq, bq, bk, nkb)

        def kv_index(b, h, i, j):
            first, last = _k_span(cfg, i, bq, bk, nkb)
            return (b, h, jnp.clip(first + j, 0, jnp.maximum(last, 0)), 0)

    return pl.pallas_call(
        kernel,
        grid=(b, h, lq // bq, steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), kv_index),
            pl.BlockSpec((1, 1, bk, d_v), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_v), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, _STAT_LANES), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            _out_struct((b, h, lq, d_v), q.dtype, q, k, v),
            _out_struct((b, h, lq, _STAT_LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),  # running max
            pltpu.VMEM((bq, _STAT_LANES), jnp.float32),  # running denominator
            pltpu.VMEM((bq, d_v), jnp.float32),           # output accumulator
        ],
        interpret=cfg.interpret,
        compiler_params=_COMPILER_PARAMS,
        # names the HLO custom call, and so its event on a profiler
        # trace's XLA Ops line (otherwise the enclosing scope's name)
        name="_fwd_kernel",
    )(q, k, v)


# Fused-backward eligibility (what the v5e compiler's scoped vmem admits).
# The fused kernel's [Lq, D] float32 dq scratch plus its block working set
# must fit the scoped-vmem budget; boundaries at D=64:
#   (1024, 1024) blocks fit when BOTH the dq scratch and the streamed kv
#     length stay small (through Lq=Lk=16k), and are preferred to
#     (512, 1024) wherever they fit; OOM when Lk reaches 32k;
#   (512, 1024) blocks fit through Lq=16k (dq scratch 4.2M) at ANY Lk
#     (the 32k leg runs them via q-chunking), OOM at unchunked Lq=32k;
#     queries and keys of 192 against values of 128 (latent attention) at
#     Lq=8k (dq scratch 6.3M, over the cap) take them in two q-chunks: one
#     call fits and is no faster inside a window program (39.8 against
#     40.0 ms a layer; 42.3 against 47.3 ms alone: chip, PR 34);
#   (512, 512) blocks fit through Lq=32k (dq scratch 8.4M);
#   above that, fall back to the two-kernel backward with wide blocks.
# SINGLE-BLOCK tier: when the k block spans the WHOLE sequence (reachable
# from auto-select when Lq, Lk <= 2048; cross-length shapes like
# Lq 2048 / Lk 1024 take the same single-k-block structure) the fused
# backward runs in one grid step, skipping no causal blocks — the same
# fewer-passes-for-more-FLOPs trade the forward makes at these lengths.
# Its [bq, bk] f32 score/dp + bf16 p tiles (~10 B/element) outgrow the
# standard 24M grant, so ``_bwd_compiler_params`` sizes the grant per
# call.  Past 2048 wide blocks would have q-chunks re-stream k/v and
# forgo the causal skip, hence the lk == bk_kv containment rather than
# a general wide tier.  (The cells' 2,048-position rows run this tier:
# PERF.md's ``flash_bwd_roofline`` is its measurement.)
_FUSED_WIDE_CAP = 5 * 1024 * 1024       # dq / lk-stream cap for 1024-wide blocks
_FUSED_DQ_SCRATCH_CAP = 12 * 1024 * 1024  # dq scratch cap for (<=512, <=512)
_BWD_WS_BYTES_PER_ELEM = 10             # f32 s + f32 dp + bf16 p per score
_BWD_WIDE_WS_CAP = 44 * 1024 * 1024     # blocks through (2048, 2048)


def _fused_bwd_ok(lq: int, d: int, bq_kv: int, bk_kv: int, lk: int) -> bool:
    """``d``: the query/key head size, the width of the dq scratch."""
    dq_bytes = lq * d * 4
    if bk_kv == lk and 1024 < max(bq_kv, bk_kv) <= 2048:
        # single-k-block wide tier: one (or few) grid passes, sized grant
        return (dq_bytes <= _FUSED_DQ_SCRATCH_CAP
                and bq_kv * bk_kv * _BWD_WS_BYTES_PER_ELEM <= _BWD_WIDE_WS_CAP)
    if bk_kv > 1024:
        return False
    if bq_kv > 1024:
        return False
    if bq_kv > 512:
        return dq_bytes <= _FUSED_WIDE_CAP and lk * d * 4 <= _FUSED_WIDE_CAP
    if bk_kv <= 512:
        return dq_bytes <= _FUSED_DQ_SCRATCH_CAP
    return dq_bytes <= _FUSED_WIDE_CAP


def _bwd_compiler_params(bq_kv: int, bk_kv: int) -> pltpu.CompilerParams:
    """Scoped-vmem grant for a backward call, sized to its score-tile
    working set: the standard minimum-that-fits 24M grant through
    (1024, 1024) blocks; the wide single-block tier gets 48M, above
    what its tiles need.  >= so the boundary pair (2048, 1024)
    — reachable cross-length, e.g. Lq 2048 vs Lk 1024 — gets the sized
    grant its exactly-20M score tiles need rather than the 24M grant
    that only fits the 10M working set of (1024, 1024)."""
    if bq_kv * bk_kv * _BWD_WS_BYTES_PER_ELEM >= 20 * 1024 * 1024:
        return pltpu.CompilerParams(vmem_limit_bytes=48 * 1024 * 1024)
    return _COMPILER_PARAMS


def _fused_backward_call(q, k, v, do, lse, delta, cfg: _Config, scale: float):
    b, h, lq, d = q.shape
    lk, d_v = k.shape[2], v.shape[3]
    bq_kv, bk_kv = cfg.block_q_bwd, cfg.block_k_bwd
    nqb = lq // bq_kv
    steps, q_index = nqb, lambda b, h, j, i: (b, h, i, 0)
    if cfg.window:
        # query blocks that cannot see a key block are not visited (the
        # forward's scheme, transposed)
        steps = _span_steps(_q_span, cfg, lk // bk_kv, bq_kv, bk_kv, nqb)

        def q_index(b, h, j, i):
            first, last = _q_span(cfg, j, bq_kv, bk_kv, nqb)
            return (b, h, jnp.clip(first + i, 0, jnp.maximum(last, 0)), 0)

    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, cfg=cfg, scale=scale, q_blocks=nqb),
        grid=(b, h, lk // bk_kv, steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq_kv, d), q_index),                           # q
            pl.BlockSpec((1, 1, bk_kv, d), lambda b, h, j, i: (b, h, j, 0)),   # k
            pl.BlockSpec((1, 1, bk_kv, d_v), lambda b, h, j, i: (b, h, j, 0)),  # v
            pl.BlockSpec((1, 1, bq_kv, d_v), q_index),                          # do
            pl.BlockSpec((1, 1, bq_kv, _STAT_LANES), q_index),                 # lse
            pl.BlockSpec((1, 1, bq_kv, _STAT_LANES), q_index),                 # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk_kv, d), lambda b, h, j, i: (b, h, j, 0)),   # dk
            pl.BlockSpec((1, 1, bk_kv, d_v), lambda b, h, j, i: (b, h, j, 0)),  # dv
            pl.BlockSpec((1, 1, bq_kv, d), q_index),                           # dq
        ],
        out_shape=[
            _out_struct((b, h, lk, d), k.dtype, q, k, v, do),
            _out_struct((b, h, lk, d_v), v.dtype, q, k, v, do),
            _out_struct((b, h, lq, d), q.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_kv, d), jnp.float32),
            pltpu.VMEM((bk_kv, d_v), jnp.float32),
            pltpu.VMEM((lq, d), jnp.float32),
        ],
        interpret=cfg.interpret,
        compiler_params=_bwd_compiler_params(bq_kv, bk_kv),
        name="_bwd_fused_kernel",
    )(q, k, v, do, lse, delta)


_FUSED_MAX_CHUNKS = 16


def _fused_q_chunks(lq: int, d: int, bq_kv: int, bk_kv: int, lk: int):
    """Number of equal q-range chunks that makes the fused backward's
    [chunk, D] dq scratch fit scoped vmem (1 = single call, None = cannot
    chunk: fall back to the two-kernel backward).  Chunks re-stream k/v, so
    cap the count — beyond ~16 the repeated kv DMA erodes the win."""
    for n in range(1, _FUSED_MAX_CHUNKS + 1):
        if lq % n:
            continue
        chunk = lq // n
        if chunk % bq_kv == 0 and _fused_bwd_ok(chunk, d, bq_kv, bk_kv, lk):
            return n
    return None


def _backward(q, k, v, o, lse, do, cfg: _Config, dlse=None):
    b, h, lq, d = q.shape
    lk, d_v = k.shape[2], v.shape[3]
    bq, bk = cfg.block_q_dq, cfg.block_k_dq
    bq_kv, bk_kv = cfg.block_q_bwd, cfg.block_k_bwd
    scale = 1.0 / (d ** 0.5)
    # delta[b, h, i] = sum_d dO * O — the softmax-jacobian row term; tiny
    # elementwise reduce, XLA fuses it, no kernel needed.  When the caller
    # also differentiates the lse OUTPUT (flash_attention_with_lse), its
    # cotangent folds into the same kernels: dL/ds = p * (dp - delta + dlse)
    # — i.e. the kernels just see delta' = delta - dlse
    delta = jnp.einsum("bhld,bhld->bhl", do.astype(jnp.float32), o.astype(jnp.float32))
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None], (b, h, lq, _STAT_LANES))

    n_chunks = _fused_q_chunks(lq, d, bq_kv, bk_kv, lk)
    if n_chunks == 1:
        dk, dv, dq = _fused_backward_call(q, k, v, do, lse, delta, cfg, scale)
        return dq, dk, dv
    if n_chunks is not None:
        # chunk the q range so each fused call's dq scratch fits scoped
        # vmem: dq concatenates over chunks, dk/dv sum partial results
        # (kv blocks invisible to a chunk flush zeros, so the sum is exact;
        # each chunk's q_offset keeps the causal predication global)
        chunk = lq // n_chunks
        dk = dv = None
        dqs = []
        for c in range(n_chunks):
            sl = lambda x: jax.lax.slice_in_dim(x, c * chunk, (c + 1) * chunk, axis=2)
            cfg_c = cfg._replace(q_offset=cfg.q_offset + c * chunk)
            dk_c, dv_c, dq_c = _fused_backward_call(
                sl(q), k, v, sl(do), sl(lse), sl(delta), cfg_c, scale)
            # accumulate partials in f32: summing bf16 chunk outputs would
            # round at every add, a precision cliff vs the unchunked path
            dk = dk_c.astype(jnp.float32) if dk is None else dk + dk_c
            dv = dv_c.astype(jnp.float32) if dv is None else dv + dv_c
            dqs.append(dq_c)
        return (jnp.concatenate(dqs, axis=2), dk.astype(k.dtype), dv.astype(v.dtype))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, cfg=cfg, scale=scale),
        grid=(b, h, lq // bq, lk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),   # q
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0)),   # k
            pl.BlockSpec((1, 1, bk, d_v), lambda b, h, i, j: (b, h, j, 0)),  # v
            pl.BlockSpec((1, 1, bq, d_v), lambda b, h, i, j: (b, h, i, 0)),  # do
            pl.BlockSpec((1, 1, bq, _STAT_LANES), lambda b, h, i, j: (b, h, i, 0)),  # lse
            pl.BlockSpec((1, 1, bq, _STAT_LANES), lambda b, h, i, j: (b, h, i, 0)),  # delta
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=_out_struct((b, h, lq, d), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=cfg.interpret,
        # size the grant to THIS kernel's score tile too (ADVICE round 5):
        # when the fused path is rejected with full-length forward-inherited
        # blocks (large head_dim, Lq=Lk<=2048), the dq working set can
        # outgrow the fixed 24M grant and fail Mosaic compilation
        compiler_params=_bwd_compiler_params(bq, bk),
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, cfg=cfg, scale=scale),
        grid=(b, h, lk // bk_kv, lq // bq_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq_kv, d), lambda b, h, j, i: (b, h, i, 0)),   # q
            pl.BlockSpec((1, 1, bk_kv, d), lambda b, h, j, i: (b, h, j, 0)),   # k
            pl.BlockSpec((1, 1, bk_kv, d_v), lambda b, h, j, i: (b, h, j, 0)),  # v
            pl.BlockSpec((1, 1, bq_kv, d_v), lambda b, h, j, i: (b, h, i, 0)),  # do
            pl.BlockSpec((1, 1, bq_kv, _STAT_LANES), lambda b, h, j, i: (b, h, i, 0)),  # lse
            pl.BlockSpec((1, 1, bq_kv, _STAT_LANES), lambda b, h, j, i: (b, h, i, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk_kv, d), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk_kv, d_v), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            _out_struct((b, h, lk, d), k.dtype, q, k, v, do),
            _out_struct((b, h, lk, d_v), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk_kv, d), jnp.float32),
            pltpu.VMEM((bk_kv, d_v), jnp.float32),
        ],
        interpret=cfg.interpret,
        compiler_params=_bwd_compiler_params(bq_kv, bk_kv),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# The forward kernel's two results, as ``jax.checkpoint`` policies know
# them: a remat with ``save_only_these_names(FLASH_OUT_NAME, FLASH_LSE_NAME)``
# keeps them from the forward pass, so its backward pass does not run the
# forward kernel again (``TransformerLM.remat``).  Outside a checkpoint a
# name is an identity that lowers to nothing.
FLASH_OUT_NAME = "flash_attention.out"  # o:   [B, H, Lq, D], the inputs' dtype
FLASH_LSE_NAME = "flash_attention.lse"  # lse: [B, H, Lq, _STAT_LANES] float32


def _named_forward(q, k, v, cfg: _Config):
    o, lse = _forward(q, k, v, cfg)
    return checkpoint_name(o, FLASH_OUT_NAME), checkpoint_name(lse, FLASH_LSE_NAME)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: _Config):
    o, _ = _forward(q, k, v, cfg)
    return o


def _flash_fwd(q, k, v, cfg: _Config):
    o, lse = _named_forward(q, k, v, cfg)
    return o, (q, k, v, o, lse)


def _flash_bwd(cfg: _Config, res, do):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, do, cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_lse(q, k, v, cfg: _Config):
    o, lse = _forward(q, k, v, cfg)
    return o, lse[..., 0]


def _flash_lse_fwd(q, k, v, cfg: _Config):
    o, lse = _named_forward(q, k, v, cfg)
    return (o, lse[..., 0]), (q, k, v, o, lse)


def _flash_lse_bwd(cfg: _Config, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    return _backward(q, k, v, o, lse, do, cfg, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _pick_block(block: int, length: int) -> int:
    block = min(block, length)
    while length % block:
        block //= 2
    return max(block, 1)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, q_offset: int = 0, k_offset: int = 0,
                    block_q: Optional[int] = None, block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Flash attention over q, k [B, L, H, D] and v [B, L, H, Dv] -> [B, L, H,
    Dv] (same layout/semantics as ``ops.attention.dense_attention``,
    including the shard offsets; Dv may differ from D, the scale is D^-1/2).

    ``window`` (causal only): query ``i`` sees key ``j`` iff ``0 <= i - j <
    window``.  The forward and the fused backward do not visit blocks
    wholly outside the window (their inner grid is the widest span of
    visible blocks, not the whole sequence); the diagonal and the window's
    edge are masked.

    Kernel structure and block defaults: the forward uses one
    full-length block when the [Lq, Lk] score tile fits scoped vmem and
    (1024, 1024) above that; the backward normally runs as ONE fused
    kernel producing dq, dk and dv from a single score/probability
    recompute (the classic two-kernel backward recomputes them twice),
    preferring (512, 1024) blocks and chunking the q range when its
    [Lq, D] f32 dq scratch outgrows scoped vmem (``_fused_q_chunks``:
    queries and keys of 192 at 8k run in two chunks);
    the two-kernel path remains as the fallback for shapes that cannot
    chunk.  Small blocks pay per-block overhead many times over: keep
    them wide.  What the kernels reach of their rooflines in the
    benchmark's cells is in PERF.md.

    Explicit knobs: ``block_q``/``block_k`` govern the forward kernel;
    absent bwd overrides the backward AUTO-SELECTS fused-compatible blocks
    (capped at 1024/512 per ``_fused_bwd_ok``) and only inherits the
    forward pair verbatim on the non-fused fallback tier — so a >1024
    forward sweep does NOT reach the backward.  Explicit
    ``block_q_bwd``/``block_k_bwd`` pin the backward kernels exactly
    (including forcing it out of the fused path if too large to fit).
    ``_pick_block`` shrinks every block to fit short sequences
    automatically.

    ``interpret=None`` selects Mosaic on the TPU backend and the Pallas
    interpreter on the CPU backend, so the identical kernel code runs
    (slowly) in CPU tests; any other backend name is an error
    (``platform.on_tpu``).
    """
    cfg = _make_config(q, k, causal, q_offset, k_offset, block_q, block_k,
                       block_q_bwd, block_k_bwd, interpret, window)
    # [B, L, H, D] -> [B, H, L, D] for the kernels; the transposes sit outside
    # the custom_vjp so their adjoints are handled by XLA
    o = _flash(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), cfg)
    return jnp.swapaxes(o, 1, 2)


def flash_attention_with_lse(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                             causal: bool = True, q_offset: int = 0,
                             k_offset: int = 0,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             block_q_bwd: Optional[int] = None,
                             block_k_bwd: Optional[int] = None,
                             interpret: Optional[bool] = None):
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp of the scaled scores: ``(o [B, L, H, D], lse [B, H, L]
    float32)``.

    The pair is exactly what blockwise/ring composition needs — partial
    attentions over kv blocks merge as ``out = sum_s o_s * exp(lse_s - M)
    / sum_s exp(lse_s - M)`` — and BOTH outputs are differentiable: the
    lse cotangent folds into the same backward kernels as a delta shift
    (see ``_backward``), so ``ops.attention.ring_attention`` gets exact
    gradients through the merge.  Fully-masked rows report lse 0 (finite
    sentinel) and o exactly 0, matching ``flash_attention``.
    """
    cfg = _make_config(q, k, causal, q_offset, k_offset, block_q, block_k,
                       block_q_bwd, block_k_bwd, interpret)
    o, lse = _flash_lse(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), cfg)
    return jnp.swapaxes(o, 1, 2), lse


def _make_config(q, k, causal, q_offset, k_offset, block_q, block_k,
                 block_q_bwd, block_k_bwd, interpret, window=None) -> _Config:
    if interpret is None:
        interpret = not on_tpu()
    lq, lk = q.shape[1], k.shape[1]
    if q.shape[-1] != k.shape[-1]:
        raise ValueError(f"queries of head size {q.shape[-1]} against keys of {k.shape[-1]}")
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")
    if window is not None and window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    # a window that reaches past every key this call holds is plain causal
    window = 0 if (window is None or window >= q_offset + lq - k_offset) else int(window)
    d = q.shape[-1]
    # forward defaults: one full-length block when the whole [Lq, Lk] score
    # tile fits scoped vmem (no online correction passes, no grid
    # overhead), (1024, 1024) above that ([2048, 2048] f32 scores OOM at
    # 8k+)
    if block_q is None:
        block_q = lq if (lq <= 2048 and lk <= 2048) else 1024
    if block_k is None:
        block_k = lk if (lq <= 2048 and lk <= 2048) else 1024
    if block_q_bwd is None and block_k_bwd is None:
        # backward defaults aim for the FUSED single-pass backward kernel
        # (one s/p recompute instead of two): first the single-block wide
        # tier (only
        # reachable when the forward already runs full-length blocks, i.e.
        # Lq = Lk <= 2048 — see the _fused_bwd_ok tier note), then
        # (1024, 1024), degrading to (512, 1024), (512, 512) and finally
        # the two-kernel path with forward-inherited blocks as Lq * D
        # grows (see _fused_bwd_ok)
        if _fused_q_chunks(lq, d, min(block_q, 2048), min(block_k, 2048), lk):
            dq_q = dkv_q = min(block_q, 2048)
            dq_k = dkv_k = min(block_k, 2048)
        elif _fused_q_chunks(lq, d, min(block_q, 1024), min(block_k, 1024), lk):
            dq_q = dkv_q = min(block_q, 1024)
            dq_k = dkv_k = min(block_k, 1024)
        elif _fused_q_chunks(lq, d, min(block_q, 512), min(block_k, 1024), lk):
            dq_q = dkv_q = min(block_q, 512)
            dq_k = dkv_k = min(block_k, 1024)
        elif _fused_q_chunks(lq, d, min(block_q, 512), min(block_k, 512), lk):
            dq_q = dkv_q = min(block_q, 512)
            dq_k = dkv_k = min(block_k, 512)
        else:
            dq_q = dkv_q = block_q
            dq_k = dkv_k = block_k
    else:
        dq_q = dkv_q = block_q_bwd if block_q_bwd is not None else block_q
        dq_k = dkv_k = block_k_bwd if block_k_bwd is not None else block_k
    bq, bk = _pick_block(block_q, lq), _pick_block(block_k, lk)
    bq_dq, bk_dq = _pick_block(dq_q, lq), _pick_block(dq_k, lk)
    bq_kv, bk_kv = _pick_block(dkv_q, lq), _pick_block(dkv_k, lk)
    for name, blk, length in (("block_q", bq, lq), ("block_k", bk, lk),
                              ("block_q_dq", bq_dq, lq), ("block_k_dq", bk_dq, lk),
                              ("block_q_bwd", bq_kv, lq), ("block_k_bwd", bk_kv, lk)):
        # Mosaic tiling: the sublane block dim must be 8-divisible or span
        # the whole array dim (interpret mode is lenient, but keep semantics
        # identical so CPU tests catch what TPU would reject)
        if blk % 8 != 0 and blk != length:
            raise ValueError(
                f"no Mosaic-legal {name} for sequence length {length}: "
                f"largest fitting divisor is {blk}, which is neither "
                f"8-divisible nor the full length; pad the sequence or use "
                f"impl='dense'")
    return _Config(causal=bool(causal), q_offset=int(q_offset), k_offset=int(k_offset),
                   block_q=bq, block_k=bk, block_q_dq=bq_dq, block_k_dq=bk_dq,
                   block_q_bwd=bq_kv, block_k_bwd=bk_kv,
                   interpret=bool(interpret), window=window)
