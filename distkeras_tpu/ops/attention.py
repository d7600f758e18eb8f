"""Attention ops: dense causal attention + ring attention (sequence parallel).

The reference framework predates transformers and has no long-context
machinery (SURVEY.md §5 "Long-context: absent entirely"); this module is
TPU-native headroom, built first-class per the framework's scaling goals.

Ring attention (Liu et al. 2023 pattern): shard the sequence over a mesh
axis; each device holds a query block and streams key/value blocks around
the ring with ``lax.ppermute``, accumulating softmax online (flash-style
running max / denominator), so attention over a sequence of length L costs
O(L/sp) memory per chip and the KV transfers ride the ICI ring.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.platform import on_tpu


def repeat_kv_heads(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray):
    """Broadcast grouped KV heads up to the query head count (GQA).

    q [B, Lq, H, D], k/v [B, Lk, Hkv, D] with H a multiple of Hkv: each
    group of H/Hkv query heads shares one KV head (Ainslie et al. 2023).
    Identity when the counts already match (MHA).  The repeat happens at
    the last possible moment — callers that MOVE k/v first (the ring's
    ppermute rotation, the decode cache's HBM reads) keep the Hkv-sized
    tensors on the wire/in memory, which is the point of GQA."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq == hkv:
        return k, v
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    g = hq // hkv
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def dense_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = True,
                    q_offset: int = 0, k_offset: int = 0,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Plain softmax attention. Shapes: q [B, Lq, H, D], k/v [B, Lk, H, D]
    (or [B, Lk, Hkv, D] with grouped KV heads — broadcast up internally);
    v may have a head size of its own, [B, Lk, H, Dv], which is the output's
    (the scale is the query/key size's).

    ``q_offset``/``k_offset`` are the global positions of the first query /
    key element — needed when the caller holds only a shard of the sequence.
    ``window`` (causal only): query ``i`` sees key ``j`` iff
    ``0 <= i - j < window``.
    """
    if window is not None and not causal:
        raise ValueError("a sliding window needs causal=True")
    k, v = repeat_kv_heads(q, k, v)
    depth = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(depth).astype(q.dtype)
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        logits = jnp.where(mask[None, None, :, :], logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    if causal:
        # rows with no visible key (q_offset < k_offset shards) output exactly
        # 0 — softmax of an all-masked row would otherwise emit uniform(V);
        # ring/flash attention both use the zero convention
        any_visible = mask.any(axis=-1)  # [Lq]
        out = jnp.where(any_visible[None, :, None, None], out, 0)
    return out


def ring_block_impl(l_local: int, head_dim: int) -> str:
    """The per-block compute ``ring_attention`` auto-selects for a shard of
    ``l_local`` positions on TPU; dense-XLA below the crossover, the flash
    kernel above it (which also needs Mosaic-legal 128-divisible blocks).

    The crossover tracks per-block WORK, not length alone: a small block
    cannot amortize the kernel's fixed VPU overhead, and a wider head
    amortizes it at a shorter shard, so the rule is the area
    ``l_local * head_dim >= 2048 * 64``.  It was set from a July 2026
    device-time sweep that no benchmark cell has repeated (no cell runs
    the ring: PERF.md); re-measure at your shape before moving it.
    Single source for the threshold."""
    return ("flash" if (on_tpu()
                        and l_local * head_dim >= 2048 * 64
                        and l_local % 128 == 0)
            else "dense")


def attention_impl(lq: int, lk: int) -> str:
    """The implementation ``attention`` auto-selects off the ring path for
    ``lq`` queries against ``lk`` keys: the flash kernel on TPU whenever
    the sequence is long enough for Mosaic-legal blocks, dense XLA
    otherwise (including the CPU backend, where the interpreted kernel is
    test-only).

    Deliberately LENGTH-only, unlike ``ring_block_impl``'s area rule:
    from 2048 positions on the kernel never materializes the [L, L]
    scores dense XLA writes and re-reads, at every batch and head size;
    below that the winner flips with batch as well as head size, so
    there is no clean sub-2048 predicate and dense XLA is the safe
    choice.  Every benchmark cell runs at 2048 positions or more and so
    takes the kernel; its measured share of its roofline is PERF.md's
    ``flash_fwd_roofline`` / ``flash_bwd_roofline``."""
    return ("flash" if (on_tpu() and lq >= 2048
                        and lq % 128 == 0 and lk % 128 == 0)
            else "dense")


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, axis_name: str,
                   causal: bool = True, impl: Optional[str] = None) -> jnp.ndarray:
    """Sequence-parallel attention under ``shard_map`` over ``axis_name``.

    Each caller holds the local sequence shard: q/k/v [B, L_local, H, D].
    KV blocks rotate around the ring; the block held at step ``s`` is the
    one that originated on rank ``(my_rank - s) mod sp``.

    TPU-grade schedule (round 3):

    - the per-step block compute is the Pallas flash kernel via
      ``flash_attention_with_lse`` (bf16 matmuls at MXU rate, f32
      softmax stats) instead of dense f32 XLA attention;
    - under causal masking only step 0 needs a mask at all: a LIVE step
      ``s > 0`` holds kv from rank ``my - s`` — strictly the past, every
      position visible — so it runs the cheaper non-causal kernel, and a
      DEAD step (``src > my``: kv entirely in this rank's future, about
      half of all (rank, step) pairs) skips the kernel entirely behind
      ``lax.cond`` — the per-device predicate is local control flow, only
      the ``ppermute`` rotation stays unconditional;
    - per-step (o_s, lse_s) partials merge online in float32:
      ``out = sum_s o_s * exp(lse_s - M) / sum_s exp(lse_s - M)`` with a
      running max M, so per-chip memory stays O(L_local) and gradients
      flow exactly through both outputs (the lse cotangent folds into the
      flash backward as a delta shift).

    ``impl``: ``None`` auto-selects — the flash kernel on TPU for shards
    long enough to amortize the kernel's VPU overhead
    (:func:`ring_block_impl`), dense-XLA otherwise (including CPU
    meshes, where interpret-mode flash is also prohibitively slow for
    tests).  ``"flash"``/``"dense"`` force a path (CPU flash-ring
    composition tests; numerical cross-checks).
    """
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, l_local, h, d = q.shape
    if v.shape[-1] != d:
        raise ValueError(f"ring attention (sequence parallelism) knows one head size: "
                         f"queries and keys of {d} with values of {v.shape[-1]} (latent "
                         f"attention) run without a sequence axis")
    if impl is None:
        use_flash = ring_block_impl(l_local, d) == "flash"
    elif impl in ("flash", "dense"):
        use_flash = impl == "flash"
    else:
        raise ValueError(f"unknown ring impl {impl!r}: expected 'flash' or 'dense'")

    def block_attn(k_blk, v_blk, step_causal):
        # one (o, lse) partial for the local q block against one kv block;
        # lse is log-sum-exp of the scaled scores [B, H, Lq].  Grouped KV
        # heads (GQA) broadcast up HERE — after the ppermute rotation — so
        # the ICI ring carries only the Hkv-sized tensors.  The flash
        # kernel always runs causal=True: a live step s > 0 passes
        # q_offset=l_local so every key is provably in the past and the
        # kernel's mask takes its identity branch everywhere (same cost as
        # an unmasked kernel, and it sidesteps a pallas-interpreter vma
        # bug that trips the causal=False kernel under shard_map on CPU)
        k_blk, v_blk = repeat_kv_heads(q, k_blk, v_blk)
        if use_flash:
            from distkeras_tpu.ops.flash_attention import flash_attention_with_lse

            return flash_attention_with_lse(q, k_blk, v_blk, causal=True,
                                            q_offset=0 if step_causal else l_local,
                                            k_offset=0)
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k_blk.astype(jnp.float32)) * scale
        if step_causal:
            pos = jnp.arange(l_local)
            logits = jnp.where((pos[:, None] >= pos[None, :])[None, None], logits,
                               -jnp.inf)
        m = jnp.max(logits, axis=-1)
        p = jnp.exp(logits - m[..., None])
        l_sum = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
        # stay in f32: the merge accumulates in f32 anyway, and the dense
        # branch doubles as the exact reference for numerical cross-checks
        return o / l_sum.transpose(0, 2, 1)[..., None], m + jnp.log(l_sum)

    # constants entering per-device results must carry q's full varying set
    # (covers extra mesh axes like dp) or cond/accumulation types mismatch
    vma = tuple(jax.typeof(q).vma) or (axis_name,)

    def live_step(k_blk, v_blk, step_causal):
        o_s, lse_s = block_attn(k_blk, v_blk, step_causal)
        return o_s.astype(jnp.float32), lse_s

    def dead_step(k_blk, v_blk):
        return tuple(lax.pcast(x, vma, to="varying") for x in (
            jnp.zeros((b, l_local, h, d), jnp.float32),
            jnp.full((b, h, l_local), -jnp.inf, jnp.float32)))

    m0 = jnp.full((b, h, l_local), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, l_local), dtype=jnp.float32)
    acc0 = jnp.zeros((b, l_local, h, d), dtype=jnp.float32)
    m0, l0, acc0 = (lax.pcast(x, vma, to="varying") for x in (m0, l0, acc0))

    m, l_sum, acc = m0, l0, acc0
    k_blk, v_blk = k, v
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    # python loop: sp is static, and a static step index makes step 0 the
    # ONLY masked kernel (the scan-based version had to mask every step)
    for s in range(sp):
        if s:
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (my - s) % sp  # global rank the current kv block came from
        if causal and s:
            # step_causal is static (False for s > 0: the kv block is
            # strictly in the past), so it closes over the branches rather
            # than riding the cond operands
            o_s, lse_s = lax.cond(src <= my,
                                  lambda kb, vb: live_step(kb, vb, False),
                                  dead_step, k_blk, v_blk)
        else:
            o_s, lse_s = live_step(k_blk, v_blk, causal)
        new_m = jnp.maximum(m, lse_s)
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - safe_m))
        w = jnp.exp(jnp.where(jnp.isneginf(lse_s), -jnp.inf, lse_s - safe_m))
        l_sum = l_sum * corr + w
        wq = w.transpose(0, 2, 1)[..., None]      # [B, Lq, H, 1]
        corrq = corr.transpose(0, 2, 1)[..., None]
        acc = acc * corrq + o_s * wq
        m = new_m
    denom = jnp.maximum(l_sum, 1e-20).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def attention(q, k, v, causal: bool = True, axis_name: Optional[str] = None,
              impl: Optional[str] = None, window: Optional[int] = None):
    """Dispatch: ring attention when a sequence mesh axis is given, else dense.

    A sequence-parallel model traced outside ``shard_map`` (e.g. parameter
    init, or single-device eval of the same spec) has no bound axis; fall
    back to dense attention — parameters and semantics are identical, only
    the schedule differs.  The fallback applies ONLY when no mesh axes are
    bound at all: inside a shard_map whose axes don't include ``axis_name``,
    falling back would silently attend within each local shard, so that is
    an error instead.

    ``impl``: ``"flash"`` forces the Pallas flash kernel, ``"dense"``
    forces plain XLA softmax attention, ``None`` auto-selects flash on TPU
    for sequences long enough to benefit (the kernel skips masked key
    blocks and never materializes [Lq, Lk]).  Under sequence parallelism
    the schedule is always ring attention and ``impl`` selects its
    per-block compute (``ring_attention``'s own crossover applies when
    ``None``).

    ``window``: sliding-window causal attention (``0 <= i - j < window``),
    in both the flash kernels and ``dense_attention``; the ring schedule
    has no window and refuses one.
    """
    if axis_name is not None and not jax.typeof(q).vma:
        axis_name = None  # traced outside any shard_map: dense is exact
    if axis_name is None:
        if impl is None:
            impl = attention_impl(q.shape[1], k.shape[1])
        if impl == "flash":
            from distkeras_tpu.ops.flash_attention import flash_attention

            # the Pallas kernel contracts equal head counts; grouped KV
            # heads broadcast up here (training holds the full sequence
            # anyway — GQA's memory win is the decode cache and the ring's
            # ICI traffic, both handled elsewhere)
            k, v = repeat_kv_heads(q, k, v)
            return flash_attention(q, k, v, causal=causal, window=window)
        if impl != "dense":
            raise ValueError(f"unknown attention impl {impl!r}: expected 'flash' or 'dense'")
        return dense_attention(q, k, v, causal=causal, window=window)
    if window is not None:
        raise ValueError("ring attention (sequence parallelism) has no sliding "
                         "window; run window layers without a sequence axis")
    try:
        lax.axis_size(axis_name)
    except NameError:
        raise ValueError(
            f"sequence axis {axis_name!r} is not bound by the enclosing shard_map "
            f"(bound varying axes: {sorted(jax.typeof(q).vma)}); the model's seq_axis "
            f"must match the mesh axis the sequence is sharded over") from None
    # the schedule is ring attention; impl selects its PER-BLOCK compute
    # (flash kernel vs dense XLA), auto-selected by shard length when None
    return ring_attention(q, k, v, axis_name=axis_name, causal=causal, impl=impl)
