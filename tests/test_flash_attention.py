"""Pallas flash attention vs the dense reference implementation.

Runs the real kernels through the Pallas interpreter on CPU (same code
path as TPU modulo Mosaic lowering)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.attention import dense_attention
from distkeras_tpu.ops.flash_attention import flash_attention


def _rand_qkv(rng, b=2, l=64, h=2, d=32, lk=None, dtype=np.float32):
    lk = l if lk is None else lk
    q = rng.normal(size=(b, l, h, d)).astype(dtype)
    k = rng.normal(size=(b, lk, h, d)).astype(dtype)
    v = rng.normal(size=(b, lk, h, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_dense(causal):
    q, k, v = _rand_qkv(np.random.default_rng(0))
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32, interpret=True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_forward_with_offsets():
    # flash over the second half of the queries against the full key set ==
    # the corresponding slice of full dense attention (a ring-attention shard)
    rng = np.random.default_rng(1)
    q, k, v = _rand_qkv(rng, l=64)
    q_half = q[:, 32:]
    out = flash_attention(q_half, k, v, causal=True, q_offset=32, k_offset=0,
                          block_q=16, block_k=16, interpret=True)
    ref = dense_attention(q, k, v, causal=True)[:, 32:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_grads_match_dense(causal):
    rng = np.random.default_rng(2)
    q, k, v = _rand_qkv(rng, b=1, l=32, h=2, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16, interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = dense_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
                                   err_msg=f"grad mismatch for {name}")


def test_bfloat16_forward():
    q, k, v = _rand_qkv(np.random.default_rng(3), d=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(qb, kb, vb, causal=True, block_q=32, block_k=32, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_fully_masked_rows_zero_output_and_grads():
    # q_offset < k_offset: the first 8 query rows precede every key — they
    # must output exactly 0 with finite (zero) gradients, in both impls
    rng = np.random.default_rng(5)
    q, k, v = _rand_qkv(rng, b=1, l=16, h=1, d=16, lk=16)

    out = flash_attention(q, k, v, causal=True, q_offset=0, k_offset=8,
                          block_q=16, block_k=16, interpret=True)
    ref = dense_attention(q, k, v, causal=True, q_offset=0, k_offset=8)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def loss(fn):
        def f(q, k, v):
            if fn is flash_attention:
                o = fn(q, k, v, causal=True, q_offset=0, k_offset=8,
                       block_q=16, block_k=16, interpret=True)
            else:
                o = fn(q, k, v, causal=True, q_offset=0, k_offset=8)
            return jnp.sum(o * o)
        return f

    gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        assert np.isfinite(np.asarray(a)).all(), f"non-finite flash grad for {name}"
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5,
                                   err_msg=f"grad mismatch for {name}")


def test_unknown_impl_raises():
    from distkeras_tpu.ops.attention import attention

    q, k, v = _rand_qkv(np.random.default_rng(6), l=16, d=8)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attention(q, k, v, impl="Flash")


def test_mosaic_illegal_length_raises():
    # L=513 with a sub-length requested block has no 8-divisible divisor
    # (513 is odd, so _pick_block halves down to 1); flash must reject it
    # with a clear error instead of failing in Mosaic lowering.  A block
    # request >= L falls back to the full length (513 == L, legal), so pin
    # both blocks below L to hit the validation path deterministically.
    q, k, v = _rand_qkv(np.random.default_rng(7), l=513, d=8)
    with pytest.raises(ValueError, match="Mosaic-legal"):
        flash_attention(q, k, v, block_q=256, block_k=256, interpret=True)


def test_forced_impl_under_sequence_parallelism_selects_ring_block():
    """Under a bound sequence axis the schedule stays ring attention and
    ``impl`` selects the PER-BLOCK compute — both choices must match the
    dense full-sequence reference."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    from distkeras_tpu.ops.attention import attention

    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _rand_qkv(np.random.default_rng(8), l=16, d=8)
    ref = dense_attention(q, k, v, causal=True)

    for impl in ("dense", "flash"):
        fn = jax.shard_map(
            lambda q, k, v, i=impl: attention(q, k, v, axis_name="sp", impl=i),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"ring per-block impl={impl}")


def test_odd_block_sizes_fall_back_to_divisors():
    # L=48 with requested block 32 -> picker must choose a divisor
    q, k, v = _rand_qkv(np.random.default_rng(4), l=48)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_split_backward_fallback_matches_fused():
    """The two-kernel backward (taken when the fused kernel's [Lq, D] dq
    scratch would overflow scoped vmem) must produce the same gradients as
    the fused default."""
    import distkeras_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)) * 0.1, jnp.float32)
               for _ in range(3))

    def grads():
        return jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)

    assert fa._fused_bwd_ok(64, 16, 16, 16, 64)
    fused = grads()
    caps = fa._FUSED_WIDE_CAP, fa._FUSED_DQ_SCRATCH_CAP
    try:
        fa._FUSED_WIDE_CAP = fa._FUSED_DQ_SCRATCH_CAP = 0
        assert not fa._fused_bwd_ok(64, 16, 16, 16, 64)
        split = grads()
    finally:
        fa._FUSED_WIDE_CAP, fa._FUSED_DQ_SCRATCH_CAP = caps
    for a, b, name in zip(fused, split, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   err_msg=f"fused/split grad mismatch for {name}")


def test_single_block_bwd_tier_selection():
    """The wide tier: auto-select takes the single-block fused
    backward exactly when the forward runs full-length blocks (Lq = Lk
    <= 2048) past 1024, keeps the (1024, 1024) rung at 8k+, and sizes
    the scoped-vmem grant to the score-tile working set."""
    import distkeras_tpu.ops.flash_attention as fa

    def cfg_for(l):
        q = jnp.zeros((1, l, 4, 128), jnp.bfloat16)
        return fa._make_config(q, q, True, 0, 0, None, None, None, None, True)

    c2k = cfg_for(2048)
    assert (c2k.block_q_bwd, c2k.block_k_bwd) == (2048, 2048)
    c8k = cfg_for(8192)
    assert (c8k.block_q_bwd, c8k.block_k_bwd) == (1024, 1024)
    c1k = cfg_for(1024)  # already single-block under the pre-existing rungs
    assert (c1k.block_q_bwd, c1k.block_k_bwd) == (1024, 1024)
    # the wide tier is gated on the k block spanning the WHOLE sequence:
    # 2048-wide k blocks against a longer sequence are rejected (measured
    # slower at 8k — q-chunks re-stream k/v and give up the causal skip)
    assert not fa._fused_bwd_ok(2048, 128, 2048, 2048, 8192)
    assert fa._fused_bwd_ok(2048, 128, 2048, 2048, 2048)
    # grant sizing: standard 24M through (1024, 1024), 48M for the wide tier
    assert fa._bwd_compiler_params(1024, 1024).vmem_limit_bytes == fa._VMEM_LIMIT
    assert fa._bwd_compiler_params(2048, 2048).vmem_limit_bytes == 48 * 1024 * 1024


def test_bwd_blocks_inherit_explicit_fwd_blocks():
    """Explicit block_q/block_k govern the backward too (multi-block bwd
    scratch accumulation is exercised), and a full-length block on a
    non-8-divisible sequence stays legal for both passes."""
    import jax

    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)) * 0.1, jnp.float32)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v))

    # blocks of 16 over L=64 -> 4x4 bwd grids: cross-block accumulation
    small = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, causal=True)),
                   argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(small, ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)

    # L=33: only the full-length block is Mosaic-legal; fwd AND bwd must
    # both inherit it rather than erroring on the bwd default of 512->1
    q2, k2, v2 = (jnp.asarray(rng.normal(size=(1, 33, 2, 16)) * 0.1, jnp.float32)
                  for _ in range(3))
    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=33, block_k=33, interpret=True)),
        argnums=(0, 1, 2))(q2, k2, v2)
    r = jax.grad(loss(lambda q, k, v: dense_attention(q, k, v, causal=True)),
                 argnums=(0, 1, 2))(q2, k2, v2)
    for got, want in zip(g, r):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_chunked_fused_backward_matches_single_call():
    """Force the q-chunked fused backward (tiny caps) and check gradients
    against the unchunked default, including the causal q_offset shifts."""
    import distkeras_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(10)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 64, 2, 16)) * 0.1, jnp.float32)
               for _ in range(3))

    def grads():
        return jax.grad(
            lambda q, k, v: jnp.sum(fa.flash_attention(
                q, k, v, causal=True, block_q=16, block_k=16, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)

    whole = grads()
    caps = fa._FUSED_WIDE_CAP, fa._FUSED_DQ_SCRATCH_CAP
    try:
        # cap fits 32 rows of d=16 f32 (2K) -> 64-row input must chunk in 2
        fa._FUSED_WIDE_CAP = fa._FUSED_DQ_SCRATCH_CAP = 32 * 16 * 4
        assert fa._fused_q_chunks(64, 16, 16, 16, 64) == 2
        chunked = grads()
    finally:
        fa._FUSED_WIDE_CAP, fa._FUSED_DQ_SCRATCH_CAP = caps
    for a, b, name in zip(whole, chunked, "qkv"):
        # rtol covers dk/dv cross-chunk summation-order differences
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-8,
                                   err_msg=f"chunked/whole grad mismatch for {name}")


def test_flash_under_dp_shard_map_matches_unsharded():
    """flash_attention must work inside shard_map with vma checking (the
    dp-sharded LM train step) — pallas out_shapes need the inputs' vma.
    Regression: round-3 verify caught ShapeDtypeStruct vma=None errors."""
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng(11)
    q, k, v = _rand_qkv(rng, b=4, l=32, h=2, d=16)

    def fn(q, k, v):
        def loss(q):
            return jnp.sum(flash_attention(q, k, v, causal=True, block_q=16,
                                           block_k=16, interpret=True))
        return jax.grad(loss)(q)

    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("dp",))
    sharded = jax.shard_map(fn, mesh=mesh, in_specs=(P("dp"),) * 3,
                            out_specs=P("dp"))
    got = sharded(q, k, v)
    want = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
