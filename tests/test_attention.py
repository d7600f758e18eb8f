"""Ring attention correctness: sequence-parallel result must match dense
attention on the full sequence (8-way sequence sharding on the CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distkeras_tpu.ops.attention import dense_attention, ring_attention
from distkeras_tpu.parallel.mesh import create_mesh

SP = 8


def _run_ring(q, k, v, causal, impl="flash"):
    # impl="flash" by default so CPU tests exercise the TPU schedule (the
    # per-block flash kernel through the interpreter); the auto-select
    # would pick dense for these tiny shards
    mesh = create_mesh(SP, axis_name="sp")
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal,
                                       impl=impl),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    return np.asarray(fn(q, k, v))


def _rand_qkv(b=2, l=64, h=2, d=8, seed=0):
    rng = np.random.default_rng(seed)
    shape = (b, l, h, d)
    return (jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32),
            jnp.asarray(rng.normal(size=shape), jnp.float32))


def test_ring_matches_dense_causal():
    q, k, v = _rand_qkv()
    expected = np.asarray(dense_attention(q, k, v, causal=True))
    for impl in ("flash", "dense"):
        got = _run_ring(q, k, v, causal=True, impl=impl)
        np.testing.assert_allclose(got, expected, atol=1e-4,
                                   err_msg=f"ring impl={impl}")


def test_ring_matches_dense_noncausal():
    q, k, v = _rand_qkv(seed=1)
    expected = np.asarray(dense_attention(q, k, v, causal=False))
    got = _run_ring(q, k, v, causal=False)
    np.testing.assert_allclose(got, expected, atol=1e-4)


def test_dense_attention_causality():
    """Output at position t must not depend on keys/values after t."""
    q, k, v = _rand_qkv(b=1, l=16, h=1, d=4, seed=2)
    out1 = np.asarray(dense_attention(q, k, v, causal=True))
    k2 = k.at[:, 8:].set(999.0)
    v2 = v.at[:, 8:].set(999.0)
    out2 = np.asarray(dense_attention(q, k2, v2, causal=True))
    np.testing.assert_allclose(out1[:, :8], out2[:, :8], atol=1e-5)


def test_ring_dead_steps_are_predicated():
    """Causal ring steps whose kv block is entirely in a rank's future must
    be skipped behind lax.cond (s = 1..sp-1), not merely masked — the jaxpr
    carries one cond per rotated step, and the non-causal schedule (every
    step live) carries none."""
    mesh = create_mesh(4, axis_name="sp")
    q, k, v = _rand_qkv(l=32)

    def count_conds(causal):
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=causal,
                                           impl="flash"),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        ))
        jaxpr = str(jax.make_jaxpr(fn)(q, k, v))
        return jaxpr.count("cond[")

    causal_conds = count_conds(True)
    noncausal_conds = count_conds(False)
    # causal: one dead-step cond per rotated step (sp - 1 = 3) on top of
    # whatever the per-block kernel itself contributes (present in both)
    assert causal_conds - noncausal_conds == 3, (causal_conds, noncausal_conds)


def test_ring_gradients_match_dense():
    """Gradients through the flash-backed ring (incl. the lse cotangent
    path through the online merge) == dense attention gradients."""
    mesh = create_mesh(4, axis_name="sp")
    q, k, v = _rand_qkv(l=32, seed=3)

    def ring_loss(q, k, v):
        fn = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True,
                                           impl="flash"),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))
        o = fn(q, k, v)
        return jnp.sum(o * jnp.cos(o))

    def dense_loss(q, k, v):
        o = dense_attention(q, k, v, causal=True)
        return jnp.sum(o * jnp.cos(o))

    gr = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"ring/dense grad mismatch for {name}")


def test_ring_block_impl_area_rule(monkeypatch):
    """The flash/dense auto-select crossover tracks per-block WORK
    (l_local * head_dim >= 2048*64, measured on v5e at head_dim 64 and
    128 — see the docstring), is TPU-only, and requires 128-divisible
    block lengths."""
    from distkeras_tpu.ops import attention as att

    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    assert att.ring_block_impl(2048, 64) == "flash"
    assert att.ring_block_impl(1024, 64) == "dense"   # 0.79x measured
    assert att.ring_block_impl(1024, 128) == "flash"  # 1.05x measured
    assert att.ring_block_impl(512, 128) == "dense"   # 0.72x measured
    assert att.ring_block_impl(2050, 64) == "dense"   # not 128-divisible
    # the plain (non-ring) selector: length-only, 128-divisible, TPU-only
    assert att.attention_impl(2048, 2048) == "flash"
    assert att.attention_impl(1920, 1920) == "dense"
    assert att.attention_impl(2048, 2000) == "dense"  # keys not 128-divisible
    monkeypatch.setattr(att.jax, "default_backend", lambda: "cpu")
    assert att.ring_block_impl(4096, 128) == "dense"  # interpret mode is slow
    assert att.attention_impl(4096, 4096) == "dense"
    # a backend that is neither tpu nor cpu must not quietly fall through to
    # the interpreter / the XLA reference
    monkeypatch.setattr(att.jax, "default_backend", lambda: "mystery")
    with pytest.raises(RuntimeError, match="unexpected JAX backend 'mystery'"):
        att.attention_impl(4096, 4096)
