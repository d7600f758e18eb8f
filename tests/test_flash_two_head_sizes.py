"""Flash attention with queries and keys of one head size and values (and
the output) of another — latent attention's 192 / 128, here 24 / 16 —
against ``dense_attention``; and with one size, the program the kernels
traced to before they knew two.  Interpret mode on the CPU."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu.ops.flash_attention as fa
from distkeras_tpu.ops.attention import attention, dense_attention
from distkeras_tpu.parallel.mesh import create_nd_mesh

D_QK, D_V = 24, 16


def _qkv(seed, length=64, d_qk=D_QK, d_v=D_V, batch=1, heads=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda d: (batch, length, heads, d)
    return (jax.random.normal(ks[0], shape(d_qk)), jax.random.normal(ks[1], shape(d_qk)),
            jax.random.normal(ks[2], shape(d_v)), jax.random.normal(ks[3], shape(d_v)))


def _explicit(q, k, v):
    """Causal softmax attention written out, scale d_qk^-1/2."""
    l = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.arange(l)[None, :] <= jnp.arange(l)[:, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def test_dense_attention_takes_two_head_sizes():
    q, k, v, _ = _qkv(0)
    out = dense_attention(q, k, v)
    assert out.shape == v.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(_explicit(q, k, v)), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(attention(q, k, v, impl="dense")), np.asarray(out))


# the forward's and the backward's tiers: one full-length block; several
# blocks through the fused backward; the fused backward in two q-chunks
# (its dq scratch capped); the two-kernel fallback (no fused tier admitted)
TIERS = {"full_length": ({}, None),
         "blocked": ({"block_q": 16, "block_k": 32, "block_q_bwd": 32, "block_k_bwd": 16}, None),
         "chunked": ({"block_q": 16, "block_k": 16}, 32 * D_QK * 4),
         "two_kernels": ({"block_q": 32, "block_k": 16}, 0)}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_two_head_sizes_match_dense_forward_and_gradients(tier, monkeypatch):
    kw, cap = TIERS[tier]
    if cap is not None:
        monkeypatch.setattr(fa, "_FUSED_WIDE_CAP", cap)
        monkeypatch.setattr(fa, "_FUSED_DQ_SCRATCH_CAP", cap)
    q, k, v, g = _qkv(1)
    cfg = fa._make_config(q, k, True, 0, 0, kw.get("block_q"), kw.get("block_k"),
                          kw.get("block_q_bwd"), kw.get("block_k_bwd"), True)
    chunks = fa._fused_q_chunks(64, D_QK, cfg.block_q_bwd, cfg.block_k_bwd, 64)
    assert chunks == {"full_length": 1, "blocked": 1, "chunked": 2, "two_kernels": None}[tier]
    scalar = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v) * g))
    flash = lambda q, k, v: fa.flash_attention(q, k, v, interpret=True, **kw)
    assert flash(q, k, v).shape == (1, 64, 2, D_V)
    want, want_g = jax.value_and_grad(scalar(dense_attention), (0, 1, 2))(q, k, v)
    got, got_g = jax.value_and_grad(scalar(flash), (0, 1, 2))(q, k, v)
    assert abs(float(got - want)) < 1e-4
    for a, b, width in zip(got_g, want_g, (D_QK, D_QK, D_V)):
        assert a.shape[-1] == width
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_log_sum_exp_and_its_cotangent_at_two_head_sizes():
    """``flash_attention_with_lse``: the statistic is the scaled scores' (at
    d_qk^-1/2) and its cotangent reaches q and k."""
    q, k, v, g = _qkv(2, length=32)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D_QK)
        s = jnp.where(jnp.arange(32)[None, :] <= jnp.arange(32)[:, None], s, -jnp.inf)
        return _explicit(q, k, v), jax.scipy.special.logsumexp(s, axis=-1)

    both = lambda f: (lambda q, k, v: (lambda o, lse: jnp.sum(o * g) + jnp.sum(jnp.sin(lse)))(
        *f(q, k, v)))
    flash = lambda q, k, v: fa.flash_attention_with_lse(q, k, v, block_q=16, block_k=16,
                                                        interpret=True)
    want, want_g = jax.value_and_grad(both(plain), (0, 1, 2))(q, k, v)
    got, got_g = jax.value_and_grad(both(flash), (0, 1, 2))(q, k, v)
    assert abs(float(got - want)) < 1e-4
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_kept_results_are_value_wide_under_the_remat_policy():
    """What ``TransformerLM.remat`` keeps of a layer: the output at d_v."""
    q, k, v, _ = _qkv(3, length=32)
    kept = jax.checkpoint(
        lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, interpret=True)),
        policy=jax.checkpoint_policies.save_only_these_names(fa.FLASH_OUT_NAME,
                                                             fa.FLASH_LSE_NAME))
    text = str(jax.make_jaxpr(jax.grad(kept, (0, 1, 2)))(q, k, v))
    assert text.count("name=_fwd_kernel") == 1 and text.count("name=_bwd_fused_kernel") == 1
    named = dict(re.findall(r":f32\[([\d,]+)\] = name\[name=(flash_attention\.\w+)\]", text))
    assert named == {f"1,2,32,{D_V}": fa.FLASH_OUT_NAME,
                     f"1,2,32,{fa._STAT_LANES}": fa.FLASH_LSE_NAME}


def test_mismatched_query_and_key_sizes_are_refused():
    q, k, v, _ = _qkv(4, length=16)
    with pytest.raises(ValueError, match="head size"):
        fa.flash_attention(q, k[..., :16], v, interpret=True)


def test_the_ring_refuses_two_head_sizes_by_name():
    from jax.sharding import PartitionSpec as P

    q, _, v, _ = _qkv(5, length=16)
    mesh = create_nd_mesh((2,), ("sp",))
    ring = jax.shard_map(lambda q, v: attention(q, q, v, axis_name="sp"), mesh=mesh,
                         in_specs=P(None, "sp"), out_specs=P(None, "sp"))
    with pytest.raises(ValueError, match="latent attention"):
        jax.eval_shape(ring, q, v)


def _grad_text(d_qk, d_v, **kw):
    q = jax.ShapeDtypeStruct((2, 64, 2, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 64, 2, d_v), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, interpret=True, **kw)
                                   .astype(jnp.float32))
    return str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, v))


# sha256 of the jaxpr text the PARENT of PR 34 (e7dd97e) traces these calls
# to under this installation (jax 0.9.0): with one head size the kernels,
# their grids, blocks and scratch are what they were
ONE_SIZE = {
    "full_length": ({}, "a6e23f3e5d82d58dcc20c2f126e37b0b0c38563fc6e3d4171d573c417b8997aa"),
    "blocked": ({"block_q": 16, "block_k": 32, "block_q_bwd": 32, "block_k_bwd": 16},
                "22524893a8e2cf63ff867d707696717642e46694c93dd1935c3db77996d09da0"),
    "window": ({"block_q": 16, "block_k": 16, "window": 24},
               "fe94820b7ad3381f81a99dfa517276b3236e4179c9aa8f3468d4b019dfb783fa"),
}


@pytest.mark.parametrize("case", sorted(ONE_SIZE))
def test_one_head_size_traces_to_the_jaxpr_it_had(case):
    kw, parent_sha = ONE_SIZE[case]
    text = _grad_text(16, 16, **kw)
    assert hashlib.sha256(text.encode()).hexdigest() == parent_sha, (
        "with d_qk == d_v the flash kernels no longer trace to the jaxpr of before PR 34 "
        "(or the installation's JAX changed: re-pin from the parent commit)")
    # and two sizes differ from it only in shapes: the same equations in the same order
    two = _grad_text(24, 16, **kw)
    ops = lambda t: [line.split(" = ")[1].split("[")[0].split()[0] for line in t.splitlines()
                     if " = " in line and ":" in line.split(" = ")[0]]
    assert ops(two) == ops(text)
