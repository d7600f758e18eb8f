"""``ops/linear_attention.py::gated_delta_rule`` (chunk-parallel) against the
recurrence it stands for, token by token: output and every gradient.

Tolerances.  float32: both sides do the same float32 arithmetic in another
order (a forward substitution and matmuls over a chunk there, T rank-one
updates here), so they differ in their last bits times the length of the
sums: 5e-5 of a result's largest entry covers the readings (at most 1.3e-5)
and is far under what a wrong mask, decay or sign moves (1e-2 and more).
bfloat16 operands: every matmul operand is rounded to 8 bits of mantissa
(2^-9 relative) and the recurrence is followed in float32 on the SAME
rounded inputs, so what is left is the rounding of the chunk form's
intermediate operands (W, V', the state): 3e-2 of the largest entry covers
the readings (at most 7.5e-3, on a gradient).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distkeras_tpu.ops.linear_attention import gated_delta_rule

B, T, H, DK, DV = 2, 128, 3, 8, 16
NAMES = ("q", "k", "v", "g", "beta")
TOL = 5e-5


def recurrence(q, k, v, g, beta):
    """S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T, o_t = S_t q_t,
    a token at a time, float32."""
    def one(q, k, v, g, beta):                      # [T, .] of one head
        def step(s, x):
            q, k, v, g, beta = x
            s = jnp.exp(g) * (s - beta * jnp.outer(s @ k, k)) + beta * jnp.outer(v, k)
            return s, s @ q
        return lax.scan(step, jnp.zeros((v.shape[-1], k.shape[-1]), jnp.float32),
                        (q, k, v, g, beta))[1]
    heads = jax.vmap(one, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    return jax.vmap(heads)(*(x.astype(jnp.float32) for x in (q, k, v, g, beta)))


def inputs(seed, decay="mixed", beta_max=2.0):
    kq, kk, kv, kg, kb = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(kq, (B, T, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(kk, (B, T, H, DK)))
    v = jax.random.normal(kv, (B, T, H, DV))
    lo, hi = {"mixed": (1e-3, 3.0), "near_one": (1e-5, 1e-3), "near_zero": (5.0, 40.0)}[decay]
    g = -jnp.exp(jax.random.uniform(kg, (B, T, H), minval=np.log(lo), maxval=np.log(hi)))
    beta = beta_max * jax.nn.sigmoid(2.0 * jax.random.normal(kb, (B, T, H)))
    return q, k, v, g, beta


def both(fn, args, seed):
    """(output, gradient by input) of sum(fn(*args) * cotangent)."""
    ct = jax.random.normal(jax.random.PRNGKey(100 + seed), (B, T, H, DV))
    loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct)
    return fn(*args), jax.grad(loss, argnums=tuple(range(5)))(*args)


def gap(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) / (float(jnp.max(jnp.abs(b))) or 1.0)


@pytest.fixture(scope="module")
def float32_sides():
    cache = {}

    def get(seed, chunk, decay="mixed"):
        key = (seed, chunk, decay)
        if key not in cache:
            args = inputs(seed, decay)
            if (seed, decay) not in cache:
                cache[(seed, decay)] = both(recurrence, args, seed)
            cache[key] = (both(lambda *a: gated_delta_rule(*a, chunk=chunk), args, seed),
                          cache[(seed, decay)])
        return cache[key]
    return get


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chunked_output_matches_the_recurrence(float32_sides, seed, chunk):
    (out, _), (want, _) = float32_sides(seed, chunk)
    assert out.shape == (B, T, H, DV) and out.dtype == jnp.float32
    assert gap(out, want) < TOL


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chunked_gradient_matches_the_recurrence(float32_sides, seed, chunk, name):
    (_, grads), (_, want) = float32_sides(seed, chunk)
    n = NAMES.index(name)
    assert gap(grads[n], want[n]) < TOL, name


@pytest.mark.parametrize("decay", ["near_one", "near_zero"])
def test_decays_near_one_and_near_zero(float32_sides, decay):
    """alpha within 1e-3 of 1 (the state only ever grows: the delta rule
    alone bounds it) and alpha under exp(-5) (the state is gone within a
    token; the chunk's cumulative decay underflows to exactly 0)."""
    (out, grads), (want, want_g) = float32_sides(0, 32, decay)
    assert np.isfinite(np.asarray(out)).all()
    assert gap(out, want) < TOL
    for n, name in enumerate(NAMES):
        assert np.isfinite(np.asarray(grads[n])).all(), name
        assert gap(grads[n], want_g[n]) < TOL, name


def test_write_strength_above_one_and_a_repeated_key():
    """beta in (1, 2): I - beta k k^T has a negative eigenvalue.  With ONE
    key repeated at beta = 2 the reflections cancel in pairs and the power
    series of the chunk's triangular matrix would lose every digit.  The
    recurrence itself is at the edge of stability here (an eigenvalue of
    -0.999 a token, 128 tokens), so both sides' round-off is amplified:
    1e-4 covers the reading, 2.2e-5."""
    q, k, v, g, _ = inputs(3)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full((B, T, H), 2.0 - 1e-3)
    assert float(beta.min()) > 1.0
    out = gated_delta_rule(q, k, v, g * 1e-3, beta, chunk=64)
    assert gap(out, recurrence(q, k, v, g * 1e-3, beta)) < 1e-4


def test_a_length_that_is_no_multiple_of_the_chunk_raises():
    q, k, v, g, beta = (x[:, :100] for x in inputs(0))
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        gated_delta_rule(q, k, v, g, beta, chunk=64)
    assert gated_delta_rule(q, k, v, g, beta, chunk=50).shape == (B, 100, H, DV)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_operands_stay_near_the_recurrence(seed):
    """q, k, v arrive in bfloat16 (g and beta stay float32, as the layer
    hands them over): the matmuls run on bfloat16 operands with float32
    accumulation, the output comes back in bfloat16."""
    q, k, v, g, beta = inputs(seed)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    (out, grads) = both(lambda *a: gated_delta_rule(*a, chunk=32), (q, k, v, g, beta), seed)
    (want, want_g) = both(recurrence, (q, k, v, g, beta), seed)
    assert out.dtype == jnp.bfloat16 and grads[0].dtype == jnp.bfloat16
    assert gap(out, want) < 3e-2
    for n, name in enumerate(NAMES):
        assert gap(grads[n], want_g[n]) < 3e-2, name


def test_the_state_is_carried_across_chunks_not_reset():
    """The planted fault a chunked form invites: each chunk from S = 0."""
    args = inputs(1, "near_one")
    whole = gated_delta_rule(*args, chunk=32)
    alone = gated_delta_rule(*(x[:, 32:64] for x in args), chunk=32)
    assert gap(whole[:, 32:64], alone) > 1e-2
