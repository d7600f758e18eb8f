"""The gated delta rule (Gated DeltaNet, Yang et al. 2024, arXiv:2412.06464):
linear attention whose per-head state is a matrix that every token decays,
partly erases along its key and writes its value into,

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,   S_0 = 0
    o_t = S_t q_t                                  (S in R^{d_v x d_k})

with ``alpha_t = exp(g_t)`` one scalar a head (``g_t <= 0``) and ``beta_t``
the write strength (in (0, 2) where negative eigenvalues are allowed).

No reference counterpart (the reference predates transformers).  Token by
token this is T dependent steps of rank-one work; here it runs in its
chunk-parallel form (the WY / UT transform of the DeltaNet papers).  With
``gamma_i`` the cumulative sum of ``g`` inside a chunk of C tokens and

    A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)    for i > j, else 0
    W = (I + A)^-1 diag(beta exp(gamma)) K              [C, d_k]
    U = (I + A)^-1 diag(beta) V                         [C, d_v]

a chunk that starts from the state ``S`` (held here as ``[d_k, d_v]``) gives

    V' = U - W S
    O  = (Q * exp(gamma)) S + tril(Q K^T * exp(gamma_i - gamma_j)) V'
    S <- exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

so the sequential part is a ``lax.scan`` over T / C chunks that carries one
state a head, and everything else is matmuls over whole chunks.  ``I + A``
is unit lower triangular: W and U come from ONE forward substitution
(``solve_triangular``) in float32 — the power series of ``A`` would do in
matmuls alone but loses every digit once ``beta`` nears 2 on repeated keys.
Every exponent taken is <= 0, so nothing overflows however strong the decay.

Precision: the triangular system, the cumulative decay and the carried
state are float32; the matmuls take their operands in the dtype of ``q``
(the caller's compute dtype) and accumulate in float32.  Differentiable by
JAX's own rules: the backward pass of the scan keeps one state a CHUNK
(T / C of them), never one a token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular


def gated_delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, g: jnp.ndarray,
                     beta: jnp.ndarray, chunk: int = 64) -> jnp.ndarray:
    """``q``, ``k`` [B, T, H, d_k] (already normalised and scaled as the layer
    wants them), ``v`` [B, T, H, d_v], ``g`` (log decay, <= 0) and ``beta``
    [B, T, H] -> ``o`` [B, T, H, d_v] in ``v``'s dtype.  ``T`` must be a
    multiple of ``chunk``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(f"gated_delta_rule: length {t} is no multiple of the chunk {chunk}")
    n, dtype, f32 = t // chunk, q.dtype, jnp.float32

    def chunks(x):      # [B, T, H, ...] -> [N, B, H, C, ...]: the scan runs over N
        x = x.reshape((b, n, chunk, h) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    def mm(eq, x, y):   # operands in the compute dtype, float32 accumulation
        return jnp.einsum(eq, x.astype(dtype), y.astype(dtype), preferred_element_type=f32)

    q, k, v = chunks(q), chunks(k), chunks(v)
    beta = chunks(beta.astype(f32))
    gamma = jnp.cumsum(chunks(g.astype(f32)), axis=-1)              # [N, B, H, C]
    i = jnp.arange(chunk)
    lower, strict = i[:, None] >= i[None, :], i[:, None] > i[None, :]
    # exp(gamma_i - gamma_j) for i >= j; masked BEFORE the exp, whose
    # argument above the diagonal is positive without bound
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    a = jnp.where(strict, beta[..., None] * mm("nbhid,nbhjd->nbhij", k, k) * decay, 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(gamma))[..., None] * k.astype(f32),
                           beta[..., None] * v.astype(f32)], axis=-1)
    wu = solve_triangular(a + jnp.eye(chunk, dtype=f32), rhs, lower=True, unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = jnp.where(lower, mm("nbhid,nbhjd->nbhij", q, k) * decay, 0.0)
    q_in = q.astype(f32) * jnp.exp(gamma)[..., None]
    k_out = k.astype(f32) * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    keep = jnp.exp(gamma[..., -1])[..., None, None]                  # [N, B, H, 1, 1]

    def step(state, xs):
        w, u, qk, q_in, k_out, keep = xs
        v_new = u - mm("bhck,bhkv->bhcv", w, state)
        o = mm("bhck,bhkv->bhcv", q_in, state) + mm("bhij,bhjv->bhiv", qk, v_new)
        return state * keep + mm("bhck,bhcv->bhkv", k_out, v_new), o

    state = jnp.zeros((b, h, dk, dv), f32)
    vma = tuple(jax.typeof(w).vma)      # under shard_map the carry varies as its updates do
    if vma:
        state = lax.pcast(state, vma, to="varying")
    _, o = lax.scan(step, state, (w, u, qk, q_in, k_out, keep))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)                   # [B, N, C, H, d_v]
    return o.reshape(b, t, h, dv).astype(v.dtype)
