"""Speculative decoding: draft-model lookahead with exact target parity.

No reference counterpart (the reference predates LMs) — TPU-native
inference headroom on top of ``models/decode.py``: a small draft model
proposes ``k`` tokens autoregressively, the target model scores the whole
proposal in ONE k+1-token cached forward (an MXU-shaped matmul instead of
k+1 serial single-token steps), and the longest agreeing prefix commits.
Greedy acceptance makes every committed token the argmax of a target
forward over the true committed prefix — the output is a greedy decode of
the target by construction; the draft changes the schedule, never the
distribution.  In float32 it is bit-identical to ``make_generate_fn``'s
single-token path (the test invariant, ``tests/test_speculative``); in
bfloat16 the k+1-window forward can flip argmax near-ties relative to the
single-token forward (different matmul shapes accumulate differently), so
the two equally-valid greedy trajectories may diverge after such a tie.

What it gains depends on the acceptance rate; decoding has no benchmark
cell, so no figure for it is on the ledger (ROADMAP, "Never on the
ledger").

Per loop iteration, with m = number of accepted draft tokens (0..k):
``m + 1`` tokens commit (the accepted prefix plus the target's correction
— or, when all k agree, its bonus token from the same forward).  Serial
target steps per committed token: 1/(m+1).

KV-cache bookkeeping exploits the decode module's position masking: cache
rows beyond the current write position are dead (masked by
``key_pos <= q_pos``), so rejecting a speculation is just *not advancing*
the position — the stale rows get overwritten when decoding resumes
there.  After each iteration one extra draft token-forward fills the one
cache row sequential drafting didn't write, so both caches stay
row-aligned with the committed sequence.

The whole generation — both prefills and the while-loop of
draft/verify/commit iterations — is one compiled program.

Batched decoding commits in LOCKSTEP: each round accepts the batch
MINIMUM agreeing prefix, so every row advances the shared cache write
position together and the cache machinery stays identical to batch 1.
Rows whose own prefix was longer commit tokens that their verification
already endorsed (their accepted draft token equals their greedy token at
every committed position), so per-row outputs remain exact greedy decodes
— the batch minimum costs throughput (expected accepted prefix shrinks
as agreement^batch per position), never correctness.

``temperature > 0`` switches from greedy verification to exact
speculative SAMPLING (:func:`speculative_accept`): proposals are sampled
from the draft and accepted with prob ``min(1, p/q)``, rejections
resample the residual — committed tokens are exact temperature-T target
samples, in distribution rather than bit-equality.

``eos_id`` enables EOS with the plain decoder's exact semantics (EOS
kept, pads after, per row) and the loop exits EARLY once every row is
done; finished rows are credited a full accept so their pad-fed drafts
cannot throttle the live rows' lockstep minimum.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.base import ModelSpec
from distkeras_tpu.models.decode import (KVCache, _sample, dequant_embed,
                                         forward_with_cache, fused_token_forward,
                                         init_cache, make_fused_state)


def speculative_accept(key, target_probs, draft_probs, drafted):
    """One row's exact speculative-SAMPLING acceptance (the standard
    accept/residual scheme: Leviathan et al. / Chen et al. 2023).

    ``target_probs`` [k+1, V] — the target distribution after each prefix
    position of the verification window; ``draft_probs`` [k, V] — the
    draft distribution each proposal was sampled from; ``drafted`` [k].
    Returns ``(m, token_m)``: the number of accepted proposals and the
    token to commit at position ``m``.

    Rule: proposal i is accepted iff ``u_i * q(x_i) < p(x_i)`` (i.e.
    ``u_i < min(1, p/q)``); on the first rejection the committed token is
    sampled from the normalized residual ``max(p - q, 0)``; if all k are
    accepted it is a bonus sample from ``target_probs[k]`` (the residual
    expression reduces to exactly that because q is set to 0 there).
    Per-position committed-token marginals equal the target distribution
    — the property ``tests/test_speculative.py`` checks in closed form
    and statistically.
    """
    k_ = drafted.shape[0]
    u = jax.random.uniform(jax.random.fold_in(key, 0), (k_,))
    p_x = jnp.take_along_axis(target_probs[:k_], drafted[:, None], 1)[:, 0]
    q_x = jnp.take_along_axis(draft_probs, drafted[:, None], 1)[:, 0]
    # u*q < p  <=>  u < p/q, and stays well-defined at q == 0 (accept iff
    # p > 0 — a zero-probability proposal can only appear through argmax
    # ties or numerics, and the rule still keeps the output exact)
    accept = (u * q_x < p_x).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(accept))
    p_m = jnp.take(target_probs, m, axis=0)
    q_m = jnp.where(m < k_,
                    jnp.take(draft_probs, jnp.minimum(m, k_ - 1), axis=0), 0.0)
    residual = jnp.maximum(p_m - q_m, 0.0)
    token = jax.random.categorical(jax.random.fold_in(key, 1),
                                   jnp.log(residual + 1e-30))
    return m, token.astype(jnp.int32)


def make_speculative_generate_fn(target_spec: ModelSpec, draft_spec: ModelSpec,
                                 max_new_tokens: int, *, k: int = 4,
                                 temperature: float = 0.0,
                                 eos_id: Optional[int] = None, pad_id: int = 0,
                                 with_stats: bool = False,
                                 draft_step_impl: Optional[str] = None,
                                 quantize_cache: bool = False):
    """Build a jitted ``(target_params, draft_params, prompt [B, P]) ->
    tokens [B, max_new_tokens]`` — greedy; bit-identical to
    ``make_generate_fn(target_spec, ...)`` in float32 (see module docstring
    for the bfloat16 near-tie caveat and the batched lockstep-commit rule).

    ``k`` = draft tokens proposed per verification step.  The two specs
    must share vocab; the draft is typically a smaller ``num_layers``/
    ``model_dim`` model (possibly int8-quantized — both param trees ride
    the decode module's QTensor support).

    ``eos_id`` enables EOS handling with ``make_generate_fn``'s exact
    semantics: the EOS token itself is kept, rows past it emit ``pad_id``,
    and the loop exits EARLY once every row is done (the committed-token
    contract makes the pre-EOS prefix identical to the plain decoder's,
    so the two paths stay output-equal with or without EOS).

    ``temperature > 0`` switches to exact speculative SAMPLING: the draft
    samples its proposals from ``softmax(logits/T)`` and each proposal is
    accepted/resampled by :func:`speculative_accept`, so every committed
    token is distributed exactly as a plain temperature-``T`` sample from
    the target (the draft changes the schedule, never the distribution —
    same contract as the greedy path, now in distribution rather than
    bit-equality).  The returned fn then takes an optional ``rng`` last
    argument (default ``PRNGKey(0)``).  Batched sampling uses the same
    lockstep batch-minimum commit as greedy.

    ``draft_step_impl``: the draft's k sequential single-token proposal
    steps are the serial bottleneck of every round, and they run on a
    SMALL model — exactly the regime the fused Pallas decode-step
    kernel (``ops/decode_step.py``) was written for: per-op sequencing
    cost, not weight bytes, bounds the step.  ``None`` auto-selects it
    on TPU at batch 1 for draft shapes inside ``fused_step_auto``'s
    bound; ``"fused"``/``"xla"`` pin the path.  The target's k+1-token verify
    window is MXU-shaped and always stays XLA.

    ``quantize_cache=True`` stores BOTH models' KV int8 with per-(position,
    head) scales (:class:`~distkeras_tpu.models.decode.QKVCache`), exactly
    like ``make_generate_fn``'s flag: cache HBM traffic halves — the
    dominant batched-decode cost — at one rounding step per K/V row.
    Rewound draft rows re-quantize on overwrite (per-position state, so
    the rewind semantics are unchanged).  Requires the XLA draft step (the fused kernel's slabs
    are bf16), so it suits the BATCHED regime where the fused draft
    would not be auto-selected anyway.

    ``with_stats=True`` returns ``(tokens, iterations)`` where
    ``iterations`` is the number of draft/verify rounds the while-loop ran.
    Without EOS the loop commits ``max_new_tokens - 1`` tokens (the first
    output token comes from the prompt prefill, before the loop), each
    round committing ``m + 1``, so mean accepted draft tokens per round is
    ``(max_new_tokens - 1)/iterations - 1`` and the acceptance rate is
    that divided by ``k`` — the number a benchmark must report for a
    speculative-decoding claim to mean anything.  (Under an EOS early
    exit fewer tokens are committed, so that formula UNDERSTATES nothing
    but the benchmarks run without EOS.)
    """
    from distkeras_tpu.models.decode import validate_decode_spec

    t_cfg = validate_decode_spec(target_spec, "target decoding")
    d_cfg = validate_decode_spec(draft_spec, "draft decoding")
    if t_cfg["vocab_size"] != d_cfg["vocab_size"]:
        raise ValueError(f"vocab mismatch: target {t_cfg['vocab_size']} vs "
                         f"draft {d_cfg['vocab_size']}")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if not temperature >= 0.0:  # also rejects NaN
        raise ValueError(f"temperature must be >= 0, got {temperature} "
                         "(a negative value would silently select greedy)")
    if draft_step_impl not in (None, "fused", "xla"):
        raise ValueError(f"unknown draft_step_impl {draft_step_impl!r}; "
                         "use None, 'fused' or 'xla'")
    if quantize_cache and draft_step_impl == "fused":
        raise ValueError("quantize_cache requires the XLA draft step: the "
                         "fused kernel's slabs are bf16 (draft_step_impl="
                         "'xla' or None)")
    if quantize_cache:
        from distkeras_tpu.models.decode import warn_quantized_cache_gqa

        # both caches quantize; warn per model so the message names which
        # spec carries the GQA config (the draft rarely does)
        warn_quantized_cache_gqa(t_cfg, "make_speculative_generate_fn (target)")
        warn_quantized_cache_gqa(d_cfg, "make_speculative_generate_fn (draft)")

    sampling = temperature > 0.0

    @functools.partial(jax.jit, static_argnames=("prompt_len", "d_impl"))
    def run(t_params, d_params, prompt, rng, prompt_len, d_impl):
        n = max_new_tokens
        b = prompt.shape[0]
        total = prompt_len + n + k + 1  # speculative writes may run past n
        for name, cfg in (("target", t_cfg), ("draft", d_cfg)):
            # learned positional tables bound the reachable positions; rope
            # models have no table (cache sizing is the only capacity here)
            if ((cfg.get("positional") or "learned") == "learned"
                    and total > cfg["max_seq_len"]):
                raise ValueError(
                    f"prompt + max_new_tokens + k = {total} exceeds the "
                    f"{name} positional table max_seq_len = "
                    f"{cfg['max_seq_len']}")
        t_params = dequant_embed(t_params)
        d_params = dequant_embed(d_params)
        d_total = total
        if d_impl == "fused":
            from distkeras_tpu.ops.decode_step import round_cache_len

            d_total = round_cache_len(total)  # dead rows stay masked
        t_cache = init_cache(t_cfg, b, total, quantized=quantize_cache)
        d_cache = init_cache(d_cfg, b, d_total, quantized=quantize_cache)

        t_logits, t_cache = forward_with_cache(t_params, t_cfg, prompt, 0,
                                               t_cache, last_only=True)
        _, d_cache = forward_with_cache(d_params, d_cfg, prompt, 0, d_cache,
                                        last_only=True)
        if d_impl == "fused":
            from distkeras_tpu.ops.decode_step import transpose_k_cache

            # built once (loop-invariant); draft K goes lane-major for the
            # fused kernel, exactly as in make_generate_fn's fused branch
            d_state = make_fused_state(d_params, d_cfg)
            d_cache = KVCache(transpose_k_cache(d_cache.k), d_cache.v)

        def draft_token_step(tok, pos_, cache):
            """One draft single-token forward: [B] -> (f32 logits [B, V],
            cache) via the fused kernel or the XLA step."""
            if d_impl == "fused":
                logits, k_t, v_all = fused_token_forward(
                    d_state, tok, pos_, cache.k, cache.v)
                return logits[:, -1].astype(jnp.float32), KVCache(k_t, v_all)
            logits, cache = forward_with_cache(d_params, d_cfg, tok[:, None],
                                               pos_, cache)
            return logits[:, -1].astype(jnp.float32), cache
        if sampling:
            rng, sub = jax.random.split(rng)
            cur = _sample(t_logits[:, -1].astype(jnp.float32), sub,
                          temperature, 0)  # [B]
        else:
            cur = jnp.argmax(t_logits[:, -1], axis=-1).astype(jnp.int32)  # [B]

        # out buffer padded by k+1: each iteration writes a full k+1 slab at
        # n_out; uncommitted tail is overwritten by the next iteration
        out = jnp.zeros((b, n + k + 1), jnp.int32)
        out = lax.dynamic_update_slice(out, cur[:, None], (0, 0))
        pos = jnp.asarray(prompt_len, jnp.int32)  # cache rows valid below pos
        n_out = jnp.asarray(1, jnp.int32)
        iters = jnp.asarray(0, jnp.int32)
        # the EOS token itself is kept in the output; rows pad after it
        done = (jnp.zeros(b, bool) if eos_id is None else cur == eos_id)

        def cond(carry):
            # early exit once EVERY row is done — the speculative loop's
            # version of the plain decoder's carried-done convention
            return (carry[0] < n) & ~jnp.all(carry[8])

        def body(carry):
            n_out, cur, pos, out, iters, rng, t_cache, d_cache, done = carry
            if sampling:
                rng, k_draft, k_verify = jax.random.split(rng, 3)

            # 1. draft k tokens autoregressively from cur (whole batch):
            # greedy argmax, or (sampling) draws from softmax(logits/T)
            # with the full draft distribution recorded for the accept rule
            def draft_step(c, i):
                tok, cache = c
                logits, cache = draft_token_step(tok, pos + i, cache)
                if sampling:
                    scaled = logits / temperature
                    nxt = jax.random.categorical(
                        jax.random.fold_in(k_draft, i), scaled,
                        axis=-1).astype(jnp.int32)
                    return (nxt, cache), (nxt, jax.nn.softmax(scaled, axis=-1))
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (nxt, cache), (nxt, jnp.float32(0))

            (_, d_cache), (drafted, d_probs) = lax.scan(
                draft_step, (cur, d_cache), jnp.arange(k))
            drafted = drafted.T  # [B, k]

            # 2. target scores the whole window [cur, d_1..d_k] in one pass
            window = jnp.concatenate([cur[:, None], drafted], axis=1)  # [B, k+1]
            t_logits, t_cache = forward_with_cache(t_params, t_cfg, window,
                                                   pos, t_cache)

            # 3. per-row accepted-prefix length m_r and the token each row
            # would commit at its own boundary
            if sampling:
                t_probs = jax.nn.softmax(
                    t_logits.astype(jnp.float32) / temperature, axis=-1)
                row_keys = jax.vmap(jax.random.fold_in, (None, 0))(
                    k_verify, jnp.arange(b))
                m_rows, token_rows = jax.vmap(speculative_accept)(
                    row_keys, t_probs, d_probs.transpose(1, 0, 2), drafted)
            else:
                greedy = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
                matches = (drafted == greedy[:, :k]).astype(jnp.int32)
                m_rows = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)
                token_rows = None  # greedy[:, m] is taken after m is known

            if eos_id is not None:
                # rows that finished BEFORE this round draft pad-fed
                # garbage; letting their arbitrary m_r into the batch
                # minimum would throttle every live row toward 1 token/
                # round.  Their slab is fully pad-masked below, so
                # crediting them a full accept is safe and removes the drag
                m_rows = jnp.where(done, k, m_rows)

            # lockstep commit: truncate every row to the batch MINIMUM so
            # all rows advance the shared cache position together.
            # Positions < m are accepted by EVERY row; at position m a row
            # whose private prefix ran longer (m_r > m) commits its own
            # ACCEPTED proposal drafted[r, m] (== its greedy token in the
            # greedy mode; an exact-marginal sample in sampling mode),
            # and a row with m_r == m commits its correction/residual
            # token — so each row's output stays an exact greedy decode /
            # exact temperature-T sample of the target.  Batch-1 reduces
            # to the classic per-row rule (min over 1 row).
            m = jnp.min(m_rows)
            if sampling:
                own = jnp.take(drafted, jnp.minimum(m, k - 1), axis=1)
                token_m = jnp.where(m_rows > m, own, token_rows)
            else:
                token_m = jnp.take(greedy, m, axis=1)
            idx = jnp.arange(k + 1)
            padded = jnp.concatenate([drafted, drafted[:, -1:]], axis=1)
            slab = jnp.where(idx[None, :] < m, padded,
                             token_m[:, None])  # [B, k+1]
            if eos_id is not None:
                # committed positions strictly AFTER a row's first EOS (or
                # every position of an already-done row) become pad_id;
                # EOS beyond the committed prefix is dead weight and must
                # not latch `done`.  Rows whose pre-EOS tokens are exact
                # stay exact — only the padded tail differs from the raw
                # slab, exactly like the plain decoder's carried-done rule.
                committed_mask = idx[None, :] <= m
                is_eos = (slab == eos_id) & committed_mask
                eos_before = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                              - is_eos.astype(jnp.int32)) > 0
                after = done[:, None] | eos_before
                slab = jnp.where(after, pad_id, slab)
                done = done | jnp.any(is_eos, axis=1)
            out = lax.dynamic_update_slice(out, slab, (0, n_out))
            committed = m + 1
            cur = jnp.take(slab, m, axis=1)  # [B]

            # 4. complete the draft cache: sequential drafting wrote rows
            # pos..pos+k-1 for [cur, d_1..d_{k-1}]; only the d_k row at
            # pos+k is missing, so ONE draft token-forward fills it (K/V
            # rows depend only on (token, position)).  Rows past
            # pos+committed are dead until decoding resumes there.  (On
            # the fused path the unused logits' unembed matmul is DCE'd.)
            _, d_cache = draft_token_step(drafted[:, -1], pos + k, d_cache)
            return (n_out + committed, cur, pos + committed, out, iters + 1,
                    rng, t_cache, d_cache, done)

        n_out, cur, pos, out, iters, _, _, _, done = lax.while_loop(
            cond, body,
            (n_out, cur, pos, out, iters, rng, t_cache, d_cache, done))
        if eos_id is not None:
            # an early exit leaves columns n_out..n unwritten (zeros);
            # they belong to all-done rows and must read as pad_id
            out = jnp.where(jnp.arange(n + k + 1)[None, :] < n_out, out, pad_id)
        if with_stats:
            return out[:, :n], iters
        return out[:, :n]

    def generate_fn(t_params, d_params, prompt, rng=None):
        from distkeras_tpu.ops.decode_step import resolve_step_impl

        prompt = jnp.asarray(prompt)
        if rng is None:
            rng = jax.random.PRNGKey(0)
        if quantize_cache:
            impl = "xla"  # QKVCache slabs are int8; the fused kernel's bf16
        else:
            impl = resolve_step_impl(
                d_cfg, prompt.shape[0],
                prompt.shape[1] + max_new_tokens + k + 1,
                draft_step_impl, what="draft_step_impl")
        return run(t_params, d_params, prompt, rng, prompt.shape[1], impl)

    return generate_fn
