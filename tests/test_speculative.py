"""Speculative decoding: the one invariant that matters is bit-identity
with the target model's own greedy decoding — for ANY draft model."""

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.decode import generate
from distkeras_tpu.models.speculative import make_speculative_generate_fn
from distkeras_tpu.models.transformer import small_lm_spec


def _spec(layers=2, dim=32, **kw):
    cfg = dict(vocab_size=47, model_dim=dim, num_heads=2, num_layers=layers,
               max_seq_len=64)
    cfg.update(kw)
    spec = small_lm_spec(**cfg)
    spec.config["compute_dtype"] = "float32"
    return spec


@pytest.fixture(scope="module")
def target():
    return Model.init(_spec(layers=3, dim=48, num_heads=4), seed=0)


def test_matches_target_greedy_with_good_draft(target):
    """Draft = the target itself: every proposal accepted, output equal."""
    prompt = jnp.asarray([[5, 17, 3, 9]], jnp.int32)
    want = generate(target, prompt, max_new_tokens=12)
    fn = make_speculative_generate_fn(target.spec, target.spec, 12, k=4)
    got = fn(target.params, target.params, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_matches_target_greedy_with_unrelated_draft(target):
    """Draft = a differently-seeded small model: proposals mostly rejected,
    output STILL equal (correctness never depends on draft quality)."""
    draft = Model.init(_spec(layers=1, dim=32), seed=99)
    prompt = jnp.asarray([[40, 2, 21]], jnp.int32)
    want = generate(target, prompt, max_new_tokens=10)
    for k in (1, 3, 5):
        fn = make_speculative_generate_fn(target.spec, draft.spec, 10, k=k)
        got = fn(target.params, draft.params, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"k={k}")


def test_quantized_draft_still_exact(target):
    """int8 draft params: schedule changes, tokens don't."""
    from distkeras_tpu.ops.quantize import quantize_params

    draft = Model.init(_spec(layers=1, dim=32), seed=7)
    qd = quantize_params(draft.params, min_size=64)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    want = generate(target, prompt, max_new_tokens=8)
    fn = make_speculative_generate_fn(target.spec, draft.spec, 8, k=3)
    got = fn(target.params, qd, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantized_kv_cache_matches_plain_quantized_decode(target):
    """quantize_cache speculative == plain decode with the SAME int8
    cache rounding: both attend over identically-quantized K/V rows, so
    the committed-token contract holds verbatim (the draft changes the
    schedule, never the math).  Also covers the rewound-row re-quantize
    path (uncommitted draft rows overwritten next round)."""
    from distkeras_tpu.models.decode import make_generate_fn

    draft = Model.init(_spec(layers=1, dim=32), seed=99)
    prompt = jnp.asarray([[40, 2, 21], [7, 7, 1]], jnp.int32)
    want = make_generate_fn(target.spec, 10, quantize_cache=True)(
        target.params, prompt)
    fn = make_speculative_generate_fn(target.spec, draft.spec, 10, k=3,
                                      quantize_cache=True)
    got = fn(target.params, draft.params, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the fused draft step cannot serve an int8 cache: loud refusal
    import pytest
    with pytest.raises(ValueError, match="quantize_cache"):
        make_speculative_generate_fn(target.spec, draft.spec, 10, k=3,
                                     quantize_cache=True,
                                     draft_step_impl="fused")


def test_batched_matches_per_row_greedy(target):
    """Batched lockstep commit: every row of a batch-3 speculative decode
    equals that row's own plain greedy decode, for a good AND a bad
    draft (the batch-min prefix changes the schedule, never a token)."""
    prompt = jnp.asarray([[5, 17, 3, 9], [40, 2, 21, 1], [1, 1, 1, 1]],
                         jnp.int32)
    want = generate(target, prompt, max_new_tokens=10)
    for draft in (target, Model.init(_spec(layers=1, dim=32), seed=99)):
        fn = make_speculative_generate_fn(target.spec, draft.spec, 10, k=3,
                                          with_stats=True)
        got, iters = fn(target.params, draft.params, prompt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(iters) >= 1
    # identical draft: every round accepts everything, so the batch run
    # takes exactly as few rounds as batch-1 would
    fn = make_speculative_generate_fn(target.spec, target.spec, 10, k=3,
                                      with_stats=True)
    _, iters = fn(target.params, target.params, prompt)
    assert int(iters) == -(-(10 - 1) // 4)  # ceil((n-1)/(k+1))


def test_eos_matches_plain_decode_and_exits_early(target):
    """EOS semantics equal make_generate_fn's exactly — EOS kept, pads
    after, per row — for eos ids that fire at different points (or never),
    with the good and the bad draft; and the loop exits early when every
    row finishes (iters shrinks vs the no-EOS run)."""
    prompt = jnp.asarray([[5, 17, 3, 9], [40, 2, 21, 1]], jnp.int32)
    plain = np.asarray(generate(target, prompt, max_new_tokens=12))
    # candidate eos ids: tokens the greedy decode actually emits early,
    # plus one that never appears
    eos_candidates = [int(plain[0, 0]), int(plain[1, 2]), 46]
    bad_draft = Model.init(_spec(layers=1, dim=32), seed=99)
    for eos in eos_candidates:
        want = np.asarray(generate(target, prompt, max_new_tokens=12,
                                   eos_id=eos, pad_id=45))
        for draft in (target, bad_draft):
            fn = make_speculative_generate_fn(target.spec, draft.spec, 12,
                                              k=3, eos_id=eos, pad_id=45)
            got = np.asarray(fn(target.params, draft.params, prompt))
            np.testing.assert_array_equal(got, want, err_msg=f"eos={eos}")

    # early exit MUST engage: duplicate row 0 so eos = its first emitted
    # token finishes every row in round 1, and assert the loop really
    # stopped early (a vacuous <= would pass with early exit broken)
    both = jnp.asarray(np.stack([np.asarray(prompt[0])] * 2))
    fn_all = make_speculative_generate_fn(target.spec, target.spec, 12, k=3,
                                          with_stats=True)
    _, iters_full = fn_all(target.params, target.params, both)
    eos_first = int(plain[0, 0])
    fn_eos = make_speculative_generate_fn(target.spec, target.spec, 12,
                                          k=3, eos_id=eos_first,
                                          with_stats=True)
    toks_eos, iters_eos = fn_eos(target.params, target.params, both)
    assert int(iters_eos) < int(iters_full), \
        f"early exit did not engage: {int(iters_eos)} vs {int(iters_full)}"
    # and the output still matches the plain decoder's EOS semantics
    want = np.asarray(generate(target, both, max_new_tokens=12,
                               eos_id=eos_first, pad_id=0))
    np.testing.assert_array_equal(np.asarray(toks_eos), want)


def test_speculative_accept_closed_form():
    """The accept/residual rule in its two analytic corners."""
    import jax

    from distkeras_tpu.models.speculative import speculative_accept

    V, k = 5, 3
    # identical distributions: every proposal accepted (u*q < p a.s.),
    # m == k, and the committed token is the bonus sample from p_t[k]
    p = jnp.asarray(np.full((k + 1, V), 1.0 / V, np.float32))
    q = p[:k]
    for seed in range(8):
        key = jax.random.PRNGKey(seed)
        drafted = jnp.asarray([1, 3, 0], jnp.int32)
        m, tok = speculative_accept(key, p, q, drafted)
        assert int(m) == k
        assert 0 <= int(tok) < V
    # disjoint supports: the draft proposes a token the target gives zero
    # mass -> immediate rejection (m == 0) and the residual IS p_t[0]
    p0 = np.zeros(V, np.float32)
    p0[2:] = 1.0 / 3
    pt = jnp.asarray(np.stack([p0] * (k + 1)))
    qd = np.zeros((k, V), np.float32)
    qd[:, 0] = 1.0
    toks = []
    for seed in range(64):
        m, tok = speculative_accept(jax.random.PRNGKey(seed), pt,
                                    jnp.asarray(qd), jnp.zeros(k, jnp.int32))
        assert int(m) == 0
        toks.append(int(tok))
    assert set(toks) <= {2, 3, 4}  # residual support == target support


def test_speculative_accept_exact_marginal():
    """The whole point of the scheme: the FIRST committed token's marginal
    equals the target distribution regardless of the draft, combining the
    accept path (drafted[0] kept) and the reject path (residual resample).
    20k vmapped trials; total-variation tolerance 0.02 (~3 sigma for this
    N and vocab)."""
    import jax

    from distkeras_tpu.models.speculative import speculative_accept

    V, k, N = 7, 3, 20000
    rng = np.random.default_rng(0)
    p_t = jnp.asarray(rng.dirichlet(np.ones(V), size=k + 1).astype(np.float32))
    p_d = jnp.asarray(rng.dirichlet(np.ones(V), size=k).astype(np.float32))

    def trial(key):
        kd, ka = jax.random.split(key)
        drafted = jax.vmap(
            lambda kk, q: jax.random.categorical(kk, jnp.log(q)))(
            jax.random.split(kd, k), p_d).astype(jnp.int32)
        m, tok = speculative_accept(ka, p_t, p_d, drafted)
        return jnp.where(m >= 1, drafted[0], tok)

    firsts = np.asarray(jax.vmap(trial)(jax.random.split(jax.random.PRNGKey(1), N)))
    emp = np.bincount(firsts, minlength=V) / N
    tv = 0.5 * np.abs(emp - np.asarray(p_t[0])).sum()
    assert tv < 0.02, f"TV {tv}: empirical {emp} vs target {np.asarray(p_t[0])}"


def test_sampling_generation_runs_and_is_seeded(target):
    """Speculative sampling end to end: valid tokens, deterministic per
    rng, different across rngs, batched and batch-1."""
    import jax

    draft = Model.init(_spec(layers=1, dim=32), seed=99)
    prompt = jnp.asarray([[5, 17, 3, 9], [1, 2, 3, 4]], jnp.int32)
    fn = make_speculative_generate_fn(target.spec, draft.spec, 10, k=3,
                                      temperature=0.8, with_stats=True)
    out1, it1 = fn(target.params, draft.params, prompt, jax.random.PRNGKey(0))
    out2, _ = fn(target.params, draft.params, prompt, jax.random.PRNGKey(0))
    out3, _ = fn(target.params, draft.params, prompt, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert np.asarray(out1).shape == (2, 10)
    assert int(it1) >= 1
    a = np.asarray(out1)
    assert ((a >= 0) & (a < 47)).all()
    assert not np.array_equal(a, np.asarray(out3))  # rng actually used


def test_guards(target):
    draft = _spec(layers=1)
    with pytest.raises(ValueError, match="vocab mismatch"):
        make_speculative_generate_fn(target.spec, _spec(vocab_size=13), 8)
    with pytest.raises(ValueError, match="k must be"):
        make_speculative_generate_fn(target.spec, draft, 8, k=0)
    fn = make_speculative_generate_fn(target.spec, draft, 8, k=2)
    with pytest.raises(ValueError, match="max_seq_len"):
        fn(target.params, Model.init(draft, seed=1).params,
           jnp.zeros((1, 60), jnp.int32))


def test_fused_draft_steps_match_xla_draft_steps():
    """The fused Pallas draft path must commit exactly the XLA draft
    path's tokens (the target verify window is identical either way, so
    any divergence is a fused-step bug).  Needs a lane-tiled draft —
    model_dim 128 — and runs the kernel through the Pallas interpreter
    on CPU."""
    dspec = _spec(layers=2, dim=128, num_heads=2)
    tspec = _spec(layers=3, dim=128, num_heads=2)
    tgt = Model.init(tspec, seed=1)
    drf = Model.init(dspec, seed=2)
    prompt = jnp.asarray([[3, 14, 1]], jnp.int32)
    want = np.asarray(make_speculative_generate_fn(
        tspec, dspec, 10, k=3, draft_step_impl="xla")(
        tgt.params, drf.params, prompt))
    got = np.asarray(make_speculative_generate_fn(
        tspec, dspec, 10, k=3, draft_step_impl="fused")(
        tgt.params, drf.params, prompt))
    np.testing.assert_array_equal(got, want)


def test_fused_draft_rejects_unsupported_draft_shape(target):
    """dim-48 drafts are not lane-tiled: explicit 'fused' fails loudly,
    auto quietly uses the XLA step."""
    prompt = jnp.asarray([[5, 2]], jnp.int32)
    with pytest.raises(ValueError, match="fused"):
        make_speculative_generate_fn(
            target.spec, target.spec, 6, k=2, draft_step_impl="fused")(
            target.params, target.params, prompt)
    toks = make_speculative_generate_fn(target.spec, target.spec, 6, k=2)(
        target.params, target.params, prompt)
    assert np.asarray(toks).shape == (1, 6)
