"""KV-cache decoding: parity with the training-path forward and the
semantics of generation (greedy, EOS padding, sampling, guards)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.decode import (forward_with_cache, generate,
                                         init_cache, make_generate_fn)
from distkeras_tpu.models.transformer import small_lm_spec


def _spec(**kw):
    # float32 compute so parity tolerances are tight (bf16 would add
    # rounding noise between the einsum and flax Dense formulations)
    cfg = dict(vocab_size=61, model_dim=32, num_heads=2, num_layers=2,
               max_seq_len=32)
    cfg.update(kw)
    spec = small_lm_spec(**cfg)
    spec.config["compute_dtype"] = "float32"
    return spec


@pytest.fixture(scope="module")
def model():
    return Model.init(_spec(), seed=0)


def test_prefill_logits_match_training_forward(model):
    """forward_with_cache at start_pos=0 must reproduce the Flax module's
    logits exactly (same math, different formulation)."""
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 61, (2, 9)))
    want = model.apply(toks)
    cache = init_cache(model.spec.config, 2, 16)
    got, cache2 = forward_with_cache(model.params, model.spec.config, toks, 0, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # the cache rows beyond the prompt stay zero (dead until written)
    assert np.all(np.asarray(cache2.k[:, :, 9:]) == 0)


def test_incremental_decode_matches_full_forward(model):
    """Feeding tokens one at a time through the cache must give the same
    last-position logits as re-running the full prefix each time."""
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, 61, (1, 8)))
    cache = init_cache(model.spec.config, 1, 8)
    logits_p, cache = forward_with_cache(model.params, model.spec.config,
                                         toks[:, :3], 0, cache)
    last = [logits_p[:, -1]]
    for pos in range(3, 8):
        step_logits, cache = forward_with_cache(
            model.params, model.spec.config, toks[:, pos:pos + 1],
            jnp.asarray(pos, jnp.int32), cache)
        last.append(step_logits[:, -1])
    for pos in range(3, 9):
        want = model.apply(toks[:, :pos])[:, -1]
        np.testing.assert_allclose(np.asarray(last[pos - 3]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_greedy_generate_matches_naive_argmax_loop(model):
    """generate(temperature=0) must equal the O(L^2) loop that re-runs the
    module on the growing sequence and argmaxes the last position."""
    prompt = jnp.asarray([[5, 17, 3], [40, 2, 60]], jnp.int32)
    out = generate(model, prompt, max_new_tokens=6)
    assert out.shape == (2, 6)

    seq = prompt
    for _ in range(6):
        nxt = jnp.argmax(model.apply(seq)[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(seq[:, 3:]))


def test_eos_rows_pad_after_stopping(model):
    """Find an EOS id the greedy run actually emits, regenerate with it
    declared: the EOS itself is kept, everything after is pad_id."""
    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    free = np.asarray(generate(model, prompt, max_new_tokens=6))[0]
    eos = int(free[2])  # declare the 3rd emitted token to be EOS
    out = np.asarray(generate(model, prompt, max_new_tokens=6,
                              eos_id=eos, pad_id=0))[0]
    np.testing.assert_array_equal(out[:3], free[:3])
    assert np.all(out[3:] == 0)


def test_sampled_generation_reproducible_and_in_range(model):
    fn = make_generate_fn(model.spec, 5, temperature=0.8, top_k=10)
    rng = jax.random.PRNGKey(7)
    a = fn(model.params, jnp.zeros((3, 4), jnp.int32), rng)
    b = fn(model.params, jnp.zeros((3, 4), jnp.int32), rng)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (3, 5)
    assert np.all((np.asarray(a) >= 0) & (np.asarray(a) < 61))


def test_generate_rejects_overflow_and_sharded_specs(model):
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(model, jnp.zeros((1, 30), jnp.int32), max_new_tokens=10)
    sharded = _spec(seq_axis="sp")
    with pytest.raises(ValueError, match="non-sharded"):
        make_generate_fn(sharded, 4)
    moe = _spec(moe_experts=4)
    with pytest.raises(ValueError, match="MoE"):
        make_generate_fn(moe, 4)


def test_oversized_cache_with_short_sequence_is_fine(model):
    """An explicit cache larger than needed (even than max_seq_len's worth
    of live rows) must not be rejected — dead rows are masked."""
    fn = make_generate_fn(model.spec, 4, cache_len=32)
    out = fn(model.params, jnp.asarray([[5, 17, 3]], jnp.int32))
    want = generate(model, jnp.asarray([[5, 17, 3]], jnp.int32), max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_generate_rejects_undersized_cache(model):
    fn = make_generate_fn(model.spec, 8, cache_len=4)
    with pytest.raises(ValueError, match="cannot hold"):
        fn(model.params, jnp.zeros((1, 3), jnp.int32))


def test_sharded_generate_matches_single_device(model):
    """GSPMD-partitioned decoding ((dp x tp) mesh) must reproduce the
    single-device greedy tokens — the collectives change the schedule,
    not the math (float32 compute keeps argmax ties deterministic)."""
    from distkeras_tpu.models.decode import make_sharded_generate_fn
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    mesh = create_nd_mesh((2, 2), ("dp", "tp"))
    prompt = jnp.asarray([[5, 17, 3], [40, 2, 60]], jnp.int32)
    want = generate(model, prompt, max_new_tokens=6)
    fn = make_sharded_generate_fn(model.spec, mesh, 6, tp_axis="tp", dp_axis="dp")
    got = fn(model.params, prompt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_generate_rejects_indivisible_heads(model):
    from distkeras_tpu.models.decode import make_sharded_generate_fn
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    mesh = create_nd_mesh((8,), ("tp",))  # model has 2 heads
    with pytest.raises(ValueError, match="not divisible"):
        make_sharded_generate_fn(model.spec, mesh, 4)


def test_sharded_generate_rejects_bad_axis_and_spec(model):
    from distkeras_tpu.models.decode import make_sharded_generate_fn
    from distkeras_tpu.models.sequential import dense, sequential_spec
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    mesh = create_nd_mesh((2, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="not a mesh axis"):
        make_sharded_generate_fn(model.spec, mesh, 4, tp_axis="model")
    with pytest.raises(ValueError, match="transformer_lm"):
        make_sharded_generate_fn(sequential_spec([dense(4)], input_shape=(3,)),
                                 mesh, 4)


def test_quantized_tree_decodes_and_matches(model):
    """int8 params decode through the same generate fn; greedy tokens stay
    reasonable (exactly equal on this tiny f32 model whose argmax margins
    dwarf int8 error is too strong a claim — check token validity + high
    agreement instead)."""
    from distkeras_tpu.ops.quantize import quantize_params

    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    full = np.asarray(generate(model, prompt, max_new_tokens=8))
    qp = quantize_params(model.params, min_size=64)
    fn = make_generate_fn(model.spec, 8)
    q = np.asarray(fn(qp, prompt))
    assert q.shape == full.shape
    assert ((q >= 0) & (q < 61)).all()
    # int8 error is tiny on this f32 model: the greedy path must track the
    # full-precision tokens closely, or the scale broadcasting is wrong
    assert (q == full).mean() >= 0.75, f"int8 tokens diverged: {q} vs {full}"
    from distkeras_tpu.models.decode import make_sharded_generate_fn
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    with pytest.raises(ValueError, match="quantized"):
        make_sharded_generate_fn(model.spec, create_nd_mesh((2,), ("tp",)), 4,
                                 tp_axis="tp")(qp, prompt)


# --- fused Pallas decode step (ops/decode_step.py) -------------------------
# CPU runs the kernel through the Pallas interpreter (auto-selected
# off-TPU), so these pin kernel/XLA parity without hardware; keep the
# token counts small — interpreted kernels are slow.


def _fused_spec(**kw):
    cfg = dict(vocab_size=97, model_dim=128, num_heads=2, num_layers=2,
               max_seq_len=64)
    cfg.update(kw)
    return small_lm_spec(**cfg)


@pytest.fixture(scope="module")
def fused_model():
    return Model.init(_fused_spec(), seed=3)


def test_fused_step_greedy_parity(fused_model):
    """The fused block kernel must emit exactly the XLA step's greedy
    tokens — batch 1 (sublane-padded to 8) and batch 3."""
    rng = np.random.default_rng(0)
    for batch in (1, 3):
        prompt = jnp.asarray(rng.integers(0, 97, (batch, 5)), jnp.int32)
        want = np.asarray(make_generate_fn(fused_model.spec, 8, step_impl="xla")(
            fused_model.params, prompt))
        got = np.asarray(make_generate_fn(fused_model.spec, 8, step_impl="fused")(
            fused_model.params, prompt))
        np.testing.assert_array_equal(got, want, err_msg=f"batch={batch}")


def test_fused_step_eos_padding_parity(fused_model):
    """EOS/pad semantics live outside the kernel and must be unaffected:
    pick an eos id the greedy decode actually emits."""
    prompt = jnp.asarray([[11, 60, 2]], jnp.int32)
    plain = np.asarray(make_generate_fn(fused_model.spec, 6, step_impl="xla")(
        fused_model.params, prompt))
    eos = int(plain[0, 1])
    want = np.asarray(make_generate_fn(fused_model.spec, 6, step_impl="xla",
                                       eos_id=eos, pad_id=7)(
        fused_model.params, prompt))
    got = np.asarray(make_generate_fn(fused_model.spec, 6, step_impl="fused",
                                      eos_id=eos, pad_id=7)(
        fused_model.params, prompt))
    np.testing.assert_array_equal(got, want)


def test_fused_step_int8_tree_parity(fused_model):
    """QTensor leaves dequantize inside stack_decode_weights: the fused
    path must match the XLA path run on the SAME quantized tree."""
    from distkeras_tpu.ops.quantize import quantize_params

    qp = quantize_params(fused_model.params, min_size=64)
    prompt = jnp.asarray([[40, 8]], jnp.int32)
    want = np.asarray(make_generate_fn(fused_model.spec, 6, step_impl="xla")(
        qp, prompt))
    got = np.asarray(make_generate_fn(fused_model.spec, 6, step_impl="fused")(
        qp, prompt))
    np.testing.assert_array_equal(got, want)


def test_fused_step_cache_len_rounds_up(fused_model):
    """A cache_len that is not 128-aligned is rounded up inside the fused
    run (the transposed K slab puts sequence on lanes); dead cache rows
    are masked, so different cache sizes must decode identically.
    (Fused-vs-fused on purpose: an xla-vs-fused check here once tripped
    over a genuine 3e-5 logit near-tie on this random bf16 model —
    cross-impl float noise, not round-up mechanics.)"""
    prompt = jnp.asarray([[9, 9, 10]], jnp.int32)
    want = np.asarray(make_generate_fn(fused_model.spec, 5, step_impl="fused",
                                       cache_len=256)(fused_model.params, prompt))
    got = np.asarray(make_generate_fn(fused_model.spec, 5, step_impl="fused",
                                      cache_len=17)(fused_model.params, prompt))
    np.testing.assert_array_equal(got, want)


def test_fused_step_rejects_unsupported_shapes(model):
    """model_dim 32 is not lane-tiled: explicit step_impl='fused' must
    fail loudly, and auto-select must silently use the XLA step."""
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match="fused"):
        make_generate_fn(model.spec, 4, step_impl="fused")(model.params, prompt)
    toks = make_generate_fn(model.spec, 4)(model.params, prompt)  # auto
    assert np.asarray(toks).shape == (1, 4)


# --- nucleus (top-p) sampling ----------------------------------------------


def test_top_p_restricts_support_and_keeps_argmax():
    """Direct _sample checks on a hand-built distribution: the nucleus
    contains exactly the smallest prefix of sorted probs reaching top_p,
    and a tiny top_p degrades to greedy."""
    from distkeras_tpu.models.decode import _sample

    # probs ~ [0.5, 0.25, 0.15, 0.1]: top_p=0.6 keeps {0, 1} (0.5 < 0.6,
    # exclusive-prefix rule), top_p=0.76 keeps {0, 1, 2}
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.1]], jnp.float32))
    seen = {int(_sample(logits, jax.random.PRNGKey(s), 1.0, 0, 0.6)[0])
            for s in range(200)}
    assert seen == {0, 1}, seen
    seen = {int(_sample(logits, jax.random.PRNGKey(s), 1.0, 0, 0.76)[0])
            for s in range(400)}
    assert seen == {0, 1, 2}, seen
    # nucleus always contains the argmax: top_p -> 0 is greedy
    assert all(int(_sample(logits, jax.random.PRNGKey(s), 1.0, 0, 1e-6)[0]) == 0
               for s in range(20))
    # ties at the nucleus boundary must NOT re-admit every tied token (a
    # probability-threshold cut would keep all 4): uniform probs with
    # top_p=0.3 keep exactly the 2-token prefix whose mass reaches 0.3
    tied = jnp.zeros((1, 4), jnp.float32)
    seen = {int(_sample(tied, jax.random.PRNGKey(s), 1.0, 0, 0.3)[0])
            for s in range(100)}
    assert len(seen) == 2, seen


def test_top_p_out_of_range_rejected(model):
    """A negative top_p would pass the `top_p and top_p < 1.0` gate, mask
    every token (including the argmax) to -inf, and categorical over an
    all--inf row silently emits token 0 — so the builder must reject it
    loudly, like the speculative path's temperature guard."""
    for bad in (-0.1, 1.5, float("nan")):
        with pytest.raises(ValueError, match="top_p"):
            make_generate_fn(model.spec, 4, temperature=1.0, top_p=bad)
    with pytest.raises(ValueError, match="temperature"):
        make_generate_fn(model.spec, 4, temperature=-1.0)
    for bad_k in (-1, 10_000):
        with pytest.raises(ValueError, match="top_k"):
            make_generate_fn(model.spec, 4, temperature=1.0, top_k=bad_k)


def test_undersized_cache_len_rejected_on_both_impls(fused_model):
    """cache_len=100 for prompt 90 + 20 new tokens must raise on BOTH
    step impls: the fused path's lane round-up (100 -> 128) must not
    rescue a capacity the user explicitly undersized (the same call
    erroring or not depending on auto impl selection)."""
    prompt = jnp.zeros((1, 90), jnp.int32)
    for impl in ("xla", "fused"):
        with pytest.raises(ValueError, match="cannot hold"):
            make_generate_fn(fused_model.spec, 20, cache_len=100,
                             step_impl=impl)(fused_model.params, prompt)


def test_generate_with_top_p_reproducible_and_in_range(model):
    toks1 = generate(model, jnp.asarray([[3, 7]], jnp.int32), 8,
                     temperature=0.8, top_p=0.9, seed=5)
    toks2 = generate(model, jnp.asarray([[3, 7]], jnp.int32), 8,
                     temperature=0.8, top_p=0.9, seed=5)
    np.testing.assert_array_equal(np.asarray(toks1), np.asarray(toks2))
    a = np.asarray(toks1)
    assert a.shape == (1, 8) and ((a >= 0) & (a < 61)).all()


# --- int8-quantized KV cache (QKVCache) ------------------------------------


def test_quantized_cache_matches_dequantized_oracle(model, monkeypatch):
    """The int8-cache forward must equal the SAME math over the
    rounded-then-dequantized values.  Oracle = the production code path
    itself, with _quantize_rows faked to store the dequantized f32
    values at scale 1 (int8 in [-127, 127] converts to bf16/f32
    exactly, so the two runs differ only in where the scale multiply
    happens — an exact-to-float-noise identity if the plumbing is
    right)."""
    import distkeras_tpu.models.decode as dec
    from distkeras_tpu.models.decode import QKVCache

    cfg = model.spec.config
    toks = jnp.asarray(np.random.default_rng(3).integers(0, 61, (2, 6)))
    cache_q = init_cache(cfg, 2, 16, quantized=True)
    logits_q, cache_q = forward_with_cache(model.params, cfg, toks, 0, cache_q)
    step_q, _ = forward_with_cache(model.params, cfg,
                                   jnp.asarray([[7], [9]], jnp.int32),
                                   jnp.asarray(6, jnp.int32), cache_q)

    real = dec._quantize_rows

    def fake(x):
        q, s = real(x)
        return q.astype(jnp.float32) * s, jnp.ones_like(s)

    monkeypatch.setattr(dec, "_quantize_rows", fake)
    shape = cache_q.k.shape
    oracle = QKVCache(jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
                      jnp.ones(shape[:-1] + (1,), jnp.float32),
                      jnp.ones(shape[:-1] + (1,), jnp.float32))
    logits_o, oracle = forward_with_cache(model.params, cfg, toks, 0, oracle)
    step_o, _ = forward_with_cache(model.params, cfg,
                                   jnp.asarray([[7], [9]], jnp.int32),
                                   jnp.asarray(6, jnp.int32), oracle)
    np.testing.assert_allclose(np.asarray(logits_q), np.asarray(logits_o),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(step_q), np.asarray(step_o),
                               rtol=1e-4, atol=1e-4)


def test_quantized_cache_generation_runs_and_tracks_plain(model):
    """End-to-end generate with quantize_cache: valid tokens, and on
    this tiny f32 model the per-row rounding (<0.8% relative) keeps
    greedy tokens mostly equal to the full-precision decode."""
    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    plain = np.asarray(make_generate_fn(model.spec, 8)(model.params, prompt))
    quant = np.asarray(make_generate_fn(model.spec, 8, quantize_cache=True)(
        model.params, prompt))
    assert quant.shape == plain.shape
    assert ((quant >= 0) & (quant < 61)).all()
    assert (quant == plain).mean() >= 0.5, f"{quant} vs {plain}"


def test_quantized_cache_rejects_fused_step(fused_model):
    with pytest.raises(ValueError, match="quantize_cache"):
        make_generate_fn(fused_model.spec, 4, quantize_cache=True,
                         step_impl="fused")


def test_quantized_cache_forces_xla_step_on_tpu_auto(fused_model, monkeypatch):
    """With quantize_cache the auto step selection must resolve to the
    XLA step even where fused_step_auto would fire (TPU, batch 1, small
    model) — the fused kernel's bf16 slabs would silently drop the int8
    scales.  Faking a TPU backend on CPU makes the bug observable: the
    buggy path tries to Mosaic-compile the fused kernel and fails, the
    fixed path decodes through XLA."""
    import distkeras_tpu.ops.decode_step as ds

    monkeypatch.setattr(ds.jax, "default_backend", lambda: "tpu")
    toks = make_generate_fn(fused_model.spec, 5, quantize_cache=True)(
        fused_model.params, jnp.asarray([[8, 2]], jnp.int32))
    assert np.asarray(toks).shape == (1, 5)
