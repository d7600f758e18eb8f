"""Grouped-query attention (GQA, Ainslie et al. 2023).

The oracle: a GQA model is EXACTLY an MHA model whose K/V projection
weights repeat each KV head across its query group — so every GQA test
compares against an MHA twin built by weight repetition, in float32 for
exact equality.  The feature's point (the KV cache shrinking to
num_kv_heads) is asserted directly on cache shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.decode import generate, init_cache, make_generate_fn
from distkeras_tpu.models.transformer import small_lm_spec

H, HKV, D, LAYERS, VOCAB = 4, 2, 32, 2, 61


def _gqa_spec(**kw):
    cfg = dict(vocab_size=VOCAB, model_dim=D, num_heads=H, num_kv_heads=HKV,
               num_layers=LAYERS, max_seq_len=48)
    cfg.update(kw)
    spec = small_lm_spec(**cfg)
    spec.config["compute_dtype"] = "float32"  # exact-oracle tolerances
    return spec


def _mha_twin(gqa_model):
    """MHA model whose fused qkv weights replicate the GQA weights: the
    q slice is the GQA q kernel; the k/v slices repeat each KV head over
    its group.  Forward math is then IDENTICAL to grouped attention."""
    spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                         num_layers=LAYERS, max_seq_len=48)
    spec.config["compute_dtype"] = "float32"
    twin = Model.init(spec, seed=0)
    g = H // HKV
    params = jax.tree.map(np.asarray, twin.params)
    for i in range(LAYERS):
        blk = dict(gqa_model.params[f"block_{i}"])
        qk = np.asarray(blk["q"]["kernel"])          # [E, H, Dh]
        kvk = np.asarray(blk["kv"]["kernel"])        # [E, 2, HKV, Dh]
        fused = np.stack([qk,
                          np.repeat(kvk[:, 0], g, axis=1),
                          np.repeat(kvk[:, 1], g, axis=1)], axis=1)  # [E, 3, H, Dh]
        tb = dict(params[f"block_{i}"])
        tb.pop("qkv")
        tb["qkv"] = {"kernel": fused}
        for name in ("LayerNorm_0", "LayerNorm_1", "proj", "up", "down"):
            tb[name] = jax.tree.map(np.asarray, blk[name])
        params[f"block_{i}"] = tb
    for name in ("embed", "pos_embed", "final_norm"):
        params[name] = jax.tree.map(np.asarray, gqa_model.params[name])
    return Model(spec=spec, params=jax.tree.map(jnp.asarray, params))


@pytest.fixture(scope="module")
def gqa_model():
    return Model.init(_gqa_spec(), seed=3)


def test_param_layout_and_cache_shrink(gqa_model):
    blk = gqa_model.params["block_0"]
    assert "qkv" not in blk and blk["q"]["kernel"].shape == (D, H, D // H)
    assert blk["kv"]["kernel"].shape == (D, 2, HKV, D // H)
    cache = init_cache(dict(gqa_model.spec.config), batch=2, cache_len=32)
    assert cache.k.shape == (LAYERS, 2, 32, HKV, D // H)  # HKV heads, not H
    qcache = init_cache(dict(gqa_model.spec.config), batch=2, cache_len=32,
                        quantized=True)
    assert qcache.k.shape == (LAYERS, 2, 32, HKV, D // H)


def test_forward_matches_mha_twin(gqa_model):
    """Grouped attention == full attention over group-repeated KV weights
    (exact in f32): the one identity that pins the whole feature."""
    twin = _mha_twin(gqa_model)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, VOCAB, (2, 16)),
                       jnp.int32)
    np.testing.assert_allclose(np.asarray(gqa_model.apply(toks)),
                               np.asarray(twin.apply(toks)),
                               rtol=1e-5, atol=1e-5)


def test_decode_matches_mha_twin_and_full_forward(gqa_model):
    """The Hkv-headed cache decode commits the same greedy tokens as the
    MHA twin's full-headed cache decode — and the cache path agrees with
    the no-cache forward (the standard decode-correctness pin)."""
    twin = _mha_twin(gqa_model)
    prompt = jnp.asarray([[5, 17, 3], [40, 2, 21]], jnp.int32)
    got = np.asarray(generate(gqa_model, prompt, max_new_tokens=10))
    want = np.asarray(generate(twin, prompt, max_new_tokens=10))
    np.testing.assert_array_equal(got, want)


def test_quantized_cache_gqa(gqa_model):
    """int8 QKVCache under GQA: per-(position, head) scales quantize the
    same values as the twin's repeated heads, so tokens still match."""
    twin = _mha_twin(gqa_model)
    prompt = jnp.asarray([[9, 9, 10]], jnp.int32)
    got = np.asarray(make_generate_fn(gqa_model.spec, 8, quantize_cache=True)(
        gqa_model.params, prompt))
    want = np.asarray(make_generate_fn(twin.spec, 8, quantize_cache=True)(
        twin.params, prompt))
    np.testing.assert_array_equal(got, want)


def test_quantized_cache_gqa_warns_net_loss(gqa_model):
    """int8 KV x GQA (a measured net loss in a July 2026 record, see
    ``warn_quantized_cache_gqa``) composes silently in config — every
    decode builder must emit the documented warning, and must NOT emit it
    for int8-on-MHA or GQA-without-int8 (issue 2 satellite)."""
    import warnings as _warnings

    from distkeras_tpu.models.speculative import make_speculative_generate_fn

    with pytest.warns(UserWarning, match="measured net loss"):
        make_generate_fn(gqa_model.spec, 4, quantize_cache=True)
    # speculative builder routes through the same guard (GQA target)
    draft = Model.init(small_lm_spec(vocab_size=VOCAB, model_dim=D,
                                     num_heads=2, num_layers=1,
                                     max_seq_len=48), seed=9)
    with pytest.warns(UserWarning, match="measured net loss"):
        make_speculative_generate_fn(gqa_model.spec, draft.spec, 4, k=2,
                                     quantize_cache=True)
    # no warning when the trap is absent: MHA + int8, and GQA without int8
    mha = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                        num_layers=LAYERS, max_seq_len=48)
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        make_generate_fn(mha, 4, quantize_cache=True)
        make_generate_fn(gqa_model.spec, 4)


def test_beam_and_speculative_match_mha_twin(gqa_model):
    """The rest of the serving family rides the same cache math: beam
    search scores and speculative commits equal the MHA twin's."""
    from distkeras_tpu.models.beam import make_beam_search_fn
    from distkeras_tpu.models.speculative import make_speculative_generate_fn

    twin = _mha_twin(gqa_model)
    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    g_toks, g_scores = make_beam_search_fn(gqa_model.spec, 6, beam_width=3)(
        gqa_model.params, prompt)
    t_toks, t_scores = make_beam_search_fn(twin.spec, 6, beam_width=3)(
        twin.params, prompt)
    np.testing.assert_array_equal(np.asarray(g_toks), np.asarray(t_toks))
    np.testing.assert_allclose(np.asarray(g_scores), np.asarray(t_scores),
                               rtol=1e-5, atol=1e-5)
    # GQA target with an MHA draft: the committed-token contract holds
    draft = Model.init(small_lm_spec(vocab_size=VOCAB, model_dim=D,
                                     num_heads=2, num_layers=1,
                                     max_seq_len=48), seed=9)
    sfn = make_speculative_generate_fn(gqa_model.spec, draft.spec, 8, k=3)
    got = np.asarray(sfn(gqa_model.params, draft.params, prompt))
    want = np.asarray(generate(gqa_model, prompt, max_new_tokens=8))
    np.testing.assert_array_equal(got, want)


def test_gqa_under_sequence_parallelism():
    """Ring attention with grouped KV: the ICI ring carries Hkv-headed
    blocks; output equals the unsharded forward."""
    from distkeras_tpu.parallel.lm import (lm_data_shardings, lm_state_shardings,
                                           make_lm_train_step, shift_targets)
    from distkeras_tpu.parallel.mesh import create_nd_mesh
    import optax

    mesh = create_nd_mesh((2, 2), ("dp", "sp"))
    spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                         num_kv_heads=HKV, num_layers=2, max_seq_len=16,
                         seq_axis="sp")
    model = Model.init(spec, seed=1)
    opt = optax.sgd(0.05)
    step = make_lm_train_step(spec, opt, mesh, sp_axis="sp")
    psh, osh = lm_state_shardings(mesh, opt, model.params)
    params = jax.device_put(jax.tree.map(jnp.asarray, model.params), psh)
    opt_state = jax.device_put(opt.init(params), osh)
    toks = np.random.default_rng(2).integers(0, VOCAB, (4, 16)).astype(np.int32)
    tgts = shift_targets(toks)
    dsh = lm_data_shardings(mesh, sp_axis="sp")
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state,
                                       jax.device_put(toks, dsh),
                                       jax.device_put(tgts, dsh))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_gqa_with_tensor_parallelism():
    """tp=2 shards H=4 query heads and HKV=2 kv heads; the step runs and
    learns.  An indivisible kv count fails loudly at module level."""
    from distkeras_tpu.parallel.lm import (lm_data_shardings, lm_state_shardings,
                                           make_lm_train_step, shift_targets)
    from distkeras_tpu.parallel.mesh import create_nd_mesh
    import optax

    mesh = create_nd_mesh((2, 2), ("dp", "tp"))
    spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                         num_kv_heads=HKV, num_layers=2, max_seq_len=16,
                         tp_axis="tp")
    model = Model.init(spec, seed=1)
    opt = optax.sgd(0.05)
    step = make_lm_train_step(spec, opt, mesh, sp_axis=None, tp_axis="tp")
    psh, osh = lm_state_shardings(mesh, opt, model.params, tp_axis="tp")
    params = jax.device_put(jax.tree.map(jnp.asarray, model.params), psh)
    opt_state = jax.device_put(opt.init(params), osh)
    # kv slabs really are distributed over tp
    kvk = params["block_0"]["kv"]["kernel"]
    assert kvk.addressable_shards[0].data.shape[2] == HKV // 2
    toks = np.random.default_rng(2).integers(0, VOCAB, (4, 16)).astype(np.int32)
    tgts = shift_targets(toks)
    dsh = lm_data_shardings(mesh)
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state,
                                       jax.device_put(toks, dsh),
                                       jax.device_put(tgts, dsh))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]

    bad = small_lm_spec(vocab_size=VOCAB, model_dim=64, num_heads=4,
                        num_kv_heads=1, num_layers=1, max_seq_len=16,
                        tp_axis="tp")
    from distkeras_tpu.models.base import build_module
    module = build_module(bad.name, dict(bad.config, tp_size=2))
    with pytest.raises(ValueError, match="num_kv_heads"):
        module.init(jax.random.PRNGKey(0),
                    jnp.zeros((1, 16), jnp.int32))


def test_fused_step_refuses_gqa():
    """The fused Pallas decode kernel is MHA-only (v1): auto-select must
    fall back to the XLA step, explicit 'fused' must fail loudly."""
    from distkeras_tpu.ops.decode_step import fused_step_supported, resolve_step_impl

    spec = _gqa_spec(model_dim=128, num_heads=2, num_kv_heads=1)
    cfg = dict(spec.config)
    assert not fused_step_supported(cfg, 1, 256)
    assert resolve_step_impl(cfg, 1, 256, None) == "xla"
