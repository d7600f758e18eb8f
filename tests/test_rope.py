"""Rotary position embeddings (ops/rotary.py + positional="rope").

Pins: the rotation's defining algebraic properties, the no-table param
tree, cached decode == the training forward's argmax (the decode-path
identity), sequence-parallel global positions, and composition with GQA
and the pipeline schedules.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model
from distkeras_tpu.models.decode import generate, make_generate_fn
from distkeras_tpu.models.transformer import small_lm_spec
from distkeras_tpu.ops.rotary import rope_rotate

VOCAB, D, H, LAYERS = 61, 32, 2, 2


def _rope_spec(**kw):
    cfg = dict(vocab_size=VOCAB, model_dim=D, num_heads=H, num_layers=LAYERS,
               max_seq_len=48, positional="rope")
    cfg.update(kw)
    spec = small_lm_spec(**cfg)
    spec.config["compute_dtype"] = "float32"
    return spec


def test_rotation_properties():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 8, 3, 16)), jnp.float32)
    # position 0 is the identity
    np.testing.assert_allclose(np.asarray(rope_rotate(x, jnp.zeros(8, jnp.int32))),
                               np.asarray(x), rtol=1e-6)
    # rotations preserve vector norms
    pos = jnp.asarray([0, 3, 7, 11, 100, 1000, 5000, 9999], jnp.int32)
    r = rope_rotate(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)
    # the score depends only on the RELATIVE offset: <R(p)q, R(p+d)k> is
    # invariant to shifting both positions
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)

    def score(pq, pk):
        rq = rope_rotate(q, jnp.asarray([pq], jnp.int32))
        rk = rope_rotate(k, jnp.asarray([pk], jnp.int32))
        return float(jnp.sum(rq * rk))

    assert score(3, 10) == pytest.approx(score(20, 27), rel=1e-4)
    assert score(0, 5) == pytest.approx(score(95, 100), rel=1e-4)
    # and genuinely DEPENDS on the offset
    assert abs(score(3, 10) - score(3, 4)) > 1e-4
    with pytest.raises(ValueError, match="even"):
        rope_rotate(x[..., :15], pos)


@pytest.mark.slow  # 30-36 s alone: thirty un-jitted Adam steps
def test_rope_tree_has_no_table_and_model_learns():
    model = Model.init(_rope_spec(), seed=0)
    assert "pos_embed" not in model.params
    import optax
    from distkeras_tpu.ops.losses import lm_token_cross_entropy
    from distkeras_tpu.parallel.lm import shift_targets

    module = model.spec.build()
    toks = np.random.default_rng(1).integers(0, VOCAB, (4, 16)).astype(np.int32)
    tgts = jnp.asarray(shift_targets(toks))
    toks = jnp.asarray(toks)
    opt = optax.adam(1e-2)

    def loss_fn(p):
        return lm_token_cross_entropy(module, p, toks, tgts)[:, :-1].mean()

    params = jax.tree.map(jnp.asarray, model.params)
    state = opt.init(params)
    losses = []
    for _ in range(30):
        l, g = jax.value_and_grad(loss_fn)(params)
        up, state = opt.update(g, state, params)
        params = jax.tree.map(lambda a, b: a + b, params, up)
        losses.append(float(l))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.7


def test_cached_decode_matches_training_forward():
    """The decode-path identity: greedy generation through the KV cache
    (rotated-K rows) equals stepwise argmax of the TRAINING forward over
    the growing sequence — position math must agree exactly."""
    model = Model.init(_rope_spec(), seed=3)
    prompt = np.asarray([[5, 17, 3], [40, 2, 21]], np.int32)
    got = np.asarray(generate(model, jnp.asarray(prompt), max_new_tokens=8))
    seq = prompt.copy()
    for _ in range(8):
        logits = np.asarray(model.apply(jnp.asarray(seq)))
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, seq[:, prompt.shape[1]:])


def test_rope_quantized_cache_and_gqa_decode():
    """RoPE composes with the int8 cache (rows quantized AFTER rotation)
    and with GQA (rotation is head-count agnostic)."""
    spec = _rope_spec(num_kv_heads=1, num_heads=2)
    model = Model.init(spec, seed=4)
    prompt = jnp.asarray([[9, 9, 10]], jnp.int32)
    plain = np.asarray(make_generate_fn(spec, 8)(model.params, prompt))
    quant = np.asarray(make_generate_fn(spec, 8, quantize_cache=True)(
        model.params, prompt))
    # int8 KV is an approximation; on this tiny model greedy argmaxes agree
    np.testing.assert_array_equal(plain, quant)
    # and the cache really is Hkv-headed
    from distkeras_tpu.models.decode import init_cache
    assert init_cache(dict(spec.config), 1, 16).k.shape[3] == 1


def test_rope_under_sequence_parallelism_matches_single_device():
    """Global positions under sp: the sharded loss equals the unsharded
    loss — each shard rotates by rank * L_local + local index."""
    import optax
    from distkeras_tpu.ops.losses import lm_token_cross_entropy
    from distkeras_tpu.parallel.lm import (lm_data_shardings, lm_state_shardings,
                                           make_lm_train_step, shift_targets)
    from distkeras_tpu.parallel.mesh import create_nd_mesh

    mesh = create_nd_mesh((2, 2), ("dp", "sp"))
    spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                         num_layers=2, max_seq_len=16, positional="rope",
                         seq_axis="sp")
    spec.config["compute_dtype"] = "float32"
    model = Model.init(spec, seed=1)
    toks = np.random.default_rng(2).integers(0, VOCAB, (4, 16)).astype(np.int32)
    tgts = shift_targets(toks)

    # unsharded reference loss over the SAME batch
    ref_spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                             num_layers=2, max_seq_len=16, positional="rope")
    ref_spec.config["compute_dtype"] = "float32"
    module = ref_spec.build()
    ref = float(lm_token_cross_entropy(module, model.params, jnp.asarray(toks),
                                       jnp.asarray(tgts))[:, :-1].mean())

    opt = optax.sgd(0.0)  # lr 0: read the loss without moving params
    step = make_lm_train_step(spec, opt, mesh, sp_axis="sp")
    psh, osh = lm_state_shardings(mesh, opt, model.params)
    params = jax.device_put(jax.tree.map(jnp.asarray, model.params), psh)
    opt_state = jax.device_put(opt.init(params), osh)
    dsh = lm_data_shardings(mesh, sp_axis="sp")
    _, _, loss = step(params, opt_state, jax.device_put(toks, dsh),
                      jax.device_put(tgts, dsh))
    assert float(loss) == pytest.approx(ref, rel=1e-5)


def test_rope_with_pipeline_schedules():
    """RoPE (and GQA) through both pipeline schedules: the blocks rotate
    from position 0 per microbatch, matching the single-device step."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.parallel.lm import shift_targets
    from distkeras_tpu.parallel.mesh import create_nd_mesh
    from distkeras_tpu.parallel.pipeline import (
        make_pp_train_step, pp_state_shardings, split_block_params)

    mesh = create_nd_mesh((2, 2), ("dp", "pp"))
    spec = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=2,
                         num_kv_heads=1, num_layers=2, max_seq_len=16,
                         positional="rope")
    spec.config["compute_dtype"] = "float32"
    model = Model.init(spec, seed=0)
    opt = optax.sgd(0.1)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, VOCAB, size=(8, 16)).astype(np.int32)
    targets = shift_targets(tokens)

    module = spec.build()

    def loss_fn(params, tok, tgt):
        import optax as _o
        logits = module.apply({"params": params}, tok)
        ce = _o.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), tgt)
        return ce[:, :-1].mean()

    loss_ref = float(loss_fn(model.params, jnp.asarray(tokens),
                             jnp.asarray(targets)))

    dsh = NamedSharding(mesh, P("dp"))
    for schedule in ("gpipe", "1f1b"):
        outer, blocks = split_block_params(
            jax.tree.map(jnp.array, model.params))
        step = make_pp_train_step(spec, opt, mesh, num_microbatches=2,
                                  schedule=schedule)
        psh, osh = pp_state_shardings(mesh, opt, outer, blocks)
        params = jax.device_put((outer, blocks), psh)
        opt_state = jax.device_put(opt.init((outer, blocks)), osh)
        _, _, loss = step(params, opt_state, jax.device_put(tokens, dsh),
                          jax.device_put(targets, dsh))
        assert float(loss) == pytest.approx(loss_ref, rel=1e-4), schedule


def test_rope_generates_past_max_seq_len():
    """No positional table => max_seq_len is NOT a generation bound for
    rope models (only the cache size is): generating past it works, and
    the decode prefix is unchanged by the longer run.  A learned-table
    model with the same shape still refuses."""
    spec = _rope_spec(max_seq_len=16)
    model = Model.init(spec, seed=5)
    prompt = jnp.asarray([[5, 17, 3]], jnp.int32)
    long = np.asarray(make_generate_fn(spec, 24)(model.params, prompt))
    short = np.asarray(make_generate_fn(spec, 8)(model.params, prompt))
    assert long.shape == (1, 24)
    np.testing.assert_array_equal(long[:, :8], short)

    learned = small_lm_spec(vocab_size=VOCAB, model_dim=D, num_heads=H,
                            num_layers=LAYERS, max_seq_len=16)
    lmodel = Model.init(learned, seed=5)
    with pytest.raises(ValueError, match="positional table"):
        make_generate_fn(learned, 24)(lmodel.params, prompt)


def test_fused_step_refuses_rope():
    from distkeras_tpu.ops.decode_step import fused_step_supported, resolve_step_impl

    spec = _rope_spec(model_dim=128, num_heads=1)
    cfg = dict(spec.config)
    assert not fused_step_supported(cfg, 1, 256)
    assert resolve_step_impl(cfg, 1, 256, None) == "xla"


# -- PR 34: the adjacent-pair convention (``interleaved=True``) ----------------

def test_adjacent_pairs_rotate_by_an_explicit_two_by_two_rotation():
    """Channels (2i, 2i+1) of a head turn together by pos * base^(-2i/D)."""
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 5, 3, 8))
    pos, base = np.asarray([0, 1, 7, 100, 5000]), 1e6
    want = np.empty_like(x)
    for t, p in enumerate(pos):
        for i in range(4):
            a = p * base ** (-2.0 * i / 8)
            rot = np.array([[np.cos(a), -np.sin(a)], [np.sin(a), np.cos(a)]])
            want[:, t, :, 2 * i:2 * i + 2] = x[:, t, :, 2 * i:2 * i + 2] @ rot.T
    got = rope_rotate(jnp.asarray(x, jnp.float32), jnp.asarray(pos), base=base, interleaved=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # one shared key a token (a single head) turns like any head's
    one = rope_rotate(jnp.asarray(x[:, :, :1], jnp.float32), jnp.asarray(pos), base=base,
                      interleaved=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got[:, :, :1]))


@pytest.mark.parametrize("dim", [8, 64])
def test_adjacent_pairs_are_split_half_under_the_channel_permutation(dim):
    """Reordering channels (0, 2, 4, ..., 1, 3, 5, ...) and rotating halves is
    the adjacent-pair rotation reordered the same way (what the published
    DeepSeek-V3 code does), so scores are the same in both conventions."""
    rng = np.random.default_rng(dim)
    q, k = (jnp.asarray(rng.normal(size=(1, 6, 2, dim)), jnp.float32) for _ in range(2))
    pos = jnp.asarray([0, 2, 3, 50, 999, 8191])
    perm = np.concatenate([np.arange(0, dim, 2), np.arange(1, dim, 2)])
    pairs = lambda t: rope_rotate(t, pos, base=1e6, interleaved=True)
    halves = lambda t: rope_rotate(t[..., perm], pos, base=1e6)
    np.testing.assert_allclose(np.asarray(pairs(q)[..., perm]), np.asarray(halves(q)), atol=1e-6)
    score = lambda a, b: np.asarray(jnp.einsum("bqhd,bkhd->bhqk", a, b))
    np.testing.assert_allclose(score(pairs(q), pairs(k)), score(halves(q), halves(k)), atol=1e-4)
    # the default is today's split-half, bit for bit
    np.testing.assert_array_equal(np.asarray(rope_rotate(q, pos)),
                                  np.asarray(rope_rotate(q, pos, interleaved=False)))
    assert np.abs(np.asarray(pairs(q) - rope_rotate(q, pos, base=1e6))).max() > 1e-2
