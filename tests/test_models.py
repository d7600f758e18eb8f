"""Model-zoo smoke tests: build, forward-shape, registry round-trip."""

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.models.cnn import mnist_cnn_spec
from distkeras_tpu.models.mlp import mnist_mlp_spec
from distkeras_tpu.models.resnet import resnet20_spec
from distkeras_tpu.models.transformer import small_lm_spec


@pytest.mark.parametrize("spec_fn,batch_shape,out_shape", [
    (mnist_mlp_spec, (2, 784), (2, 10)),
    (mnist_cnn_spec, (2, 28, 28, 1), (2, 10)),
])
def test_forward_shapes(spec_fn, batch_shape, out_shape):
    model = Model.init(spec_fn(), seed=0)
    x = np.zeros(batch_shape, dtype=np.float32)
    assert model.apply(x).shape == out_shape


def test_resnet20_forward():
    model = Model.init(resnet20_spec(num_outputs=100), seed=0)
    x = np.zeros((2, 32, 32, 3), dtype=np.float32)
    assert model.apply(x).shape == (2, 100)


def test_transformer_forward():
    spec = small_lm_spec(vocab_size=64, model_dim=32, num_heads=2, num_layers=2, max_seq_len=16)
    model = Model.init(spec, seed=0)
    tokens = np.zeros((2, 16), dtype=np.int32)
    logits = model.apply(tokens)
    assert logits.shape == (2, 16, 64)


def test_unknown_architecture_raises():
    with pytest.raises(ValueError, match="unknown architecture"):
        ModelSpec(name="nope", config={}, input_shape=(4,)).build()


def test_spec_dict_roundtrip():
    spec = mnist_cnn_spec()
    assert ModelSpec.from_dict(spec.to_dict()) == spec


def test_transformer_remat_matches_non_remat():
    """remat=True must be a pure memory trade: identical loss and grads."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec

    base = small_lm_spec(vocab_size=64, model_dim=32, num_heads=2,
                         num_layers=2, max_seq_len=16)
    rem = small_lm_spec(vocab_size=64, model_dim=32, num_heads=2,
                        num_layers=2, max_seq_len=16, remat=True)
    m = Model.init(base, seed=0)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)), jnp.int32)
    tgt = jnp.roll(toks, -1, axis=1)

    def loss_for(spec):
        apply = spec.apply_fn()

        def f(p):
            logits = apply(p, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tgt).mean()

        return f

    l0, g0 = jax.value_and_grad(loss_for(base))(m.params)
    l1, g1 = jax.value_and_grad(loss_for(rem))(m.params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_transformer_attn_impl_parity():
    """``attn_impl`` pins the attention kernel without changing semantics:
    flash (interpret mode on CPU) and dense produce the same logits and
    grads, and the auto default equals dense on short CPU shapes.  Lengths
    are flash-legal (L=128 spans the whole sequence as one Mosaic block)."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.transformer import small_lm_spec

    kw = dict(vocab_size=64, model_dim=32, num_heads=2, num_layers=2,
              max_seq_len=128)
    dense = small_lm_spec(attn_impl="dense", **kw)
    flash = small_lm_spec(attn_impl="flash", **kw)
    auto = small_lm_spec(**kw)
    m = Model.init(dense, seed=0)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 128)), jnp.int32)
    tgt = jnp.roll(toks, -1, axis=1)

    def loss_for(spec):
        apply = spec.apply_fn()

        def f(p):
            logits = apply(p, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tgt).mean()

        return f

    l_dense, g_dense = jax.value_and_grad(loss_for(dense))(m.params)
    l_flash, g_flash = jax.value_and_grad(loss_for(flash))(m.params)
    l_auto = loss_for(auto)(m.params)
    # flash keeps bf16 matmuls + f32 stats vs dense's f32 softmax: a few
    # 1e-5 of relative loss drift is the expected bf16 rounding, not skew
    np.testing.assert_allclose(float(l_dense), float(l_flash), rtol=2e-4)
    np.testing.assert_allclose(float(l_dense), float(l_auto), rtol=1e-7)
    # loose bound: bf16 kernel rounding puts ~1-2% noise on small grad
    # elements; kernel-grad EXACTNESS is tests/test_flash_attention.py's
    # job — this asserts the plumbing reached a working kernel (wrong
    # math would be O(1) off)
    for a, b in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g_flash)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=2e-3)


def test_model_summary():
    import jax

    from distkeras_tpu.models.transformer import small_lm_spec

    m = Model.init(small_lm_spec(vocab_size=64, model_dim=32, num_heads=2,
                                 num_layers=2, max_seq_len=16), seed=0)
    s = m.summary()
    assert "block_0" in s and "embed" in s and "total:" in s
    want = sum(int(l.size) for l in jax.tree.leaves(m.params))
    assert f"{want:,} params" in s


@pytest.mark.slow  # 64-73 s alone: four classic families, each compiled in two dtypes
def test_compute_dtype_policy_parity_classic_family():
    """bf16-compute CNN/MLP/ResNet: identical float32 param trees (the
    policy touches activations only), logits within bf16 rounding of the
    f32 forward, and one SGD train step's loss within tolerance — the LM
    stack's mixed-precision scheme extended to the parity family."""
    import jax
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.models.base import Model
    from distkeras_tpu.models.cnn import mnist_cnn_spec
    from distkeras_tpu.models.mlp import mnist_mlp_spec
    from distkeras_tpu.models.resnet import resnet20_spec
    from distkeras_tpu.ops.losses import get_loss

    rng = np.random.default_rng(0)
    cases = [
        (mnist_cnn_spec, (8, 28, 28, 1), 10),
        (mnist_mlp_spec, (8, 784), 10),
        (resnet20_spec, (4, 32, 32, 3), 100),
    ]
    loss_fn = get_loss("categorical_crossentropy")
    for make_spec, shape, classes in cases:
        x = jnp.asarray(rng.normal(size=shape), jnp.float32)
        y = jnp.asarray(np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, size=shape[0])])
        f32 = Model.init(make_spec(), seed=0)
        bf16 = Model.init(make_spec(compute_dtype="bfloat16"), seed=0)
        # params are float32 and IDENTICAL under both policies
        for a, b in zip(jax.tree.leaves(f32.params), jax.tree.leaves(bf16.params)):
            assert a.dtype == np.float32 and b.dtype == np.float32
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        lf = np.asarray(f32.apply(x), np.float32)
        raw = np.asarray(bf16.apply(x))
        assert raw.dtype == np.float32  # head emits f32 logits (pre-cast!)
        lb = raw
        scale = max(1.0, float(np.abs(lf).max()))
        np.testing.assert_allclose(lb / scale, lf / scale, atol=3e-2,
                                   err_msg=make_spec.__name__)

        def step_loss(model):
            apply = model.spec.apply_fn()
            opt = optax.sgd(0.05)

            def obj(p):
                return loss_fn(apply(p, x), y)

            l0, g = jax.value_and_grad(obj)(model.params)
            p1 = optax.apply_updates(model.params, opt.update(g, opt.init(model.params))[0])
            return float(l0), float(obj(p1))

        (l0f, l1f), (l0b, l1b) = step_loss(f32), step_loss(bf16)
        # the two policies track each other before AND after an update
        # (one random-data SGD step is not a learning guarantee — only
        # parity and finiteness are asserted)
        assert abs(l0b - l0f) < 0.05 * max(1.0, abs(l0f)), make_spec.__name__
        assert abs(l1b - l1f) < 0.05 * max(1.0, abs(l1f)), make_spec.__name__
        assert np.isfinite([l0f, l1f, l0b, l1b]).all(), make_spec.__name__
