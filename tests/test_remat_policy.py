"""``TransformerLM(remat=True)`` keeps what the flash forward handed back:
its output and log-sum-exp are saved by name, so the gradient program holds
ONE forward and one backward kernel a flash layer, remat on or off, and
the same loss and gradients.  Kernels in interpret mode, tiny shapes."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models.base import ModelSpec
from distkeras_tpu.ops.flash_attention import (FLASH_LSE_NAME, FLASH_OUT_NAME, _STAT_LANES,
                                               flash_attention_with_lse)

SAVED = jax.checkpoint_policies.save_only_these_names(FLASH_OUT_NAME, FLASH_LSE_NAME)


def _lm(over, **cfg) -> ModelSpec:
    cfg.update(over, attn_impl="flash")
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(cfg["max_seq_len"],),
                     input_dtype="int32")


def gpt(**over) -> ModelSpec:
    return _lm(over, vocab_size=64, model_dim=32, num_heads=2, num_layers=2, max_seq_len=32,
               positional="learned", compute_dtype="float32")


def trinity_like(**over) -> ModelSpec:
    """A sliding-window layer (dense MLP) and a full layer (held experts),
    two KV heads for four query heads, gate, QK-norm, RoPE on the window."""
    return _lm(over, vocab_size=64, model_dim=32, num_heads=4, num_kv_heads=2, head_dim=8,
               num_layers=2, max_seq_len=32, positional="rope", rope_layers="sliding",
               layer_types=("sliding", "full"), sliding_window=8, norm="rmsnorm", qk_norm=True,
               attn_gate=True, post_norm=True, mlp="swiglu", mlp_dim=48, num_dense_layers=1,
               routed_experts=4, experts_held=(0, 2), routed_top_k=2, routed_dim=16,
               route_balance_coeff=0.001, tie_word_embeddings=False, compute_dtype="float32")


def olmo_like(**over) -> ModelSpec:
    """One period: three gated-delta-rule layers, then one full layer."""
    return _lm(over, vocab_size=64, model_dim=32, num_heads=2, num_layers=4, max_seq_len=64,
               positional="none", layer_types=("linear_attention",) * 3 + ("full_attention",),
               norm="rmsnorm", qk_norm="full", pre_norm=False, post_norm=True, mlp="swiglu",
               mlp_dim=48, tie_word_embeddings=False, linear_num_heads=2, linear_key_dim=8,
               linear_value_dim=16, linear_conv_width=4, linear_neg_eigval=True,
               compute_dtype="float32")


# builder, flash layers, [B, heads, L, head size] of a flash layer's output
MODELS = {"gpt": (gpt, 2, (2, 2, 32, 16)), "trinity_like": (trinity_like, 2, (2, 4, 32, 8)),
          "olmo_like": (olmo_like, 1, (2, 2, 64, 16))}


def _equations(jaxpr, out):
    """Every equation of a jaxpr and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _equations(sub, out)
    return out


def kernel_calls(fn, *args) -> collections.Counter:
    """Pallas calls by kernel name in the jaxpr of ``fn(*args)``."""
    return collections.Counter(
        eqn.params["name"] for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr, [])
        if eqn.primitive.name == "pallas_call")


def named_values(fn, *args) -> dict:
    """name -> set of (shape, dtype) of the ``checkpoint_name``d values."""
    found = collections.defaultdict(set)
    for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr, []):
        if eqn.primitive.name == "name":
            aval = eqn.outvars[0].aval
            found[eqn.params["name"]].add((aval.shape, aval.dtype))
    return dict(found)


def _param_shapes(spec):
    return jax.eval_shape(lambda: spec.init_params(0))


def _loss(spec):
    module, sown = spec.build(), list(spec.sown_collections())
    batch = (2, spec.config["max_seq_len"])
    tokens = jax.random.randint(jax.random.PRNGKey(3), batch, 0, 64)

    def loss(params):
        y = module.apply({"params": params}, tokens, mutable=sown or False)
        return jnp.mean((y[0] if sown else y).astype(jnp.float32) ** 2)

    return loss


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_one_forward_and_one_backward_kernel_a_flash_layer(model, remat):
    """Without the policy a remat'd block runs ``_fwd_kernel`` twice (the
    second time to make ``o`` and ``lse`` for the backward kernel again)."""
    build, flash_layers, _ = MODELS[model]
    spec = build(remat=remat)
    calls = kernel_calls(jax.grad(_loss(spec)), _param_shapes(spec))
    assert calls == {"_fwd_kernel": flash_layers, "_bwd_fused_kernel": flash_layers}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_remat_changes_neither_loss_nor_gradients(model):
    build = MODELS[model][0]
    params = jax.jit(lambda: build().init_params(0))()
    plain, plain_g = jax.jit(jax.value_and_grad(_loss(build(remat=False))))(params)
    remat, remat_g = jax.jit(jax.value_and_grad(_loss(build(remat=True))))(params)
    np.testing.assert_allclose(float(plain), float(remat), rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(plain_g), jax.tree.leaves(remat_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_what_a_flash_layer_keeps_is_its_output_and_eight_lanes_of_lse(model, dtype):
    """``B x L x heads x (head size x itemsize + 32)`` bytes a flash layer:
    the output at the QUERY heads' count in the compute dtype, the
    log-sum-exp float32 over ``_STAT_LANES`` lanes (PERF.md section 4 sizes
    the 8k cells' programs by this)."""
    build, _, (b, h, l, d) = MODELS[model]
    spec = build(remat=True, compute_dtype=dtype)
    named = named_values(jax.grad(_loss(spec)), _param_shapes(spec))
    assert named == {FLASH_OUT_NAME: {((b, h, l, d), jnp.dtype(dtype))},
                     FLASH_LSE_NAME: {((b, h, l, _STAT_LANES), jnp.dtype("float32"))}}
    kept = sum(int(np.prod(shape)) * dt.itemsize
               for values in named.values() for shape, dt in values)
    assert kept == b * l * h * (d * jnp.dtype(dtype).itemsize + 32)


def _ring_entry(q, k, v, wo, wl):
    o, lse = flash_attention_with_lse(q, k, v)
    return jnp.sum(o * wo) + jnp.sum(jnp.sin(lse) * wl)


def test_the_lse_entry_under_a_checkpoint_with_the_policy_runs_the_forward_once():
    """Ring attention's block: three Pallas calls under a plain checkpoint
    (forward, forward again, backward), two with the names saved; the
    gradients, the log-sum-exp's cotangent folded in, are the same."""
    q, k, v, wo = (jax.random.normal(key, (2, 32, 2, 16), jnp.float32)
                   for key in jax.random.split(jax.random.PRNGKey(0), 4))
    wl = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 32), jnp.float32)
    grad = lambda f: jax.grad(f, (0, 1, 2))
    plain = jax.checkpoint(_ring_entry, prevent_cse=True)
    saved = jax.checkpoint(_ring_entry, prevent_cse=True, policy=SAVED)
    assert kernel_calls(grad(plain), q, k, v, wo, wl) == {"_fwd_kernel": 2, "_bwd_fused_kernel": 1}
    assert kernel_calls(grad(saved), q, k, v, wo, wl) == {"_fwd_kernel": 1, "_bwd_fused_kernel": 1}
    assert kernel_calls(grad(_ring_entry), q, k, v, wo, wl) == {"_fwd_kernel": 1,
                                                                "_bwd_fused_kernel": 1}
    want = grad(_ring_entry)(q, k, v, wo, wl)
    lse_only = grad(lambda q, k, v: _ring_entry(q, k, v, jnp.zeros_like(wo), wl))(q, k, v)
    assert max(float(jnp.abs(g).max()) for g in lse_only[:2]) > 1e-3   # the cotangent reaches q, k
    for got in (grad(saved)(q, k, v, wo, wl), grad(plain)(q, k, v, wo, wl)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_name_outside_a_checkpoint_lowers_to_nothing(monkeypatch):
    """The configurations that run with remat off get the program they had:
    the text they lower to is the text without the names, but for the
    counter MLIR appends to a repeated private function's symbol
    (``@_where_149`` for ``@_where_148``: the same functions, one on)."""
    import re

    import distkeras_tpu.ops.flash_attention as fa

    spec = gpt(remat=False)

    def lowered():
        text = jax.jit(jax.grad(_loss(spec))).lower(_param_shapes(spec)).as_text()
        return re.sub(r"(@[A-Za-z_]+)_\d+\b", r"\1_N", text)

    named = lowered()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert lowered() == named
