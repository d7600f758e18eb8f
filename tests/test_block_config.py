"""The config-driven block: the GPT-2-style specs build the tree they always
built, window attention against the explicit mask, and every path that
cannot run the new block says so by name."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distkeras_tpu.models.base import ModelSpec
from distkeras_tpu.models.transformer import small_lm_spec, unsupported_features
from distkeras_tpu.ops.attention import attention, dense_attention
from distkeras_tpu.ops.flash_attention import flash_attention
from distkeras_tpu.parallel.mesh import create_nd_mesh


def routed_spec(**over) -> ModelSpec:
    cfg = {"vocab_size": 64, "model_dim": 32, "num_heads": 2, "num_kv_heads": 1,
           "head_dim": 8, "num_layers": 2, "max_seq_len": 16, "positional": "rope",
           "rope_layers": "sliding", "layer_types": ("sliding", "full"),
           "sliding_window": 4, "norm": "rmsnorm", "qk_norm": True, "attn_gate": True,
           "post_norm": True, "mlp": "swiglu", "mlp_dim": 48, "num_dense_layers": 1,
           "routed_experts": 4, "experts_held": (0, 2), "routed_top_k": 2, "routed_dim": 16,
           "route_balance_coeff": 0.001, "tie_word_embeddings": False, "embed_scale": 2.0,
           "compute_dtype": "float32"}
    cfg.update(over)
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(16,), input_dtype="int32")


# -- (e) the Cerebras specs' parameter tree, keys and shapes, unchanged --------

def gpt_tree(vocab, dim, heads, layers, positions):
    """The tree ``TransformerLM(positional="learned")`` has built since PR 2."""
    hd = dim // heads
    tree = {"['embed']['embedding']": (vocab, dim), "['pos_embed']": (positions, dim),
            "['final_norm']['scale']": (dim,), "['final_norm']['bias']": (dim,)}
    for i in range(layers):
        b = f"['block_{i}']"
        tree.update({b + "['LayerNorm_0']['scale']": (dim,), b + "['LayerNorm_0']['bias']": (dim,),
                     b + "['LayerNorm_1']['scale']": (dim,), b + "['LayerNorm_1']['bias']": (dim,),
                     b + "['qkv']['kernel']": (dim, 3, heads, hd),
                     b + "['proj']['kernel']": (heads, hd, dim),
                     b + "['up']['kernel']": (dim, 4 * dim), b + "['down']['kernel']": (4 * dim, dim)})
    return tree


@pytest.mark.parametrize("name,sizes", [
    ("cerebras-gpt-590m", (50257, 1536, 12, 18, 2048)),
    ("cerebras-gpt-1.3b", (50257, 2048, 16, 10, 2048)),
])
def test_cerebras_specs_build_the_tree_they_built_before(name, sizes):
    vocab, dim, heads, layers, positions = sizes
    spec = small_lm_spec(vocab_size=vocab, model_dim=dim, num_heads=heads, num_layers=layers,
                         max_seq_len=positions, positional="learned")
    assert unsupported_features(spec.config) == []
    assert spec.sown_collections() == () and spec.step_hook() is None
    got = {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: spec.init_params(0)))[0]}
    assert got == gpt_tree(*sizes)


def test_switch_layer_still_sows_a_loss_and_is_refused():
    spec = small_lm_spec(vocab_size=64, model_dim=32, num_heads=2, num_layers=1,
                         max_seq_len=16, moe_experts=4)
    assert "aux_loss" in spec.sown_collections()
    with pytest.raises(ValueError, match="aux losses"):
        spec.reject_silent_aux("a trainer")
    routed_spec().reject_silent_aux("a trainer")      # counts only: not refused


# -- (c) window attention against the explicit mask ----------------------------

def masked_reference(q, k, v, window):
    l = q.shape[1]
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where((j <= i) & (i - j < window), s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


WINDOW_CASES = [("dense", 40, 12, None), ("flash", 40, 12, 8), ("flash", 48, 20, 16),
                ("flash", 96, 40, 32), ("flash", 64, 9, 64)]


@pytest.mark.parametrize("impl,length,window,block", WINDOW_CASES)
def test_window_attention_matches_the_explicit_mask(impl, length, window, block):
    """Forward and gradients, at lengths that are no multiple of the window;
    the flash kernels run in interpret mode, the fused backward included."""
    assert length % window
    q, k, v, g = (jax.random.normal(key, (2, length, 2, 16), jnp.float32)
                  for key in jax.random.split(jax.random.PRNGKey(length), 4))
    if impl == "dense":
        fn = lambda q, k, v: dense_attention(q, k, v, window=window)
    else:
        fn = lambda q, k, v: flash_attention(q, k, v, window=window, block_q=block,
                                             block_k=block // 2 if block > 8 else block,
                                             block_q_bwd=block, block_k_bwd=block)
    scalar = lambda f: (lambda q, k, v: jnp.sum(f(q, k, v) * g))
    want, want_g = jax.value_and_grad(scalar(lambda q, k, v: masked_reference(q, k, v, window)),
                                      (0, 1, 2))(q, k, v)
    got, got_g = jax.value_and_grad(scalar(fn), (0, 1, 2))(q, k, v)
    assert abs(float(got - want)) < 1e-4
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-6)


def test_window_reaching_past_the_sequence_is_plain_causal():
    q, k, v = (jax.random.normal(key, (1, 32, 2, 16), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(0), 3))
    np.testing.assert_array_equal(np.asarray(flash_attention(q, k, v, window=32)),
                                  np.asarray(flash_attention(q, k, v)))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, k, v, causal=False, window=8)


# -- everything that cannot run the new block says so by name ------------------

def _decode(spec):
    from distkeras_tpu.models.decode import validate_decode_spec

    validate_decode_spec(spec)


def _pipeline(spec):
    from distkeras_tpu.parallel.pipeline import make_pp_train_step

    make_pp_train_step(spec, optax.sgd(0.1), create_nd_mesh((2,), ("pp",)),
                       num_microbatches=2, pp_axis="pp")


def _lm_step(spec):
    from distkeras_tpu.parallel.lm import make_lm_train_step

    make_lm_train_step(spec, optax.sgd(0.1), create_nd_mesh((2, 2), ("dp", "tp")),
                       sp_axis=None, tp_axis="tp")


def _param_specs(spec):
    from distkeras_tpu.parallel.lm import lm_param_specs

    lm_param_specs(jax.eval_shape(lambda: spec.init_params(0)), "tp")


def _tensor_parallel_block(spec):
    module = ModelSpec(name="transformer_lm", config=dict(spec.config, tp_size=2),
                       input_shape=(16,), input_dtype="int32")
    jax.eval_shape(lambda: module.init_params(0))


def _zero(spec):
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.zero import make_zero_train_step

    make_zero_train_step(spec, get_loss("sparse_categorical_crossentropy"), optax.sgd(0.1),
                         create_nd_mesh((2,), ("dp",)))


def _expert_exchange(spec):
    module = ModelSpec(name="transformer_lm", config=dict(spec.config, ep_size=2, ep_axis="ep"),
                       input_shape=(16,), input_dtype="int32")
    jax.eval_shape(lambda: module.init_params(0))


def _single_trainer(spec):
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.trainers import SingleTrainer

    x = np.zeros((4, 16), np.int32)
    SingleTrainer(spec, loss="sparse_categorical_crossentropy", batch_size=2).train(
        Dataset({"features": x, "label": x}))


def _async_trainer(spec):
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.runtime.async_trainer import AsyncADAG

    x = np.zeros((4, 16), np.int32)
    AsyncADAG(spec, loss="sparse_categorical_crossentropy", num_workers=1, batch_size=2,
              communication_window=1).train(Dataset({"features": x, "label": x}))


def _ring_window(spec):
    x = jnp.zeros((1, 8, 1, 8))
    mesh = create_nd_mesh((2,), ("sp",))
    from jax.sharding import PartitionSpec as P

    jax.eval_shape(jax.shard_map(lambda q: attention(q, q, q, axis_name="sp", window=4),
                                 mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp")), x)


FULL = routed_spec()
REFUSALS = [
    (_decode, FULL, "layer_types"), (_pipeline, FULL, "routed_experts"),
    (_lm_step, FULL, "attn_gate"), (_param_specs, FULL, "experts"),
    (_tensor_parallel_block, FULL, "post_norm"), (_zero, FULL, "selection bias"),
    (_expert_exchange, FULL, "across chips"), (_ring_window, FULL, "sliding"),
    (_single_trainer, FULL, "selection bias"), (_async_trainer, FULL, "selection bias"),
    # one feature at a time is refused as well
    (_decode, routed_spec(routed_experts=0, num_dense_layers=0, layer_types=None,
                          rope_layers="all"), "head_dim"),
    (_pipeline, ModelSpec(
        name="transformer_lm", config={"vocab_size": 64, "model_dim": 32, "num_heads": 2,
                                       "num_layers": 2, "max_seq_len": 16,
                                       "tie_word_embeddings": False},
        input_shape=(16,), input_dtype="int32"), "tie_word_embeddings"),
]


@pytest.mark.parametrize("path,spec,names", REFUSALS,
                         ids=[f"{p.__name__.strip('_')}-{n}" for p, _, n in REFUSALS])
def test_paths_that_cannot_run_the_new_block_refuse_it_by_name(path, spec, names):
    with pytest.raises((ValueError, NotImplementedError), match=names):
        path(spec)


def test_step_hook_moves_the_bias_after_the_optimizer():
    """``make_minibatch_step(hook=)``: the step hands back the layers' stats
    beside the loss, and the bias leaf moves by the rule, summing to zero."""
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.engine import make_minibatch_step

    spec = routed_spec(route_balance_coeff=0.01)
    params = spec.init_params(0)
    sgd = optax.sgd(0.1)
    step = make_minibatch_step(spec.apply_fn(), get_loss("sparse_categorical_crossentropy"),
                               sgd, hook=spec.step_hook())
    xs = jax.random.randint(jax.random.PRNGKey(0), (3, 2, 16), 0, 64)
    (new, _), (losses, stats) = jax.lax.scan(step, (params, sgd.init(params)), (xs, xs))
    assert losses.shape == (3,) and losses.dtype == jnp.float32
    assert np.isfinite(np.asarray(losses)).all()
    assert stats.counts.shape == (3, 1, 4) and int(stats.counts.sum()) == 3 * 32 * 2
    # half the experts held: the bound is tokens x top-k, every call runs over all rows
    assert np.asarray(stats.calls).tolist() == [[[1, 1]]] * 3
    assert np.array_equal(np.asarray(stats), np.asarray(stats.counts))
    bias = np.asarray(new["block_1"]["experts"]["router_bias"])
    assert np.abs(bias).max() > 0 and abs(bias.sum()) < 1e-6


def test_untied_head_hands_the_loss_float32_logits():
    """In bfloat16 the loss's softmax and mean would quantise it (steps of
    0.0625 near 10); the tied head's program is not touched."""
    spec = routed_spec(compute_dtype="bfloat16")
    x = jnp.zeros((1, 16), jnp.int32)
    assert spec.apply_fn()(spec.init_params(0), x).dtype == jnp.float32
