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


# -- PR 32: linear-attention layers, norm placement and QK-norm as data ---------

def hybrid_spec(**over) -> ModelSpec:
    """The Olmo-Hybrid shape of the block, tiny: no norm before a sublayer,
    gated-delta-rule layers three to one full layer, QK-norm over the whole
    projection, no positional signal."""
    cfg = {"vocab_size": 64, "model_dim": 32, "num_heads": 2, "num_layers": 4,
           "max_seq_len": 64, "positional": "none",
           "layer_types": ("linear_attention",) * 3 + ("full_attention",),
           "norm": "rmsnorm", "qk_norm": "full", "pre_norm": False, "post_norm": True,
           "mlp": "swiglu", "mlp_dim": 48, "tie_word_embeddings": False,
           "linear_num_heads": 2, "linear_key_dim": 8, "linear_value_dim": 16,
           "linear_conv_width": 4, "linear_neg_eigval": True, "compute_dtype": "float32"}
    cfg.update(over)
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(64,), input_dtype="int32")


def _shapes(spec):
    return {jax.tree_util.keystr(k): v.shape for k, v in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: spec.init_params(0)))[0]}


def _step_text(spec) -> str:
    """StableHLO of a value-and-grad of the spec's forward, from shapes."""
    module = spec.build()
    sown = list(spec.sown_collections())

    def loss(p, x):
        y = module.apply({"params": p}, x, mutable=sown or False)
        return jnp.mean((y[0] if sown else y).astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss)).lower(
        jax.eval_shape(lambda: spec.init_params(0)),
        jax.ShapeDtypeStruct((2,) + tuple(spec.input_shape), jnp.int32)).as_text()


ACCEPTED = {
    # the three configurations the benchmark had before PR 32, small; the
    # sha256 is of the text the PARENT of PR 32 (6b5c2f0) lowers them to
    # under this installation (jax 0.9.0)
    "cerebras-gpt-590m": (
        small_lm_spec(vocab_size=64, model_dim=32, num_heads=2, num_layers=2, max_seq_len=16,
                      positional="learned"),
        "3bc2e6ff932c749124908f547fe9d0a08db73176c81a778a4264df70d812f564"),
    "cerebras-gpt-1.3b": (
        small_lm_spec(vocab_size=64, model_dim=64, num_heads=4, num_layers=1, max_seq_len=16,
                      positional="learned"),
        "b8932fd33af7aafab7ad783bd0bd99f7077829bfaaf7f4b3fd9ca17b1c4fcede"),
    "trinity-mini": (
        routed_spec(remat=True, compute_dtype="bfloat16"),
        "1d219401897b8f902fdc5ee8f2c403eb1b162722eefe7ed49855e7cf082a2a1d"),
}
NEW_KEYS = {"pre_norm": True, "linear_num_heads": 0, "linear_key_dim": 0, "linear_value_dim": 0,
            "linear_conv_width": 4, "linear_neg_eigval": False}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_accepted_configurations_lower_to_the_program_they_had(name):
    """Same parameter tree and the same StableHLO text, byte for byte: as
    the parent lowered it (pinned), and with every new key spelt out at its
    default."""
    import hashlib

    spec, parent_sha = ACCEPTED[name]
    text = _step_text(spec)
    spelt = ModelSpec(name=spec.name, config=dict(spec.config, **NEW_KEYS),
                      input_shape=spec.input_shape, input_dtype=spec.input_dtype)
    assert _shapes(spelt) == _shapes(spec)
    assert _step_text(spelt) == text
    assert hashlib.sha256(text.encode()).hexdigest() == parent_sha, (
        "the config-driven block's defaults no longer lower to the program of before PR 32 "
        "(or the installation's JAX changed: re-pin from the parent commit)")


def test_linear_attention_layers_build_their_own_tree():
    got = _shapes(hybrid_spec())
    linear = {"['lin_q']['kernel']": (32, 2, 8), "['lin_k']['kernel']": (32, 2, 8),
              "['lin_v']['kernel']": (32, 2, 16), "['lin_gate']['kernel']": (32, 2, 16),
              "['lin_a']": (32, 2), "['lin_b']": (32, 2), "['conv_q']": (4, 2, 8),
              "['conv_k']": (4, 2, 8), "['conv_v']": (4, 2, 16), "['A_log']": (2,),
              "['dt_bias']": (2,), "['lin_norm']['scale']": (16,),
              "['lin_out']['kernel']": (2, 16, 32)}
    shared = {"['attn_post_norm']['scale']": (32,), "['ffn_post_norm']['scale']": (32,),
              "['gate_proj']['kernel']": (32, 48), "['up']['kernel']": (32, 48),
              "['down']['kernel']": (48, 32)}
    full = {"['qkv']['kernel']": (32, 3, 2, 16), "['proj']['kernel']": (2, 16, 32),
            "['q_norm']['scale']": (32,), "['k_norm']['scale']": (32,)}
    want = {"['embed']['embedding']": (64, 32), "['final_norm']['scale']": (32,),
            "['lm_head']['kernel']": (32, 64)}
    for i in range(4):
        for k, v in dict(shared, **(full if i == 3 else linear)).items():
            want[f"['block_{i}']" + k] = v
    assert got == want
    spec = hybrid_spec()
    assert spec.sown_collections() == () and spec.step_hook() is None
    params = spec.init_params(0)
    # A_log = log(uniform(1, 16)); softplus(dt_bias) log-uniform in [1e-3, 1e-1]
    a = np.exp(np.asarray(params["block_0"]["A_log"]))
    dt = np.asarray(jax.nn.softplus(params["block_0"]["dt_bias"]))
    assert ((a >= 1) & (a <= 16)).all() and ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    x = jnp.arange(128).reshape(2, 64) % 64
    out = spec.apply_fn()(params, x)
    assert out.shape == (2, 64, 64) and out.dtype == jnp.float32
    # causal: what a later token holds does not reach an earlier one's logits
    other = spec.apply_fn()(params, x.at[:, 40:].set(0))
    np.testing.assert_array_equal(np.asarray(out[:, :40]), np.asarray(other[:, :40]))
    assert np.abs(np.asarray(out[:, 40:] - other[:, 40:])).max() > 1e-3


def test_linear_mixer_needs_its_sizes_and_a_whole_number_of_chunks():
    with pytest.raises(ValueError, match="linear_num_heads"):
        jax.eval_shape(lambda: hybrid_spec(linear_num_heads=0).init_params(0))
    with pytest.raises(ValueError, match="no multiple of the chunk"):
        jax.eval_shape(lambda: ModelSpec(name="transformer_lm", config=hybrid_spec().config,
                                         input_shape=(40,), input_dtype="int32").init_params(0))
    with pytest.raises(ValueError, match="unknown kind"):
        jax.eval_shape(lambda: hybrid_spec(layer_types=("linear_attention",) * 3
                                           + ("latent_attention",)).init_params(0))
    with pytest.raises(ValueError, match="qk_norm"):
        jax.eval_shape(lambda: hybrid_spec(qk_norm="rows").init_params(0))


@pytest.mark.parametrize("pre,post", [(False, True), (True, True), (True, False)])
def test_norm_placement_is_data(pre, post):
    """``a = x + post(Mixer(pre(x)))``: each norm is there or not by its key,
    and the post-norm-only block computes what its equation says."""
    spec = hybrid_spec(layer_types=("full",), num_layers=1, pre_norm=pre, post_norm=post,
                       qk_norm=False)
    names = set(jax.eval_shape(lambda: spec.init_params(0))["block_0"])
    assert ({"attn_norm", "ffn_norm"} <= names) == pre
    assert ({"attn_post_norm", "ffn_post_norm"} <= names) == post
    if (pre, post) != (False, True):
        return
    p = spec.init_params(1)
    b = p["block_0"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    rms = lambda t, g: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6) * g
    q, k, v = (jnp.einsum("ble,ehd->blhd", x, b["qkv"]["kernel"][:, n]) for n in range(3))
    o = jnp.einsum("blhd,hde->ble", dense_attention(q, k, v), b["proj"]["kernel"])
    a = x + rms(o, b["attn_post_norm"]["scale"])            # the mixer saw x itself, un-normed
    f = (jax.nn.silu(a @ b["gate_proj"]["kernel"]) * (a @ b["up"]["kernel"])) @ b["down"]["kernel"]
    want = a + rms(f, b["ffn_post_norm"]["scale"])
    from distkeras_tpu.models.transformer import TransformerBlock

    block = TransformerBlock(model_dim=32, num_heads=2, positional="none", norm="rmsnorm",
                             pre_norm=False, post_norm=True, mlp="swiglu", mlp_dim=48,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(block.apply({"params": b}, x)), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("kind", ["full", "head", True])
def test_qk_norm_over_the_whole_projection_or_a_head(kind):
    """``"full"``: ONE RMSNorm over all heads' channels, a gain a channel;
    ``"head"`` (and ``True``, which keeps its meaning): one a head vector."""
    from distkeras_tpu.models.transformer import TransformerBlock

    block = TransformerBlock(model_dim=32, num_heads=2, positional="none", norm="rmsnorm",
                             qk_norm=kind, compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    b = block.init(jax.random.PRNGKey(1), x)["params"]
    width = 32 if kind == "full" else 16
    assert b["q_norm"]["scale"].shape == b["k_norm"]["scale"].shape == (width,)
    b = dict(b, q_norm={"scale": 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (width,))})
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * b["attn_norm"]["scale"]
    q, k, v = (jnp.einsum("ble,ehd->blhd", y, b["qkv"]["kernel"][:, n]) for n in range(3))

    def norm(t, g):
        flat = t.reshape(1, 16, -1) if kind == "full" else t
        out = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + 1e-6) * g
        return out.reshape(t.shape)

    o = dense_attention(norm(q, b["q_norm"]["scale"]), norm(k, b["k_norm"]["scale"]), v)
    a = x + jnp.einsum("blhd,hde->ble", o, b["proj"]["kernel"])
    h = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + 1e-6) * b["ffn_norm"]["scale"]
    want = a + jax.nn.gelu(h @ b["up"]["kernel"]) @ b["down"]["kernel"]
    np.testing.assert_allclose(np.asarray(block.apply({"params": b}, x)), np.asarray(want),
                               atol=2e-5)


HYBRID = hybrid_spec()
PLAIN = {"vocab_size": 64, "model_dim": 32, "num_heads": 2, "num_layers": 2, "max_seq_len": 64}
NEW_REFUSALS = [
    (_decode, HYBRID, "layer_types"), (_decode, HYBRID, "linear_num_heads"),
    (_decode, HYBRID, "pre_norm"), (_pipeline, HYBRID, "linear_key_dim"),
    (_lm_step, HYBRID, "linear_value_dim"), (_lm_step, HYBRID, "qk_norm"),
    (_param_specs, HYBRID, "lin_q"), (_param_specs, HYBRID, "A_log"),
    (_tensor_parallel_block, HYBRID, "pre_norm"),
    (_tensor_parallel_block, HYBRID, "layer_types"),
    # one new key at a time
    (_decode, ModelSpec(name="transformer_lm", config=dict(PLAIN, pre_norm=False),
                        input_shape=(64,), input_dtype="int32"), "pre_norm"),
    (_pipeline, ModelSpec(name="transformer_lm", config=dict(PLAIN, qk_norm="full"),
                          input_shape=(64,), input_dtype="int32"), "qk_norm"),
    (_lm_step, ModelSpec(name="transformer_lm", config=dict(PLAIN, linear_neg_eigval=True),
                         input_shape=(64,), input_dtype="int32"), "linear_neg_eigval"),
    (_decode, ModelSpec(name="transformer_lm", config=dict(PLAIN, linear_conv_width=2),
                        input_shape=(64,), input_dtype="int32"), "linear_conv_width"),
]


@pytest.mark.parametrize("path,spec,names", NEW_REFUSALS,
                         ids=[f"{p.__name__.strip('_')}-{n}" for p, _, n in NEW_REFUSALS])
def test_paths_that_know_the_old_block_refuse_the_new_keys_by_name(path, spec, names):
    with pytest.raises((ValueError, NotImplementedError), match=names):
        path(spec)


def test_zero_steps_the_hybrid_block():
    """ZeRO shards the optimizer's state over a flat parameter vector and
    asks nothing of the block: it refuses a spec with a step hook, which the
    hybrid block has not, and steps it (the scan's carry inside shard_map)."""
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.zero import make_zero_train_step, zero_init_state

    spec, mesh = hybrid_spec(), create_nd_mesh((2,), ("dp",))
    sgd = optax.sgd(0.01)
    step = make_zero_train_step(spec, get_loss("sparse_categorical_crossentropy"), sgd, mesh,
                                axis="dp")
    params = spec.init_params(0)
    before = jax.tree.map(np.asarray, params)      # the step donates its parameters
    x = jax.random.randint(jax.random.PRNGKey(0), (4, 64), 0, 64)
    new, _, loss = step(params, zero_init_state(params, sgd, mesh, axis="dp"), x, x)
    assert np.isfinite(float(loss))
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), new, before)
    assert all(v > 0 for v in jax.tree.leaves(moved))


# -- PR 34: latent attention, the shared expert's own width --------------------

def latent_spec(**over) -> ModelSpec:
    """The Kanana-2 (DeepSeek-V3 without the query latent) shape of the
    block, tiny: every layer latent attention with queries and keys of 8 + 4
    against values of 8, adjacent-pair RoPE, a leading dense SwiGLU layer,
    then top-2 of 4 experts beside a shared expert of twice their width."""
    cfg = {"vocab_size": 64, "model_dim": 32, "num_heads": 2, "num_layers": 2,
           "max_seq_len": 16, "positional": "rope", "rope_theta": 1e6, "rope_interleave": True,
           "layer_types": ("latent", "latent"), "kv_lora_rank": 12, "qk_nope_head_dim": 8,
           "qk_rope_head_dim": 4, "v_head_dim": 8, "norm": "rmsnorm", "mlp": "swiglu",
           "mlp_dim": 48, "num_dense_layers": 1, "routed_experts": 4, "experts_held": (0, 2),
           "routed_top_k": 2, "routed_dim": 16, "n_shared_experts": 2, "route_scale": 2.448,
           "route_balance_coeff": 0.001, "tie_word_embeddings": False,
           "compute_dtype": "float32"}
    cfg.update(over)
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(16,), input_dtype="int32")


def test_latent_layers_build_their_own_tree():
    got = _shapes(latent_spec())
    mixer = {"['attn_norm']['scale']": (32,), "['ffn_norm']['scale']": (32,),
             "['q']['kernel']": (32, 2, 12), "['kv_down']['kernel']": (32, 16),
             "['kv_norm']['scale']": (12,), "['kv_up']['kernel']": (12, 2, 16),
             "['proj']['kernel']": (2, 8, 32)}
    dense = {"['gate_proj']['kernel']": (32, 48), "['up']['kernel']": (32, 48),
             "['down']['kernel']": (48, 32)}
    experts = {"['experts']['router']": (32, 4), "['experts']['router_bias']": (4,),
               "['experts']['w_gate']": (2, 32, 16), "['experts']['w_up']": (2, 32, 16),
               "['experts']['w_down']": (2, 16, 32),
               "['experts']['shared_gate']['kernel']": (32, 32),
               "['experts']['shared_up']['kernel']": (32, 32),
               "['experts']['shared_down']['kernel']": (32, 32)}
    want = {"['embed']['embedding']": (64, 32), "['final_norm']['scale']": (32,),
            "['lm_head']['kernel']": (32, 64)}
    for i, ffn in enumerate((dense, experts)):
        want.update({f"['block_{i}']" + k: v for k, v in dict(mixer, **ffn).items()})
    assert got == want
    spec = latent_spec()
    assert spec.sown_collections() == ("moe_counts",)
    params = spec.init_params(0)
    x = jnp.arange(32).reshape(2, 16) % 64
    out = spec.apply_fn()(params, x)
    assert out.shape == (2, 16, 64) and out.dtype == jnp.float32
    # causal: a later token does not move an earlier logit; and position
    # reaches it through the rotary channels alone, in either convention
    other = spec.apply_fn()(params, x.at[:, 10:].set(0))
    np.testing.assert_allclose(np.asarray(out[:, :10]), np.asarray(other[:, :10]), atol=1e-6)
    assert np.abs(np.asarray(out[:, 10:] - other[:, 10:])).max() > 1e-3
    for change in ({"positional": "none"}, {"rope_interleave": False}):
        moved = latent_spec(**change).apply_fn()(params, x)
        assert np.abs(np.asarray(out - moved)).max() > 1e-4
    with pytest.raises(ValueError, match="kv_lora_rank"):
        jax.eval_shape(lambda: latent_spec(kv_lora_rank=0).init_params(0))


@pytest.mark.parametrize("interleave", [True, False])
def test_latent_mixer_computes_its_equations(interleave):
    """The block against the equations written out: one rotary key a token
    for all heads, the latent's norm, scores at (8 + 4)^-1/2, values of 8."""
    from distkeras_tpu.models.transformer import TransformerBlock
    from distkeras_tpu.ops.rotary import rope_rotate

    block = TransformerBlock(model_dim=32, num_heads=2, positional="rope", norm="rmsnorm",
                             attn_kind="latent", kv_lora_rank=12, qk_nope_head_dim=8,
                             qk_rope_head_dim=4, v_head_dim=8, rope_interleave=interleave,
                             rope_theta=100.0, mlp="swiglu", mlp_dim=48,
                             compute_dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    b = block.init(jax.random.PRNGKey(1), x)["params"]
    b = dict(b, kv_norm={"scale": 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (12,))})
    rms = lambda t, g: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6) * g
    u = rms(x, b["attn_norm"]["scale"])
    q = jnp.einsum("ble,ehd->blhd", u, b["q"]["kernel"])
    down = u @ b["kv_down"]["kernel"]
    kv = jnp.einsum("blz,zhd->blhd", rms(down[..., :12], b["kv_norm"]["scale"]),
                    b["kv_up"]["kernel"])
    pos = jnp.arange(16)
    rot = lambda t: rope_rotate(t, pos, base=100.0, interleaved=interleave)
    q_r, k_r = rot(q[..., 8:]), rot(down[:, :, None, 12:])
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :8], kv[..., :8])
         + jnp.einsum("bqhd,bkd->bhqk", q_r, k_r[:, :, 0])) / np.sqrt(12)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), kv[..., 8:])
    a = x + jnp.einsum("blhd,hde->ble", o, b["proj"]["kernel"])
    h = rms(a, b["ffn_norm"]["scale"])
    want = a + (jax.nn.silu(h @ b["gate_proj"]["kernel"]) * (h @ b["up"]["kernel"])) \
        @ b["down"]["kernel"]
    np.testing.assert_allclose(np.asarray(block.apply({"params": b}, x)), np.asarray(want),
                               atol=2e-5)


PR34_KEYS = {"kv_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0, "v_head_dim": 0,
             "rope_interleave": False, "n_shared_experts": 1}
# the four configurations the benchmark had before PR 34, small, with the
# sha256 of the text the PARENT of PR 34 (e7dd97e) lowers them to
ACCEPTED_34 = dict(ACCEPTED, **{"olmo-hybrid-7b": (
    hybrid_spec(remat=True, compute_dtype="bfloat16"), "e32081f2ab08b0499fe492a11adc73f32404c590d78266ff461d4565b1491db5")})


@pytest.mark.parametrize("name", sorted(ACCEPTED_34))
def test_the_four_accepted_configurations_do_not_move_under_pr34s_keys(name):
    """The same parameter tree and the same StableHLO text as the parent
    lowered (pinned), and with every key of PR 34 spelt out at its default."""
    import hashlib

    spec, parent_sha = ACCEPTED_34[name]
    text = _step_text(spec)
    spelt = ModelSpec(name=spec.name, config=dict(spec.config, **PR34_KEYS),
                      input_shape=spec.input_shape, input_dtype=spec.input_dtype)
    assert unsupported_features(spelt.config) == unsupported_features(spec.config)
    assert _shapes(spelt) == _shapes(spec)
    assert _step_text(spelt) == text
    assert hashlib.sha256(text.encode()).hexdigest() == parent_sha


LATENT = latent_spec()
LATENT_DENSE = latent_spec(routed_experts=0, num_dense_layers=0)     # no step hook
LATENT_REFUSALS = [
    (_decode, LATENT, "kv_lora_rank"), (_decode, LATENT, "qk_nope_head_dim"),
    (_decode, LATENT, "rope_interleave"), (_pipeline, LATENT, "qk_rope_head_dim"),
    (_pipeline, LATENT, "n_shared_experts"), (_lm_step, LATENT, "v_head_dim"),
    (_lm_step, LATENT, "kv_lora_rank"), (_param_specs, LATENT, "kv_down"),
    (_tensor_parallel_block, LATENT, "layer_types"), (_zero, LATENT, "selection bias"),
    (_single_trainer, LATENT, "selection bias"),
    # one new key at a time
    (_decode, ModelSpec(name="transformer_lm", config=dict(PLAIN, kv_lora_rank=8),
                        input_shape=(64,), input_dtype="int32"), "kv_lora_rank"),
    (_pipeline, ModelSpec(name="transformer_lm", config=dict(PLAIN, qk_nope_head_dim=8),
                          input_shape=(64,), input_dtype="int32"), "qk_nope_head_dim"),
    (_lm_step, ModelSpec(name="transformer_lm", config=dict(PLAIN, qk_rope_head_dim=4),
                         input_shape=(64,), input_dtype="int32"), "qk_rope_head_dim"),
    (_decode, ModelSpec(name="transformer_lm", config=dict(PLAIN, v_head_dim=8),
                        input_shape=(64,), input_dtype="int32"), "v_head_dim"),
    (_pipeline, ModelSpec(name="transformer_lm", config=dict(PLAIN, rope_interleave=True),
                          input_shape=(64,), input_dtype="int32"), "rope_interleave"),
    (_lm_step, ModelSpec(name="transformer_lm", config=dict(PLAIN, n_shared_experts=2),
                         input_shape=(64,), input_dtype="int32"), "n_shared_experts"),
]


@pytest.mark.parametrize("path,spec,names", LATENT_REFUSALS,
                         ids=[f"{p.__name__.strip('_')}-{n}" for p, _, n in LATENT_REFUSALS])
def test_paths_that_know_the_old_block_refuse_latent_attention_by_name(path, spec, names):
    with pytest.raises((ValueError, NotImplementedError), match=names):
        path(spec)


def test_latent_block_under_a_sequence_axis_is_refused():
    module = ModelSpec(name="transformer_lm", config=dict(LATENT_DENSE.config, seq_axis="sp"),
                       input_shape=(16,), input_dtype="int32")
    with pytest.raises(ValueError, match="layer_types"):
        jax.eval_shape(lambda: module.init_params(0))


def test_zero_steps_the_latent_block_without_experts():
    """ZeRO asks nothing of the block: it refuses the step hook (the expert
    layer's bias, above) and steps latent attention itself."""
    from distkeras_tpu.ops.losses import get_loss
    from distkeras_tpu.parallel.zero import make_zero_train_step, zero_init_state

    mesh, sgd = create_nd_mesh((2,), ("dp",)), optax.sgd(0.01)
    step = make_zero_train_step(LATENT_DENSE, get_loss("sparse_categorical_crossentropy"), sgd,
                                mesh, axis="dp")
    params = LATENT_DENSE.init_params(0)
    before = jax.tree.map(np.asarray, params)
    x = jax.random.randint(jax.random.PRNGKey(0), (4, 16), 0, 64)
    new, _, loss = step(params, zero_init_state(params, sgd, mesh, axis="dp"), x, x)
    assert np.isfinite(float(loss))
    moved = jax.tree.map(lambda a, b: float(np.abs(np.asarray(a) - b).max()), new, before)
    assert all(v > 0 for v in jax.tree.leaves(moved))


def test_shared_expert_of_its_own_width_against_a_hand_written_sum():
    """``HeldExpertsMLP(shared_dim=)``: ONE SwiGLU of that width beside the
    routed sum; the default is the routed width, the layer as it was."""
    from distkeras_tpu.parallel.moe import HeldExpertsMLP

    kw = dict(num_experts=4, experts_held=(0, 4), model_dim=32, hidden_dim=16, top_k=2,
              route_scale=2.448, compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (24, 32), jnp.float32)
    wide = HeldExpertsMLP(shared_dim=40, **kw)
    p = wide.init(jax.random.PRNGKey(1), u)["params"]
    assert p["shared_gate"]["kernel"].shape == p["shared_up"]["kernel"].shape == (32, 40)
    assert p["shared_down"]["kernel"].shape == (40, 32) and p["w_gate"].shape == (4, 32, 16)
    p = dict(p, router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(2), (4,)))
    swiglu = lambda g, up, down: (jax.nn.silu(u @ g) * (u @ up)) @ down
    s = jax.nn.sigmoid(u @ p["router"])
    _, pick = jax.lax.top_k(s + p["router_bias"], 2)
    chosen = jnp.any(pick[:, :, None] == jnp.arange(4), axis=1)
    w = jnp.where(chosen, s, 0.0)
    w = 2.448 * w / (w.sum(-1, keepdims=True) + 1e-20)
    want = swiglu(p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                  p["shared_down"]["kernel"])
    for e in range(4):
        want = want + w[:, e:e + 1] * swiglu(p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(wide.apply({"params": p}, u)), np.asarray(want),
                                   atol=2e-5)
    # the default: the parent's layer, tree and values bit for bit
    plain, spelt = HeldExpertsMLP(**kw), HeldExpertsMLP(shared_dim=16, **kw)
    tree = plain.init(jax.random.PRNGKey(3), u)["params"]
    assert jax.tree.map(jnp.shape, tree) == jax.tree.map(
        jnp.shape, spelt.init(jax.random.PRNGKey(3), u)["params"])
    assert tree["shared_gate"]["kernel"].shape == (32, 16)
    np.testing.assert_array_equal(np.asarray(plain.apply({"params": tree}, u)),
                                  np.asarray(spelt.apply({"params": tree}, u)))
    lower = lambda m: jax.jit(lambda t, x: m.apply({"params": t}, x)).lower(tree, u).as_text()
    assert lower(plain) == lower(spelt)


def test_a_shared_expert_of_no_width_is_refused_by_name():
    """``shared_dim=0`` (a block with ``n_shared_experts=0``) is refused: it
    must not quietly build one shared expert of the routed width."""
    from distkeras_tpu.parallel.moe import HeldExpertsMLP

    u = jnp.zeros((8, 32), jnp.float32)
    layer = HeldExpertsMLP(num_experts=4, experts_held=(0, 4), model_dim=32, hidden_dim=16,
                           top_k=2, shared_dim=0)
    with pytest.raises(ValueError, match="shared_dim 0"):
        layer.init(jax.random.PRNGKey(0), u)
