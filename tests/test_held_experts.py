"""``HeldExpertsMLP``'s row bound: the bounded path computes what the
full-size path computes, a step over the bound takes the full-size path and
says so, and no ``tokens x top_k`` buffer exists outside that path."""

import dataclasses
import re
import unittest.mock as mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.models.base import ModelSpec
from distkeras_tpu.models.transformer import RoutedStats
from distkeras_tpu.parallel import moe
from distkeras_tpu.parallel.moe import HeldExpertsMLP, held_row_bound

# 2 of 16 experts held, top-2, 1,024 tokens: 2,048 assignments, 256 of them
# held when the router is balanced, and a bound of 512 rows
T, D, F, E, K = 1024, 32, 24, 16, 2
BOUND = 512


def layer(held=(0, 2)) -> HeldExpertsMLP:
    return HeldExpertsMLP(num_experts=E, experts_held=held, model_dim=D, hidden_dim=F,
                          top_k=K, route_scale=1.5, compute_dtype=jnp.float32)


def seeded(module, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (T, D), jnp.float32)
    tree = module.init(jax.random.PRNGKey(100 + seed), x)["params"]
    # a router with some spread and a bias that is not all zero, as in training
    tree = dict(tree, router=tree["router"] * 20.0,
                router_bias=0.05 * jax.random.normal(jax.random.PRNGKey(200 + seed), (E,)))
    return tree, x


def value_and_grads(module, tree, x):
    """(output, what the layer sowed, gradients into the leaves and into x)
    under a loss that weighs every output element differently."""
    mix = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def loss(tree, x):
        out, sown = module.apply({"params": tree}, x, mutable=["moe_counts"])
        return jnp.sum(out * mix), (out, sown["moe_counts"])

    (_, (out, sown)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(
        tree, x)
    return out, sown, grads


def full_size():
    """The layer with its bound out of reach: no conditional, the full-size path."""
    return mock.patch.object(moe, "_ROW_BOUND_OVER_BALANCED", E)


def plain(tree, x, held):
    """Every held expert over every token, weighted by the gate where the
    token chose it: no sort, no gather, no bound."""
    lo, hi = held
    scores = jax.nn.sigmoid(jnp.dot(x, tree["router"], precision="highest"))
    _, choice = jax.lax.top_k(scores + tree["router_bias"], K)
    gates = jnp.take_along_axis(scores, choice, axis=-1)
    gates = 1.5 * gates / (gates.sum(-1, keepdims=True) + 1e-20)

    def swiglu(gate, up, down):
        return jnp.dot(jax.nn.silu(jnp.dot(x, gate, precision="highest"))
                       * jnp.dot(x, up, precision="highest"), down, precision="highest")

    out = swiglu(tree["shared_gate"]["kernel"], tree["shared_up"]["kernel"],
                 tree["shared_down"]["kernel"])
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(choice == e, gates, 0.0), axis=-1)
        out = out + w[:, None] * swiglu(tree["w_gate"][e - lo], tree["w_up"][e - lo],
                                        tree["w_down"][e - lo])
    return out


def close(a, b, rtol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b).max() <= rtol * max(np.abs(b).max(), 1e-30)


# -- the bound -------------------------------------------------------------------

@pytest.mark.parametrize("t,k,held_n,experts,rows", [
    (16384, 8, 16, 128, 32768),     # twice the balanced share, a quarter of tokens x top-k
    (T, K, 2, E, BOUND),
    (1000, 8, 3, 128, 512),         # 375 rounds up to whole row tiles
    (64, 2, 4, 8, 128),             # half the experts held: tokens x top-k, no bounded path
    (64, 2, 2, 16, 128),            # a bound of one row tile reaches it too
    (T, K, 16, 16, T * K),
])
def test_the_bound_is_twice_the_balanced_share_in_whole_row_tiles(t, k, held_n, experts, rows):
    assert held_row_bound(t, k, held_n, experts) == rows


def test_the_bound_is_no_option_of_the_layer():
    assert [f.name for f in dataclasses.fields(HeldExpertsMLP) if f.name not in ("parent", "name")] \
        == ["num_experts", "experts_held", "model_dim", "hidden_dim", "top_k", "route_scale",
            "compute_dtype", "shared_dim"]      # PR 34: the shared expert's width, a shape


# -- (a) the bounded path against the full-size one ------------------------------------

@pytest.fixture(scope="module", params=[(0, 2), (6, 8), (14, 16)], ids=lambda h: f"held{h[0]}-{h[1]}")
def held(request):
    return request.param


@pytest.fixture(scope="module", params=[0, 1, 2], ids=lambda s: f"seed{s}")
def both_paths(request, held):
    module = layer(held)
    tree, x = seeded(module, request.param)
    bounded = value_and_grads(module, tree, x)
    with full_size():
        full = value_and_grads(module, tree, x)
    return held, tree, x, bounded, full


def test_each_path_reports_itself_and_counts_the_same(both_paths):
    held, _, _, (_, sown, _), (_, sown_full, _) = both_paths
    counts = np.asarray(sown["assignments"][0])
    assert counts.sum() == T * K and 0 < counts[held[0]:held[1]].sum() <= BOUND
    assert np.array_equal(counts, np.asarray(sown_full["assignments"][0]))
    assert np.asarray(sown["calls"][0]).tolist() == [1, 0]
    assert np.asarray(sown_full["calls"][0]).tolist() == [1, 1]


def test_bounded_output_equals_the_full_size_path_and_the_plain_sum(both_paths):
    held, tree, x, (out, _, _), (out_full, _, _) = both_paths
    assert close(out, out_full, rtol=1e-6)
    assert close(out, plain(tree, x, held))


LEAVES = ["router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down", "x"]


@pytest.mark.parametrize("leaf", LEAVES)
def test_bounded_gradient_equals_the_full_size_path(both_paths, leaf):
    _, _, _, (_, _, (g, gx)), (_, _, (g_full, gx_full)) = both_paths
    a, b = (gx, gx_full) if leaf == "x" else (g[leaf], g_full[leaf])
    a, b = jax.tree.leaves(a)[0], jax.tree.leaves(b)[0]
    assert np.abs(np.asarray(b)).max() > 0
    assert close(a, b, rtol=1e-5), leaf


def test_the_selection_bias_has_no_gradient_on_either_path(both_paths):
    _, _, _, (_, _, (g, _)), (_, _, (g_full, _)) = both_paths
    assert not np.any(np.asarray(g["router_bias"])) and not np.any(np.asarray(g_full["router_bias"]))


# -- (b), (c) steps on either side of the bound -----------------------------------------

def steered(held_tokens, held=(0, 2)):
    """A router that seats ``held_tokens`` of the T tokens on held expert 1
    and expert 9, and the others on experts 9 and 12 (both held elsewhere):
    exactly ``held_tokens`` held assignments."""
    module = layer(held)
    tree, x = seeded(module, 7)
    kinds = jnp.arange(T) < held_tokens
    x = x.at[:, 0].set(jnp.where(kinds, 1.0, 0.0)).at[:, 1].set(jnp.where(kinds, 0.0, 1.0))
    router = jnp.zeros((D, E)).at[0, jnp.array([1, 9])].set(8.0).at[1, jnp.array([9, 12])].set(8.0)
    router = router.at[2:].set(tree["router"][2:] * 0.01)
    return module, dict(tree, router=router, router_bias=jnp.zeros(E)), x


@pytest.mark.parametrize("held_tokens,full", [(BOUND - 1, 0), (BOUND, 0), (BOUND + 1, 1),
                                              (T, 1)],
                         ids=["under", "at-the-bound", "one-over", "every-token"])
def test_a_step_over_the_bound_takes_the_full_size_path_and_loses_no_row(held_tokens, full):
    module, tree, x = steered(held_tokens)
    out, sown, (g, gx) = value_and_grads(module, tree, x)
    counts = np.asarray(sown["assignments"][0])
    assert counts[1] == held_tokens and counts[:2].sum() == held_tokens and counts.sum() == T * K
    assert np.asarray(sown["calls"][0]).tolist() == [1, full]
    want = plain(tree, x, (0, 2))
    assert close(out, want)
    assert np.all(np.abs(np.asarray(out - want)).max(axis=1) < 1e-4)      # no row lost
    with full_size():
        out_full, _, (g_full, gx_full) = value_and_grads(module, tree, x)
    assert close(out, out_full, rtol=1e-6) and close(gx, gx_full, rtol=1e-5)
    for leaf in ("router", "w_gate", "w_up", "w_down"):
        assert close(g[leaf], g_full[leaf], rtol=1e-5), leaf


def test_the_hook_hands_the_calls_on_and_publish_counts_them():
    from distkeras_tpu.models.transformer import routed_step_hook

    spec = lm_spec(experts_held=(0, 2))
    hook = routed_step_hook(spec)
    x = jax.random.randint(jax.random.PRNGKey(0), (4, 256), 0, 64)
    _, stats = jax.jit(hook.apply)(spec.init_params(0), x)
    assert isinstance(stats, RoutedStats) and stats.counts.shape == (1, E)
    assert np.asarray(stats.calls).tolist() == [[1, 0]]
    assert np.array_equal(np.asarray(stats), np.asarray(stats.counts))   # the counts, read as an array
    # a window's sum over 5 steps, two of them over the bound, in two windows
    window = RoutedStats(np.asarray(stats.counts)[None] * 5, np.array([[[5, 2]]]))
    obs.reset()
    obs.enable()
    try:
        hook.publish(jax.tree.map(lambda a: np.concatenate([a, a]), window))
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    assert counters["moe_layer_calls_total"] == 10 and counters["moe_layer_calls_full_total"] == 4
    assert counters["moe_assignments_total"] == 10 * T * K


# -- (d), (e) what the compiled program holds --------------------------------------------

def lm_spec(**over) -> ModelSpec:
    """One dense and one expert layer, remat'd; 4 x 256 = T tokens a step."""
    cfg = {"vocab_size": 64, "model_dim": D, "num_heads": 2, "num_kv_heads": 1, "head_dim": 8,
           "num_layers": 2, "max_seq_len": 256, "positional": "rope", "norm": "rmsnorm",
           "mlp": "swiglu", "mlp_dim": 48, "num_dense_layers": 1, "routed_experts": E,
           "experts_held": (0, 2), "routed_top_k": K, "routed_dim": F, "remat": True,
           "tie_word_embeddings": False, "compute_dtype": "float32"}
    cfg.update(over)
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(256,), input_dtype="int32")


def compiled_step(spec) -> str:
    apply = spec.apply_fn()

    def loss(params, x):
        return jnp.mean(apply(params, x) ** 2)

    x = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    params = jax.eval_shape(lambda: spec.init_params(0))
    return jax.jit(jax.value_and_grad(loss)).lower(params, x).compile().as_text()


def computations(text):
    """{computation: its instruction lines} of a compiled module's text."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def reachable(comps, root):
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:calls|to_apply|body|condition|true_computation|"
                               r"false_computation)=%?([\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def branches(comps):
    """[(false branch, true branch)] of every conditional."""
    found = []
    for lines in comps.values():
        for line in lines:
            if " conditional(" not in line:
                continue
            pair = re.search(r"true_computation=%?([\w.\-]+), false_computation=%?([\w.\-]+)", line)
            if pair:
                found.append((pair.group(2), pair.group(1)))
            else:
                group = re.search(r"branch_computations=\{([^}]*)\}", line).group(1)
                found.append(tuple(b.strip().lstrip("%") for b in group.split(",")))
    return found


def full_size_buffers(comps, names):
    """Instructions of ``names`` (fusions' bodies are no buffers) whose
    result is a float array of ``T * K`` rows of more than one element, or
    [T, K, width]; the router's own [T * K, 2] scatter indices are none."""
    fused = {m for lines in comps.values() for line in lines
             for m in re.findall(r" fusion\(.*calls=%?([\w.\-]+)", line)}
    hits = []
    for name in names - fused:
        for line in comps[name]:
            shape = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (?:f|bf)\d+\[([\d,]+)\]", line)
            dims = [int(d) for d in shape.group(1).split(",")] if shape else []
            if (dims[:1] == [T * K] and np.prod(dims[1:]) > 1) or (
                    len(dims) == 3 and dims[:2] == [T, K] and dims[2] in (D, F)):
                hits.append(line.strip()[:160])
    return hits


def test_adag_trains_through_the_bounded_path_as_through_the_full_size_one():
    """``ADAG.train`` on two replicas: the conditional inside ``shard_map``,
    the window's ``scan`` and the block's remat; the counters of the calls."""
    from distkeras_tpu.data.dataset import Dataset
    from distkeras_tpu.models.base import Model
    from distkeras_tpu.trainers import ADAG

    spec = lm_spec(route_balance_coeff=0.001)
    x = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (16, 256), 0, 64), np.int32)

    def train():
        trainer = ADAG(Model(spec=spec, params=spec.init_params(1)), num_workers=2, batch_size=4,
                       communication_window=2, learning_rate=0.05,
                       loss="sparse_categorical_crossentropy", chunk_windows=1)
        params = trainer.train(Dataset({"features": x, "label": x}), shuffle=False).params
        return params, trainer.history

    obs.reset()
    obs.enable()
    try:
        bounded, losses = train()
        counters = obs.snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()
    # one window of two steps on each of two replicas, one expert layer
    assert counters["moe_layer_calls_total"] == 4 and counters["moe_layer_calls_full_total"] == 0
    with full_size():
        full, losses_full = train()
    assert np.allclose(losses, losses_full, rtol=1e-5)
    start = spec.init_params(1)
    for (path, a), b, c in zip(jax.tree_util.tree_flatten_with_path(bounded)[0],
                               jax.tree.leaves(full), jax.tree.leaves(start)):
        change = np.abs(np.asarray(b) - np.asarray(c)).max()
        assert change > 0 and np.abs(np.asarray(a) - np.asarray(b)).max() <= 1e-3 * change, path


def test_a_replica_holding_half_the_experts_compiles_no_conditional():
    text = compiled_step(lm_spec(experts_held=(0, 8)))
    assert " conditional(" not in text
    assert full_size_buffers(computations(text), set(computations(text)))   # the one path it has


def test_no_full_size_buffer_outside_the_conditionals_full_size_branch():
    comps = computations(compiled_step(lm_spec()))
    pairs = branches(comps)
    assert len(pairs) == 3        # the forward, the recomputed forward that keeps its rows, the backward
    inside_full, inside_bounded = set(), set()
    for bounded, full in pairs:
        inside_bounded |= reachable(comps, bounded)
        inside_full |= reachable(comps, full)
    assert full_size_buffers(comps, inside_full - inside_bounded)   # the search finds them there
    assert full_size_buffers(comps, set(comps) - inside_full) == []
