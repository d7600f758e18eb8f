"""The DeepSeek-V3 family without the query's low-rank path (Kanana-2) at a
small size on the CPU: the program against
``benchmark/reference/deepseek_v3_lm.py``, the shares of an expert-parallel
layer, the family's contract with the harness and its counts, and the
cell's files (whole runs of the harness: ``test_benchmark_deepseek_v3_run.py``,
a file of its own so that another worker takes it).

Small size (``data/deepseek_v3_small/config.json``): hidden 64, 4 heads
with queries and keys of 16 + 8 against values of 16, a latent of 32, 8
experts top-2 with 4 held beside a shared expert of twice their width, 1
dense + 2 expert layers, length 32, vocabulary 64; float32 on both sides.
Tolerances: the two sides do the same float32 arithmetic in different
orders (a row at a time, an expert at a time and the score as two products
there; batched, sorted and one product over the joined channels here), so
sums differ in their last bits: 2e-5 relative to a leaf's largest entry
covers the readings (at most 4e-6) with room, and is far under what a wrong
rotation, scale, width or normaliser moves (1e-2 and more) and under what a
bfloat16 operand moves (3e-3 and more: ``test_a_bfloat16_program_is_told_
apart``).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import ROOT  # noqa: E402

from benchmark import run as bench  # noqa: E402
from benchmark.harness import program, spec  # noqa: E402
from distkeras_tpu.data.dataset import Dataset  # noqa: E402
from distkeras_tpu.ops.losses import get_loss  # noqa: E402
from distkeras_tpu.trainers import ADAG  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "deepseek_v3_small", "config.json")) as f:
    SMALL = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs", "kanana-2-30b-a3b.json")) as f:
    KANANA = json.load(f)
# the published config.json, every key (the model catalog's row)
PUBLISHED = {"attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
             "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
             "max_position_embeddings": 32768, "model_type": "deepseek_v3",
             "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
             "n_routed_experts": 128, "n_shared_experts": 2, "norm_topk_prob": True,
             "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 48,
             "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
             "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
             "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
             "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
             "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
             "v_head_dim": 128, "vocab_size": 128256}
REF = spec.load_reference(SMALL, ROOT)
FAM = spec.load_family(SMALL, ROOT)
RTOL = 2e-5


def close(a, b, rtol=RTOL):
    scale = float(jnp.max(jnp.abs(b))) or 1.0
    return float(jnp.max(jnp.abs(a - b))) <= rtol * scale


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 64, (n, 32)).astype(np.int32),
            rng.integers(0, 64, (n, 32)).astype(np.int32))


def seeded(seed=3, bias=0.05):
    """Reference leaves with a selection bias that is not all zero."""
    params = REF.init_params(SMALL, seed)
    for n, name in enumerate(k for k in sorted(params) if k.endswith(".bias")):
        params[name] = bias * jax.random.normal(jax.random.PRNGKey(9 + n), params[name].shape)
    return params


def program_side(cfg):
    """(loss, counts, gradients by reference leaf) of the program built from
    ``cfg`` on the seeded weights and one batch."""
    params, (x, y) = seeded(), rows(2)
    hook = FAM.model_spec(cfg).step_hook()
    loss = get_loss("sparse_categorical_crossentropy")

    def prog(tree):
        out, counts = hook.apply(tree, x)
        return loss(out, y), counts

    with jax.default_matmul_precision("highest"):
        (pl, pc), pg = jax.value_and_grad(prog, has_aux=True)(FAM.to_program_tree(params, cfg))
    return pl, pc, FAM.from_program_tree(pg, cfg)


@pytest.fixture(scope="module")
def reference_side():
    """The same of the reference."""
    params, (x, y) = seeded(), rows(2)

    def plain(p):
        total, counts = 0.0, 0
        for r in range(x.shape[0]):
            l, c = REF.row_loss(p, x[r], y[r], REF._cfg_key(SMALL))
            total, counts = total + l, counts + c
        return total / x.size, counts

    with jax.default_matmul_precision("highest"):
        (rl, rc), rg = jax.value_and_grad(plain, has_aux=True)(params)
    return rl, rc, rg


@pytest.fixture(scope="module")
def both_sides(reference_side):
    return program_side(SMALL), reference_side


def test_program_loss_and_counts_match_the_reference(both_sides):
    (pl, pc, _), (rl, rc, _) = both_sides
    assert abs(float(pl) - float(rl)) <= RTOL * float(rl)
    assert np.array_equal(np.asarray(pc), np.asarray(rc))
    assert int(np.asarray(pc).sum()) == 2 * 64 * 2     # layers x tokens x top-k: none lost


@pytest.mark.parametrize("leaf", sorted(REF.param_shapes(SMALL)))
def test_program_gradient_matches_the_reference(both_sides, leaf):
    (_, _, pg), (_, _, rg) = both_sides
    if leaf.endswith(".bias"):  # selects only: no gradient on either side
        assert not np.any(np.asarray(pg[leaf])) and not np.any(np.asarray(rg[leaf]))
    else:
        assert close(pg[leaf], rg[leaf]), leaf


def test_a_bfloat16_program_is_told_apart(reference_side):
    """Where the small configuration states float32 a program that computes
    in bfloat16 fails the tolerance, on the loss and on gradients."""
    narrow = dict(SMALL, stated_precision=dict(SMALL["stated_precision"],
                                               compute_dtype="bfloat16"))
    (pl, _, pg), (rl, _, rg) = program_side(narrow), reference_side
    assert abs(float(pl) - float(rl)) > RTOL * float(rl)
    far = [leaf for leaf in rg if not leaf.endswith(".bias") and not close(pg[leaf], rg[leaf])]
    assert len(far) > len(rg) // 2


def test_center_after_two_adag_windows_matches_the_reference():
    """Through ``ADAG.train``: Trainer -> WindowEngine -> make_minibatch_step
    with the step hook, the bias leaf committed like any other."""
    seed, lr = 5, 0.05
    x, y = rows(12, seed=1)
    with jax.default_matmul_precision("highest"):
        model = program.build_model(SMALL, FAM, REF, seed)
        trainer = ADAG(model, num_workers=1, batch_size=2, communication_window=3,
                       learning_rate=lr, loss="sparse_categorical_crossentropy",
                       chunk_windows=1)
        got = FAM.from_program_tree(
            trainer.train(Dataset({"features": x, "label": y}), shuffle=False).params, SMALL)
        center = REF.init_params(SMALL, seed)
        start = dict(center)
        xs, ys = x.reshape(2, 3, 2, 32), y.reshape(2, 3, 2, 32)
        for w in range(2):
            after, loss = REF.sgd_window(SMALL, jax.tree.map(jnp.copy, center),
                                         jnp.asarray(xs[w]), jnp.asarray(ys[w]), lr=lr)
            center = jax.tree.map(lambda c, a: c + (a - c), center, after)
            assert abs(trainer.history[w] - float(loss)) < 1e-4
    for leaf in center:
        # against the leaf's CHANGE: a leaf that did not move would pass any
        # comparison of values
        change = float(jnp.max(jnp.abs(center[leaf] - start[leaf])))
        assert change > 0, leaf
        assert float(jnp.max(jnp.abs(got[leaf] - center[leaf]))) <= 1e-3 * change, leaf
    # the bias moved by whole steps of the rule, summing to zero a layer
    for layer in range(1, 3):
        moved = np.asarray(center[f"layers.{layer}.bias"])
        assert abs(moved.sum()) < 1e-6 and np.abs(moved).max() > 0


def test_follow_gives_the_harness_what_it_compares():
    x, y = rows(6, seed=2)
    calls = [(x[:2].reshape(1, 1, 2, 32), y[:2].reshape(1, 1, 2, 32)),
             (x[2:].reshape(2, 1, 2, 32), y[2:].reshape(2, 1, 2, 32))]
    rare = np.arange(40, 64, dtype=np.int32)
    out = REF.follow(SMALL, 7, calls, lr=0.05, rare_rows=rare)
    assert [len(o["losses"]) for o in out] == [1, 2]
    assert set(out[0]["norms"]) == set(REF.param_shapes(SMALL)) | {"wte.rare"}
    assert all(np.asarray(v).shape == () for v in out[0]["norms"].values())
    with pytest.raises(ValueError, match="synchronous plane"):
        REF.follow(SMALL, 7, calls, lr=0.05, self_staleness=1)
    # the control and the planted fault are other trajectories
    fp8 = REF.follow(SMALL, 7, calls, lr=0.05, rare_rows=rare, precision="fp8")
    half = REF.follow(SMALL, 7, calls, lr=0.05, rare_rows=rare, rows="half")
    assert abs(fp8[0]["losses"][0] - out[0]["losses"][0]) > 1e-4
    assert abs(half[0]["losses"][0] - out[0]["losses"][0]) > 1e-4


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "deepseek_v3_lm.py")) as f:
        text = f.read()
    assert "import distkeras_tpu" not in text and "from distkeras_tpu" not in text
    assert "pallas" not in text and "ragged_dot" not in text


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_whole():
    """One expert layer at the published counts (128 router outputs, top-6,
    two shared experts) and a small width: the routed parts from
    ``experts_held`` [0, 16) ... [112, 128), the shared expert counted once,
    are the uncut reference's layer (all 128 held)."""
    from distkeras_tpu.parallel.moe import HeldExpertsMLP

    whole_cfg = dict(SMALL, router_outputs=128, experts_held=[0, 128], n_routed_experts=128,
                     num_experts_per_tok=6)
    s = REF.sizes(whole_cfg)
    p = REF.init_params(whole_cfg, 11)
    layer = {k.split(".", 2)[2]: v for k, v in p.items() if k.startswith("layers.1.")}
    layer["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), layer["bias"].shape)
    u = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)

    def program_share(lo, hi, shared):
        module = HeldExpertsMLP(num_experts=128, experts_held=(lo, hi), model_dim=64,
                                hidden_dim=32, shared_dim=64, top_k=6, route_scale=s["scale"],
                                compute_dtype=jnp.float32)
        tree = {"router": layer["w_router"], "router_bias": layer["bias"],
                "w_gate": layer["w1"][lo:hi], "w_up": layer["w3"][lo:hi],
                "w_down": layer["w2"][lo:hi],
                "shared_gate": {"kernel": layer["shared_w1"] * shared},
                "shared_up": {"kernel": layer["shared_w3"]},
                "shared_down": {"kernel": layer["shared_w2"]}}
        return module.apply({"params": tree}, u)

    with jax.default_matmul_precision("highest"):
        whole, counts = REF._moe(u, layer, s, "float32")
        # silu(0) * x = 0: a zeroed gate kernel switches the shared expert off
        parts = sum(program_share(lo, lo + 16, float(lo == 0)) for lo in range(0, 128, 16))
    assert layer["shared_w1"].shape == (64, 64) and s["S"] == 2 * s["M"]
    assert int(counts.sum()) == 40 * 6
    assert close(parts, whole, rtol=1e-5)


# -- the family's contract with the harness, and its counts --------------------

def test_family_obeys_the_contract():
    ms = FAM.model_spec(SMALL)
    assert ms.name == "transformer_lm" and ms.sown_collections() == ("moe_counts",)
    ms.reject_silent_aux("a trainer")          # sows no loss: nothing to refuse
    params = REF.init_params(SMALL, 1)
    tree = FAM.to_program_tree(params, SMALL)
    shape = lambda t: {jax.tree_util.keystr(k): v.shape
                       for k, v in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert shape(tree) == shape(jax.eval_shape(lambda: ms.init_params(0)))
    back = FAM.from_program_tree(tree, SMALL)
    assert set(back) == set(params)
    assert all(np.array_equal(np.asarray(back[k]), np.asarray(params[k])) for k in params)
    assert FAM.shapes(SMALL, {"data": {"seq_len": 16}}) == {"seq_len": 16, "vocab": 64}
    with pytest.raises(ValueError):
        FAM.shapes(SMALL, {"data": {"seq_len": 64}})
    with pytest.raises(KeyError):
        FAM.kernel_work(SMALL, "_gmm_kernel", 2, 32)


@pytest.mark.parametrize("key,value", [("q_lora_rank", 1536), ("n_group", 8),
                                       ("scoring_func", "softmax"), ("qk_head_dim", 128),
                                       ("rope_scaling", {"type": "yarn"})])
def test_family_refuses_what_the_program_does_not_build(key, value):
    with pytest.raises(ValueError):
        FAM.model_spec(dict(SMALL, **{key: value}))


def test_flop_and_kernel_counts_match_a_brute_force_count():
    s, seq = REF.sizes(SMALL), 32
    pairs = sum(j <= i for i in range(seq) for j in range(seq))
    assert FAM.score_pairs(seq) == pairs
    shapes = REF.param_shapes(SMALL)
    size = lambda k: int(np.prod(shapes[k]))
    held_share = s["top_k"] / s["R"]     # expected share of a HELD expert's choices a token
    per_token = size("lm_head")
    for i in range(s["N"]):
        layer = lambda k: size(f"layers.{i}.{k}")
        per_token += sum(layer(k) for k in ("w_q", "w_dkv", "w_ukv", "w_o"))
        if i < s["Nd"]:
            per_token += sum(layer(k) for k in ("w1", "w2", "w3"))
        else:
            per_token += (sum(layer("shared_" + k) for k in ("w1", "w2", "w3"))
                          + layer("w_router")
                          + held_share * sum(layer(k) for k in ("w1", "w2", "w3")))
    flops = FAM.train_flops_per_token(SMALL, seq)
    assert flops["dense"] == pytest.approx(6.0 * per_token)
    # QK^T at 24 and PV at 16, 2 FLOPs a MAC, forward and twice backward
    assert flops["attention"] == pytest.approx(
        3 * 2.0 * pairs * (s["Q"] + s["P"]) * s["H"] * s["N"] / seq)
    assert flops["total"] == flops["dense"] + flops["attention"]
    fwd = FAM.kernel_work(SMALL, "_fwd_kernel", 2, seq)
    assert fwd["flops"] == pytest.approx(2.0 * pairs * (s["Q"] + s["P"]) * 2 * s["H"])
    assert fwd["bytes"] == 2 * s["H"] * seq * 2 * (2 * s["Q"] + 2 * s["P"])     # q, k; v, o
    bwd = FAM.kernel_work(SMALL, "_bwd_fused_kernel", 2, seq)
    assert bwd["flops"] == pytest.approx(2.0 * pairs * (3 * s["Q"] + 2 * s["P"]) * 2 * s["H"])
    assert bwd["bytes"] == 2 * fwd["bytes"]           # + do, dv at 16; dq, dk at 24
    # with one head size the counts are the other families' (4 d and 10 d a pair)
    one = dict(SMALL, qk_nope_head_dim=8, qk_rope_head_dim=8, qk_head_dim=16)
    assert FAM.kernel_work(one, "_bwd_fused_kernel", 2, seq)["flops"] == pytest.approx(
        2.5 * FAM.kernel_work(one, "_fwd_kernel", 2, seq)["flops"])


def test_published_widths_are_uncut_and_the_arithmetic_holds():
    """ISSUE 34's numbers at the published sizes."""
    differs = {k for k, v in PUBLISHED.items() if KANANA.get(k, "missing") != v}
    assert differs == set(KANANA["reduced"])            # every other key as published
    assert {k: PUBLISHED[k] for k in differs} == {k: KANANA["published"][k] for k in differs}
    assert KANANA["q_lora_rank"] is None and KANANA["rope_scaling"] is None
    assert KANANA["rope_interleave"] is True and KANANA["tie_word_embeddings"] is False
    assert KANANA["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (KANANA["num_hidden_layers"], KANANA["n_routed_experts"], KANANA["vocab_size"],
            KANANA["experts_held"]) == (6, 16, 16032, [0, 16])
    for key in ("published", "assumed", "departures", "stated_precision", "deployment"):
        assert KANANA[key]
    sizes = {k: int(np.prod(v)) for k, v in REF.param_shapes(KANANA).items()}
    attn = sum(sizes[f"layers.0.{k}"] for k in ("w_q", "w_dkv", "w_ukv", "w_o"))
    assert attn == 26_345_472                           # 26.35M a layer
    expert_layer = sum(v for k, v in sizes.items() if k.startswith("layers.1."))
    assert expert_layer == pytest.approx(111.55e6, rel=2e-3)
    assert sum(sizes.values()) == pytest.approx(687.6e6, rel=2e-3)     # 2.75 GB a tree
    m = FAM.matmul_params_per_token(KANANA)
    assert sum(m.values()) == pytest.approx(294.9e6, rel=1e-3)
    flops = FAM.train_flops_per_token(KANANA, 8192)
    assert flops["dense"] == pytest.approx(1.769e9, rel=1e-3)
    assert flops["attention"] == pytest.approx(1.510e9, rel=1e-3)
    assert flops["total"] * 16384 == pytest.approx(53.7e12, rel=2e-3)
    assert flops["attention"] / flops["total"] == pytest.approx(0.46, abs=0.005)
    fwd = FAM.kernel_work(KANANA, "_fwd_kernel", 2, 8192)
    bwd = FAM.kernel_work(KANANA, "_bwd_fused_kernel", 2, 8192)
    pairs = 8192 * 8193 // 2 * 2 * 32
    assert fwd == {"flops": 2.0 * pairs * 320, "bytes": 2 * 32 * 8192 * 2 * (384 + 256)}
    assert bwd == {"flops": 2.0 * pairs * 832, "bytes": 2 * 32 * 8192 * 2 * (768 + 512)}


def test_cell_files_load_and_every_new_entry_has_its_files():
    cell = spec.load_cell("kanana_sync8k", ROOT)
    assert cell["config"] == "kanana-2-30b-a3b" and cell["traffic"] == "sync_adag_8k"
    assert cell["chips"] == 1 and cell["config_file"]["family"] == "deepseek_v3_lm"
    sh = bench.shapes(cell)
    assert (sh["batch"], sh["seq_len"], sh["steps"], sh["vocab"]) == (2, 8192, 5, 16032)
    assert sh["rows_per_window"] * sh["seq_len"] == 81920
    names = {m["name"] for m in cell["per_layer"]}
    assert names == {"compile_s", "train_call_fixed_s", "feed_load_ms_per_window",
                     "feed_wait_ms_per_window", "engine_epoch_ms", "engine_host_ms",
                     "step_device_ms", "step_mfu", "device_idle_share", "idle_attributed_share",
                     "flash_fwd_roofline", "flash_bwd_roofline", "attn_device_share",
                     "moe_device_share", "moe_held_share", "moe_expert_load_max_over_mean",
                     "moe_full_path_share", "mla_device_share", "mla_latent_device_share"}
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s_per_chip",
                                                       "loss_at_tokens", "setup_s"}
    nothing = {"trace": None, "counters": {}, "gauges": {}, "histograms": {}, "spans": {}}
    for m in cell["per_layer"]:
        read, args = spec.load_reader(m["name"], ROOT)
        assert callable(read) and isinstance(args, dict)
    for name in ("mla_device_share", "mla_latent_device_share"):
        with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
            body = json.load(f)
        assert body["name"] == name and body["what"] and body["reader"] == "trace_scope"
        assert all(s.startswith("attn.latent") for s in body["args"]["scopes"])
        read, args = spec.load_reader(name, ROOT)
        assert read(nothing, **args) is None           # nothing to read: nothing, no raise
    assert set(cell["check"]["limits"]) == {"loss_first", "first_gap", "change_gap", "rare_gap"}
    b = spec.load_benchmark(ROOT)
    entry = next(c for c in b["configs"] if c["name"] == "kanana-2-30b-a3b")
    assert os.path.exists(os.path.join(ROOT, entry["file"]))
    assert entry["reduced"] == KANANA["reduced"] and entry["source"] in KANANA["source"]
    for kind in ("reference", "families"):
        assert os.path.exists(os.path.join(ROOT, "benchmark", kind, "deepseek_v3_lm.py"))
    # appended where the lists ended at PR 34 (a later PR appends after them)
    cells = [w["name"] for w in b["workloads"]]
    assert cells.index("kanana_sync8k") == cells.index("olmohybrid_sync8k") + 1
    metrics = [m["name"] for m in b["per_layer"]]
    at = metrics.index("linattn_scan_device_share")
    assert metrics[at + 1:at + 3] == ["mla_device_share", "mla_latent_device_share"]
    for m in b["per_layer"] + b["end_to_end"]:
        if "kanana_sync8k" in m.get("workloads", ()):
            order = [cells.index(c) for c in m["workloads"]]
            assert order == sorted(order), m["name"]
