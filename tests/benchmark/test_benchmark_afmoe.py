"""The AFMoE family (Trinity) at a small size on the CPU: the program
against ``benchmark/reference/afmoe_lm.py``, the share of an expert-parallel
layer, the family's contract with the harness and its counts, the cell's
files, and a whole run of the harness.

Small size (``data/afmoe_small/config.json``): hidden 64, 4 query / 2 KV
heads of 16, 8 experts top-2 with 4 held, window 8 at length 32, 1 dense +
4 expert layers, vocabulary 64; float32 on both sides.  Tolerances: the two
sides do the same float32 arithmetic in different orders (a row at a time
and an expert at a time there, batched and sorted here), so sums differ in
their last bits: 2e-5 relative to a leaf's largest entry covers the readings
(at most 5e-6, the embedding's) with room, and is far under what a wrong
mask, weight or normaliser moves (1e-2 and more).
"""

import itertools
import json
import os
import shutil
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
with open(os.path.join(HERE, "data", "afmoe_small", "config.json")) as f:
    SMALL = json.load(f)
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


@pytest.fixture(scope="module")
def both_sides():
    """(loss, counts, gradients by reference leaf) of the program and of the
    reference on one batch."""
    params, (x, y) = seeded(), rows(2)
    hook = FAM.model_spec(SMALL).step_hook()
    loss = get_loss("sparse_categorical_crossentropy")

    def prog(tree):
        out, counts = hook.apply(tree, x)
        return loss(out, y), counts

    def plain(p):
        total, counts = 0.0, 0
        for r in range(x.shape[0]):
            l, c = REF.row_loss(p, x[r], y[r], REF._cfg_key(SMALL))
            total, counts = total + l, counts + c
        return total / x.size, counts

    with jax.default_matmul_precision("highest"):
        (pl, pc), pg = jax.value_and_grad(prog, has_aux=True)(
            FAM.to_program_tree(params, SMALL))
        (rl, rc), rg = jax.value_and_grad(plain, has_aux=True)(params)
    return (pl, pc, FAM.from_program_tree(pg, SMALL)), (rl, rc, rg)


def test_program_loss_and_counts_match_the_reference(both_sides):
    (pl, pc, _), (rl, rc, _) = both_sides
    assert abs(float(pl) - float(rl)) <= RTOL * float(rl)
    assert np.array_equal(np.asarray(pc), np.asarray(rc))
    assert int(np.asarray(pc).sum()) == 4 * 64 * 2     # layers x tokens x top-k: none lost


@pytest.mark.parametrize("leaf", sorted(REF.param_shapes(SMALL)))
def test_program_gradient_matches_the_reference(both_sides, leaf):
    (_, _, pg), (_, _, rg) = both_sides
    if leaf.endswith(".bias"):  # selects only: no gradient on either side
        assert not np.any(np.asarray(pg[leaf])) and not np.any(np.asarray(rg[leaf]))
    else:
        assert close(pg[leaf], rg[leaf]), leaf


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
    for layer in range(1, 5):
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


def test_shares_of_an_expert_parallel_layer_add_up_to_the_whole():
    """The routed parts from ``experts_held`` [0, 4) and [4, 8), the shared
    expert counted once, are the uncut layer (all 8 held)."""
    from distkeras_tpu.parallel.moe import HeldExpertsMLP

    s = REF.sizes(SMALL)
    whole_cfg = dict(SMALL, experts_held=[0, 8], num_experts=8)
    p = REF.init_params(whole_cfg, 11)
    layer = {k.split(".", 2)[2]: v for k, v in p.items() if k.startswith("layers.1.")}
    layer["bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(1), layer["bias"].shape)
    u = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)

    def program_share(lo, hi, shared):
        module = HeldExpertsMLP(num_experts=8, experts_held=(lo, hi), model_dim=64,
                                hidden_dim=32, top_k=2, route_scale=s["scale"],
                                compute_dtype=jnp.float32)
        tree = {"router": layer["w_router"], "router_bias": layer["bias"],
                "w_gate": layer["w1"][lo:hi], "w_up": layer["w3"][lo:hi],
                "w_down": layer["w2"][lo:hi],
                "shared_gate": {"kernel": layer["shared_w1"] * shared},
                "shared_up": {"kernel": layer["shared_w3"]},
                "shared_down": {"kernel": layer["shared_w2"]}}
        return module.apply({"params": tree}, u)

    with jax.default_matmul_precision("highest"):
        whole, counts = REF._moe(u, layer, REF.sizes(whole_cfg), "float32")
        # silu(0) * x = 0: a zeroed gate kernel switches the shared expert off
        parts = program_share(0, 4, 1.0) + program_share(4, 8, 0.0)
    assert int(counts.sum()) == 40 * 2
    assert close(parts, whole, rtol=1e-5)


def test_no_assignment_is_dropped_when_every_token_goes_to_one_held_expert():
    from distkeras_tpu.parallel.moe import HeldExpertsMLP

    module = HeldExpertsMLP(num_experts=8, experts_held=(0, 4), model_dim=64, hidden_dim=32,
                            top_k=2, compute_dtype=jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(0), (48, 64), jnp.float32)
    tree = module.init(jax.random.PRNGKey(1), u)["params"]
    # the bias sends every token to experts 2 (held) and 6 (held elsewhere)
    tree = dict(tree, router_bias=jnp.zeros(8).at[jnp.array([2, 6])].set(10.0),
                router=jnp.zeros_like(tree["router"]))
    out, sown = module.apply({"params": tree}, u, mutable=["moe_counts"])
    counts = np.asarray(sown["moe_counts"]["assignments"][0])
    assert counts.tolist() == [0, 0, 48, 0, 0, 0, 48, 0]
    # every row is expert 2's own output at weight sigmoid(0), plus the shared one
    def swiglu(u, g, up, down):
        return (jax.nn.silu(u @ g) * (u @ up)) @ down
    want = 0.5 * swiglu(u, tree["w_gate"][2], tree["w_up"][2], tree["w_down"][2]) + swiglu(
        u, tree["shared_gate"]["kernel"], tree["shared_up"]["kernel"],
        tree["shared_down"]["kernel"])
    assert close(out, want, rtol=1e-5)
    assert np.all(np.abs(np.asarray(out - want)).max(axis=1) < 1e-5)   # no row lost


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


def test_flop_and_kernel_counts_match_a_brute_force_count():
    s, seq = REF.sizes(SMALL), 32
    pairs = {"full_attention": 0, "sliding_attention": 0}
    for i, j in itertools.product(range(seq), repeat=2):
        pairs["full_attention"] += j <= i
        pairs["sliding_attention"] += 0 <= i - j < s["W"]
    assert FAM.score_pairs(SMALL, seq) == pairs
    shapes = REF.param_shapes(SMALL)
    size = lambda k: int(np.prod(shapes[k]))
    held_share = s["top_k"] / s["R"]     # expected share of a HELD expert's choices a token
    per_token = size("lm_head")
    for i in range(s["N"]):
        layer = lambda k: size(f"layers.{i}.{k}")
        per_token += sum(layer(k) for k in ("w_q", "w_k", "w_v", "w_g", "w_o"))
        if i < s["Nd"]:
            per_token += sum(layer(k) for k in ("w1", "w2", "w3"))
        else:
            per_token += (sum(layer("shared_" + k) for k in ("w1", "w2", "w3"))
                          + layer("w_router")
                          + held_share * sum(layer(k) for k in ("w1", "w2", "w3")))
    flops = FAM.train_flops_per_token(SMALL, seq)
    assert flops["dense"] == pytest.approx(6.0 * per_token)
    rows_pairs = sum(pairs[k] for k in s["kinds"])
    assert flops["attention"] == pytest.approx(12.0 * rows_pairs * s["H"] * s["D"] / seq)
    assert flops["total"] == flops["dense"] + flops["attention"]
    fwd = FAM.kernel_work(SMALL, "_fwd_kernel", 2, seq)
    assert fwd["flops"] == pytest.approx(4.0 * rows_pairs / s["N"] * s["D"] * 2 * s["H"])
    assert fwd["bytes"] == 4 * 2 * s["H"] * seq * s["D"] * 2
    bwd = FAM.kernel_work(SMALL, "_bwd_fused_kernel", 2, seq)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"]) and bwd["bytes"] == 2 * fwd["bytes"]


def test_published_widths_are_uncut_and_the_arithmetic_holds():
    with open(os.path.join(ROOT, "benchmark", "configs", "trinity-mini.json")) as f:
        cfg = json.load(f)
    published = {"hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
                 "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 1024,
                 "num_experts_per_tok": 8, "num_shared_experts": 1, "sliding_window": 2048,
                 "global_attn_every_n_layers": 4, "route_scale": 2.826, "rms_norm_eps": 1e-5,
                 "rope_theta": 10000, "load_balance_coeff": 0.001,
                 "max_position_embeddings": 131072, "router_outputs": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["score_func"] == "sigmoid" and cfg["route_norm"] and cfg["mup_enabled"]
    assert cfg["tie_word_embeddings"] is False
    assert sorted(cfg["reduced"]) == sorted(["num_hidden_layers", "num_dense_layers",
                                             "layer_types", "num_experts", "vocab_size"])
    n = sum(int(np.prod(s)) for s in REF.param_shapes(cfg).values())
    assert 705.3e6 < n < 705.6e6                       # 705.4M parameters, 2.82 GB a tree
    flops = FAM.train_flops_per_token(cfg, 8192)
    assert flops["total"] == pytest.approx(2.21e9, rel=0.005)
    assert flops["attention"] / flops["total"] == pytest.approx(0.25, abs=0.005)
    pairs = FAM.score_pairs(cfg, 8192)
    assert pairs["sliding_attention"] / pairs["full_attention"] == pytest.approx(0.44, abs=0.005)


def test_cell_files_load():
    cell = spec.load_cell("trinity_sync8k", ROOT)
    assert cell["config"] == "trinity-mini" and cell["traffic"] == "sync_adag_8k"
    assert cell["chips"] == 1
    sh = bench.shapes(cell)
    assert (sh["batch"], sh["seq_len"], sh["steps"], sh["vocab"]) == (2, 8192, 5, 25024)
    assert sh["rows_per_window"] * sh["seq_len"] == 81920
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_device_share", "attn_device_share", "moe_held_share",
            "moe_expert_load_max_over_mean", "step_mfu", "flash_fwd_roofline",
            "flash_bwd_roofline", "device_idle_share"} <= names
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s_per_chip",
                                                       "loss_at_tokens", "setup_s"}
    for m in cell["per_layer"]:
        read, args = spec.load_reader(m["name"], ROOT)
        assert callable(read) and isinstance(args, dict)
    assert set(cell["check"]["limits"]) == {"loss_first", "first_gap", "change_gap", "rare_gap"}


def test_appended_per_layer_entries_keep_their_order_and_bring_their_files():
    """What holds of ``per_layer`` however the benchmark grows: PR 25's thirteen
    entries stand together in their order; each of them and every entry after
    them has its metric file and a reader that finds nothing in an empty run
    and says so with ``None``; a metric's ``workloads`` names cells of the
    benchmark in the order the benchmark got them, so a list only ever grew at
    its end.  (``test_benchmark_phases.py::test_every_new_entry_has_its_files_
    and_lists_accepted_cells`` also pins those thirteen to the END of the list
    and to PR 25's three cells: it fails since PR 28 appended, PERF.md section 7.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    per_layer = b["per_layer"]
    pr25 = ["async_pull_wait_ms", "async_h2d_ms", "async_commit_d2h_ms",
            "client_commit_drain_ms", "client_commit_pack_ms", "client_commit_send_ms",
            "hub_commit_recv_ms", "hub_apply_ms", "hub_pull_send_ms",
            "wire_bytes_per_window", "engine_host_ms", "feed_wait_ms_per_window",
            "idle_attributed_share"]
    names = [m["name"] for m in per_layer]
    at = names.index(pr25[0])
    assert names[at:at + len(pr25)] == pr25            # in order, nothing between
    arrived = {w["name"]: i for i, w in enumerate(b["workloads"])}
    end_to_end = {m["name"] for m in b["end_to_end"]}
    for m in per_layer[at:]:
        assert m["workloads"] and m["moves"] in end_to_end
        with open(os.path.join(ROOT, "benchmark", "metrics", m["name"] + ".json")) as f:
            body = json.load(f)
        assert body["name"] == m["name"] and body["what"]
        read, args = spec.load_reader(m["name"], ROOT)
        # nothing to read: nothing, and no raise
        assert read({"trace": None, "counters": {}, "gauges": {}}, **args) is None
    for m in per_layer + b["end_to_end"]:
        order = [arrived[c] for c in m.get("workloads", ())]
        assert order == sorted(set(order)), m["name"]


# -- a whole run of the harness -----------------------------------------------

@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The benchmark's data tree with the small configuration as a cell."""
    dst = str(tmp_path_factory.mktemp("afmoe") / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(dst, "benchmark", "configs", "afmoe-small.json"), "w") as f:
        json.dump(SMALL, f)
    b["configs"].append({"name": "afmoe-small", "source": "test", "reduced": [],
                         "file": "benchmark/configs/afmoe-small.json", "why": "test"})
    b["workloads"].append({"name": "afmoe_small", "config": "afmoe-small",
                           "traffic": "sync_adag", "chips": 1, "why": "test"})
    for m in b["per_layer"] + b["end_to_end"]:
        if "trinity_sync8k" in m.get("workloads", ()):
            m["workloads"].append("afmoe_small")
    with open(os.path.join(dst, "benchmark", "workloads", "afmoe_small.json"), "w") as f:
        json.dump({"name": "afmoe_small", "config": "afmoe-small", "traffic": "sync_adag",
                   "chips": 1, "why": "test", "windows_per_second": 20,
                   "loss_at_tokens": {"mark_windows": 2, "average_windows": 3},
                   "check": {"calls": [1, 2], "rare_min_rank": 40,
                             "limits": {"loss_first": 0.01, "first_gap": 0.01,
                                        "change_gap": 0.01, "rare_gap": 0.05}},
                   "trace": {"max_seconds": 5}}, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return dst


def test_whole_traced_run_of_the_harness(capsys, small_root):
    rc = bench.main(["--workload", "afmoe_small", "--seed", "3000000011", "--seconds", "0.2",
                     "--trace", "1"], skip_device_check=True, root=small_root)
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the counters exist on the CPU; the device-trace readers find no device
    # plane there and report nothing (never a 0)
    assert {"moe_held_share", "moe_expert_load_max_over_mean", "compile_s"} <= set(got)
    assert not set(got) & {"moe_device_share", "attn_device_share", "step_mfu"}
    assert 0 < got["moe_held_share"]["value"] < 100
    assert got["moe_expert_load_max_over_mean"]["value"] >= 1.0


def _broken(kind, real):
    """The program's own step builder with a fault planted in what it builds,
    whatever its arguments (``test_benchmark_run.py``'s stand-in has the
    signature of before ``hook=`` and fails on it since PR 28: PERF.md
    section 7)."""
    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def broken(carry, batch):
            if kind == "half_batch":          # half left out, mean over the rest
                batch = tuple(b[: b.shape[0] // 2] for b in batch)
            new, out = step(carry, batch)
            # state_unchanged: the step hands its state back
            return (carry if kind == "state_unchanged" else new), out

        return broken
    return make


@pytest.mark.parametrize("kind,number", [("state_unchanged", "first_gap"),
                                         ("half_batch", "rare_gap")])
def test_a_broken_hooked_step_reads_not_correct(capsys, small_root, monkeypatch, kind, number):
    from distkeras_tpu.parallel import engine

    monkeypatch.setattr(engine, "make_minibatch_step",
                        _broken(kind, engine.make_minibatch_step))
    rc = bench.main(["--workload", "afmoe_small", "--seed", "3000000011", "--seconds", "0.2",
                     "--trace", "0"], skip_device_check=True, root=small_root)
    out, _ = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    if kind == "state_unchanged":
        assert c["value"] == pytest.approx(1.0, abs=1e-4)
