"""The Olmo-Hybrid family at a small size on the CPU: the program (the
chunk-parallel gated delta rule inside the config-driven block) against
``benchmark/reference/olmo_hybrid_lm.py`` (the recurrence, token by token),
the family's contract with the harness and its counts, the published widths,
the cell's files, and whole runs of the harness, one with a planted fault.

Small size (``data/olmo_hybrid_small/config.json``): hidden 64, 4 heads of 16
in the full layer, 4 linear heads with keys of 8 and values of 16, conv 4,
SwiGLU 96, [linear, linear, linear, full], vocabulary 64, rows of 128 tokens
(two chunks of the program's 64, two blocks of the reference's); float32 on
both sides.  Tolerance: the two sides do the same float32 arithmetic in
different orders — a forward substitution and matmuls over a chunk here,
128 rank-one updates there, and everything after a linear layer inherits the
difference — so 2e-4 of a leaf's largest entry covers the readings (at most
4.2e-5, a key projection's gradient) and is far under what a wrong decay,
sign, tap order or norm placement moves (1e-2 and more).
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
with open(os.path.join(HERE, "data", "olmo_hybrid_small", "config.json")) as f:
    SMALL = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b.json")) as f:
    PUBLISHED = json.load(f)
REF = spec.load_reference(SMALL, ROOT)
FAM = spec.load_family(SMALL, ROOT)
RTOL = 2e-4
CELL = "olmohybrid_sync8k"
LENGTH = 128


def close(a, b, rtol=RTOL):
    scale = float(jnp.max(jnp.abs(b))) or 1.0
    return float(jnp.max(jnp.abs(a - b))) <= rtol * scale


def rows(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 64, (n, LENGTH)).astype(np.int32),
            rng.integers(0, 64, (n, LENGTH)).astype(np.int32))


@pytest.fixture(scope="module")
def both_sides():
    """(loss, gradients by reference leaf) of the program and of the
    reference on one batch of two rows."""
    params, (x, y) = REF.init_params(SMALL, 3), rows(2)
    apply = FAM.model_spec(SMALL).apply_fn()
    loss = get_loss("sparse_categorical_crossentropy")

    def plain(p):
        return sum(REF.row_loss(p, x[r], y[r], REF._cfg_key(SMALL))
                   for r in range(x.shape[0])) / x.size

    with jax.default_matmul_precision("highest"):
        pl, pg = jax.value_and_grad(lambda tree: loss(apply(tree, x), y))(
            FAM.to_program_tree(params, SMALL))
        rl, rg = jax.value_and_grad(plain)(params)
    return (pl, FAM.from_program_tree(pg, SMALL)), (rl, rg)


def test_program_loss_matches_the_reference(both_sides):
    (pl, _), (rl, _) = both_sides
    assert abs(float(pl) - float(rl)) <= 2e-6 * float(rl)


@pytest.mark.parametrize("leaf", sorted(REF.param_shapes(SMALL)))
def test_program_gradient_matches_the_reference(both_sides, leaf):
    (_, pg), (_, rg) = both_sides
    assert float(jnp.max(jnp.abs(rg[leaf]))) > 0, leaf
    assert close(pg[leaf], rg[leaf]), leaf


def test_center_after_two_adag_windows_matches_the_reference():
    """Through ``ADAG.train``: Trainer -> WindowEngine -> the block under
    remat, leaf by leaf against the reference's windows and commits.  The
    rate: at this size plain SGD on this model is past its edge of stability
    at 0.01 (two float32 runs 1e-7 apart are 1e-2 apart five steps later,
    through the L2-normalised keys of the first layer); at 0.001 they stay
    1e-6 apart."""
    seed, lr = 5, 0.001
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
        xs, ys = x.reshape(2, 3, 2, LENGTH), y.reshape(2, 3, 2, LENGTH)
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
        # ... and two float32 steps of the leaf's own values, which a gain
        # near 1 that moved by 5e-5 cannot be told closer than
        floor = 2.4e-7 * float(jnp.max(jnp.abs(center[leaf])))
        assert float(jnp.max(jnp.abs(got[leaf] - center[leaf]))) <= 1e-3 * change + floor, leaf


def test_follow_gives_the_harness_what_it_compares():
    x, y = rows(3, seed=2)
    calls = [(x[:1].reshape(1, 1, 1, LENGTH), y[:1].reshape(1, 1, 1, LENGTH)),
             (x[1:].reshape(2, 1, 1, LENGTH), y[1:].reshape(2, 1, 1, LENGTH))]
    rare = np.arange(40, 64, dtype=np.int32)
    out = REF.follow(SMALL, 7, calls, lr=0.05, rare_rows=rare)
    assert [len(o["losses"]) for o in out] == [1, 2]
    assert set(out[0]["norms"]) == set(REF.param_shapes(SMALL)) | {"wte.rare"}
    assert all(np.asarray(v).shape == () for v in out[0]["norms"].values())
    with pytest.raises(ValueError, match="synchronous plane"):
        REF.follow(SMALL, 7, calls, lr=0.05, self_staleness=1)
    # the controls move what the check compares: operands in float8, and of a
    # batch of ONE row the second half of its positions left out
    for kw in ({"precision": "fp8"}, {"rows": "half"}):
        other = REF.follow(SMALL, 7, calls[:1], lr=0.05, rare_rows=rare, **kw)
        assert abs(other[0]["norms"]["wte"] / out[0]["norms"]["wte"] - 1.0) > 1e-3, kw


def test_the_reference_is_the_recurrence_and_its_convolution_is_causal():
    """One token's output depends on no later token; the state is carried
    across the reference's blocks of 64 tokens."""
    s = REF.sizes(SMALL)
    p = {k.split(".", 2)[2]: v for k, v in REF.init_params(SMALL, 1).items()
         if k.startswith("layers.0.")}
    u = jax.random.normal(jax.random.PRNGKey(0), (LENGTH, 64), jnp.float32)
    out = REF._linear_attention(u, p, s, "float32")
    cut = REF._linear_attention(u.at[100:].set(0.0), p, s, "float32")
    assert np.array_equal(np.asarray(out[:100]), np.asarray(cut[:100]))
    alone = REF._linear_attention(u[64:], p, s, "float32")
    assert not close(alone[8:], out[72:], rtol=1e-2)     # tokens 0..63 are still felt
    # four shifted adds: tap j multiplies the token (W - 1 - j) back
    x = jnp.arange(6, dtype=jnp.float32).reshape(6, 1, 1)
    taps = jnp.array([1000.0, 100.0, 10.0, 1.0]).reshape(4, 1, 1)
    raw = sum(jnp.concatenate([jnp.zeros((3, 1, 1)), x])[j:j + 6] * taps[j] for j in range(4))
    assert raw[:, 0, 0].tolist() == [0.0, 1.0, 12.0, 123.0, 1234.0, 2345.0]
    assert close(REF._conv_silu(x, taps), jax.nn.silu(raw), rtol=1e-6)


# -- the family's contract with the harness, and its counts --------------------

def test_family_obeys_the_contract():
    ms = FAM.model_spec(SMALL)
    assert ms.name == "transformer_lm" and ms.sown_collections() == ()
    assert ms.step_hook() is None
    params = REF.init_params(SMALL, 1)
    tree = FAM.to_program_tree(params, SMALL)
    shape = lambda t: {jax.tree_util.keystr(k): v.shape
                       for k, v in jax.tree_util.tree_flatten_with_path(t)[0]}
    assert shape(tree) == shape(jax.eval_shape(lambda: ms.init_params(0)))
    back = FAM.from_program_tree(tree, SMALL)
    assert set(back) == set(params)
    assert all(np.array_equal(np.asarray(back[k]), np.asarray(params[k])) for k in params)
    assert FAM.shapes(SMALL, {"data": {"seq_len": 64}}) == {"seq_len": 64, "vocab": 64}
    with pytest.raises(ValueError):
        FAM.shapes(SMALL, {"data": {"seq_len": 256}})
    with pytest.raises(KeyError):
        FAM.kernel_work(SMALL, "_scan_kernel", 1, 128)
    with pytest.raises(ValueError, match="no rotary"):
        FAM.model_spec(dict(SMALL, rope_parameters={"rope_theta": 10000.0}))


def test_flop_and_kernel_counts_match_a_brute_force_count():
    s, seq = REF.sizes(SMALL), 128
    shapes = REF.param_shapes(SMALL)
    size = lambda k: int(np.prod(shapes[k]))
    per_token = size("lm_head")
    recurrent = 0
    for i, kind in enumerate(s["kinds"]):
        names = ["w_q", "w_k", "w_v", "w_o", "w1", "w2", "w3"]
        if kind == "linear_attention":
            names += ["w_g", "w_a", "w_b"]
            # forward, a head a token: S k, the rank-one update, S q, each
            # d_k x d_v multiply-adds; training triples it
            recurrent += 3 * (3 * 2 * s["K"] * s["U"]) * s["G"]
        per_token += sum(size(f"layers.{i}.{k}") for k in names)
    assert REF.matmul_params(SMALL) == per_token
    pairs = sum(j <= i for i, j in itertools.product(range(seq), repeat=2))
    flops = FAM.train_flops_per_token(SMALL, seq)
    assert flops["dense"] == pytest.approx(6.0 * per_token)
    assert flops["attention"] == pytest.approx(
        12.0 * pairs * s["H"] * s["D"] / seq + recurrent)
    assert flops["total"] == flops["dense"] + flops["attention"]
    fwd = FAM.kernel_work(SMALL, "_fwd_kernel", 1, seq)
    assert fwd["flops"] == pytest.approx(4.0 * (seq * seq / 2) * s["D"] * s["H"])
    assert fwd["bytes"] == 4 * s["H"] * seq * s["D"] * 2
    bwd = FAM.kernel_work(SMALL, "_bwd_fused_kernel", 1, seq)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"]) and bwd["bytes"] == 2 * fwd["bytes"]


def test_published_widths_are_uncut_and_the_arithmetic_holds():
    cfg = PUBLISHED
    published = {"hidden_size": 3840, "intermediate_size": 11008, "num_attention_heads": 30,
                 "num_key_value_heads": 30, "linear_num_key_heads": 30,
                 "linear_num_value_heads": 30, "linear_key_head_dim": 96,
                 "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
                 "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
                 "max_position_embeddings": 65536, "hidden_act": "silu",
                 "attention_bias": False, "tie_word_embeddings": False,
                 "model_type": "olmo_hybrid", "rope_parameters": {"rope_theta": None}}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types", "vocab_size"]
    assert cfg["layer_types"] == ["linear_attention"] * 3 + ["full_attention"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (4, 100352 // 8)
    for key in ("published", "assumed", "departures", "stated_precision", "deployment"):
        assert cfg[key], key
    # the PROGRAM's tree at the published widths, by shapes alone
    tree = jax.eval_shape(lambda: FAM.model_spec(cfg).init_params(0))
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    assert count(tree) == 928_862_196 == sum(
        int(np.prod(s)) for s in REF.param_shapes(cfg).values())        # 928.9M, 3.72 GB a tree
    linear = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 11520 * 4 + 2 * 30 + 192 + 3840
    swiglu = 3 * 3840 * 11008 + 3840
    assert [count(tree[f"block_{i}"]) for i in range(4)] == [linear + swiglu] * 3 + [
        4 * 3840 * 3840 + 3 * 3840 + swiglu]
    assert count(tree["embed"]) == count(tree["lm_head"]) == 12544 * 3840
    flops = FAM.train_flops_per_token(cfg, 8192)
    assert flops["total"] == pytest.approx(5.50e9, rel=0.005)           # 45 TFLOP a step
    assert flops["attention"] / flops["total"] == pytest.approx(0.04, abs=0.005)


def test_cell_files_load_and_the_entries_were_appended():
    cell = spec.load_cell(CELL, ROOT)
    assert cell["config"] == "olmo-hybrid-7b" and cell["traffic"] == "sync_adag_8k_b1"
    assert cell["chips"] == 1
    sh = bench.shapes(cell)
    assert (sh["batch"], sh["seq_len"], sh["steps"], sh["vocab"]) == (1, 8192, 5, 12544)
    assert sh["rows_per_window"] * sh["seq_len"] == 40960
    with open(os.path.join(ROOT, "benchmark", "traffic", "sync_adag_8k.json")) as f:
        twin = json.load(f)
    mine = cell["traffic_file"]
    assert mine["constructor"] == dict(twin["constructor"], batch_size=1)
    assert {k: v for k, v in mine.items() if k not in ("name", "constructor", "data")} == {
        k: v for k, v in twin.items() if k not in ("name", "constructor", "data")}
    assert {k: v for k, v in mine["data"].items() if k != "note"} == {
        k: v for k, v in twin["data"].items() if k != "note"}
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-2:] == ["linattn_device_share", "linattn_scan_device_share"]
    assert {"compile_s", "train_call_fixed_s", "feed_load_ms_per_window", "engine_epoch_ms",
            "engine_host_ms", "feed_wait_ms_per_window", "step_device_ms", "step_mfu",
            "flash_fwd_roofline", "flash_bwd_roofline", "attn_device_share",
            "device_idle_share", "idle_attributed_share"} == set(names[:-2])
    assert {m["name"] for m in cell["end_to_end"]} == {"tokens_per_s_per_chip",
                                                       "loss_at_tokens", "setup_s"}
    for m in cell["per_layer"]:
        read, args = spec.load_reader(m["name"], ROOT)
        assert read({"trace": None, "counters": {}, "gauges": {}, "spans": {},
                     "histograms": {}}, **args) is None
    assert set(cell["check"]["limits"]) == {"loss_first", "first_gap", "change_gap", "rare_gap"}
    # the cell stands LAST in every list that names it, and is the fifth cell
    b = spec.load_benchmark(ROOT)
    assert [w["name"] for w in b["workloads"]][-1] == CELL and len(b["workloads"]) == 5
    assert all(w["chips"] == 1 for w in b["workloads"])
    assert b["configs"][-1]["name"] == "olmo-hybrid-7b"
    assert b["configs"][-1]["reduced"] == PUBLISHED["reduced"]
    for m in b["per_layer"] + b["end_to_end"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]


# -- whole runs of the harness --------------------------------------------------

@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The benchmark's data tree with the small configuration as a cell."""
    dst = str(tmp_path_factory.mktemp("olmo") / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    with open(os.path.join(dst, "benchmark", "configs", "olmo-hybrid-small.json"), "w") as f:
        json.dump(SMALL, f)
    b["configs"].append({"name": "olmo-hybrid-small", "source": "test", "reduced": [],
                         "file": "benchmark/configs/olmo-hybrid-small.json", "why": "test"})
    # the accepted traffic at a tenth of its rate: see the ADAG test above
    with open(os.path.join(dst, "benchmark", "traffic", "sync_adag.json")) as f:
        slow = json.load(f)
    slow["name"] = "sync_adag_slow"
    slow["constructor"].update(learning_rate=0.001, communication_window=2, batch_size=2)
    slow["steps_per_program"] = 2
    with open(os.path.join(dst, "benchmark", "traffic", "sync_adag_slow.json"), "w") as f:
        json.dump(slow, f)
    b["workloads"].append({"name": "olmo_small", "config": "olmo-hybrid-small",
                           "traffic": "sync_adag_slow", "chips": 1, "why": "test"})
    for m in b["per_layer"] + b["end_to_end"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("olmo_small")
    with open(os.path.join(dst, "benchmark", "workloads", "olmo_small.json"), "w") as f:
        # readings at this size (CPU, float32 both sides): the program against
        # the reference at most 2e-4 on the gaps; the decay left out 0.2 and more
        json.dump({"name": "olmo_small", "config": "olmo-hybrid-small",
                   "traffic": "sync_adag_slow",
                   "chips": 1, "why": "test", "windows_per_second": 20,
                   "loss_at_tokens": {"mark_windows": 2, "average_windows": 3},
                   "check": {"calls": [1, 1], "rare_min_rank": 40,
                             "limits": {"loss_first": 0.01, "first_gap": 0.01,
                                        "change_gap": 0.01, "rare_gap": 0.05}},
                   "trace": {"max_seconds": 5}}, f)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    return dst


def run_small(capsys, root, trace):
    rc = bench.main(["--workload", "olmo_small", "--seed", "3200000011", "--seconds", "0.2",
                     "--trace", str(trace)], skip_device_check=True, root=root)
    out, _ = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1])


def test_whole_traced_run_of_the_harness(capsys, small_root):
    line = run_small(capsys, small_root, 1)
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # the host clock's metrics exist on the CPU; the device-trace readers find
    # no device plane there and report nothing (never a 0)
    assert {"compile_s", "train_call_fixed_s", "engine_epoch_ms"} <= set(got)
    assert not set(got) & {"linattn_device_share", "linattn_scan_device_share",
                           "attn_device_share", "step_mfu"}


def test_the_decay_left_out_reads_not_correct(capsys, small_root, monkeypatch):
    """The planted fault: the program's scan runs with alpha = 1 (plain delta
    rule), everything else as it is."""
    from distkeras_tpu.ops import linear_attention

    real = linear_attention.gated_delta_rule
    monkeypatch.setattr(linear_attention, "gated_delta_rule",
                        lambda q, k, v, g, beta, **kw: real(q, k, v, jnp.zeros_like(g), beta,
                                                            **kw))
    line = run_small(capsys, small_root, 0)
    assert line["correct"] is False
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    assert "first_gap" in over and "change_gap" in over


def test_window_program_carries_the_scopes():
    """``attn.linear`` and its four parts are in the compiled program's
    ``op_name``s, forward and backward, beside the full layer's ``attn.full``."""
    ms = FAM.model_spec(SMALL)
    apply, loss = ms.apply_fn(), get_loss("sparse_categorical_crossentropy")
    x, y = rows(1)
    text = jax.jit(jax.grad(lambda p: loss(apply(p, x), y))).lower(
        jax.eval_shape(lambda: ms.init_params(0))).as_text(debug_info=True)
    for scope in ("attn.linear/attn.linear.proj", "attn.linear/attn.linear.conv",
                  "attn.linear/attn.linear.scan", "attn.linear/attn.linear.out", "attn.full",
                  # the recomputation under remat, and the backward pass
                  "checkpoint/block_0.<lambda>/block_0/attn.linear/attn.linear.scan",
                  "block_0/attn.linear/attn.linear.scan/transpose"):
        assert scope in text, scope
