"""The plain reference tied to ``TransformerLM`` at a tiny size, and the
control of the correctness check (kept here at a size a test run holds; on
the chip it was read at the cells' own sizes, see PERF.md)."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_fixtures import TINY, TINY_LIMITS  # noqa: E402

from benchmark.harness import check, program, spec, tokens  # noqa: E402

CFG = dict(TINY, family="gpt_lm")
REF = spec.load_reference(CFG)
FAM = spec.load_family(CFG)
SEED = 3_000_000_007          # more than 32 signed bits hold
RARE = tokens.rare_token_ids(512, 40)


def _rows(windows):
    rows = tokens.make_rows(windows * 20, 64, 512, SEED)
    return tuple(rows[k].reshape(windows, 5, 4, 64) for k in ("features", "label"))


@pytest.fixture(scope="module")
def followed():
    xs, ys = _rows(3)
    calls = [(xs[:1], ys[:1]), (xs[1:], ys[1:])]
    return calls, REF.follow(CFG, SEED, calls, lr=0.01, rare_rows=RARE)


def test_reference_matches_transformer_lm_loss_and_gradients():
    from distkeras_tpu.ops.losses import get_loss

    model = program.build_model(CFG, FAM, REF, SEED)
    apply, loss = model.spec.apply_fn(), get_loss("sparse_categorical_crossentropy")
    xs, ys = _rows(1)
    x, y = jnp.asarray(xs[0, 0]), jnp.asarray(ys[0, 0])
    p_loss, p_grads = jax.value_and_grad(lambda p: loss(apply(p, x), y))(model.params)
    params = jax.jit(lambda s: REF.init_params(CFG, s))(jnp.uint32(SEED % 2**32))
    with jax.default_matmul_precision("highest"):
        r_loss, r_grads = jax.value_and_grad(REF.batch_loss)(params, x, y)
    assert float(p_loss) == pytest.approx(float(r_loss), abs=0.05)   # bf16 logits
    mapped = FAM.from_program_tree(p_grads, CFG)
    assert set(mapped) == set(r_grads) == set(REF.param_shapes(CFG))
    for name, g in r_grads.items():
        got = np.asarray(mapped[name], np.float32)
        assert got.shape == REF.param_shapes(CFG)[name]
        err = np.linalg.norm(got - np.asarray(g)) / np.linalg.norm(np.asarray(g))
        assert err < 0.03, (name, err)      # the program computes in bfloat16


def test_program_tree_round_trip_is_pure_indexing():
    params = REF.init_params(CFG, 7)
    back = FAM.from_program_tree(FAM.to_program_tree(params, CFG), CFG)
    for name, v in params.items():
        assert np.array_equal(np.asarray(back[name]), np.asarray(v)), name
    assert not np.array_equal(np.asarray(REF.init_params(CFG, 8)["wte"]),
                              np.asarray(params["wte"]))


def test_same_seed_same_rows_and_a_large_seed_is_fine():
    a, b = tokens.make_rows(8, 64, 512, SEED), tokens.make_rows(8, 64, 512, SEED)
    assert np.array_equal(a["features"], b["features"])
    assert np.array_equal(a["features"][:, 1:], a["label"][:, :-1])
    assert 0 <= a["features"].min() and a["features"].max() < 512
    assert not np.array_equal(a["features"], tokens.make_rows(8, 64, 512, SEED + 1)["features"])
    # learnable structure: the unigram entropy lies well under ln(vocab)
    _, counts = np.unique(tokens.make_rows(64, 64, 512, 1)["features"], return_counts=True)
    p = counts / counts.sum()
    assert -(p * np.log(p)).sum() < 0.9 * np.log(512)


def test_reference_passes_its_own_limits_and_the_control_fails(followed):
    calls, ref = followed
    again = REF.follow(CFG, SEED, calls, lr=0.01, rare_rows=RARE)
    ok, compared = check.verdict(check.compare(again, ref), TINY_LIMITS)
    assert ok and all(c["value"] == 0.0 for c in compared.values())
    # the control: the reference in the program's place, matmul operands in fp8
    control = REF.follow(CFG, SEED, calls, lr=0.01, precision="fp8", rare_rows=RARE)
    ok, compared = check.verdict(check.compare(control, ref), TINY_LIMITS)
    assert not ok
    assert compared["first_gap"]["value"] > 2 * TINY_LIMITS["first_gap"]


@pytest.mark.parametrize("kw,number", [
    ({"rows": "half"}, "rare_gap"),           # half of the batch left out
    ({"self_staleness": 1}, "change_gap"),    # the exchange's rule broken
])
def test_planted_faults_in_the_reference_fail(followed, kw, number):
    calls, ref = followed
    other = REF.follow(CFG, SEED, calls, lr=0.01, rare_rows=RARE, **kw)
    ok, compared = check.verdict(check.compare(other, ref), TINY_LIMITS)
    assert not ok and compared[number]["value"] > 10 * TINY_LIMITS[number] / 5


def test_worst_leaf_gap_rules():
    ref = {"a": np.float32(1.0), "blocks.w": np.array([1.0, 2.0, 1e-9])}
    same = check.worst_leaf_gap(ref, ref)
    assert same == (0.0, "")
    # a leaf that did not move reads 1; a quiet reference leaf is left out
    still = {"a": np.float32(0.0), "blocks.w": np.array([1.0, 2.0, 5.0])}
    assert check.worst_leaf_gap(still, ref) == (1.0, "a")
    double = {"a": np.float32(2.0), "blocks.w": np.array([1.0, 2.0, 0.0])}
    assert check.worst_leaf_gap(double, ref)[0] == 1.0
    assert check.worst_leaf_gap({"a": np.float32(1.0)}, ref)[0] == float("inf")
    nan = {"a": np.float32("nan"), "blocks.w": np.array([1.0, 2.0, 0.0])}
    ok, _ = check.verdict({"loss_first": 0, "first_gap": check.worst_leaf_gap(nan, ref)[0],
                           "change_gap": 0, "rare_gap": 0}, TINY_LIMITS)
    assert not ok
