"""The device-time account (``obs.device_account``): every instruction of the
window program sorted by the part of the step it belongs to and the pass it
runs in, off the program ``ADAG.train`` really compiled (CPU, tiny shapes, no
compile cache: an executable cached before a scope was added keeps its old
``op_name``s)."""

import collections

import numpy as np
import pytest

from distkeras_tpu import observability as obs
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.models.transformer import small_lm_spec
from distkeras_tpu.observability.account import PASSES, account, account_of, opcode_of
from distkeras_tpu.trainers import ADAG

PROGRAM = "jit_shard_fn"
# window losses of seed 0 taken on the commit BEFORE the scopes were added
# (99ffbf0, this machine): a named scope changes no arithmetic
PINNED = {"dense": [4.582917213439941, 4.621285438537598, 4.7457990646362305],
          "routed": [4.608879566192627, 4.621421813964844, 4.690497398376465]}
DENSE_PARTS = {"attn.full", "ffn.dense", "lm.embed", "lm.head", "step.loss", "step.update",
               "step.commit", "block.other"}
ROUTED_PARTS = DENSE_PARTS | {"moe.route", "moe.dispatch", "moe.experts", "moe.shared",
                              "moe.combine", "moe.bias"}


def dense_spec(remat: bool) -> ModelSpec:
    spec = small_lm_spec(vocab_size=64, model_dim=32, num_heads=2, num_layers=2, max_seq_len=16,
                         remat=remat)
    spec.config["compute_dtype"] = "float32"
    return spec


def routed_spec() -> ModelSpec:
    """One dense and one expert layer (held experts, a shared one, the bias
    hook), post-norms, an untied head, remat'd."""
    cfg = {"vocab_size": 64, "model_dim": 32, "num_heads": 2, "num_kv_heads": 1, "head_dim": 8,
           "num_layers": 2, "max_seq_len": 16, "positional": "rope", "norm": "rmsnorm",
           "mlp": "swiglu", "mlp_dim": 48, "num_dense_layers": 1, "routed_experts": 4,
           "experts_held": (0, 2), "routed_top_k": 2, "routed_dim": 16, "n_shared_experts": 1,
           "remat": True, "post_norm": True, "route_balance_coeff": 0.001,
           "tie_word_embeddings": False, "compute_dtype": "float32"}
    return ModelSpec(name="transformer_lm", config=cfg, input_shape=(16,), input_dtype="int32")


SPECS = {"dense": lambda: dense_spec(True), "plain": lambda: dense_spec(False),
         "routed": routed_spec}


@pytest.fixture(scope="module")
def trained():
    """{which: (window losses, account, {instruction: op_name})}, each through
    ``ADAG.train`` on two replicas with telemetry on."""
    import jax

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    out = {}
    obs.enable()
    try:
        for which, build in SPECS.items():
            rng = np.random.default_rng(0)
            toks = rng.integers(0, 64, (2 * 4 * 2 * 3, 16)).astype(np.int32)
            ds = Dataset({"features": toks, "label": np.roll(toks, -1, 1).astype(np.int32)})
            spec = build()
            trainer = ADAG(Model(spec=spec, params=spec.init_params(seed=0)), num_workers=2,
                           batch_size=4, communication_window=2, learning_rate=0.01, seed=0,
                           loss="sparse_categorical_crossentropy")
            trainer.train(ds, shuffle=False)
            out[which] = ([float(x) for x in trainer.history], obs.device_account(PROGRAM),
                          obs.device_scopes(PROGRAM))
    finally:
        obs.disable()
        obs.reset()
        jax.config.update("jax_enable_compilation_cache", cache)
    return out


@pytest.mark.parametrize("which", ["dense", "routed"])
def test_the_scopes_change_no_arithmetic(trained, which):
    assert trained[which][0] == PINNED[which]
    assert trained["plain"][0] == PINNED["dense"]          # remat changes none either


@pytest.mark.parametrize("which,parts", [("dense", DENSE_PARTS), ("routed", ROUTED_PARTS)])
def test_every_part_has_instructions(trained, which, parts):
    count = collections.Counter(part for part, _ in trained[which][1].table.values())
    assert parts <= set(count), sorted(parts - set(count))
    assert set(count) <= parts | {"none"}, sorted(set(count) - parts)


@pytest.mark.parametrize("which", ["dense", "routed"])
def test_the_three_passes_are_there_and_follow_the_markers(trained, which):
    _, acct, scopes = trained[which]
    assert {pas for _, pas in acct.table.values()} == set(PASSES)
    fusions = set(acct.mixed)            # a fusion may count as the matmul in its body
    for name, op_name in scopes.items():
        if name in fusions or name not in acct.table:
            continue
        pas = acct.table[name][1]
        if "rematted_computation" in op_name:
            assert pas == "recompute", (name, op_name)
        elif "transpose(" in op_name:
            assert pas == "backward", (name, op_name)
    # a remat'd block is made again: its dense MLP's matmul has all three
    ffn = {pas for part, pas in acct.table.values() if part == "ffn.dense"}
    assert ffn == {"forward", "recompute", "backward"}
    # the vocabulary's parts lie outside the remat'd blocks
    for part in ("lm.embed", "lm.head", "step.loss"):
        assert {q for p, q in acct.table.values() if p == part} <= {"forward", "backward"}


@pytest.mark.parametrize("which", ["dense", "plain", "routed"])
def test_update_and_commit_are_of_no_pass(trained, which):
    table = trained[which][1].table
    for part in ("step.update", "step.commit", "moe.bias"):
        passes = {q for p, q in table.values() if p == part}
        assert passes == ({"other"} if part != "moe.bias" or which == "routed" else set())


def test_without_remat_nothing_is_recomputed(trained):
    passes = collections.Counter(pas for _, pas in trained["plain"][1].table.values())
    assert passes["recompute"] == 0 and passes["forward"] and passes["backward"]
    assert collections.Counter(p for _, p in trained["dense"][1].table.values())["recompute"]


def test_no_program_noted_reads_none_and_a_new_note_drops_the_old_account():
    assert obs.device_account("jit_never_noted") is None
    obs.note_program("jit_t", lambda: TEXT)
    try:
        first = obs.device_account("jit_t")
        assert obs.device_account("jit_t") is first            # kept, not parsed again
        obs.note_program("jit_t", TEXT.replace("ffn.dense", "lm.head"))
        assert obs.device_account("jit_t").table["fusion.1"] == ("lm.head", "forward")
    finally:
        obs._PROGRAMS.pop("jit_t", None)
        obs._ACCOUNTS.pop("jit_t", None)


def test_an_executable_cached_before_a_scope_was_added_is_compiled_again(tmp_path):
    """JAX's persistent cache leaves metadata out of its key: the program with
    a new scope is served the executable compiled without it.  The engine's
    ask for the compiled text notices the missing scope and compiles anew,
    and the new text names the same instructions."""
    import contextlib
    import re

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from distkeras_tpu.parallel.engine import _compiled_text

    def make(scoped: bool):
        def step(x):
            with jax.named_scope("step.commit") if scoped else contextlib.nullcontext():
                return jnp.sin(x) @ x
        return jax.jit(step)

    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.ones((8, 8), jnp.float32)
    try:
        for k, v in zip(keys, (str(tmp_path), 0, -1, True)):
            jax.config.update(k, v)
        cc.reset_cache()
        make(False)(x).block_until_ready()              # the parent's program fills the cache
        new = make(True)
        new(x).block_until_ready()
        stale = new.lower(x).compile().as_text()
        if "step.commit" in stale:
            pytest.skip("this backend's persistent cache did not serve the old executable")
        text = _compiled_text(new, (x,))
        assert "step.commit" in text and "step.commit" not in stale
        names = lambda t: re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = ", t, re.M)
        assert names(text) == names(stale)
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


# -- the rules on a hand-written module --------------------------------------------------

STEP = "jit(shard_fn)/while/body/closed_call/while/body/closed_call/"
BWD = STEP + "transpose(jvp(TransformerLM))/TransformerLM._trunk/jvp(TransformerLM)/" \
    "TransformerLM._trunk/checkpoint/"


def meta(op_name: str) -> str:
    return 'metadata={op_type="x" op_name="%s" source_file="f.py"}' % op_name


TEXT = "\n".join([
    "HloModule jit_shard_fn",
    "",
    "%fused_computation.1 (p.0: f32[8,8]) -> f32[8,8] {",
    "  %p.0 = f32[8,8]{1,0} parameter(0)",
    "  ROOT %mul.1 = f32[8,8]{1,0} multiply(%p.0, %p.0), "
    + meta(STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_0.<lambda>/block_0/ffn.dense/mul"),
    "}",
    "",
    "%fused_computation.2 (p.1: f32[8,8], p.2: f32[8,8]) -> f32[8,8] {",
    "  %p.1 = f32[8,8]{1,0} parameter(0)",
    "  %p.2 = f32[8,8]{1,0} parameter(1)",
    "  %convolution.5 = f32[8,8]{1,0} convolution(%p.1, %p.2), dim_labels=bf_io->bf, "
    + meta(BWD + "block_0.<lambda>/block_0/ffn.dense/up/transpose/dot_general"),
    "  ROOT %add.9 = f32[8,8]{1,0} add(%p.1, %convolution.5), " + meta(STEP + "step.update/add"),
    "}",
    "",
    "%fused_computation.3 (p.3: f32[8,8]) -> f32[8,8] {",
    "  %p.3 = f32[8,8]{1,0} parameter(0)",
    "  %exp.1 = f32[8,8]{1,0} exponential(%p.3), "
    + meta(BWD + "rematted_computation/block_1.<lambda>/block_1/attn.full/exp"),
    "  ROOT %mul.7 = f32[8,8]{1,0} multiply(%exp.1, %p.3), "
    + meta(BWD + "block_1.<lambda>/block_1/attn.full/mul"),
    "}",
    "",
    "%fused_computation.4 (p.4: f32[8,8]) -> f32[8,8] {",
    "  %p.4 = f32[8,8]{1,0} parameter(0)",
    "  ROOT %dot.3 = f32[8,8]{1,0} dot(%p.4, %p.4), lhs_contracting_dims={1}, "
    + meta(BWD + "rematted_computation/block_0.<lambda>/block_0/experts/moe.shared/dot_general"),
    "}",
    "",
    "%fused_computation.5 (p.5: f32[8,8]) -> f32[8,8] {",
    "  %p.5 = f32[8,8]{1,0} parameter(0)",
    "  %fusion.40 = f32[8,8]{1,0} fusion(%p.5), kind=kOutput, calls=%fused_computation.4",
    "  ROOT %neg.2 = f32[8,8]{1,0} negate(%fusion.40), "
    + meta(BWD + "block_0.<lambda>/block_0/experts/moe.combine/neg"),
    "}",
    "",
    "%fused_computation.6 (p.6: f32[8,8]) -> f32[8,8] {",
    "  %p.6 = f32[8,8]{1,0} parameter(0)",
    "  %exp.6 = f32[8,8]{1,0} exponential(%p.6), " + meta(STEP + "jvp(step.loss)/exp"),
    "  ROOT %log.6 = f32[8,8]{1,0} log(%exp.6), " + meta(STEP + "jvp(step.loss)/log"),
    "}",
    "",
    "ENTRY %main.1 (a: f32[8,8]) -> f32[8,8] {",
    "  %fusion.50 = f32[8,8]{1,0} fusion(%a), kind=kCustom, calls=%fused_computation.5",
    "  %fusion.60 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.6",
    "  %a = f32[8,8]{1,0} parameter(0), " + meta("state.local[\\'up\\'][\\'kernel\\']"),
    "  %fusion.1 = f32[8,8]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1, "
    + meta(STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_0.<lambda>/block_0/ffn.dense/mul"),
    "  %multiply_add_fusion = f32[8,8]{1,0} fusion(%a, %fusion.1), kind=kOutput, "
    "calls=%fused_computation.2, " + meta(STEP + "step.update/add"),
    "  %fusion.3 = f32[8,8]{1,0} fusion(%multiply_add_fusion), kind=kLoop, "
    "calls=%fused_computation.3, " + meta(BWD + "block_1.<lambda>/block_1/attn.full/mul"),
    "  %bitcast.4 = f32[8,8]{1,0} bitcast(%fusion.3)",
    '  %ragged-dot-none.2 = f32[8,8]{1,0} custom-call(%bitcast.4, %a), '
    'custom_call_target="tpu_custom_call", ' + 'metadata={op_name="ragged-dot-none"}',
    "  %copy.8 = f32[8,8]{0,1} copy(%ragged-dot-none.2), "
    + meta("jit(shard_fn)/while/body/closed_call/while"),
    "  %cond.3 = (f32[8,8]{1,0}, s32[]) conditional(%copy.8), branch_computations={%b0, %b1}, "
    + meta(STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_1.<lambda>/block_1/experts/"
           "moe.dispatch/cond"),
    "  ROOT %while.2 = (f32[8,8]{1,0}, s32[]) while(%cond.3), condition=%c, body=%b",
    "}",
])


def test_the_rules_on_a_hand_written_module():
    acct = account(TEXT)
    t = acct.table
    assert t["fusion.1"] == ("ffn.dense", "forward")
    # an update fused onto a weight gradient's matmul is the matmul's time ...
    assert t["multiply_add_fusion"] == ("ffn.dense", "backward")
    # ... and the fusion is named among the mixed ones, by part
    assert acct.mixed["multiply_add_fusion"] == (("ffn.dense", "step.update"), ("backward",), True)
    # no matmul inside: the root's op_name; mixed by pass
    assert t["fusion.3"] == ("attn.full", "backward")
    assert acct.mixed["fusion.3"] == (("attn.full",), ("backward", "recompute"), False)
    assert "fusion.1" not in acct.mixed
    # a fusion nested in a fusion's body brings its matmul along; XLA gave the
    # outer one no op_name of its own
    assert t["fusion.50"] == ("moe.shared", "recompute")
    assert acct.mixed["fusion.50"] == (("moe.combine", "moe.shared"), ("backward", "recompute"), True)
    # no op_name and no matmul: the last instruction of the body that has one
    assert t["fusion.60"] == ("step.loss", "forward") and "fusion.60" not in acct.mixed
    # XLA's grouped matmul: the part by its name, the pass its operands' latest,
    # seen through the bitcast
    assert t["ragged-dot-none.2"] == ("moe.experts", "backward")
    assert t["copy.8"] == ("none", "other") and t["a"] == ("none", "other")
    assert t["cond.3"] == ("moe.dispatch", "forward")
    assert acct.containers == {"cond.3", "while.2"} and "bitcast.4" not in t
    assert acct.text_bytes == len(TEXT)


@pytest.mark.parametrize("op_name,want", [
    (STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_3.<lambda>/block_3/attn.linear/"
     "attn.linear.scan/while/body/mul", ("attn.linear.scan", "forward")),
    (BWD + "rematted_computation/block_2.<lambda>/block_2/attn.latent/attn.latent.rope/concatenate",
     ("attn.latent.rope", "recompute")),
    (BWD + "block_0.<lambda>/block_0/attn.sliding/_bwd_fused_kernel/pallas_call",
     ("attn.sliding", "backward")),
    (STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_0.<lambda>/block_0/attn.sliding/"
     "_fwd_kernel/pallas_call", ("attn.sliding", "forward")),
    (STEP + "jvp(TransformerLM)/TransformerLM._trunk/block_1.<lambda>/block_1/add",
     ("block.other", "forward")),
    (BWD + "rematted_computation/blocks_1.<lambda>/blocks_1/ffn_norm/mul",
     ("block.other", "recompute")),
    (STEP + "jvp(TransformerLM)/TransformerLM._trunk/TransformerLM.embed_tokens/lm.embed/embed/"
     "jit(_take)/gather", ("lm.embed", "forward")),
    (STEP + "transpose(jvp(TransformerLM))/TransformerLM.head/lm.head/final_norm/mul",
     ("lm.head", "backward")),
    # the module path alone (``TransformerLM.head``) is no scope of the account's
    (STEP + "transpose(jvp(TransformerLM))/TransformerLM.head/final_norm/mul", ("none", "backward")),
    (STEP + "jvp(step.loss)/jit(take_along_axis)/gather", ("step.loss", "forward")),
    (STEP + "transpose(jvp(step.loss))/scatter-add", ("step.loss", "backward")),
    (STEP + "step.update/moe.bias/sign", ("moe.bias", "other")),
    ("jit(shard_fn)/while/body/closed_call/step.commit/psum_invariant", ("step.commit", "other")),
    ("jit(shard_fn)/while/body/dynamic_slice", ("none", "other")),
    ("", ("none", "other")),
])
def test_account_of_an_op_name(op_name, want):
    assert account_of("fusion.7", op_name) == want


def test_opcode_of_an_instruction_text():
    assert opcode_of("f32[8,8]{1,0:T(8,128)} fusion(f32[8]{0} %a), kind=kLoop") == "fusion"
    assert opcode_of("(s32[]{:T(128)}, f32[6,4]{1,0:T(8,128)(2,1)S(1)}) while(%t), body=%b") \
        == "while"
    assert opcode_of('(bf16[4]{0}, f32[4]{0}) custom-call(%x), custom_call_target="t"') \
        == "custom-call"
    assert opcode_of("(s32[]{:T(128)}, f32[1536]{0:T(1024)}, /*index=5*/f32[61") == ""
