"""The harness's side of the test-only family ``other_lm`` (see
``reference.py`` beside this file): the program's ``TransformerLM`` with
``positional="rope"`` and ``num_kv_heads``, under Llama-style keys.  The
contract is ``benchmark/families/gpt_lm.py``'s."""

import os

from benchmark.harness import peaks, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FLASH = {"_fwd_kernel": "fwd", "_bwd_fused_kernel": "bwd"}
BLOCK = {"ln1_g": ("LayerNorm_0", "scale"), "ln1_b": ("LayerNorm_0", "bias"),
         "w_q": ("q", "kernel"), "w_kv": ("kv", "kernel"), "w_o": ("proj", "kernel"),
         "ln2_g": ("LayerNorm_1", "scale"), "ln2_b": ("LayerNorm_1", "bias"),
         "w_up": ("up", "kernel"), "w_down": ("down", "kernel")}


def model_spec(cfg):
    from distkeras_tpu.models.transformer import small_lm_spec

    if int(cfg["intermediate_size"]) != 4 * int(cfg["hidden_size"]):
        raise ValueError("the program's block has d_ffn = 4 * d_model only")
    return small_lm_spec(vocab_size=int(cfg["vocab_size"]),
                         model_dim=int(cfg["hidden_size"]),
                         num_heads=int(cfg["num_attention_heads"]),
                         num_kv_heads=int(cfg["num_key_value_heads"]),
                         num_layers=int(cfg["num_hidden_layers"]),
                         max_seq_len=int(cfg["max_position_embeddings"]),
                         positional="rope")


def to_program_tree(ref, cfg):
    tree = {"embed": {"embedding": ref["wte"]},
            "final_norm": {"scale": ref["lnf_g"], "bias": ref["lnf_b"]}}
    for i in range(int(cfg["num_hidden_layers"])):
        block = tree[f"block_{i}"] = {}
        for leaf, (module, name) in BLOCK.items():
            block.setdefault(module, {})[name] = ref["blocks." + leaf][i]
    return tree


def from_program_tree(tree, cfg):
    import jax.numpy as jnp

    out = {"wte": tree["embed"]["embedding"],
           "lnf_g": tree["final_norm"]["scale"], "lnf_b": tree["final_norm"]["bias"]}
    for leaf, (module, name) in BLOCK.items():
        out["blocks." + leaf] = jnp.stack(
            [tree[f"block_{i}"][module][name]
             for i in range(int(cfg["num_hidden_layers"]))])
    return out


def shapes(cfg, traffic):
    return {"seq_len": spec.job_seq_len(traffic, int(cfg["max_position_embeddings"])),
            "vocab": int(cfg["vocab_size"])}


def train_flops_per_token(cfg, seq_len):
    reference = spec.load_reference(cfg, ROOT)
    return peaks.train_flops_per_token(reference.matmul_params(cfg),
                                       int(cfg["num_hidden_layers"]), seq_len,
                                       int(cfg["hidden_size"]))


def kernel_work(cfg, kernel, batch, seq_len):
    # the program broadcasts the grouped key/value heads before the kernels
    if kernel not in FLASH:
        raise KeyError(f"family other_lm has no kernel named {kernel!r}")
    heads = int(cfg["num_attention_heads"])
    return peaks.flash_counts(FLASH[kernel], batch, heads, seq_len,
                              int(cfg["hidden_size"]) // heads)
