"""What the harness knows of the AFMoE decoder family (Trinity): everything
that reads a key of its ``config.json``.  The contract is
``families/gpt_lm.py``'s header; this file is its second tenant.

This family, as the program runs it: the registered ``transformer_lm`` with
its block taken from configuration — RMSNorm before and after each
sublayer, a head size set apart from the width, GQA with QK-norm and an
output gate, ``layer_types`` mixing ``sliding_attention`` (RoPE, window) and
``full_attention`` (no positional signal), leading dense SwiGLU layers, then
the sigmoid-routed expert layer with a shared expert
(``parallel/moe.py::HeldExpertsMLP``) holding ``experts_held`` of the
``router_outputs`` experts, an untied head, the embedding scaled by
``sqrt(hidden_size)``.  Attention runs through the two flash kernels
``_fwd_kernel`` and ``_bwd_fused_kernel`` (K and V repeated to the query
head count before the call, so the kernels read them at H heads); the
experts run through ``lax.ragged_dot``, which XLA lowers itself: there is
no kernel of the repo's to bound.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.harness import spec

FLASH = {"_fwd_kernel": "fwd", "_bwd_fused_kernel": "bwd"}
# reference leaf (short name) -> path in the program's block
ATTN = {"g_attn_in": ("attn_norm", "scale"), "g_attn_out": ("attn_post_norm", "scale"),
        "g_ffn_in": ("ffn_norm", "scale"), "g_ffn_out": ("ffn_post_norm", "scale"),
        "w_q": ("q", "kernel"), "w_g": ("gate", "kernel"), "w_o": ("proj", "kernel"),
        "g_q": ("q_norm", "scale"), "g_k": ("k_norm", "scale")}
DENSE = {"w1": ("gate_proj", "kernel"), "w3": ("up", "kernel"), "w2": ("down", "kernel")}
MOE = {"w_router": ("experts", "router"), "bias": ("experts", "router_bias"),
       "w1": ("experts", "w_gate"), "w3": ("experts", "w_up"), "w2": ("experts", "w_down"),
       "shared_w1": ("experts", "shared_gate", "kernel"),
       "shared_w3": ("experts", "shared_up", "kernel"),
       "shared_w2": ("experts", "shared_down", "kernel")}


def _paths(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    return dict(ATTN, **(DENSE if i < int(cfg["num_dense_layers"]) else MOE))


def model_spec(cfg: Dict[str, Any]):
    from distkeras_tpu.models.base import ModelSpec

    if (cfg["score_func"] != "sigmoid" or not cfg["mup_enabled"] or not cfg["route_norm"]
            or int(cfg["num_shared_experts"]) != 1):
        raise ValueError("the program's expert layer routes by sigmoid scores normalised "
                         "over the selected ones (route_norm), beside ONE shared expert, "
                         "and scales the embedding (mup_enabled)")
    positions = int(cfg["max_position_embeddings"])
    return ModelSpec(
        name="transformer_lm",
        config={
            "vocab_size": int(cfg["vocab_size"]), "model_dim": int(cfg["hidden_size"]),
            "num_heads": int(cfg["num_attention_heads"]),
            "num_kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]), "num_layers": int(cfg["num_hidden_layers"]),
            "max_seq_len": positions, "positional": "rope", "rope_layers": "sliding",
            "rope_theta": float(cfg["rope_theta"]),
            "layer_types": tuple(cfg["layer_types"]),
            "sliding_window": int(cfg["sliding_window"]),
            "norm": "rmsnorm", "norm_eps": float(cfg["rms_norm_eps"]),
            "qk_norm": True, "attn_gate": True, "post_norm": True,
            "mlp": "swiglu", "mlp_dim": int(cfg["intermediate_size"]),
            "num_dense_layers": int(cfg["num_dense_layers"]),
            "routed_experts": int(cfg["router_outputs"]),
            "experts_held": tuple(int(v) for v in cfg["experts_held"]),
            "routed_top_k": int(cfg["num_experts_per_tok"]),
            "routed_dim": int(cfg["moe_intermediate_size"]),
            "route_scale": float(cfg["route_scale"]),
            "route_balance_coeff": float(cfg["load_balance_coeff"]),
            "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
            "embed_scale": math.sqrt(int(cfg["hidden_size"])),
            "remat": bool(cfg.get("remat", False)),
            "compute_dtype": cfg["stated_precision"]["compute_dtype"],
        },
        input_shape=(positions,), input_dtype="int32")


def to_program_tree(ref: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Reference leaves -> the parameter tree ``TransformerLM`` builds.  Pure
    indexing: no arithmetic (K and V stack into the program's ``kv``, the
    head transposes)."""
    import jax.numpy as jnp

    tree = {"embed": {"embedding": ref["wte"]}, "lm_head": {"kernel": ref["lm_head"].T},
            "final_norm": {"scale": ref["lnf_g"]}}
    for i in range(int(cfg["num_hidden_layers"])):
        block = {"kv": {"kernel": jnp.stack([ref[f"layers.{i}.w_k"], ref[f"layers.{i}.w_v"]],
                                            axis=1)}}
        for name, path in _paths(cfg, i).items():
            node = block
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = ref[f"layers.{i}.{name}"]
        tree[f"block_{i}"] = block
    return tree


def from_program_tree(tree: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The inverse, traceable."""
    ref = {"wte": tree["embed"]["embedding"], "lm_head": tree["lm_head"]["kernel"].T,
           "lnf_g": tree["final_norm"]["scale"]}
    for i in range(int(cfg["num_hidden_layers"])):
        block = tree[f"block_{i}"]
        ref[f"layers.{i}.w_k"] = block["kv"]["kernel"][:, 0]
        ref[f"layers.{i}.w_v"] = block["kv"]["kernel"][:, 1]
        for name, path in _paths(cfg, i).items():
            node = block
            for key in path:
                node = node[key]
            ref[f"layers.{i}.{name}"] = node
    return ref


def shapes(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, int]:
    return {"seq_len": spec.job_seq_len(traffic, int(cfg["max_position_embeddings"])),
            "vocab": int(cfg["vocab_size"])}


def score_pairs(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Score pairs one head of one row needs, by layer kind: ``j <= i`` is
    ``i + 1`` keys for query ``i``, the window at most ``sliding_window``."""
    window = int(cfg["sliding_window"])
    full = seq_len * (seq_len + 1) / 2.0
    w = min(window, seq_len)
    return {"full_attention": full,
            "sliding_attention": w * (w + 1) / 2.0 + (seq_len - w) * float(w)}


def matmul_params_per_token(cfg: Dict[str, Any]) -> Dict[str, float]:
    """The matmul parameters a token multiplies.  The routed part is an
    EXPECTATION: of a token's ``num_experts_per_tok`` choices over
    ``router_outputs`` experts, ``held / router_outputs`` land on this chip
    when the router is balanced (what the bias drives it to)."""
    e, h, hkv, d = (int(cfg[k]) for k in ("hidden_size", "num_attention_heads",
                                          "num_key_value_heads", "head_dim"))
    n, nd = int(cfg["num_hidden_layers"]), int(cfg["num_dense_layers"])
    lo, hi = cfg["experts_held"]
    r, k, fm = (int(cfg[x]) for x in ("router_outputs", "num_experts_per_tok",
                                      "moe_intermediate_size"))
    expert = 3 * e * fm
    return {"attention": n * e * d * (3 * h + 2 * hkv),          # q, gate, o; k, v
            "dense_mlp": nd * 3 * e * int(cfg["intermediate_size"]),
            "shared": (n - nd) * expert * int(cfg["num_shared_experts"]),
            "router": (n - nd) * e * r,
            "routed_expected": (n - nd) * expert * k * (hi - lo) / r,
            "head": e * int(cfg["vocab_size"])}


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """6 FLOPs per matmul parameter a token multiplies; attention 12 FLOPs
    a score pair and unit of head size (QK^T and PV, forward and twice
    backward), over the pairs each layer's mask needs.  Never the
    recomputation."""
    dense = 6.0 * sum(matmul_params_per_token(cfg).values())
    pairs = score_pairs(cfg, seq_len)
    per_row = sum(pairs[kind] for kind in cfg["layer_types"])
    attention = 12.0 * per_row * int(cfg["num_attention_heads"]) * int(cfg["head_dim"]) / seq_len
    return {"dense": dense, "attention": attention, "total": dense + attention}


def kernel_work(cfg: Dict[str, Any], kernel: str, batch: int, seq_len: int
                ) -> Dict[str, float]:
    """The MEAN call of a flash kernel over the calls a step makes of it:
    one a layer, window layers and full layers at their own pairs.  Bytes
    as the kernel reads them: q, k, v and o (and do, dq, dk, dv backward) at
    the QUERY head count, because the program repeats K and V before the
    call."""
    if kernel not in FLASH:
        raise KeyError(f"family afmoe_lm has no kernel named {kernel!r}; "
                       f"it has {sorted(FLASH)}")
    h, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    pairs = score_pairs(cfg, seq_len)
    mean_pairs = sum(pairs[kind] for kind in cfg["layer_types"]) / len(cfg["layer_types"])
    fwd = 4.0 * mean_pairs * d * batch * h            # QK^T and PV, 2 FLOPs a MAC
    tensor = batch * h * seq_len * d * 2
    if FLASH[kernel] == "fwd":
        return {"flops": fwd, "bytes": 4.0 * tensor}
    return {"flops": 2.5 * fwd, "bytes": 8.0 * tensor}
