"""What the harness knows of the Olmo-Hybrid decoder family: everything that
reads a key of its ``config.json``.  The contract is ``families/gpt_lm.py``'s
header; this file is its third tenant.

This family, as the program runs it: the registered ``transformer_lm`` with
its block taken from configuration — RMSNorm AFTER each sublayer only
(``pre_norm`` off, ``post_norm`` on), ``layer_types`` mixing
``linear_attention`` (the gated delta rule: ``ops/linear_attention.py``
behind ``TransformerBlock._linear_attention``) and ``full_attention`` (the
fused-qkv multi-head path at heads of ``hidden_size / num_attention_heads``,
QK-norm over the whole projection, no positional signal), a SwiGLU at the
stated width, an untied float32 head.  The full layers run through the two
flash kernels ``_fwd_kernel`` and ``_bwd_fused_kernel``; the linear layers'
scan is plain ``lax``: there is no kernel of the repo's to bound there.
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.harness import peaks, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FLASH = {"_fwd_kernel": "fwd", "_bwd_fused_kernel": "bwd"}
# reference leaf (short name) -> path in the program's block
COMMON = {"g_attn_out": ("attn_post_norm", "scale"), "g_ffn_out": ("ffn_post_norm", "scale"),
          "w1": ("gate_proj", "kernel"), "w3": ("up", "kernel"), "w2": ("down", "kernel")}
FULL = {"w_o": ("proj", "kernel"), "g_q": ("q_norm", "scale"), "g_k": ("k_norm", "scale")}
LINEAR = {"w_q": ("lin_q", "kernel"), "w_k": ("lin_k", "kernel"), "w_v": ("lin_v", "kernel"),
          "w_g": ("lin_gate", "kernel"), "w_o": ("lin_out", "kernel"),
          "w_a": ("lin_a",), "w_b": ("lin_b",), "c_q": ("conv_q",), "c_k": ("conv_k",),
          "c_v": ("conv_v",), "a_log": ("A_log",), "dt_bias": ("dt_bias",),
          "g_o": ("lin_norm", "scale")}


def _paths(kind: str) -> Dict[str, tuple]:
    return dict(COMMON, **(FULL if kind == "full_attention" else LINEAR))


def model_spec(cfg: Dict[str, Any]):
    from distkeras_tpu.models.base import ModelSpec

    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["rope_parameters"]["rope_theta"] is not None
            or int(cfg["num_key_value_heads"]) != int(cfg["num_attention_heads"])
            or int(cfg["linear_num_key_heads"]) != int(cfg["linear_num_value_heads"])):
        raise ValueError("the program's block for this family has a SwiGLU, no bias, no "
                         "rotary, and as many key/value heads as query heads in both kinds "
                         "of layer")
    positions = int(cfg["max_position_embeddings"])
    return ModelSpec(
        name="transformer_lm",
        config={
            "vocab_size": int(cfg["vocab_size"]), "model_dim": int(cfg["hidden_size"]),
            "num_heads": int(cfg["num_attention_heads"]),
            "num_layers": int(cfg["num_hidden_layers"]),
            "max_seq_len": positions, "positional": "none",
            "layer_types": tuple(cfg["layer_types"]),
            "norm": "rmsnorm", "norm_eps": float(cfg["rms_norm_eps"]),
            "qk_norm": "full", "pre_norm": False, "post_norm": True,
            "mlp": "swiglu", "mlp_dim": int(cfg["intermediate_size"]),
            "linear_num_heads": int(cfg["linear_num_value_heads"]),
            "linear_key_dim": int(cfg["linear_key_head_dim"]),
            "linear_value_dim": int(cfg["linear_value_head_dim"]),
            "linear_conv_width": int(cfg["linear_conv_kernel_dim"]),
            "linear_neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
            "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
            "remat": bool(cfg.get("remat", False)),
            "compute_dtype": cfg["stated_precision"]["compute_dtype"],
        },
        input_shape=(positions,), input_dtype="int32")


def to_program_tree(ref: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Reference leaves -> the parameter tree ``TransformerLM`` builds.  Pure
    indexing: no arithmetic (a full layer's q, k and v stack into the
    program's fused ``qkv``, the head transposes)."""
    import jax.numpy as jnp

    tree = {"embed": {"embedding": ref["wte"]}, "lm_head": {"kernel": ref["lm_head"].T},
            "final_norm": {"scale": ref["lnf_g"]}}
    for i, kind in enumerate(cfg["layer_types"]):
        block = {}
        if kind == "full_attention":
            block["qkv"] = {"kernel": jnp.stack(
                [ref[f"layers.{i}.{w}"] for w in ("w_q", "w_k", "w_v")], axis=1)}
        for name, path in _paths(kind).items():
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
    for i, kind in enumerate(cfg["layer_types"]):
        block = tree[f"block_{i}"]
        if kind == "full_attention":
            for n, w in enumerate(("w_q", "w_k", "w_v")):
                ref[f"layers.{i}.{w}"] = block["qkv"]["kernel"][:, n]
        for name, path in _paths(kind).items():
            node = block
            for key in path:
                node = node[key]
            ref[f"layers.{i}.{name}"] = node
    return ref


def shapes(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, int]:
    return {"seq_len": spec.job_seq_len(traffic, int(cfg["max_position_embeddings"])),
            "vocab": int(cfg["vocab_size"])}


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """6 FLOPs per matmul parameter a token multiplies (every kernel and the
    head; not the embedding, the convolutions' taps, gains and per-head
    vectors).  A full layer: 12 FLOPs a score pair and unit of head size
    (QK^T and PV, forward and twice backward) over the causal pairs.  A
    linear layer by the RECURRENT form: three ``d_k x d_v`` products a token
    a head forward (S k, the rank-one update, S q), 2 FLOPs a
    multiply-add, times three for training: ``18 d_k d_v`` a head; never the
    chunked form's extra arithmetic, never the recomputation."""
    reference = spec.load_reference(cfg, ROOT)
    dense = 6.0 * reference.matmul_params(cfg)
    kinds = list(cfg["layer_types"])
    head = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    pairs = seq_len * (seq_len + 1) / 2.0
    full = (12.0 * pairs * int(cfg["num_attention_heads"]) * head / seq_len
            * kinds.count("full_attention"))
    linear = (18.0 * int(cfg["linear_key_head_dim"]) * int(cfg["linear_value_head_dim"])
              * int(cfg["linear_num_value_heads"]) * kinds.count("linear_attention"))
    return {"dense": dense, "attention": full + linear, "total": dense + full + linear}


def kernel_work(cfg: Dict[str, Any], kernel: str, batch: int, seq_len: int
                ) -> Dict[str, float]:
    """One call of a flash kernel: every call a step makes is a full layer's,
    all heads, no head repeated (``harness.peaks.flash_counts``)."""
    if kernel not in FLASH:
        raise KeyError(f"family olmo_hybrid_lm has no kernel named {kernel!r}; "
                       f"it has {sorted(FLASH)}")
    heads = int(cfg["num_attention_heads"])
    return peaks.flash_counts(FLASH[kernel], batch, heads, seq_len,
                              int(cfg["hidden_size"]) // heads)
