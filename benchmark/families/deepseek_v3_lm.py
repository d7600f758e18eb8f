"""What the harness knows of the DeepSeek-V3 decoder family without the
query's low-rank path (Kanana-2): everything that reads a key of its
``config.json``.  The contract is ``families/gpt_lm.py``'s header; this
file is its fourth tenant.

This family, as the program runs it: the registered ``transformer_lm`` with
its block taken from configuration — RMSNorm before each sublayer, every
layer of kind ``latent`` (``models/transformer.py::TransformerBlock.
_latent_attention``: keys and values through a shared latent of
``kv_lora_rank``, one rotary key a token broadcast to the heads, adjacent-
pair RoPE, queries and keys of ``qk_nope_head_dim + qk_rope_head_dim``
against values of ``v_head_dim``), ``first_k_dense_replace`` leading dense
SwiGLU layers, then the sigmoid-routed expert layer
(``parallel/moe.py::HeldExpertsMLP``) holding ``experts_held`` of the
``router_outputs`` experts beside ONE shared SwiGLU of ``n_shared_experts``
times the routed width, an untied head.  Attention runs through the two
flash kernels ``_fwd_kernel`` and ``_bwd_fused_kernel`` at the two head
sizes; the experts run through ``lax.ragged_dot``, which XLA lowers
itself: there is no kernel of the repo's to bound.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness import spec

FLASH = {"_fwd_kernel": "fwd", "_bwd_fused_kernel": "bwd"}
# reference leaf (short name) -> path in the program's block
ATTN = {"g_attn": ("attn_norm", "scale"), "g_ffn": ("ffn_norm", "scale"),
        "w_q": ("q", "kernel"), "w_dkv": ("kv_down", "kernel"), "g_c": ("kv_norm", "scale"),
        "w_ukv": ("kv_up", "kernel"), "w_o": ("proj", "kernel")}
DENSE = {"w1": ("gate_proj", "kernel"), "w3": ("up", "kernel"), "w2": ("down", "kernel")}
MOE = {"w_router": ("experts", "router"), "bias": ("experts", "router_bias"),
       "w1": ("experts", "w_gate"), "w3": ("experts", "w_up"), "w2": ("experts", "w_down"),
       "shared_w1": ("experts", "shared_gate", "kernel"),
       "shared_w3": ("experts", "shared_up", "kernel"),
       "shared_w2": ("experts", "shared_down", "kernel")}


def _paths(cfg: Dict[str, Any], i: int) -> Dict[str, tuple]:
    return dict(ATTN, **(DENSE if i < int(cfg["first_k_dense_replace"]) else MOE))


def model_spec(cfg: Dict[str, Any]):
    from distkeras_tpu.models.base import ModelSpec

    if (cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]
            or cfg["topk_method"] != "noaux_tc" or int(cfg["n_group"]) != 1
            or int(cfg["topk_group"]) != 1 or int(cfg["moe_layer_freq"]) != 1):
        raise ValueError("the program's expert layer routes by sigmoid scores normalised "
                         "over the selected ones (norm_topk_prob), selects with a bias and "
                         "no loss term (noaux_tc) from ONE group, in every layer after the "
                         "leading dense ones")
    if cfg["q_lora_rank"] is not None or cfg["rope_scaling"] is not None or cfg["attention_bias"]:
        raise ValueError("the program's latent mixer has no query latent (q_lora_rank), no "
                         "rotary scaling and no bias")
    if int(cfg["qk_head_dim"]) != int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is not qk_nope_head_dim + qk_rope_head_dim")
    positions, n = int(cfg["max_position_embeddings"]), int(cfg["num_hidden_layers"])
    return ModelSpec(
        name="transformer_lm",
        config={
            "vocab_size": int(cfg["vocab_size"]), "model_dim": int(cfg["hidden_size"]),
            "num_heads": int(cfg["num_attention_heads"]), "num_layers": n,
            "max_seq_len": positions, "positional": "rope", "rope_layers": "all",
            "rope_theta": float(cfg["rope_theta"]),
            "rope_interleave": bool(cfg["rope_interleave"]),
            "layer_types": ("latent",) * n,
            "kv_lora_rank": int(cfg["kv_lora_rank"]),
            "qk_nope_head_dim": int(cfg["qk_nope_head_dim"]),
            "qk_rope_head_dim": int(cfg["qk_rope_head_dim"]),
            "v_head_dim": int(cfg["v_head_dim"]),
            "norm": "rmsnorm", "norm_eps": float(cfg["rms_norm_eps"]),
            "mlp": "swiglu", "mlp_dim": int(cfg["intermediate_size"]),
            "num_dense_layers": int(cfg["first_k_dense_replace"]),
            "routed_experts": int(cfg["router_outputs"]),
            "experts_held": tuple(int(v) for v in cfg["experts_held"]),
            "routed_top_k": int(cfg["num_experts_per_tok"]),
            "routed_dim": int(cfg["moe_intermediate_size"]),
            "n_shared_experts": int(cfg["n_shared_experts"]),
            "route_scale": float(cfg["routed_scaling_factor"]),
            "route_balance_coeff": float(cfg["bias_update_speed"]),
            "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
            "remat": bool(cfg.get("remat", False)),
            "compute_dtype": cfg["stated_precision"]["compute_dtype"],
        },
        input_shape=(positions,), input_dtype="int32")


def to_program_tree(ref: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Reference leaves -> the parameter tree ``TransformerLM`` builds.  Pure
    indexing: no arithmetic (the head transposes)."""
    tree = {"embed": {"embedding": ref["wte"]}, "lm_head": {"kernel": ref["lm_head"].T},
            "final_norm": {"scale": ref["lnf_g"]}}
    for i in range(int(cfg["num_hidden_layers"])):
        block: Dict[str, Any] = {}
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
        for name, path in _paths(cfg, i).items():
            node = tree[f"block_{i}"]
            for key in path:
                node = node[key]
            ref[f"layers.{i}.{name}"] = node
    return ref


def shapes(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, int]:
    return {"seq_len": spec.job_seq_len(traffic, int(cfg["max_position_embeddings"])),
            "vocab": int(cfg["vocab_size"])}


def score_pairs(seq_len: int) -> float:
    """Score pairs one head of one row needs: ``j <= i``, every layer."""
    return seq_len * (seq_len + 1) / 2.0


def matmul_params_per_token(cfg: Dict[str, Any]) -> Dict[str, float]:
    """The matmul parameters a token multiplies.  The routed part is an
    EXPECTATION: of a token's ``num_experts_per_tok`` choices over
    ``router_outputs`` experts, ``held / router_outputs`` land on this chip
    when the router is balanced (what the bias drives it to)."""
    e, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dn, dr, dv, z = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                           "v_head_dim", "kv_lora_rank"))
    n, nd = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    lo, hi = cfg["experts_held"]
    r, k, fm = (int(cfg[x]) for x in ("router_outputs", "num_experts_per_tok",
                                      "moe_intermediate_size"))
    expert = 3 * e * fm
    return {"attention": n * (e * h * (dn + dr) + e * (z + dr)        # q; down
                              + z * h * (dn + dv) + h * dv * e),     # up; o
            "dense_mlp": nd * 3 * e * int(cfg["intermediate_size"]),
            "shared": (n - nd) * expert * int(cfg["n_shared_experts"]),
            "router": (n - nd) * e * r,
            "routed_expected": (n - nd) * expert * k * (hi - lo) / r,
            "head": e * int(cfg["vocab_size"])}


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """6 FLOPs per matmul parameter a token multiplies; attention ``6 x
    (d_qk + d_v)`` FLOPs a causal score pair and head (QK^T at the
    query/key size and PV at the value size, forward and twice backward).
    Never the recomputation."""
    dense = 6.0 * sum(matmul_params_per_token(cfg).values())
    d_qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    attention = (6.0 * (d_qk + int(cfg["v_head_dim"])) * int(cfg["num_attention_heads"])
                 * int(cfg["num_hidden_layers"]) * score_pairs(seq_len) / seq_len)
    return {"dense": dense, "attention": attention, "total": dense + attention}


def kernel_work(cfg: Dict[str, Any], kernel: str, batch: int, seq_len: int
                ) -> Dict[str, float]:
    """One call of a flash kernel (every layer's is the same): forward ``2 x
    pairs x (d_qk + d_v)`` FLOPs a head and row over q, k (``d_qk``), v, o
    (``d_v``) in bfloat16; fused backward ``2 x pairs x (3 d_qk + 2 d_v)``
    (dq, dk and the score product at ``d_qk``; dp and dv at ``d_v``;
    recomputed scores count once) over q, k, v, o, do, dq, dk, dv at their
    own widths."""
    if kernel not in FLASH:
        raise KeyError(f"family deepseek_v3_lm has no kernel named {kernel!r}; "
                       f"it has {sorted(FLASH)}")
    h, d_v = int(cfg["num_attention_heads"]), int(cfg["v_head_dim"])
    d_qk = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    pairs = score_pairs(seq_len) * batch * h
    row = batch * h * seq_len * 2                     # one channel of a tensor, bfloat16
    if FLASH[kernel] == "fwd":
        return {"flops": 2.0 * pairs * (d_qk + d_v), "bytes": row * (2.0 * d_qk + 2.0 * d_v)}
    return {"flops": 2.0 * pairs * (3 * d_qk + 2 * d_v), "bytes": row * (4.0 * d_qk + 4.0 * d_v)}
