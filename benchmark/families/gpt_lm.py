"""What the harness knows of the GPT-2-style dense decoder family
(Cerebras-GPT): everything that reads a key of its ``config.json``.

A model family is files, found by the ``"family"`` a configuration file
names: ``reference/<family>.py`` (the plain reference) and
``families/<family>.py`` (this), loaded by ``harness.spec.load_family``.
``run.py``, ``harness/*.py`` and ``readers/*.py`` name no key of any
``config.json`` but ``family``; they call these five functions:

``model_spec(cfg)``
    The program's ``ModelSpec`` for the configuration.
``to_program_tree(ref_leaves, cfg)`` / ``from_program_tree(tree, cfg)``
    Pure indexing between the reference's leaves and the parameter tree the
    program builds.  Traceable: both run under ``jax.jit``
    (``harness.program.build_model`` / ``change_norms``).
``shapes(cfg, traffic)`` -> ``{"seq_len", "vocab"}``
    The job's sequence length is ``traffic["data"]["seq_len"]`` where the
    traffic file gives one, else the configuration's own positions; a
    length over what the configuration declares raises
    (``harness.spec.job_seq_len``).
``train_flops_per_token(cfg, seq_len)`` -> ``{"dense", "attention", "total"}``
    The FLOPs a trained token REQUIRES in this family: the parameters a
    token really multiplies, the score pairs its masks really need;
    recomputation never counted.  ``run.py`` multiplies by batch x seq_len
    and hands ``step_mfu`` the product.
``kernel_work(cfg, kernel, batch, seq_len)`` -> ``{"flops", "bytes"}``
    The mean required work of ONE call of the kernel named ``kernel`` over
    the calls one training step makes of it (layers of different kinds
    average here), for each kernel name one of the family's metrics reads
    (``metrics/<metric>.json``: ``"reader": "trace_kernel", "args":
    {"kernel": ...}``); an unknown name raises.

What a new family's PR adds, all new files and appended entries:
``configs/<name>.json`` with ``"family": "<f>"``, ``reference/<f>.py``,
``families/<f>.py``, its traffic, ``workloads/<cell>.json`` and metric
files, and the entries in ``BENCHMARK.json``.

This family, as the program runs it: ``TransformerLM(positional="learned")``
with ``d_ffn = 4 * d_model``, heads x head size = width, full causal
attention in every layer through the two flash kernels ``_fwd_kernel`` and
``_bwd_fused_kernel`` (the ``name=`` of the ``pallas_call``s in
``ops/flash_attention.py``).
"""

from __future__ import annotations

import os
from typing import Any, Dict

from benchmark.harness import peaks, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FLASH = {"_fwd_kernel": "fwd", "_bwd_fused_kernel": "bwd"}


def model_spec(cfg: Dict[str, Any]):
    from distkeras_tpu.models.transformer import small_lm_spec

    if int(cfg["n_inner"]) != 4 * int(cfg["n_embd"]):
        raise ValueError("the program's block has d_ffn = 4 * d_model only")
    return small_lm_spec(vocab_size=int(cfg["vocab_size"]),
                         model_dim=int(cfg["n_embd"]),
                         num_heads=int(cfg["n_head"]),
                         num_layers=int(cfg["n_layer"]),
                         max_seq_len=int(cfg["n_positions"]),
                         positional="learned")


def to_program_tree(ref: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Reference leaves (blocks stacked on a layer axis) -> the parameter
    tree ``TransformerLM`` builds.  Pure indexing: no arithmetic."""
    tree = {"embed": {"embedding": ref["wte"]}, "pos_embed": ref["wpe"],
            "final_norm": {"scale": ref["lnf_g"], "bias": ref["lnf_b"]}}
    for i in range(int(cfg["n_layer"])):
        tree[f"block_{i}"] = {
            "LayerNorm_0": {"scale": ref["blocks.ln1_g"][i], "bias": ref["blocks.ln1_b"][i]},
            "qkv": {"kernel": ref["blocks.w_qkv"][i]},
            "proj": {"kernel": ref["blocks.w_o"][i]},
            "LayerNorm_1": {"scale": ref["blocks.ln2_g"][i], "bias": ref["blocks.ln2_b"][i]},
            "up": {"kernel": ref["blocks.w_up"][i]},
            "down": {"kernel": ref["blocks.w_down"][i]},
        }
    return tree


def from_program_tree(tree: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The inverse, traceable (stacks the per-layer leaves)."""
    import jax.numpy as jnp

    def stack(*path):
        def get(b):
            x = tree[f"block_{b}"]
            for k in path:
                x = x[k]
            return x
        return jnp.stack([get(b) for b in range(int(cfg["n_layer"]))])

    return {"wte": tree["embed"]["embedding"], "wpe": tree["pos_embed"],
            "lnf_g": tree["final_norm"]["scale"], "lnf_b": tree["final_norm"]["bias"],
            "blocks.ln1_g": stack("LayerNorm_0", "scale"),
            "blocks.ln1_b": stack("LayerNorm_0", "bias"),
            "blocks.w_qkv": stack("qkv", "kernel"), "blocks.w_o": stack("proj", "kernel"),
            "blocks.ln2_g": stack("LayerNorm_1", "scale"),
            "blocks.ln2_b": stack("LayerNorm_1", "bias"),
            "blocks.w_up": stack("up", "kernel"), "blocks.w_down": stack("down", "kernel")}


def shapes(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> Dict[str, int]:
    return {"seq_len": spec.job_seq_len(traffic, int(cfg["n_positions"])),
            "vocab": int(cfg["vocab_size"])}


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Every matmul parameter is touched by every token, and every layer's
    attention is full causal at heads x head size = ``n_embd``."""
    reference = spec.load_reference(cfg, ROOT)
    return peaks.train_flops_per_token(reference.matmul_params(cfg), int(cfg["n_layer"]),
                                       seq_len, int(cfg["n_embd"]))


def kernel_work(cfg: Dict[str, Any], kernel: str, batch: int, seq_len: int
                ) -> Dict[str, float]:
    """Every layer calls each flash kernel once a step at the same shapes,
    so the mean call is any call."""
    if kernel not in FLASH:
        raise KeyError(f"family gpt_lm has no kernel named {kernel!r}; "
                       f"it has {sorted(FLASH)}")
    heads = int(cfg["n_head"])
    return peaks.flash_counts(FLASH[kernel], batch, heads, seq_len,
                              int(cfg["n_embd"]) // heads)
