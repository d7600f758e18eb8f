"""Plain reference of the DeepSeek-V3 decoder family without the query's
low-rank path (Kanana-2-30B-A3B), one chip's share.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a row at a time, the ``[L, L]``
scores of a head in blocks of queries, a layer recomputed in the backward
pass (``jax.checkpoint``); no kernel, no sort, no cache, nothing imported
from the program, nothing taken from it.  Weights are made here from the
seed; the harness hands the same arrays to the program.  All sizes come
from the configuration file (``benchmark/configs/<config>.json``), under
the published ``config.json``'s own key names.

The layer (``published`` where ``config.json`` names a key, ``assumed`` for
the rest, both in the configuration file):

- ``x0 = E[tokens]``; ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``.
- ``a = x + Attn(rms(x; g1))``, ``y = a + FFN(rms(a; g2))``; no bias
  anywhere.
- ``Attn(u)`` (latent attention): ``q = u Wq`` -> H heads of ``d_n + d_r``
  (``qk_nope_head_dim``, ``qk_rope_head_dim``), split into ``q_n``, ``q_r``
  (``q_lora_rank`` null: no query latent).  ``[c ; k_r] = u Wdkv`` ->
  ``kv_lora_rank + d_r``; ``[k_n ; v] = rms(c; gc) Wukv`` -> H heads of
  ``d_n + d_v``.  ``k_r`` is ONE vector a token, shared by all heads.
  ``q_r`` and ``k_r`` are rotated over ADJACENT channel pairs ``(2i, 2i+1)``
  by the angle ``pos * theta^(-2i / d_r)`` (``rope_interleave``; the
  published code reorders the channels to halves and rotates there, which
  is the same inner product).  Head ``h``'s score of query ``i`` and key
  ``j <= i`` is ``(q_n[i,h] . k_n[j,h] + q_r[i,h] . k_r[j]) / sqrt(d_n +
  d_r)``; softmax over ``j``; ``o[i,h] = sum_j p v[j,h]``; out ``= o Wo``.
- dense FFN (the first ``first_k_dense_replace`` layers): ``(silu(u W1) *
  (u W3)) W2`` at ``intermediate_size``.
- MoE FFN: ``s = sigmoid(u Wr)`` over ALL ``router_outputs`` experts (one
  group: ``n_group`` = ``topk_group`` = 1); ``S = top_k(s + b)``; ``w_e =
  routed_scaling_factor * s_e / (sum of the selected s + 1e-20)``; ``out =
  Shared(u) + sum over e in S and held of w_e * Expert_e(u)``, ``Shared`` ONE
  SwiGLU of ``n_shared_experts * moe_intermediate_size``, added unweighted.
  Experts outside ``experts_held`` are held by other chips and what they
  would add is left out.  Each held expert is applied to every token and
  weighted by ``w_e`` (zero where not selected): a loop over the held
  experts with a mask, no gather.
- balancing (``topk_method`` noaux_tc): no loss term; after every optimizer
  step, per MoE layer, with ``c`` the step's assignment counts over all
  experts: ``delta = bias_update_speed * sign(mean(c) - c)``, ``b <- b +
  delta - mean(delta)``.
- head: ``logits = rms(x_L; gf) Whead^T`` over the vocabulary rows held;
  mean cross-entropy over every position.

``precision``: ``"float32"`` is the reference.  ``"fp8"`` is the CONTROL of
the correctness check — every matmul operand the configuration states as
bfloat16 rounded to float8_e4m3fn, the nearest precision below it (the
router, the norms, the rotations and the softmax stay float32, as stated);
``"bfloat16"`` rounds them to bfloat16.  ``rows``: ``"half"`` is the planted
fault "half of the batch left out".
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# axes by letter (``sizes``): Q = d_n + d_r, C = kv_lora_rank + d_r, Z =
# kv_lora_rank, U = d_n + d_v, P = d_v
ATTN = {"g_attn": "E", "g_ffn": "E", "w_q": "EHQ", "w_dkv": "EC", "g_c": "Z",
        "w_ukv": "ZHU", "w_o": "HPE"}
DENSE = {"w1": "EF", "w3": "EF", "w2": "FE"}
MOE = {"w_router": "ER", "bias": "R", "w1": "XEM", "w3": "XEM", "w2": "XME",
       "shared_w1": "ES", "shared_w3": "ES", "shared_w2": "SE"}
FAN_IN = {"w_q": "E", "w_dkv": "E", "w_ukv": "Z", "w_o": "HP", "w1": "E", "w3": "E",
          "shared_w1": "E", "shared_w3": "E", "shared_w2": "S",
          "lm_head": "E"}          # w2: F in a dense layer, M in an expert
# The block has no norm after a sublayer and no embedding scale, so at
# normal(0.02) a token's own embedding is 1/50 of what the first attention
# layer adds to it: the residual stream hardly knows its token, plain SGD
# at 0.01 has to grow that path first (its step on the first layer's normed
# input is 2,500 times the rate), the loss RISES in the second window and
# rounding differences grow with it (chip, PR 34: the bfloat16 program
# against this reference read 0.001 to 0.079 nats on the first window by
# seed).  At unit scale the token is there from the first step.
EMBED_STD = 1.0


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes under the public ``config.json``'s own key names (DeepseekV3Config)."""
    lo, hi = (int(v) for v in cfg["experts_held"])
    dn, dr, dv = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    z, fm = int(cfg["kv_lora_rank"]), int(cfg["moe_intermediate_size"])
    return {"V": int(cfg["vocab_size"]), "E": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]), "dn": dn, "dr": dr, "P": dv,
            "Q": dn + dr, "Z": z, "C": z + dr, "U": dn + dv,
            "F": int(cfg["intermediate_size"]), "M": fm,
            "S": fm * int(cfg["n_shared_experts"]), "N": int(cfg["num_hidden_layers"]),
            "Nd": int(cfg["first_k_dense_replace"]), "top_k": int(cfg["num_experts_per_tok"]),
            "R": int(cfg["router_outputs"]), "lo": lo, "hi": hi, "X": hi - lo,
            "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
            "scale": float(cfg["routed_scaling_factor"]),
            "coeff": float(cfg["bias_update_speed"]),
            "L": int(cfg["max_position_embeddings"])}


def layer_leaves(s, i: int) -> Dict[str, str]:
    """Layer ``i``'s leaves, short name -> axes (letters of ``sizes``)."""
    return dict(ATTN, **(DENSE if i < s["Nd"] else MOE))


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """Leaf name -> shape.  A layer's leaves are ``layers.<i>.<name>``: one
    leaf a layer, nothing stacked."""
    s = sizes(cfg)
    shapes = {"wte": (s["V"], s["E"]), "lm_head": (s["V"], s["E"]), "lnf_g": (s["E"],)}
    for i in range(s["N"]):
        for name, axes in layer_leaves(s, i).items():
            shapes[f"layers.{i}.{name}"] = tuple(s[a] for a in axes)
    return shapes


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, jnp.ndarray]:
    """Seeded float32 weights (traceable: ``seed`` may be a tracer).
    Embedding normal(1.0) (``EMBED_STD``), router normal(0.02), kernels
    normal(1/sqrt(fan_in)), norm gains 1 + normal(0.02) so that no leaf is a
    constant the check could not see move; the selection bias starts at
    zero, as the rule says."""
    s, shapes = sizes(cfg), param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        short = name.rsplit(".", 1)[-1]
        if short == "bias":
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        fan = ("F" if len(shape) == 2 else "M") if short == "w2" else FAN_IN.get(short)
        std = 1.0 / math.sqrt(math.prod(s[a] for a in fan)) if fan else 0.02
        if name == "wte":
            std = EMBED_STD
        x = std * jax.random.normal(key, shape, jnp.float32)
        out[name] = 1.0 + x if short.startswith("g_") or short == "lnf_g" else x
    return out


def _q(x, precision: str):
    """Round a matmul operand to the stated precision (values stay f32);
    straight-through, so that a cotangent is not itself cast narrow."""
    if precision == "float32":
        return x
    dt = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    return x + lax.stop_gradient(x.astype(dt).astype(jnp.float32) - x)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def _rope_pairs(x, theta: float):
    """Rotate adjacent channel pairs: x [L, ..., D] by its own positions
    0..L-1; pair ``i`` = channels ``(2i, 2i+1)``, angle ``pos * theta^(-2i/D)``:
    ``(a, b) -> (a cos - b sin, a sin + b cos)``."""
    l, d = x.shape[0], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)           # [D/2]
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq[None, :]        # [L, D/2]
    ang = ang.reshape((l,) + (1,) * (x.ndim - 2) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1).reshape(x.shape)


def _attention(u, p, s, precision: str, q_block: int):
    """One row: u [L, E] -> [L, E]."""
    l = u.shape[0]
    uq = _q(u, precision)
    q = jnp.einsum("le,ehq->lhq", uq, _q(p["w_q"], precision))
    q_n, q_r = q[..., :s["dn"]], q[..., s["dn"]:]
    down = uq @ _q(p["w_dkv"], precision)                                   # [L, Z + d_r]
    c, k_r = _rms(down[:, :s["Z"]], p["g_c"], s["eps"]), down[:, s["Z"]:]
    kv = jnp.einsum("lz,zhu->lhu", _q(c, precision), _q(p["w_ukv"], precision))
    k_n, v = kv[..., :s["dn"]], kv[..., s["dn"]:]
    q_r, k_r = _rope_pairs(q_r, s["theta"]), _rope_pairs(k_r, s["theta"])
    scale = 1.0 / math.sqrt(s["Q"])
    nb = l // q_block if l % q_block == 0 and l >= q_block else 1
    qb = l // nb
    k_nq, k_rq, vq = _q(k_n, precision), _q(k_r, precision), _q(v, precision)

    @jax.checkpoint
    def block(b):                     # a block of queries against every key, masked
        start = b * qb
        qn = lax.dynamic_slice_in_dim(q_n, start, qb)
        qr = lax.dynamic_slice_in_dim(q_r, start, qb)
        sc = (jnp.einsum("qhd,khd->hqk", _q(qn, precision), k_nq)
              + jnp.einsum("qhd,kd->hqk", _q(qr, precision), k_rq)) * scale
        seen = jnp.arange(l)[None, :] <= start + jnp.arange(qb)[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(w, precision), vq)

    o = lax.map(block, jnp.arange(nb)).reshape(l, s["H"], s["P"])
    return jnp.einsum("lhd,hde->le", _q(o, precision), _q(p["w_o"], precision))


def _swiglu(u, w1, w3, w2, precision: str):
    uq = _q(u, precision)
    h = jax.nn.silu(uq @ _q(w1, precision)) * (uq @ _q(w3, precision))
    return _q(h, precision) @ _q(w2, precision)


def _moe(u, p, s, precision: str):
    """One row: u [L, E] -> (out [L, E], assignment counts [R] int32)."""
    score = jax.nn.sigmoid(u @ p["w_router"])                     # float32, as stated
    _, chosen = lax.top_k(score + lax.stop_gradient(p["bias"]), s["top_k"])     # [L, K]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(s["R"])[None, None, :], axis=1)
    weight = jnp.where(picked, score, 0.0)
    weight = s["scale"] * weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    out = _swiglu(u, p["shared_w1"], p["shared_w3"], p["shared_w2"], precision)

    @jax.checkpoint
    def term(held):                     # one held expert over every token
        w1, w3, w2, weight_e = held
        return weight_e[:, None] * _swiglu(u, w1, w3, w2, precision)

    out, _ = lax.scan(lambda out, held: (out + term(held), None), out,
                      (p["w1"], p["w3"], p["w2"], weight[:, s["lo"]:s["hi"]].T))
    return out, jnp.sum(picked, axis=0, dtype=jnp.int32)


def _layer(x, p, s, is_moe: bool, precision: str, q_block: int):
    """One layer on one row: x [L, E], ``p`` the layer's leaves by short name."""
    a = x + _attention(_rms(x, p["g_attn"], s["eps"]), p, s, precision, q_block)
    u = _rms(a, p["g_ffn"], s["eps"])
    if is_moe:
        f, counts = _moe(u, p, s, precision)
    else:
        f, counts = _swiglu(u, p["w1"], p["w3"], p["w2"], precision), None
    return a + f, counts


def row_loss(params, tokens, targets, cfg_key, precision: str = "float32",
             q_block: int = 256, v_block: int = 512):
    """(sum over positions of the next-token cross-entropy of ONE row,
    assignment counts [expert layers, R]): tokens, targets [L] int32."""
    s = sizes(dict(cfg_key))
    l = tokens.shape[0]
    x = params["wte"][tokens]
    counts = []
    for i in range(s["N"]):
        p = {name: params[f"layers.{i}.{name}"] for name in layer_leaves(s, i)}
        is_moe = i >= s["Nd"]
        x, c = jax.checkpoint(functools.partial(
            _layer, s=s, is_moe=is_moe, precision=precision, q_block=q_block))(x, p)
        if is_moe:
            counts.append(c)
    x = _rms(x, params["lnf_g"], s["eps"])

    # the head in blocks of positions: logits are [v_block, V] at a time
    @jax.checkpoint
    def ce(xb, tb):
        logits = _q(xb, precision) @ _q(params["lm_head"], precision).T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0])

    nb = l // v_block if l % v_block == 0 and l >= v_block else 1
    per = lax.map(lambda a: ce(*a), (x.reshape(nb, l // nb, -1), targets.reshape(nb, l // nb)))
    return jnp.sum(per), jnp.stack(counts)


def _cfg_key(cfg: Dict[str, Any]):
    """The configuration as a hashable static argument."""
    def freeze(v):
        return tuple(freeze(x) for x in v) if isinstance(v, (list, tuple)) else v
    return tuple(sorted((k, freeze(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list, tuple))))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"), donate_argnums=(0, 1, 2))
def _add_row_grad(acc, loss_acc, counts_acc, params, tokens, targets, cfg_key, precision: str):
    """``acc + d row_loss / d params``, the running loss and the running
    assignment counts, for one row, added in place: beside the parameters
    only the running sum and this row's gradient are alive."""
    with jax.default_matmul_precision("highest"):
        (loss, counts), g = jax.value_and_grad(row_loss, has_aux=True)(
            params, tokens, targets, cfg_key, precision)
    return jax.tree.map(jnp.add, acc, g), loss_acc + loss, counts_acc + counts


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(local, acc, factor, counts, coeff):
    """The SGD step, then the balancing rule on the selection bias."""
    local = jax.tree.map(lambda p, g: p - factor * g, local, acc)
    biases = sorted((k for k in local if k.endswith(".bias")),
                    key=lambda k: int(k.split(".")[1]))
    for n, name in enumerate(biases):
        c = counts[n].astype(jnp.float32)
        delta = coeff * jnp.sign(jnp.mean(c) - c)
        local[name] = local[name] + delta - jnp.mean(delta)
    return local


def sgd_step(cfg, local, x, y, lr: float, precision: str = "float32", rows: str = "all"):
    """One plain SGD step on one batch ``x, y`` [B, L] by the gradient of the
    batch's mean cross-entropy, the rows' gradients summed one row at a
    time; then the bias moves by the step's counts.  ``local``'s buffers are
    given up.  Returns (local after, loss)."""
    if rows == "half":
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    s, key = sizes(cfg), _cfg_key(cfg)
    acc = jax.tree.map(jnp.zeros_like, local)
    loss = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((s["N"] - s["Nd"], s["R"]), jnp.int32)
    for r in range(x.shape[0]):
        acc, loss, counts = _add_row_grad(acc, loss, counts, local, x[r], y[r], key, precision)
    scale = 1.0 / (x.shape[0] * x.shape[1])
    return _apply(local, acc, jnp.float32(lr * scale), counts,
                  jnp.float32(s["coeff"])), loss * scale


def sgd_window(cfg, local, xs, ys, lr: float, precision: str = "float32", rows: str = "all"):
    """One communication window: ``xs, ys`` [steps, B, L]."""
    losses = []
    for i in range(xs.shape[0]):
        local, loss = sgd_step(cfg, local, xs[i], ys[i], lr, precision, rows)
        losses.append(loss)
    return local, jnp.mean(jnp.stack(losses))


def _leaf_norms(tree: Dict[str, jnp.ndarray], rare_rows=None) -> Dict[str, jnp.ndarray]:
    """Per-leaf L2 norms.  ``rare_rows`` (row indices of ``wte``) adds the
    sub-leaf ``wte.rare``."""
    out = {}
    for name, x in tree.items():
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
    if rare_rows is not None:
        out["wte.rare"] = jnp.sqrt(jnp.sum(jnp.square(
            tree["wte"][rare_rows].astype(jnp.float32))))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _change_norms(center, seed, rare_rows, cfg_key):
    p0 = init_params(dict(cfg_key), seed)
    return _leaf_norms({k: center[k] - p0[k] for k in p0}, rare_rows)


def change_norms(cfg: Dict[str, Any], center, seed: int, rare_rows=None) -> Dict[str, Any]:
    """Per-leaf norm of ``center - init_params(seed)``; the seed's weights
    are made again inside the program, so no second tree is kept."""
    return jax.device_get(_change_norms(center, jnp.uint32(seed % 2**32), rare_rows,
                                        _cfg_key(cfg)))


def follow(cfg: Dict[str, Any], seed: int, calls: Sequence[Any], lr: float,
           num_workers: int = 1, self_staleness: int = 0,
           precision: str = "float32", rows: str = "all",
           rare_rows=None) -> List[Dict[str, Any]]:
    """Follow the trainer through its first ``train()`` calls on the
    SYNCHRONOUS plane (``self_staleness`` 0: a window trains from the center
    its predecessor left).

    ``calls`` is a list of ``(xs, ys)`` [windows, steps, B, L].  Every
    window trains ``steps`` SGD steps from the center and commits ``(after -
    pulled) / num_workers`` to it (ADAG), the bias leaf like any other.  A
    tree is 2.75 GB at the published widths and a row's float32 activations
    need the rest of the chip, so the center waits on the HOST while a
    window trains: the device holds the local tree, the gradient sum and
    one row's gradient.
    """
    if self_staleness != 0:
        raise ValueError("this family's follow is written for the synchronous plane "
                         "(self_staleness 0): it keeps one center, not a history")
    make = jax.jit(lambda s: init_params(cfg, s))
    center = jax.tree.map(np.asarray, make(jnp.uint32(seed % 2**32)))      # on the host
    commit = jax.jit(lambda c, a: jax.tree.map(lambda c, a: c + (a - c) / num_workers, c, a),
                     donate_argnums=(0,))
    out = []
    for xs, ys in calls:
        losses = []
        for w in range(xs.shape[0]):
            after, loss = sgd_window(cfg, jax.device_put(center), jnp.asarray(xs[w]),
                                     jnp.asarray(ys[w]), lr=lr, precision=precision, rows=rows)
            center = commit(jax.device_put(center), after)    # pulled == center here
            losses.append(float(loss))
            if w == xs.shape[0] - 1:
                norms = change_norms(cfg, center, seed, rare_rows)
            center = jax.tree.map(np.asarray, center)
        out.append({"losses": losses, "norms": norms})
    return out
