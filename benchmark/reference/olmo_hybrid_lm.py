"""Plain reference of the Olmo-Hybrid decoder family: gated-delta-rule
linear-attention layers and full-attention layers mixed, one chip's share
of the vocabulary.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: a row at a time, the linear
layer as its RECURRENCE, token by token (never the chunked form the program
runs), the convolution as shifted adds, attention in blocks of queries, a
layer recomputed in the backward pass (``jax.checkpoint``); no kernel, no
cache, nothing imported from the program, nothing taken from it.  Weights
are made here from the seed; the harness hands the same arrays to the
program.  All sizes come from the configuration file
(``benchmark/configs/<config>.json``).

The layer, as the configuration file states it (``published`` where
``config.json`` names a key, ``assumed`` for the rest), with ``u`` a
sublayer's input ``[L, E]`` and ``rms(x; g) = x / sqrt(mean(x^2) + eps) * g``:

- ``x0 = E[tokens]``; no norm BEFORE a sublayer: ``a = x + rms(Mixer(x);
  g1)``, ``y = a + rms(MLP(a); g2)``, ``MLP(u) = (silu(u W1) * (u W3)) W2``.
- ``full_attention``: ``q = u Wq``, ``k = u Wk``, ``v = u Wv`` (H heads of D =
  E / H); ``q = rms(q; gq)``, ``k = rms(k; gk)`` over ALL H * D channels
  together; no positional signal; mask ``j <= i``; softmax of ``q k^T /
  sqrt(D)``; out ``= (softmax v) Wo``.
- ``linear_attention`` (Gated DeltaNet): ``q~ = u Wq``, ``k~ = u Wk`` (Hl
  heads of dk), ``v~ = u Wv`` (Hl heads of dv); every channel through a
  causal depthwise convolution of width W (``y_t = sum_j c_j x_{t-(W-1)+j}``,
  no bias) and SiLU; a head's ``q_t = q^ / sqrt(|q^|^2 + 1e-6) / sqrt(dk)``,
  ``k_t = k^ / sqrt(|k^|^2 + 1e-6)``; ``beta_t = 2 sigmoid(u Wb)`` (the 2 is
  ``linear_allow_neg_eigval``); ``g_t = -exp(A_log) softplus(u Wa +
  dt_bias)``, ``alpha_t = exp(g_t)``, a scalar a head;
  ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
  ``S_0 = 0``, ``S`` [dv, dk] a head; ``o_t = S_t q_t``; ``o^_t = rms(o_t;
  go over dv) * silu(u Wg)``; out ``= concat_heads(o^) Wo``.
- head: ``logits = rms(x_L; gf) Whead^T`` over the vocabulary rows held;
  mean cross-entropy over every position.

``precision``: ``"float32"`` is the reference.  ``"fp8"`` is the CONTROL of
the correctness check — every matmul operand the configuration states as
bfloat16 rounded to float8_e4m3fn, the nearest precision below it: the
projections' and the MLP's operands, the scores' and the head's, and q, k, v
as they enter the recurrence (the norms, the softmax, the convolution, the
decay, the write strength and the state stay float32, as stated);
``"bfloat16"`` rounds them to bfloat16.  ``rows``: ``"half"`` is the planted
fault "half of the batch left out": the first half of the rows, and of a
batch of one row the first half of its positions.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

COMMON = {"g_attn_out": "E", "g_ffn_out": "E", "w1": "EF", "w3": "EF", "w2": "FE"}
FULL = {"w_q": "EHD", "w_k": "EHD", "w_v": "EHD", "w_o": "HDE", "g_q": "E", "g_k": "E"}
LINEAR = {"w_q": "EGK", "w_k": "EGK", "w_v": "EGU", "w_g": "EGU", "w_o": "GUE",
          "w_a": "EG", "w_b": "EG", "c_q": "WGK", "c_k": "WGK", "c_v": "WGU",
          "a_log": "G", "dt_bias": "G", "g_o": "U"}
FAN_IN = {"w_q": "E", "w_k": "E", "w_v": "E", "w_g": "E", "w_a": "E", "w_b": "E",
          "w1": "E", "w3": "E", "w2": "F", "lm_head": "E",
          "c_q": "W", "c_k": "W", "c_v": "W"}       # w_o: HD in a full layer, GU in a linear
T_BLOCK = 64        # tokens of the recurrence between two kept states


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Sizes under the public ``config.json``'s own key names."""
    kinds = [str(t) for t in cfg["layer_types"]]
    n, e, h = (int(cfg[k]) for k in ("num_hidden_layers", "hidden_size", "num_attention_heads"))
    if len(kinds) != n:
        raise ValueError(f"layer_types names {len(kinds)} layers, num_hidden_layers is {n}")
    if int(cfg["num_key_value_heads"]) != h or int(cfg["linear_num_key_heads"]) != int(
            cfg["linear_num_value_heads"]):
        raise ValueError("this family has as many key/value heads as query heads, in both "
                         "kinds of layer")
    return {"V": int(cfg["vocab_size"]), "E": e, "H": h, "D": e // h,
            "F": int(cfg["intermediate_size"]), "N": n, "kinds": kinds,
            "G": int(cfg["linear_num_value_heads"]), "K": int(cfg["linear_key_head_dim"]),
            "U": int(cfg["linear_value_head_dim"]), "W": int(cfg["linear_conv_kernel_dim"]),
            "neg": bool(cfg["linear_allow_neg_eigval"]), "eps": float(cfg["rms_norm_eps"])}


def layer_leaves(s, i: int) -> Dict[str, str]:
    """Layer ``i``'s leaves, short name -> axes (letters of ``sizes``)."""
    kind = s["kinds"][i]
    if kind not in ("full_attention", "linear_attention"):
        raise ValueError(f"unknown layer type {kind!r}")
    return dict(COMMON, **(FULL if kind == "full_attention" else LINEAR))


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """Leaf name -> shape.  A layer's leaves are ``layers.<i>.<name>``."""
    s = sizes(cfg)
    shapes = {"wte": (s["V"], s["E"]), "lm_head": (s["V"], s["E"]), "lnf_g": (s["E"],)}
    for i in range(s["N"]):
        for name, axes in layer_leaves(s, i).items():
            shapes[f"layers.{i}.{name}"] = tuple(s[a] for a in axes)
    return shapes


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters a token multiplies in a matmul: every kernel and the head;
    not the embedding (a lookup), the convolutions' taps, the gains and the
    per-head vectors."""
    shapes = param_shapes(cfg)
    return sum(math.prod(shape) for name, shape in shapes.items()
               if name == "lm_head" or name.rsplit(".", 1)[-1] in
               ("w_q", "w_k", "w_v", "w_g", "w_o", "w_a", "w_b", "w1", "w2", "w3"))


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, jnp.ndarray]:
    """Seeded float32 weights (traceable: ``seed`` may be a tracer).
    Embedding normal(1.0) (no norm comes before the first sublayer: the
    residual stream starts at the scale a trained model's has), kernels and
    the convolutions' taps normal(1/sqrt(fan_in)), gains 1 + normal(0.02) so
    that no leaf is a constant the check could not see move; ``A_log = log(uniform(1, 16))``
    and ``dt_bias`` the inverse softplus of a log-uniform draw from [0.001,
    0.1] (the Mamba-2 convention)."""
    s, shapes = sizes(cfg), param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        short = name.rsplit(".", 1)[-1]
        if short == "a_log":
            out[name] = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
        elif short == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            out[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            fan = shape[:2] if short == "w_o" else tuple(s[a] for a in FAN_IN.get(short, ""))
            std = 1.0 / math.sqrt(math.prod(fan)) if fan else 1.0 if name == "wte" else 0.02
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


def _full_attention(u, p, s, precision: str, q_block: int):
    """One row: u [L, E] -> [L, E]."""
    l = u.shape[0]
    uq = _q(u, precision)
    q, k, v = (jnp.einsum("le,ehd->lhd", uq, _q(p[w], precision)) for w in ("w_q", "w_k", "w_v"))
    whole = lambda t, g: _rms(t.reshape(l, -1), g, s["eps"]).reshape(t.shape)
    q, k = whole(q, p["g_q"]), whole(k, p["g_k"])
    scale = 1.0 / math.sqrt(s["D"])
    nb = l // q_block if l % q_block == 0 and l >= q_block else 1
    qb = l // nb

    @jax.checkpoint
    def block(b):           # a block of queries against every key, masked
        qs = lax.dynamic_slice_in_dim(q, b * qb, qb)
        sc = jnp.einsum("qhd,khd->hqk", _q(qs, precision), _q(k, precision)) * scale
        seen = jnp.arange(l)[None, :] <= b * qb + jnp.arange(qb)[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(w, precision), _q(v, precision))

    o = lax.map(block, jnp.arange(nb)).reshape(l, s["H"], s["D"])
    return jnp.einsum("lhd,hde->le", _q(o, precision), _q(p["w_o"], precision))


def _conv_silu(x, taps):
    """Causal depthwise convolution as shifted adds, then SiLU: x [L, G, .],
    taps [W, G, .]; tap ``j`` multiplies the token ``W - 1 - j`` back."""
    width, l = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1,) + x.shape[1:], x.dtype), x])
    return jax.nn.silu(sum(padded[j:j + l] * taps[j] for j in range(width)))


def _recurrence(q, k, v, g, beta):
    """The gated delta rule, a token at a time: q, k [L, G, K], v [L, G, U],
    g, beta [L, G] -> o [L, G, U].  Blocks of ``T_BLOCK`` tokens are
    recomputed in the backward pass, which so keeps one state a block."""
    l = q.shape[0]

    def token(state, x):    # state [G, U, K]
        q, k, v, g, beta = x
        sk = jnp.sum(state * k[:, None, :], axis=-1)                       # S k: [G, U]
        state = (jnp.exp(g)[:, None, None] * (state - beta[:, None, None] * sk[:, :, None]
                                              * k[:, None, :])
                 + beta[:, None, None] * v[:, :, None] * k[:, None, :])
        return state, jnp.sum(state * q[:, None, :], axis=-1)              # S q

    @jax.checkpoint
    def block(state, xs):
        return lax.scan(token, state, xs)

    nb = l // T_BLOCK if l % T_BLOCK == 0 else 1
    xs = jax.tree.map(lambda x: x.reshape((nb, l // nb) + x.shape[1:]), (q, k, v, g, beta))
    state = jnp.zeros((q.shape[1], v.shape[-1], q.shape[-1]), jnp.float32)
    return lax.scan(block, state, xs)[1].reshape(v.shape)


def _linear_attention(u, p, s, precision: str):
    """One row: u [L, E] -> [L, E]."""
    uq = _q(u, precision)
    proj = lambda w: jnp.einsum("le,egd->lgd", uq, _q(p[w], precision))
    unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q = unit(_conv_silu(proj("w_q"), p["c_q"])) / math.sqrt(s["K"])
    k = unit(_conv_silu(proj("w_k"), p["c_k"]))
    v = _conv_silu(proj("w_v"), p["c_v"])
    beta = jax.nn.sigmoid(uq @ _q(p["w_b"], precision)) * (2.0 if s["neg"] else 1.0)
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(uq @ _q(p["w_a"], precision) + p["dt_bias"])
    o = _recurrence(_q(q, precision), _q(k, precision), _q(v, precision), g, beta)
    o = _rms(o, p["g_o"], s["eps"]) * jax.nn.silu(proj("w_g"))
    return jnp.einsum("lgu,gue->le", _q(o, precision), _q(p["w_o"], precision))


def _swiglu(u, w1, w3, w2, precision: str):
    uq = _q(u, precision)
    h = jax.nn.silu(uq @ _q(w1, precision)) * (uq @ _q(w3, precision))
    return _q(h, precision) @ _q(w2, precision)


def _layer(x, p, s, kind: str, precision: str, q_block: int):
    """One layer on one row: x [L, E], ``p`` the layer's leaves by short name."""
    mixed = (_full_attention(x, p, s, precision, q_block) if kind == "full_attention"
             else _linear_attention(x, p, s, precision))
    a = x + _rms(mixed, p["g_attn_out"], s["eps"])
    return a + _rms(_swiglu(a, p["w1"], p["w3"], p["w2"], precision), p["g_ffn_out"], s["eps"])


def row_loss(params, tokens, targets, cfg_key, precision: str = "float32",
             count=None, q_block: int = 256, v_block: int = 512):
    """Sum over positions of the next-token cross-entropy of ONE row:
    tokens, targets [L] int32.  ``count`` (the planted fault): only the
    first ``count`` positions are summed."""
    s = sizes(dict(cfg_key))
    l = tokens.shape[0]
    x = params["wte"][tokens]
    for i, kind in enumerate(s["kinds"]):
        p = {name: params[f"layers.{i}.{name}"] for name in layer_leaves(s, i)}
        x = jax.checkpoint(functools.partial(
            _layer, s=s, kind=kind, precision=precision, q_block=q_block))(x, p)
    x = _rms(x, params["lnf_g"], s["eps"])
    live = (jnp.arange(l) < (l if count is None else count)).astype(jnp.float32)

    # the head in blocks of positions: logits are [v_block, V] at a time
    @jax.checkpoint
    def ce(xb, tb, wb):
        logits = _q(xb, precision) @ _q(params["lm_head"], precision).T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(wb * (lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]))

    nb = l // v_block if l % v_block == 0 and l >= v_block else 1
    per = lax.map(lambda a: ce(*a), (x.reshape(nb, l // nb, -1), targets.reshape(nb, l // nb),
                                     live.reshape(nb, l // nb)))
    return jnp.sum(per)


def _cfg_key(cfg: Dict[str, Any]):
    """The configuration as a hashable static argument."""
    def freeze(v):
        return tuple(freeze(x) for x in v) if isinstance(v, (list, tuple)) else v
    return tuple(sorted((k, freeze(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list, tuple))))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision", "count"))
def _row_grad(params, tokens, targets, cfg_key, precision: str, count):
    """(loss sum, d row_loss / d params) of one row."""
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(row_loss)(params, tokens, targets, cfg_key, precision, count)


_add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g), donate_argnums=(0,))
_apply = jax.jit(lambda local, acc, factor: jax.tree.map(lambda p, g: p - factor * g, local, acc),
                 donate_argnums=(0,))


def sgd_step(cfg, local, x, y, lr: float, precision: str = "float32", rows: str = "all"):
    """One plain SGD step on one batch ``x, y`` [B, L] by the gradient of the
    batch's mean cross-entropy, the rows' gradients summed one row at a
    time.  ``local``'s buffers are given up.  Returns (local after, loss)."""
    count = None
    if rows == "half":
        if x.shape[0] > 1:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        else:
            count = x.shape[1] // 2
    key = _cfg_key(cfg)
    acc, loss = None, jnp.zeros((), jnp.float32)
    for r in range(x.shape[0]):
        row, g = _row_grad(local, x[r], y[r], key, precision, count)
        acc, loss = g if acc is None else _add(acc, g), loss + row
    scale = 1.0 / (x.shape[0] * (count or x.shape[1]))
    return _apply(local, acc, jnp.float32(lr * scale)), loss * scale


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
    out = {name: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
           for name, x in tree.items()}
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
    pulled) / num_workers`` to it (ADAG).  A tree is 3.7 GB at the published
    widths, so the center waits on the HOST while a window trains: the
    device holds the local tree and one row's gradient (and, in a batch of
    more than one row, the gradient sum).
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
