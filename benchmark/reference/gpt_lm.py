"""Plain reference of the GPT-2-style decoder family (Cerebras-GPT).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, nothing
imported from the program, nothing taken from it.  Weights are made here
from the seed; the harness hands the same arrays to the program.  One file
serves every configuration of the family: all sizes come from the
configuration file (``benchmark/configs/<config>.json``).

Layout: the blocks are stacked on a leading layer axis so that one
``lax.scan`` with ``jax.checkpoint`` walks them (a layer's activations are
recomputed in the backward pass) and rows are walked one at a time, so a
step at published widths fits one chip beside four parameter-sized trees.

The family, as the configuration file states it: pre-LayerNorm decoder,
learned positions, causal multi-head attention scaled by 1/sqrt(head_dim),
tanh-approximate GELU, ``d_ffn = n_inner``, tied unembedding, no
bias terms in the dense layers (LayerNorm keeps scale and bias), mean
cross-entropy over every position.

``precision``: ``"float32"`` is the reference.  ``"fp8"`` is the CONTROL of
the correctness check — the same arithmetic with every matmul operand
rounded to float8_e4m3fn, the nearest precision below the bfloat16 the
configuration states; ``"bfloat16"`` rounds them to bfloat16 (what the
program is stated to do).  ``rows``: ``"half"`` is the planted fault "half
of the batch left out, the mean taken over the rest".
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-6


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Sizes under the public ``config.json``'s own key names (GPT2Config)."""
    e, h = int(cfg["n_embd"]), int(cfg["n_head"])
    return {"V": int(cfg["vocab_size"]), "E": e, "H": h, "D": e // h,
            "F": int(cfg["n_inner"]), "N": int(cfg["n_layer"]),
            "L": int(cfg["n_positions"])}


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, tuple]:
    """Leaf name -> shape.  ``blocks.*`` leaves carry the layer axis."""
    s = sizes(cfg)
    n, e, h, d, f = s["N"], s["E"], s["H"], s["D"], s["F"]
    return {
        "wte": (s["V"], e), "wpe": (s["L"], e),
        "blocks.ln1_g": (n, e), "blocks.ln1_b": (n, e),
        "blocks.w_qkv": (n, e, 3, h, d), "blocks.w_o": (n, h, d, e),
        "blocks.ln2_g": (n, e), "blocks.ln2_b": (n, e),
        "blocks.w_up": (n, e, f), "blocks.w_down": (n, f, e),
        "lnf_g": (e,), "lnf_b": (e,),
    }


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matmul per token: the dense kernels
    and the tied unembedding (the positional table does not)."""
    shapes = param_shapes(cfg)
    return sum(math.prod(shapes[k]) for k in
               ("wte", "blocks.w_qkv", "blocks.w_o", "blocks.w_up",
                "blocks.w_down"))


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, jnp.ndarray]:
    """Seeded float32 weights (traceable: ``seed`` may be a tracer).
    Embeddings normal(0.02), kernels normal(1/sqrt(fan_in)), LayerNorm
    scale 1 + normal(0.02) and bias normal(0.02) so that no leaf is a
    constant the check could not see move."""
    shapes = param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    fan_in = {"blocks.w_qkv": shapes["wte"][1], "blocks.w_up": shapes["wte"][1],
              "blocks.w_o": shapes["wte"][1],
              "blocks.w_down": shapes["blocks.w_down"][1]}
    out = {}
    for key, (name, shape) in zip(keys, sorted(shapes.items())):
        std = 1.0 / math.sqrt(fan_in[name]) if name in fan_in else 0.02
        x = std * jax.random.normal(key, shape, jnp.float32)
        out[name] = 1.0 + x if name.endswith("_g") else x
    return out


def _q(x, precision: str):
    """Round a matmul operand to the stated precision (values stay f32).
    The rounding is straight-through: a cotangent is not itself cast to the
    narrow type, where without a loss scale it would flush to zero."""
    if precision == "float32":
        return x
    dt = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}[precision]
    return x + lax.stop_gradient(x.astype(dt).astype(jnp.float32) - x)


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * g + b


def _block(x, p, precision: str, q_block: int):
    """One decoder block on one row: x [L, E]."""
    l, e = x.shape
    d = p["w_qkv"].shape[-1]
    y = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    qkv = jnp.einsum("le,ethd->tlhd", _q(y, precision), _q(p["w_qkv"], precision))
    q, k, v = qkv[0], qkv[1], qkv[2]
    scale = 1.0 / math.sqrt(d)
    outs = []
    # attention in blocks of queries: scores are [H, q_block, L] at a time
    for start in range(0, l, q_block):
        qb = q[start:start + q_block]
        s = jnp.einsum("qhd,khd->hqk", _q(qb, precision), _q(k, precision)) * scale
        rows = start + jnp.arange(qb.shape[0])[:, None]
        s = jnp.where(jnp.arange(l)[None, :] <= rows, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _q(w, precision), _q(v, precision)))
    o = jnp.concatenate(outs, axis=0)
    x = x + jnp.einsum("lhd,hde->le", _q(o, precision), _q(p["w_o"], precision))
    y = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    y = jax.nn.gelu(_q(y, precision) @ _q(p["w_up"], precision), approximate=True)
    return x + _q(y, precision) @ _q(p["w_down"], precision)


def row_loss(params, tokens, targets, precision: str = "float32",
             q_block: int = 512, v_block: int = 512):
    """Sum over positions of the next-token cross-entropy of ONE row:
    tokens, targets [L] int32."""
    l = tokens.shape[0]
    x = params["wte"][tokens] + params["wpe"][:l]
    blocks = {k.split(".", 1)[1]: v for k, v in params.items()
              if k.startswith("blocks.")}

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(x, p):
        return _block(x, p, precision, q_block), None

    x, _ = lax.scan(body, x, blocks)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])

    # unembedding in blocks of positions: logits are [v_block, V] at a time
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def ce(xb, tb):
        logits = _q(xb, precision) @ _q(params["wte"], precision).T
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0])

    nb = l // v_block if l % v_block == 0 and l >= v_block else 1
    per = lax.map(lambda a: ce(*a), (x.reshape(nb, l // nb, -1),
                                     targets.reshape(nb, l // nb)))
    return jnp.sum(per)


def batch_loss(params, tokens, targets, precision: str = "float32",
               rows: str = "all"):
    """Mean cross-entropy of a batch [B, L], one row at a time."""
    if rows == "half":
        tokens, targets = tokens[: tokens.shape[0] // 2], targets[: targets.shape[0] // 2]
    total = lax.map(lambda a: row_loss(params, a[0], a[1], precision),
                    (tokens, targets))
    return jnp.sum(total) / (tokens.shape[0] * tokens.shape[1])


@functools.partial(jax.jit, static_argnames=("precision",), donate_argnums=(0, 1))
def _add_row_grad(acc, loss_acc, params, tokens, targets, precision: str):
    """``(acc + d row_loss / d params, loss_acc + row_loss)`` for one row,
    added in place: a program of its own so that, beside the parameters,
    only the running sum and this row's gradient are alive."""
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(row_loss)(params, tokens, targets, precision)
    return jax.tree.map(jnp.add, acc, g), loss_acc + loss


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(local, acc, factor):
    return jax.tree.map(lambda p, g: p - factor * g, local, acc)


def sgd_step(local, x, y, lr: float, precision: str = "float32", rows: str = "all"):
    """One plain SGD step on one batch ``x, y`` [B, L] by the gradient of the
    batch's mean cross-entropy (``batch_loss``), the rows' gradients summed
    one row at a time.  ``local``'s buffers are given up.  Returns (local
    after, loss).  ``rows="half"``: the planted fault."""
    if rows == "half":
        x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
    acc = jax.tree.map(jnp.zeros_like, local)
    loss = jnp.zeros((), jnp.float32)
    for r in range(x.shape[0]):
        acc, loss = _add_row_grad(acc, loss, local, x[r], y[r], precision)
    scale = 1.0 / (x.shape[0] * x.shape[1])
    return _apply(local, acc, jnp.float32(lr * scale)), loss * scale


def sgd_window(local, xs, ys, lr: float, precision: str = "float32",
               rows: str = "all"):
    """One communication window: ``xs, ys`` [steps, B, L]; plain SGD steps
    from ``local`` (whose buffers are given up).  Returns (local after, mean
    of the steps' losses)."""
    losses = []
    for i in range(xs.shape[0]):
        local, loss = sgd_step(local, xs[i], ys[i], lr, precision, rows)
        losses.append(loss)
    return local, jnp.mean(jnp.stack(losses))


def _leaf_norms(tree: Dict[str, jnp.ndarray], rare_rows=None) -> Dict[str, jnp.ndarray]:
    """Per-leaf L2 norms; a stacked ``blocks.*`` leaf gives one per layer.
    ``rare_rows`` (row indices of ``wte``) adds the sub-leaf ``wte.rare``:
    the embedding rows of tokens a batch holds once or never, where a row
    of the batch that was left out shows (the whole leaf's norm is carried
    by the frequent tokens' rows, which every row of the batch moves)."""
    out = {}
    for name, x in tree.items():
        axes = tuple(range(1, x.ndim)) if name.startswith("blocks.") else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))
    if rare_rows is not None:
        out["wte.rare"] = jnp.sqrt(jnp.sum(jnp.square(
            tree["wte"][rare_rows].astype(jnp.float32))))
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _change_norms(center, seed, rare_rows, cfg_key):
    p0 = init_params(dict(cfg_key), seed)
    return _leaf_norms({k: center[k] - p0[k] for k in p0}, rare_rows)


def change_norms(cfg: Dict[str, Any], center: Dict[str, jnp.ndarray], seed: int,
                 rare_rows=None) -> Dict[str, Any]:
    """Per-leaf norm of ``center - init_params(seed)``, computed on the
    device without keeping a second tree (the seed's weights are made
    again inside the program)."""
    key = tuple(sorted((k, v) for k, v in cfg.items()
                       if isinstance(v, (int, float, str))))
    return jax.device_get(_change_norms(center, jnp.uint32(seed % 2**32),
                                        rare_rows, key))


def follow(cfg: Dict[str, Any], seed: int, calls: Sequence[Any], lr: float,
           num_workers: int = 1, self_staleness: int = 0,
           precision: str = "float32", rows: str = "all",
           rare_rows=None) -> List[Dict[str, Any]]:
    """Follow the trainer through its first ``train()`` calls.

    ``calls`` is a list of ``(xs, ys)`` with shape [windows, steps, B, L] —
    what each call's single worker saw, in order.  Every window trains
    ``steps`` SGD steps from the center it pulled and commits
    ``(after - pulled) / num_workers`` to the center (ADAG).  Within a call
    the pull for window ``w`` sees the commits up to ``w - 1 -
    self_staleness`` (0 on the synchronous plane; 1 for the pipelined
    asynchronous worker, whose next pull leaves before its commit); a new
    call starts from the center the previous one left.

    Returns, per call: the windows' mean losses and the per-leaf norms of
    the center's change from the seed's weights.
    """
    make = jax.jit(lambda s: init_params(cfg, s))
    center = make(jnp.uint32(seed % 2**32))
    out = []
    for xs, ys in calls:
        centers = {0: center}       # centers[i]: after i commits of this call
        losses = []
        for w in range(xs.shape[0]):
            pulled = centers[max(w - self_staleness, 0)]
            after, loss = sgd_window(jax.tree.map(jnp.copy, pulled),
                                     jnp.asarray(xs[w]), jnp.asarray(ys[w]),
                                     lr=lr, precision=precision, rows=rows)
            centers[w + 1] = jax.tree.map(
                lambda c, a, p: c + (a - p) / num_workers,
                centers[w], after, pulled)
            del after, pulled
            for k in [k for k in centers if k < w + 1 - self_staleness]:
                del centers[k]      # no later pull of this call can see it
            losses.append(float(loss))
        center = centers[xs.shape[0]]
        del centers
        out.append({"losses": losses,
                    "norms": change_norms(cfg, center, seed, rare_rows)})
    return out
