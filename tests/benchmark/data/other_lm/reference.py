"""Plain reference of a second, test-only family: the GPT-2-style decoder
of ``gpt_lm.py`` with rotary positions (split-half convention, base 10,000,
no positional table) and grouped key/value heads, under Llama-style
``config.json`` keys.  ``tests/benchmark`` copies it to
``reference/other_lm.py`` of a scratch benchmark tree to show that a family
is added as files.  It runs ``gpt_lm.py``'s source (its sibling there) in
this module and replaces what differs: the sizes, the leaves, one block and
the embedding of a row.  Nothing is imported from the program.
"""

import os

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "gpt_lm.py")) as _f:
    exec(compile(_f.read(), _f.name, "exec"), globals())


def sizes(cfg):
    e, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {"V": int(cfg["vocab_size"]), "E": e, "H": h, "D": e // h,
            "K": int(cfg["num_key_value_heads"]), "F": int(cfg["intermediate_size"]),
            "N": int(cfg["num_hidden_layers"]), "L": int(cfg["max_position_embeddings"])}


def param_shapes(cfg):
    s = sizes(cfg)
    n, e, h, k, d, f = s["N"], s["E"], s["H"], s["K"], s["D"], s["F"]
    return {
        "wte": (s["V"], e),
        "blocks.ln1_g": (n, e), "blocks.ln1_b": (n, e),
        "blocks.w_q": (n, e, h, d), "blocks.w_kv": (n, e, 2, k, d),
        "blocks.w_o": (n, h, d, e),
        "blocks.ln2_g": (n, e), "blocks.ln2_b": (n, e),
        "blocks.w_up": (n, e, f), "blocks.w_down": (n, f, e),
        "lnf_g": (e,), "lnf_b": (e,),
    }


def matmul_params(cfg):
    shapes = param_shapes(cfg)
    return sum(math.prod(shapes[k]) for k in
               ("wte", "blocks.w_q", "blocks.w_kv", "blocks.w_o", "blocks.w_up",
                "blocks.w_down"))


def _rope(x, base=10000.0):
    """Rotate x [L, heads, D] by its rows' absolute positions."""
    l, _, d = x.shape
    half = d // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(x, p, precision, q_block):
    l, e = x.shape
    h, d = p["w_q"].shape[-2:]
    group = h // p["w_kv"].shape[-2]
    y = _layer_norm(x, p["ln1_g"], p["ln1_b"])
    q = _rope(jnp.einsum("le,ehd->lhd", _q(y, precision), _q(p["w_q"], precision)))
    kv = jnp.einsum("le,etkd->tlkd", _q(y, precision), _q(p["w_kv"], precision))
    k = jnp.repeat(_rope(kv[0]), group, axis=1)
    v = jnp.repeat(kv[1], group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _q(q, precision), _q(k, precision)) / math.sqrt(d)
    s = jnp.where(jnp.arange(l)[None, :] <= jnp.arange(l)[:, None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", _q(jax.nn.softmax(s, axis=-1), precision),
                   _q(v, precision))
    x = x + jnp.einsum("lhd,hde->le", _q(o, precision), _q(p["w_o"], precision))
    y = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    y = jax.nn.gelu(_q(y, precision) @ _q(p["w_up"], precision), approximate=True)
    return x + _q(y, precision) @ _q(p["w_down"], precision)


def row_loss(params, tokens, targets, precision="float32", q_block=512, v_block=512):
    x = params["wte"][tokens]
    blocks = {k.split(".", 1)[1]: v for k, v in params.items()
              if k.startswith("blocks.")}
    x, _ = lax.scan(lambda x, p: (_block(x, p, precision, q_block), None), x, blocks)
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
    logits = _q(x, precision) @ _q(params["wte"], precision).T
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.sum(lse - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0])
