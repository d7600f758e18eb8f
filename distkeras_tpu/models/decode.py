"""KV-cache autoregressive decoding for ``TransformerLM``.

The reference has a streaming-inference story only as a Spark+Kafka
pipeline of independent ``model.predict`` calls (SURVEY.md §2.21); for the
flagship LM family the TPU-native equivalent is real incremental decoding:
a compiled prefill that ingests the whole prompt in one MXU-shaped pass and
a compiled per-token step that attends against an in-HBM KV cache instead
of re-running the full sequence (O(L) per token instead of O(L²)).

Implementation notes:

- Pure functions over the published param tree (``embed``, ``pos_embed``,
  ``block_{i}.{LayerNorm_0,qkv,proj,LayerNorm_1,up,down}``, ``final_norm``;
  GQA specs replace the fused ``qkv`` leaf with ``q`` [E, H, Dh] and
  ``kv`` [E, 2, Hkv, Dh] — ``_block`` dispatches on which is present)
  rather than a Flax method: a compact Flax module allows only one
  ``nn.compact`` method, and threading a mutable cache collection through
  ``module.apply`` would force the training path to carry decode-only
  plumbing.  Parity with ``TransformerLM.__call__`` is enforced by test
  (``tests/test_decode.py``), not by code sharing.
- One attention routine serves prefill (L = prompt) and decode (L = 1):
  new K/V rows are written into the cache at ``start_pos`` with
  ``lax.dynamic_update_slice`` and queries attend over the full cache
  under the mask ``key_pos <= start_pos + query_offset`` — dead cache rows
  are masked, so the cache can be any length >= the generated sequence.
- Static shapes throughout: the generation loop is a ``lax.scan`` of
  single-token steps over a fixed ``max_new_tokens``; finished rows (past
  EOS) keep emitting ``pad_id`` under a carried ``done`` flag instead of
  breaking out, which is the compiler-friendly form of early exit.
- The KV cache is [num_layers, B, cache_len, Hkv, Dh] in the compute dtype
  (bfloat16 by default; Hkv = ``num_kv_heads`` under GQA, else H) — the
  decode-time HBM working set — and attention logits/softmax run in
  float32 like the training path.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.ops.quantize import QTensor


def _wmul(eq: str, y: jnp.ndarray, w, dtype) -> jnp.ndarray:
    """``einsum(eq, y, w)`` where ``w`` may be an int8 ``QTensor``.

    The per-OUTPUT-channel scale commutes out of the contraction
    (``einsum(y, q * s) == einsum(y, q) * s`` when ``s`` varies only along
    the kernel's last, non-contracted axis), so the weight is consumed as
    int8 — the convert fuses into the matmul's operand read and the scale
    multiply into its epilogue, keeping per-step HBM weight traffic at 1
    byte/elem instead of materializing an f32 copy outside the decode loop.
    Every block kernel here (qkv [E,3,H,Dh] — or the GQA pair q [E,H,Dh] /
    kv [E,2,Hkv,Dh] — proj [H,Dh,E], up [E,F], down [F,E]) has its channel
    axis last and uncontracted; the embedding does NOT (``attend``
    contracts E), so it is dequantized once up front.
    """
    if isinstance(w, QTensor):
        out = jnp.einsum(eq, y, w.q.astype(dtype))
        return out * w.scale.reshape(-1).astype(dtype)
    return jnp.einsum(eq, y, w.astype(dtype))


def dequant_embed(params: Any) -> Any:
    """int8 trees (ops/quantize.py) decode transparently: block kernels are
    consumed as int8 per use via ``_wmul`` (the scale commutes out of each
    matmul), so per-step weight traffic stays at 1 byte/elem.  Only the
    embedding dequantizes up front — its scale axis (E) is contracted by
    the unembed, so the scale does not commute there.  Shared prologue of
    ``make_generate_fn`` and ``speculative.make_speculative_generate_fn``."""
    emb = params["embed"]["embedding"]
    if isinstance(emb, QTensor):
        params = dict(params, embed={"embedding": emb.dequantize(jnp.float32)})
    return params


class KVCache(NamedTuple):
    """Stacked per-layer key/value cache: [num_layers, B, S, H, Dh]."""

    k: jnp.ndarray
    v: jnp.ndarray


class QKVCache(NamedTuple):
    """int8-quantized KV cache: values [L, B, S, H, Dh] int8 with
    per-(position, head) float32 scales [L, B, S, H, 1].

    Serving memory-bandwidth lever (batched decode reads the whole cache
    every step, the dominant cost once the batch grows): storing KV int8
    halves that traffic, and XLA fuses the dequantize into the attention
    dots' operand reads.  Quantization error is
    one rounding step per K/V row — NOT bit-exact with the bf16 cache;
    the ``tests/test_decode.py`` oracle pins that the quantized-cache
    forward equals a full-precision forward over the SAME
    rounded-then-dequantized values."""

    k: jnp.ndarray        # int8
    v: jnp.ndarray        # int8
    k_scale: jnp.ndarray  # f32 [L, B, S, H, 1]
    v_scale: jnp.ndarray  # f32


def _quantize_rows(x: jnp.ndarray):
    """[B, L, H, D] -> (int8 values, f32 scales [B, L, H, 1]); symmetric
    per-(position, head), exact zero rows keep scale 1."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _cfg_dtype(config: dict) -> Any:
    return config.get("compute_dtype", jnp.bfloat16)


def validate_decode_spec(spec: ModelSpec, what: str = "decoding") -> dict:
    """Shared precondition gate for the whole decoder family (plain
    generate, speculative target/draft, beam search): KV-cache math is
    single-program transformer_lm only.  Returns a config copy."""
    config = dict(spec.config)
    if config.get("seq_axis") or config.get("tp_axis"):
        raise ValueError(f"{what} expects a plain (non-sharded) spec; strip "
                         "seq_axis/tp_axis — the cache math is single-program")
    if config.get("moe_experts"):
        raise ValueError(f"KV-cache {what} does not support MoE specs (v1)")
    if spec.name != "transformer_lm":
        raise ValueError(f"{what} is defined for transformer_lm specs, "
                         f"got {spec.name!r}")
    # the cache path has its own forward of the GPT-2-style block
    from distkeras_tpu.models.transformer import reject_block_features

    reject_block_features(config, f"KV-cache {what}")
    return config


def _layer_norm(p: dict, x: jnp.ndarray, dtype) -> jnp.ndarray:
    """flax.linen.LayerNorm semantics: stats in float32, eps 1e-6."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = jnp.square(xf - mu).mean(-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + 1e-6)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def _block(pb: dict, x: jnp.ndarray, cache, layer: int, start_pos, dtype,
           positional: str = "learned"):
    """One transformer block over ``x`` [B, L, E] with KV caching.

    ``cache`` is the STACKED [layers, B, S, H, Dh] :class:`KVCache` (or
    :class:`QKVCache`); only the L new K/V rows of layer ``layer`` are
    written (in place when XLA can alias the scan carry — the whole
    point: rewriting the full cache per decoded token would move every
    layer's slab once a token).  Queries attend over the layer's slab
    masked to ``key_pos <= start_pos + query_offset``, which also masks
    dead rows beyond the write head.

    On a quantized cache the new rows are rounded to int8 on write; the
    per-(position, head) K scale commutes out of the score dot and the V
    scale folds into the attention probabilities (both vary only along
    the key axis), so the int8 slabs feed the einsums directly and XLA
    fuses the convert into the operand reads — the cache's HBM traffic
    halves, which is the whole point at decode batch sizes.
    """
    head_dim = cache.k.shape[-1]
    quant = isinstance(cache, QKVCache)

    y = _layer_norm(pb["LayerNorm_0"], x, dtype)
    if "qkv" in pb:
        qkv = _wmul("ble,eshd->blshd", y, pb["qkv"]["kernel"], dtype)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        # GQA layout: separate q [E, H, Dh] and kv [E, 2, Hkv, Dh]
        # projections (models/transformer.py); the cache stores Hkv heads
        q = _wmul("ble,ehd->blhd", y, pb["q"]["kernel"], dtype)
        kv = _wmul("ble,eshd->blshd", y, pb["kv"]["kernel"], dtype)
        k, v = kv[:, :, 0], kv[:, :, 1]
    if positional == "rope":
        from distkeras_tpu.ops.rotary import rope_rotate

        # K enters the cache ALREADY rotated (rotation depends only on the
        # row's own absolute position, so cached rows never need revisiting)
        rpos = start_pos + jnp.arange(x.shape[1])
        q, k = rope_rotate(q, rpos), rope_rotate(k, rpos)
    if quant:
        k_rows, k_rows_scale = _quantize_rows(k)
        v_rows, v_rows_scale = _quantize_rows(v)
    else:
        k_rows, v_rows = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    k_all = lax.dynamic_update_slice(
        cache.k, k_rows[None], (layer, 0, start_pos, 0, 0))
    v_all = lax.dynamic_update_slice(
        cache.v, v_rows[None], (layer, 0, start_pos, 0, 0))
    ck, cv = k_all[layer], v_all[layer]

    # grouped heads: fold the query heads as [Hkv, G] and contract each
    # group against its single cached KV head — the cache slabs feed the
    # einsums at Hkv width, never materializing an H-headed copy (that
    # read traffic is GQA's savings); G == 1 reduces to plain MHA
    b, l, hq, _ = q.shape
    hkv = ck.shape[2]
    g = hq // hkv
    qg = q.reshape(b, l, hkv, g, head_dim)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                        ck.astype(dtype) if quant else ck,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / head_dim ** 0.5)
    if quant:
        k_scale = lax.dynamic_update_slice(
            cache.k_scale, k_rows_scale[None], (layer, 0, start_pos, 0, 0))
        v_scale = lax.dynamic_update_slice(
            cache.v_scale, v_rows_scale[None], (layer, 0, start_pos, 0, 0))
        # [L?, B, S, Hkv, 1] -> [B, Hkv, 1, 1, S] broadcast along keys
        scores = scores * k_scale[layer][..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    q_pos = start_pos + lax.broadcasted_iota(jnp.int32, scores.shape, 3)
    k_pos = lax.broadcasted_iota(jnp.int32, scores.shape, 4)
    scores = jnp.where(k_pos <= q_pos, scores, float("-inf"))
    attn = jax.nn.softmax(scores, axis=-1)
    if quant:
        attn = attn * v_scale[layer][..., 0].transpose(0, 2, 1)[:, :, None, None, :]
    attn = attn.astype(dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", attn,
                   cv.astype(dtype) if quant else cv).reshape(b, l, hq, head_dim)
    o = _wmul("bqhd,hde->bqe", o, pb["proj"]["kernel"], dtype)
    x = x + o

    y = _layer_norm(pb["LayerNorm_1"], x, dtype)
    y = jax.nn.gelu(_wmul("ble,ef->blf", y, pb["up"]["kernel"], dtype))
    y = _wmul("blf,fe->ble", y, pb["down"]["kernel"], dtype)
    new_cache = (QKVCache(k_all, v_all, k_scale, v_scale) if quant
                 else KVCache(k_all, v_all))
    return x + y, new_cache


def init_cache(config: dict, batch: int, cache_len: int,
               quantized: bool = False):
    """Zero cache sized for ``cache_len`` total positions (prompt + new);
    ``quantized`` selects the int8 :class:`QKVCache` layout.  Under GQA
    the cache holds only ``num_kv_heads`` heads — the bytes (and decode
    HBM traffic) shrink by num_kv_heads/num_heads, which is the feature's
    whole point at serving batch sizes."""
    n_layers = config["num_layers"]
    heads = config.get("num_kv_heads") or config["num_heads"]
    head_dim = config["model_dim"] // config["num_heads"]
    shape = (n_layers, batch, cache_len, heads, head_dim)
    if quantized:
        sshape = shape[:-1] + (1,)
        return QKVCache(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                        jnp.ones(sshape, jnp.float32),
                        jnp.ones(sshape, jnp.float32))
    dtype = _cfg_dtype(config)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def forward_with_cache(params: Any, config: dict, tokens: jnp.ndarray,
                       start_pos, cache: KVCache,
                       last_only: bool = False) -> Tuple[jnp.ndarray, KVCache]:
    """Run tokens [B, L] at positions ``start_pos..start_pos+L-1`` against
    the cache; returns (float32 logits, updated cache) — [B, L, vocab], or
    [B, 1, vocab] when ``last_only`` (generation consumes only the final
    position, and the [L, vocab] unembed matmul is the prefill's single
    biggest op at real vocab sizes).

    Serves both phases: prefill (L = prompt length, start_pos = 0) and
    decode (L = 1, start_pos = current length).
    """
    dtype = _cfg_dtype(config)
    n_layers = config["num_layers"]
    positional = config.get("positional") or "learned"
    x = params["embed"]["embedding"].astype(dtype)[tokens]
    if positional == "learned":
        pos = start_pos + jnp.arange(tokens.shape[1])
        x = x + params["pos_embed"][pos].astype(dtype)

    for i in range(n_layers):
        x, cache = _block(params[f"block_{i}"], x, cache, i, start_pos, dtype,
                          positional)

    if last_only:
        x = x[:, -1:]
    x = _layer_norm(params["final_norm"], x, dtype)
    logits = jnp.einsum("ble,ve->blv", x.astype(jnp.float32),
                        params["embed"]["embedding"].astype(jnp.float32))
    return logits, cache


class FusedStepState(NamedTuple):
    """Everything the fused Pallas decode step needs beyond the caches:
    the stacked weight slabs plus the embedding/head params shared with
    the XLA formulation.  Built once per generate call (loop-invariant —
    XLA hoists it out of the decode scan)."""

    weights: Any          # ops.decode_step.DecodeWeights
    embedding: jnp.ndarray  # [V, E] compute dtype (gather side)
    params: Any           # full tree (final_norm + f32 unembed + pos_embed)
    config: dict
    interpret: bool


def make_fused_state(params: Any, config: dict) -> FusedStepState:
    from distkeras_tpu.ops.decode_step import stack_decode_weights
    from distkeras_tpu.platform import on_tpu

    dtype = _cfg_dtype(config)
    return FusedStepState(
        weights=stack_decode_weights(params, config["num_layers"], dtype),
        embedding=params["embed"]["embedding"].astype(dtype),
        params=params, config=config,
        interpret=not on_tpu())


def fused_token_forward(state: FusedStepState, tok: jnp.ndarray, pos,
                        k_t: jnp.ndarray, v_all: jnp.ndarray):
    """One fused single-token step + head: [B] tokens at ``pos`` ->
    (float32 logits [B, 1, V], k_t, v_all).  The head math mirrors
    ``forward_with_cache`` exactly (f32 final norm stats, f32 unembed)."""
    from distkeras_tpu.ops.decode_step import fused_decode_step

    config, params = state.config, state.params
    dtype = _cfg_dtype(config)
    x = state.embedding[tok] + params["pos_embed"][pos].astype(dtype)
    hidden, k_t, v_all = fused_decode_step(
        state.weights, x, k_t, v_all, pos,
        heads=config["num_heads"], interpret=state.interpret)
    h = _layer_norm(params["final_norm"], hidden[:, None], dtype)
    logits = jnp.einsum("ble,ve->blv", h.astype(jnp.float32),
                        params["embed"]["embedding"].astype(jnp.float32))
    return logits, k_t, v_all


def _sample(logits: jnp.ndarray, rng, temperature: float, top_k: int,
            top_p: float = 0.0) -> jnp.ndarray:
    """[B, vocab] float32 logits -> [B] int32 token ids.

    ``top_k`` keeps the k highest logits; ``top_p`` (nucleus sampling,
    Holtzman et al. 2019) keeps the smallest set of tokens whose
    temperature-scaled probabilities sum to >= top_p — the filters
    compose (k first, then p) and both are no-ops at their 0 defaults.
    The nucleus always contains the argmax, so top_p -> 0 degrades to
    greedy, not to an empty support."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, float("-inf"), logits)
    if top_p and top_p < 1.0:
        probs = jax.nn.softmax(logits / temperature, axis=-1)
        order = jnp.argsort(-probs, axis=-1)
        sorted_probs = jnp.take_along_axis(probs, order, axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        # a token stays iff the mass BEFORE it (exclusive) is < top_p; the
        # exclusive form keeps the top-1 token unconditionally.  The mask
        # maps back through the inverse permutation (NOT a probability
        # threshold, which would re-admit every token tied with the
        # boundary and make top_p a no-op on tied distributions)
        keep_sorted = (cum - sorted_probs) < top_p
        inv = jnp.argsort(order, axis=-1)
        keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
        logits = jnp.where(keep, logits, float("-inf"))
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(jnp.int32)


def warn_quantized_cache_gqa(config: dict, context: str) -> None:
    """Warn when ``quantize_cache=True`` composes with GQA — a measured
    NET LOSS, not a neutral default.

    The int8 KV cache pays a quantize-on-write op per step to halve cache
    READ traffic; GQA (``num_kv_heads < num_heads``) has already cut that
    traffic by the head ratio, so there is little bandwidth left to win
    and the write cost dominates.  The figure the message quotes is a
    July 2026 record (v5e, batch 64, int8 stacked on a 4x-GQA cache)
    that no benchmark cell has repeated: decoding has no cell (ROADMAP,
    "Never on the ledger").  The combination composes silently in
    config, so every decode builder routes through this guard; it stays
    a WARNING (not a refusal) because the crossover may return at much
    longer cache_len — re-measure at your shape before suppressing it."""
    kv_heads = config.get("num_kv_heads") or config["num_heads"]
    if kv_heads < config["num_heads"]:
        warnings.warn(
            f"quantize_cache=True with GQA (num_kv_heads={kv_heads} < "
            f"num_heads={config['num_heads']}) in {context} is a measured "
            "net loss on v5e batched decode (94.9k -> 82.4k tok/s at "
            "batch 64, -13%): GQA already cut the cache reads by the head "
            "ratio, so int8's read savings no longer cover its "
            "quantize-on-write cost.  Drop quantize_cache (keep GQA), or "
            "re-measure at your shape before relying on this combination.",
            UserWarning, stacklevel=3)


def make_generate_fn(spec: ModelSpec, max_new_tokens: int, *,
                     temperature: float = 0.0, top_k: int = 0,
                     top_p: float = 0.0,
                     eos_id: Optional[int] = None, pad_id: int = 0,
                     cache_len: Optional[int] = None,
                     step_impl: Optional[str] = None,
                     quantize_cache: bool = False):
    """Build a jitted ``(params, prompt [B, P], rng) -> tokens [B, max_new]``.

    ``cache_len`` defaults to prompt length + ``max_new_tokens`` (it is a
    static shape, so the returned fn recompiles per distinct prompt length,
    like any jitted shape-polymorphic JAX program).  Greedy when
    ``temperature == 0``; ``top_k``/``top_p`` (nucleus) filter the sampled
    distribution (see ``_sample``).  Rows that have emitted ``eos_id``
    keep emitting ``pad_id``.

    ``quantize_cache=True`` stores KV int8 with per-(position, head)
    scales (:class:`QKVCache`): cache HBM traffic halves — the dominant
    batched-decode cost — at one rounding step of approximation per K/V
    row (an accuracy/throughput trade, NOT bit-exact; see the QKVCache
    docstring and the oracle test).  Requires the XLA step
    (``step_impl`` must not be ``"fused"``).

    ``step_impl``: ``None`` auto-selects — the fused Pallas block kernel
    (``ops/decode_step.py``) on TPU when the shapes support it, the XLA
    per-op step otherwise.  ``"fused"`` / ``"xla"`` pin the path for A/B
    measurement (``"fused"`` off-TPU runs the Pallas interpreter — slow,
    test-only).  Both paths produce the same tokens (parity-tested); the
    fused step exists because the XLA form pays ~15 ops of fixed sequencing
    cost per layer per token (see the kernel module docstring).
    """
    if step_impl not in (None, "fused", "xla"):
        raise ValueError(f"unknown step_impl {step_impl!r}; use None, 'fused' or 'xla'")
    if not 0.0 <= top_p <= 1.0:  # also rejects NaN
        raise ValueError(f"top_p must be in [0, 1], got {top_p} (a negative "
                         "value would mask every token — including the argmax "
                         "— and categorical over an all--inf row silently "
                         "emits token 0)")
    if not temperature >= 0.0:  # also rejects NaN
        raise ValueError(f"temperature must be >= 0, got {temperature} "
                         "(a negative value would silently select greedy)")
    if quantize_cache and step_impl == "fused":
        raise ValueError("quantize_cache requires the XLA step: the fused "
                         "kernel's slabs are bf16 (step_impl='xla' or None)")
    config = validate_decode_spec(spec, "decoding")
    if quantize_cache:
        warn_quantized_cache_gqa(config, "make_generate_fn")
    if not 0 <= top_k <= config["vocab_size"]:
        raise ValueError(f"top_k must be in [0, vocab_size="
                         f"{config['vocab_size']}], got {top_k} "
                         "(out-of-range values fail at trace time inside "
                         "lax.top_k, not here where the mistake is visible)")
    max_seq = config["max_seq_len"]

    @functools.partial(jax.jit, static_argnames=("prompt_len", "impl"))
    def run(params, prompt, rng, prompt_len, impl):
        params = dequant_embed(params)
        total = cache_len or (prompt_len + max_new_tokens)
        # validate the user-supplied capacity BEFORE the fused path rounds it
        # up to a lane multiple, so both impls accept/reject identically (an
        # undersized cache_len must not pass on one step_impl and raise on
        # the other depending on auto-selection)
        if prompt_len + max_new_tokens > total:
            raise ValueError(
                f"cache_len = {total} cannot hold prompt ({prompt_len}) + "
                f"max_new_tokens ({max_new_tokens}); out-of-range cache "
                "writes would silently clamp and corrupt generation")
        if impl == "fused":
            from distkeras_tpu.ops.decode_step import round_cache_len

            total = round_cache_len(total)  # K-slab lane tiling
        # the positional-TABLE bound applies only under "learned": rope has
        # no table and generates past max_seq_len freely (the cache checks
        # above are the real capacity bound there)
        if ((config.get("positional") or "learned") == "learned"
                and prompt_len + max_new_tokens > max_seq):
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the positional table max_seq_len = {max_seq}")
        cache = init_cache(config, prompt.shape[0], total,
                           quantized=quantize_cache)
        logits, cache = forward_with_cache(params, config, prompt, 0, cache,
                                           last_only=True)
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature, top_k, top_p)
        # the EOS token itself is kept in the output; rows are padded after
        done = jnp.zeros(prompt.shape[0], bool) if eos_id is None else tok == eos_id

        if impl == "fused":
            from distkeras_tpu.ops.decode_step import transpose_k_cache

            # loop-invariant w.r.t. the scan: XLA materializes this once
            # per call, not per token
            state = make_fused_state(params, config)
            # the fused kernel wants lane-major keys; transpose ONCE after
            # prefill (the scan then carries KVCache(k_t, v) — k in
            # [L, HD, B, S] layout, v unchanged)
            cache = KVCache(transpose_k_cache(cache.k), cache.v)

        def step(carry, _):
            tok, cache, pos, rng, done = carry
            if impl == "fused":
                logits, k_t, v_all = fused_token_forward(
                    state, tok, pos, cache.k, cache.v)
                cache = KVCache(k_t, v_all)
            else:
                logits, cache = forward_with_cache(
                    params, config, tok[:, None], pos, cache)
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature, top_k, top_p)
            if eos_id is not None:
                nxt = jnp.where(done, pad_id, nxt)
                done = done | (nxt == eos_id)
            return (nxt, cache, pos + 1, rng, done), nxt

        carry = (tok, cache, jnp.asarray(prompt_len, jnp.int32), rng, done)
        if max_new_tokens > 1:
            (_, _, _, _, _), rest = lax.scan(step, carry, None,
                                             length=max_new_tokens - 1)
            return jnp.concatenate([tok[:, None], rest.T], axis=1)
        return tok[:, None]

    def generate_fn(params, prompt, rng=None):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        from distkeras_tpu.ops.decode_step import resolve_step_impl

        if quantize_cache:
            # the fused kernel's slabs are bf16 — an int8 QKVCache through
            # it would silently drop the scales.  The explicit-'fused'
            # combination already raised at build time; auto must resolve
            # to the XLA step here, not just usually avoid it
            impl = "xla"
        else:
            # auto keys on the MEASURED win region (small models, batch 1
            # — see ops.decode_step.fused_step_auto), not just shape
            # support: the 8-layer/512-dim XLA step is already optimal
            impl = resolve_step_impl(
                config, prompt.shape[0],
                cache_len or (prompt.shape[1] + max_new_tokens), step_impl)
        return run(params, prompt, rng, prompt.shape[1], impl)

    return generate_fn


def make_sharded_generate_fn(spec: ModelSpec, mesh, max_new_tokens: int, *,
                             tp_axis: Optional[str] = "tp",
                             dp_axis: Optional[str] = None, **kw):
    """Distributed decoding via GSPMD sharding propagation.

    Rather than rewriting the cache math in shard_map, this places the
    params with the SAME Megatron partition specs the tensor-parallel
    training step uses (``parallel/lm.py :: lm_param_specs``: qkv
    column-parallel over heads, proj/down row-parallel, up column-parallel)
    and the prompt batch over ``dp_axis``, then lets XLA's sharding
    propagation partition the jitted generation program — the KV cache
    inherits the head sharding from the qkv einsum, attention stays local
    to the head shard, and the row-parallel matmuls become psums over ICI.
    Compiler-first: the single-device program IS the distributed program.

    Returns ``fn(params, prompt, rng=None) -> tokens [B, max_new_tokens]``;
    placement happens inside, so callers pass ordinary host/device arrays.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from distkeras_tpu.parallel.lm import lm_param_specs

    # the fused Pallas step would be an opaque box to GSPMD's sharding
    # propagation — the whole mechanism this path relies on — so the
    # sharded program always uses the XLA step (None = auto resolves to
    # it here; only an explicit 'fused' is an error)
    if kw.get("step_impl") is None:
        kw["step_impl"] = "xla"
    if kw["step_impl"] != "xla":
        raise ValueError("make_sharded_generate_fn requires step_impl='xla': "
                         "sharding propagation cannot see through the fused "
                         "Pallas decode kernel")
    inner = make_generate_fn(spec, max_new_tokens, **kw)  # validates the spec
    for name, axis in (("tp_axis", tp_axis), ("dp_axis", dp_axis)):
        # a typo'd axis must not silently degrade to full replication
        if axis is not None and axis not in mesh.shape:
            raise ValueError(f"{name} {axis!r} is not a mesh axis of {mesh}; "
                             "pass None to disable that parallelism")
    tp = mesh.shape[tp_axis] if tp_axis else 1
    if spec.config["num_heads"] % tp:
        raise ValueError(f"num_heads {spec.config['num_heads']} not divisible "
                         f"by tp={tp} over mesh axis {tp_axis!r}")
    kv_heads = spec.config.get("num_kv_heads") or spec.config["num_heads"]
    if kv_heads % tp:
        raise ValueError(f"num_kv_heads {kv_heads} not divisible by tp={tp}: "
                         "the cache's head axis is the sharded one — use a "
                         "tp that divides the KV heads, or dp-only decoding")

    def fn(params, prompt, rng=None):
        if any(isinstance(l, QTensor) for l in jax.tree.leaves(
                params, is_leaf=lambda l: isinstance(l, QTensor))):
            raise ValueError("int8-quantized trees are not supported with "
                             "sharded decoding (v1): per-channel scale dims "
                             "don't carry the Megatron partition specs; use "
                             "make_generate_fn (single-program) instead")
        if dp_axis and prompt.shape[0] % mesh.shape[dp_axis]:
            raise ValueError(f"batch {prompt.shape[0]} not divisible by "
                             f"dp={mesh.shape[dp_axis]}")
        pspecs = lm_param_specs(params, tp_axis if tp > 1 else None)
        params = jax.device_put(params, jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: isinstance(x, P)))
        prompt = jax.device_put(jnp.asarray(prompt), NamedSharding(
            mesh, P(dp_axis) if dp_axis else P()))
        return inner(params, prompt, rng)

    return fn


def generate(model: Model, prompt: jnp.ndarray, max_new_tokens: int,
             *, temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             eos_id: Optional[int] = None, pad_id: int = 0,
             seed: int = 0) -> jnp.ndarray:
    """Convenience one-shot: generate ``max_new_tokens`` continuations of
    ``prompt`` [B, P] from a trained ``Model``; returns [B, max_new_tokens].

    For repeated generation build the fn once with :func:`make_generate_fn`
    (this wrapper rebuilds — and therefore recompiles — every call).
    """
    fn = make_generate_fn(model.spec, max_new_tokens, temperature=temperature,
                          top_k=top_k, top_p=top_p, eos_id=eos_id, pad_id=pad_id)
    return fn(model.params, jnp.asarray(prompt), jax.random.PRNGKey(seed))
