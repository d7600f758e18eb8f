"""Decoder-only TransformerLM — the framework's flagship long-context model.

Not present in the reference (it predates transformers; SURVEY.md §5) —
this is the TPU-native headroom model exercising the sequence-parallel
(ring attention) and tensor-parallel paths.  Designed MXU-first: all
matmuls are [*, model_dim] x [model_dim, *] with dims that tile 128 lanes;
``param_dtype`` float32 with bfloat16 activations via ``compute_dtype``.

Tensor parallelism (Megatron split, expressed in shard_map types):
- qkv is column-parallel over heads (kernel [E, 3, H, Dh], H sharded over
  the ``tp`` mesh axis), attention runs on the local head shard;
- proj is row-parallel (kernel [H, Dh, E]) producing a partial sum that is
  ``psum``'d over tp;
- MLP up is column-parallel ([E, F], F sharded), down row-parallel
  ([F, E]) followed by the second tp ``psum``.
Initialization always builds the FULL parameter tree (``tp_size=1``
semantics); the training step shards it onto the mesh and applies a module
configured with the LOCAL sizes (``tp_size=t``) inside ``shard_map`` —
see ``parallel/lm.py :: lm_param_specs``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distkeras_tpu import observability as obs
from distkeras_tpu.models.base import ModelSpec, StepHook, register_model
from distkeras_tpu.ops.attention import attention


def _maybe_psum(x: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    """psum over ``axis_name`` when it is bound by an enclosing shard_map;
    identity when traced outside one (init, single-device eval)."""
    if axis_name is None or axis_name not in jax.typeof(x).vma:
        return x
    return lax.psum(x, axis_name)


# what a spec may set beyond the GPT-2-style block, with the value that
# leaves the block as it was: the paths that know that block only (tensor,
# sequence and pipeline parallelism, cached decoding) refuse the rest by name
_BLOCK_DEFAULTS = {
    "norm": "layernorm", "head_dim": None, "qk_norm": False, "attn_gate": False,
    "post_norm": False, "mlp": "gelu", "layer_types": None, "routed_experts": 0,
    "tie_word_embeddings": True, "embed_scale": 1.0,
    "pre_norm": True, "linear_num_heads": 0, "linear_key_dim": 0, "linear_value_dim": 0,
    "linear_conv_width": 4, "linear_neg_eigval": False,
    "kv_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0, "v_head_dim": 0,
    "rope_interleave": False, "n_shared_experts": 1,
}
LAYER_KINDS = {"full": "full", "full_attention": "full",
               "sliding": "sliding", "sliding_attention": "sliding",
               "linear": "linear", "linear_attention": "linear",
               "latent": "latent"}


def unsupported_features(config) -> list:
    """The names of the config-driven block's features ``config`` (a spec's
    config or any mapping with those keys) sets away from their defaults."""
    return sorted(k for k, default in _BLOCK_DEFAULTS.items()
                  if config.get(k, default) != default)


def reject_block_features(config, where: str) -> None:
    new = unsupported_features(config)
    if new:
        raise ValueError(f"{where} runs the GPT-2-style block only (LayerNorm, "
                         f"one attention kind, a tied head, a dense GELU FFN or the "
                         f"Switch layer); this spec sets {new}")


class TransformerBlock(nn.Module):
    """One decoder block, ``x + Mixer(x)`` then ``x + FFN(x)``, its shape
    taken from configuration.  The mixer is softmax attention over the whole
    causal prefix or a sliding window (``_attention``: fused qkv or GQA,
    QK-norm, RoPE, an output gate), the gated delta rule
    (``_linear_attention``) or latent attention (``_latent_attention``: keys
    and values through a shared low-rank latent, one rotary key a token,
    queries and keys wider than values); the FFN a GELU or SwiGLU layer, the
    Switch layer or the sigmoid-routed expert layer.  Every default is the
    GPT-2-style block; tensor and sequence parallelism run that block only
    (``_check_parallel``)."""

    model_dim: int
    num_heads: int            # GLOBAL head count; local = num_heads // tp_size
    num_kv_heads: Optional[int] = None  # grouped-query attention (GQA,
                              # Ainslie et al. 2023): K/V projected to this
                              # many heads, each shared by num_heads/
                              # num_kv_heads query heads.  None = MHA (the
                              # fused qkv projection and its param layout
                              # are preserved exactly); set => separate
                              # "q" and "kv" projections.  The win is the
                              # decode KV cache (num_kv_heads/num_heads
                              # the bytes) and the ring's ICI traffic
    mlp_ratio: int = 4
    positional: str = "learned"  # "learned" (table added at embed) | "rope"
                                 # (q/k rotated here by ABSOLUTE position —
                                 # pos_offset carries the caller's global
                                 # offset, e.g. rank * L_local under sp) |
                                 # "none" (this layer gets no positional
                                 # signal: TransformerLM.rope_layers)
    seq_axis: Optional[str] = None  # mesh axis name for ring attention
    tp_axis: Optional[str] = None   # mesh axis name for tensor parallelism
    tp_size: int = 1
    attn_impl: Optional[str] = None  # None=auto | "flash" (pallas) | "dense";
                                     # must stay None when seq_axis is set
                                     # (ring attention governs that path)
    moe_experts: int = 0       # > 0 replaces the dense FFN with a Switch
    moe_capacity: int = 0      # MoE layer (see parallel/moe.py); capacity
    moe_top_k: int = 1         # is per-expert slots per shard; top_k 1=
    ep_axis: Optional[str] = None   # Switch, 2 = GShard-style gating
    ep_size: int = 1
    moe_dispatch: str = "auto"  # "dense" | "sorted" | "auto" dispatch path
                                # (parallel/moe.py resolve_dispatch_impl)
    # -- the block's shape, from configuration.  The defaults are the
    # GPT-2-style block above (pre-LayerNorm, fused qkv, tanh-GELU at
    # mlp_ratio * model_dim): a spec that sets none of these builds the
    # same parameter tree and the same program as before they existed.
    norm: str = "layernorm"    # "layernorm" | "rmsnorm" (scale only)
    norm_eps: float = 1e-6
    head_dim: Optional[int] = None  # None = model_dim // num_heads; set
                               # apart from the width, heads x head_dim
                               # need not equal model_dim
    qk_norm: object = False    # RMSNorm over each q and k head vector (True
                               # or "head") or over the whole projection,
                               # all heads together ("full")
    attn_gate: bool = False    # o = attention(...) * sigmoid(x @ W_gate)
    pre_norm: bool = True      # a norm BEFORE each sublayer
    post_norm: bool = False    # a norm AFTER each sublayer, before the
                               # residual add (alone, with pre_norm off: the
                               # block whose residual stream is never normed)
    mlp: str = "gelu"          # "gelu" (mlp_ratio * model_dim) | "swiglu"
    mlp_dim: Optional[int] = None   # the SwiGLU's stated width
    attn_kind: str = "full"    # "full" | "sliding" (causal, sliding_window)
                               # | "linear" (the gated delta rule: no
                               # softmax, a matrix state a head) | "latent"
                               # (keys and values through a shared low-rank
                               # latent, one rotary key for all heads)
    sliding_window: Optional[int] = None
    # the "linear" mixer: its heads, the size of a key (and query) and of a
    # value head, the taps of its causal depthwise convolutions, and whether
    # the write strength may pass 1 (beta = 2 sigmoid: I - beta k k^T then
    # has eigenvalues in (-1, 1])
    linear_num_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_width: int = 4
    linear_neg_eigval: bool = False
    # the "latent" mixer, under the published names (DeepSeek-V2/V3): the
    # latent's width, a head's un-rotated and rotary query/key channels
    # (queries and keys are their sum wide) and its value channels, and
    # whether the rotary channels pair up as (2i, 2i+1)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    rope_theta: float = 10000.0
    ffn_kind: str = "dense"    # "dense" | "moe": the sigmoid-routed expert
                               # layer with a shared expert
                               # (parallel/moe.py::HeldExpertsMLP); the
                               # Switch layer above is moe_experts
    routed_experts: int = 0    # the router's outputs (all experts of the
                               # deployment), of which this replica holds
    experts_held: Optional[tuple] = None  # ... [lo, hi); None = all
    routed_top_k: int = 8
    routed_dim: int = 0        # a routed expert's width
    n_shared_experts: int = 1  # the shared expert is ONE SwiGLU this many
                               # times as wide
    route_scale: float = 1.0
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.nowrap
    def _norm(self, name: str):
        """A norm module: LayerNorm keeps flax's automatic names
        (``LayerNorm_<n>``, the tree the GPT-2-style block always had),
        RMSNorm is named by its place."""
        if self.norm == "layernorm":
            return nn.LayerNorm(epsilon=self.norm_eps, dtype=self.compute_dtype)
        if self.norm == "rmsnorm":
            return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype, name=name)
        raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', got {self.norm!r}")

    @nn.nowrap
    def _check_parallel(self) -> None:
        """Tensor and sequence parallelism know the GPT-2-style block only."""
        if self.tp_size == 1 and self.seq_axis is None:
            return
        new = unsupported_features({
            "head_dim": self.head_dim, "attn_gate": self.attn_gate,
            "pre_norm": self.pre_norm, "post_norm": self.post_norm,
            "qk_norm": self.qk_norm, "mlp": self.mlp, "norm": self.norm,
            "layer_types": (self.attn_kind,) if self.attn_kind != "full" else None,
            "routed_experts": self.routed_experts if self.ffn_kind == "moe" else 0})
        if new:
            raise ValueError(
                f"tensor / sequence parallelism (tp_size {self.tp_size}, seq_axis "
                f"{self.seq_axis!r}) runs the GPT-2-style block only; this block sets "
                f"{new}")

    @nn.compact
    def __call__(self, x: jnp.ndarray, pos_offset: int = 0) -> jnp.ndarray:
        self._check_parallel()
        if self.num_heads % self.tp_size:
            raise ValueError(f"num_heads {self.num_heads} not divisible by tp_size {self.tp_size}")
        if self.positional not in ("learned", "rope", "none"):
            raise ValueError(f"positional must be 'learned', 'rope' or 'none', "
                             f"got {self.positional!r}")
        if self.moe_experts and self.tp_size > 1:
            raise ValueError("MoE FFN does not compose with tensor parallelism (v1); "
                             "use either moe_experts or tp_size")
        if self.moe_experts and self.seq_axis is not None:
            raise ValueError("MoE FFN does not compose with sequence parallelism "
                             "(v1); train MoE LMs with make_moe_lm_train_step")
        if self.attn_kind not in ("full", "sliding", "linear", "latent"):
            raise ValueError(f"attn_kind must be 'full', 'sliding', 'linear' or 'latent', "
                             f"got {self.attn_kind!r}")
        if self.qk_norm not in (False, True, "head", "full"):
            raise ValueError(f"qk_norm must be False, True, 'head' or 'full', "
                             f"got {self.qk_norm!r}")
        if self.attn_kind == "sliding" and not self.sliding_window:
            raise ValueError("attn_kind 'sliding' needs sliding_window")
        if self.ffn_kind == "moe" and (self.ep_size != 1 or self.ep_axis is not None):
            raise NotImplementedError(
                "the routed expert layer runs one replica's share without its exchange "
                f"(ep_size {self.ep_size}, ep_axis {self.ep_axis!r}); expert layers "
                "across chips are not built")
        heads_local = self.num_heads // self.tp_size
        head_dim = self.head_dim or self.model_dim // self.num_heads
        ffn_local = self.mlp_ratio * self.model_dim // self.tp_size
        kv_heads = self.num_kv_heads or self.num_heads
        if self.num_heads % kv_heads:
            raise ValueError(f"num_heads {self.num_heads} not a multiple of "
                             f"num_kv_heads {kv_heads}")
        with jax.named_scope(f"attn.{self.attn_kind}"):
            if self.attn_kind == "linear":
                x = x + self._linear_attention(x)
            elif self.attn_kind == "latent":
                x = x + self._latent_attention(x, pos_offset)
            else:
                x = x + self._attention(x, pos_offset, heads_local, head_dim, kv_heads)
        return x + self._ffn(x, ffn_local)

    @nn.nowrap
    def _attention(self, x, pos_offset, heads_local: int, head_dim: int, kv_heads: int):
        y = self._norm("attn_norm")(x) if self.pre_norm else x
        if kv_heads == self.num_heads:
            qkv = nn.DenseGeneral((3, heads_local, head_dim), use_bias=False,
                                  dtype=self.compute_dtype, name="qkv")(y)  # [B, L, 3, Hl, Dh]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            if kv_heads % self.tp_size:
                raise ValueError(f"num_kv_heads {kv_heads} not divisible by "
                                 f"tp_size {self.tp_size}")
            q = nn.DenseGeneral((heads_local, head_dim), use_bias=False,
                                dtype=self.compute_dtype, name="q")(y)
            kv = nn.DenseGeneral((2, kv_heads // self.tp_size, head_dim),
                                 use_bias=False, dtype=self.compute_dtype,
                                 name="kv")(y)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if self.qk_norm == "full":
            # one RMSNorm over all heads' channels together, a gain a channel
            def whole(name, t):
                flat = t.reshape(t.shape[:2] + (-1,))
                return nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype,
                                  name=name)(flat).reshape(t.shape)
            q, k = whole("q_norm", q), whole("k_norm", k)
        elif self.qk_norm:
            q = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype, name="q_norm")(q)
            k = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype, name="k_norm")(k)
        if self.positional == "rope":
            from distkeras_tpu.ops.rotary import rope_rotate

            # pos_offset is the caller's GLOBAL offset of this sequence
            # block: the sp training step passes rank * L_local (the same
            # offset contract the learned table's slicing uses), decoding
            # rotates inside its own cache path, and plain training passes 0
            pos = pos_offset + jnp.arange(x.shape[1])
            q = rope_rotate(q, pos, base=self.rope_theta)
            k = rope_rotate(k, pos, base=self.rope_theta)
        window = self.sliding_window if self.attn_kind == "sliding" else None
        o = attention(q, k, v, causal=True, axis_name=self.seq_axis, impl=self.attn_impl,
                      window=window)
        if self.attn_gate:
            gate = nn.DenseGeneral((heads_local, head_dim), use_bias=False,
                                   dtype=self.compute_dtype, name="gate")(y)
            o = o * jax.nn.sigmoid(gate)
        o = nn.DenseGeneral(self.model_dim, axis=(-2, -1), use_bias=False,
                            dtype=self.compute_dtype, name="proj")(o)  # [B, L, E] partial
        o = _maybe_psum(o, self.tp_axis)
        return self._norm("attn_post_norm")(o) if self.post_norm else o

    @nn.nowrap
    def _linear_attention(self, x):
        """The gated delta rule mixer (Gated DeltaNet; the recurrence is in
        ``ops/linear_attention.py``): q, k, v each through a causal depthwise
        convolution and SiLU, q and k L2-normalised a head, a decay and a
        write strength a head and token, the chunked scan, then a gated
        RMSNorm over each head's output and the output projection.  Position
        reaches it through the convolutions and the decay alone."""
        from distkeras_tpu.ops.linear_attention import gated_delta_rule

        h, dk, dv, width = (self.linear_num_heads, self.linear_key_dim,
                            self.linear_value_dim, self.linear_conv_width)
        if not (h and dk and dv):
            raise ValueError("attn_kind 'linear' needs linear_num_heads, linear_key_dim "
                             "and linear_value_dim")
        cd, f32 = self.compute_dtype, jnp.float32
        y = self._norm("attn_norm")(x) if self.pre_norm else x
        with jax.named_scope("attn.linear.proj"):
            dense = lambda shape, name: nn.DenseGeneral(shape, use_bias=False, dtype=cd,
                                                        name=name)(y)
            q, k = dense((h, dk), "lin_q"), dense((h, dk), "lin_k")
            v, gate = dense((h, dv), "lin_v"), dense((h, dv), "lin_gate")
            # the decay's and the write strength's logits: one column a head,
            # handed on in float32 (their cumulative sum over a chunk is)
            head = lambda name: jnp.einsum(
                "ble,eh->blh", y.astype(cd),
                self.param(name, nn.initializers.lecun_normal(), (self.model_dim, h)).astype(cd),
                preferred_element_type=f32)
            a, b = head("lin_a"), head("lin_b")
        with jax.named_scope("attn.linear.conv"):
            taps = nn.initializers.normal(width ** -0.5)

            def conv(name, t):      # y_t = sum_j w_j x_{t-(width-1)+j}, then SiLU
                w = self.param(name, taps, (width,) + t.shape[2:])
                pad = jnp.pad(t.astype(f32), ((0, 0), (width - 1, 0), (0, 0), (0, 0)))
                return nn.silu(sum(pad[:, j:j + t.shape[1]] * w[j] for j in range(width)))

            unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
            q = (unit(conv("conv_q", q)) * dk ** -0.5).astype(cd)
            k = unit(conv("conv_k", k)).astype(cd)
            v = conv("conv_v", v).astype(cd)
        with jax.named_scope("attn.linear.scan"):
            a_log = self.param("A_log", _log_uniform(1.0, 16.0), (h,))
            dt_bias = self.param("dt_bias", _inverse_softplus_log_uniform(1e-3, 1e-1), (h,))
            g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
            beta = jax.nn.sigmoid(b) * (2.0 if self.linear_neg_eigval else 1.0)
            o = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("attn.linear.out"):
            o = nn.RMSNorm(epsilon=self.norm_eps, dtype=cd, name="lin_norm")(o) * nn.silu(gate)
            o = nn.DenseGeneral(self.model_dim, axis=(-2, -1), use_bias=False, dtype=cd,
                                name="lin_out")(o)
        return self._norm("attn_post_norm")(o) if self.post_norm else o

    @nn.nowrap
    def _latent_attention(self, x, pos_offset):
        """Latent attention (MLA; DeepSeek-V2/V3 without the query's low-rank
        path): ``q = y W_q`` -> H heads of ``qk_nope_head_dim +
        qk_rope_head_dim``; ``[c ; k_r] = y W_dkv`` -> ``kv_lora_rank +
        qk_rope_head_dim``; ``[k_n ; v] = RMSNorm(c) W_ukv`` -> H heads of
        ``qk_nope_head_dim + v_head_dim``.  ``k_r`` is ONE rotary key a token:
        it and each head's rotary query channels are rotated, then it is
        broadcast to the heads and joined to their un-rotated keys.  Queries
        and keys are wider than values; the scale is the query's width's."""
        h, r, dn, dr, dv = (self.num_heads, self.kv_lora_rank, self.qk_nope_head_dim,
                            self.qk_rope_head_dim, self.v_head_dim)
        if not (r and dn and dv):
            raise ValueError("attn_kind 'latent' needs kv_lora_rank, qk_nope_head_dim "
                             "and v_head_dim")
        cd = self.compute_dtype
        y = self._norm("attn_norm")(x) if self.pre_norm else x
        with jax.named_scope("attn.latent.q"):
            q = nn.DenseGeneral((h, dn + dr), use_bias=False, dtype=cd, name="q")(y)
        with jax.named_scope("attn.latent.down"):
            down = nn.Dense(r + dr, use_bias=False, dtype=cd, name="kv_down")(y)
            c = nn.RMSNorm(epsilon=self.norm_eps, dtype=cd, name="kv_norm")(down[..., :r])
            k_r = down[:, :, None, r:]                       # [B, L, 1, dr]
        with jax.named_scope("attn.latent.up"):
            kv = nn.DenseGeneral((h, dn + dv), use_bias=False, dtype=cd, name="kv_up")(c)
        with jax.named_scope("attn.latent.rope"):
            q_r = q[..., dn:]
            if self.positional == "rope" and dr:
                from distkeras_tpu.ops.rotary import rope_rotate

                pos = pos_offset + jnp.arange(x.shape[1])
                q_r, k_r = (rope_rotate(t, pos, base=self.rope_theta,
                                        interleaved=self.rope_interleave) for t in (q_r, k_r))
            q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_r, k_r.shape[:2] + (h, dr))], axis=-1)
            v = kv[..., dn:]
        with jax.named_scope("attn.latent.core"):
            o = attention(q, k, v, causal=True, axis_name=self.seq_axis, impl=self.attn_impl)
        with jax.named_scope("attn.latent.out"):
            o = nn.DenseGeneral(self.model_dim, axis=(-2, -1), use_bias=False, dtype=cd,
                                name="proj")(o)
        return self._norm("attn_post_norm")(o) if self.post_norm else o

    @nn.nowrap
    def _ffn(self, x, ffn_local: int):
        if self.ffn_kind == "dense" and not self.moe_experts:
            # the dense MLP with its norms is one part of the device account
            # (obs.device_account); the routed layers keep their moe.* scopes
            with jax.named_scope("ffn.dense"):
                return self._dense_ffn(x, ffn_local)
        y = self._norm("ffn_norm")(x) if self.pre_norm else x
        if self.ffn_kind == "moe":
            from distkeras_tpu.parallel.moe import HeldExpertsMLP

            b, l, e = y.shape
            held = tuple(self.experts_held) if self.experts_held else (0, self.routed_experts)
            y = HeldExpertsMLP(
                num_experts=self.routed_experts, experts_held=held,
                model_dim=self.model_dim, hidden_dim=self.routed_dim,
                shared_dim=self.n_shared_experts * self.routed_dim,
                top_k=self.routed_top_k, route_scale=self.route_scale,
                compute_dtype=self.compute_dtype, name="experts")(y.reshape(b * l, e))
            y = y.reshape(b, l, e)
            return self._norm("ffn_post_norm")(y) if self.post_norm else y
        if self.ffn_kind != "dense":
            raise ValueError(f"ffn_kind must be 'dense' or 'moe', got {self.ffn_kind!r}")
        # what is left: the capacity-dispatch MoE MLP (moe_experts)
        from distkeras_tpu.parallel.moe import MoEMLP

        b, l, e = y.shape
        # default capacity: factor-2 over the balanced share per expert
        # (capacity T would make dispatch [T, E, T] — O(T^2) memory)
        cap = self.moe_capacity or -(-2 * b * l // self.moe_experts)
        moe_out, aux = MoEMLP(
            num_experts=self.moe_experts, model_dim=self.model_dim,
            hidden_dim=self.mlp_ratio * self.model_dim,
            capacity=cap,
            ep_axis=self.ep_axis, ep_size=self.ep_size,
            router_top_k=self.moe_top_k,
            dispatch_impl=self.moe_dispatch,
            compute_dtype=self.compute_dtype, name="moe")(y.reshape(b * l, e))
        self.sow("aux_loss", "load_balance", aux)
        return moe_out.reshape(b, l, e)

    @nn.nowrap
    def _dense_ffn(self, x, ffn_local: int):
        y = self._norm("ffn_norm")(x) if self.pre_norm else x
        if self.mlp == "swiglu":
            if not self.mlp_dim:
                raise ValueError("mlp 'swiglu' needs its width, mlp_dim")
            gate = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.compute_dtype,
                            name="gate_proj")(y)
            up = nn.Dense(self.mlp_dim, use_bias=False, dtype=self.compute_dtype, name="up")(y)
            y = nn.silu(gate) * up
        elif self.mlp == "gelu":
            y = nn.Dense(ffn_local, use_bias=False, dtype=self.compute_dtype, name="up")(y)
            y = nn.gelu(y)
        else:
            raise ValueError(f"mlp must be 'gelu' or 'swiglu', got {self.mlp!r}")
        y = nn.Dense(self.model_dim, use_bias=False, dtype=self.compute_dtype, name="down")(y)
        y = _maybe_psum(y, self.tp_axis)
        return self._norm("ffn_post_norm")(y) if self.post_norm else y


def _log_uniform(lo: float, hi: float):
    """Initializer: log of a uniform draw from [lo, hi] (the Mamba-2 ``A_log``)."""
    return lambda key, shape, dtype=jnp.float32: jnp.log(
        jax.random.uniform(key, shape, dtype, lo, hi))


def _inverse_softplus_log_uniform(lo: float, hi: float):
    """Initializer: ``x`` with ``softplus(x)`` log-uniform in [lo, hi] (the
    Mamba-2 ``dt_bias``)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype, np.log(lo), np.log(hi)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


@register_model("transformer_lm")
class TransformerLM(nn.Module):
    """Causal LM over integer tokens [B, L] -> logits [B, L, vocab].

    When ``seq_axis`` is set the module must be called under ``shard_map``
    with the sequence dim sharded over that axis; position embeddings are
    then indexed by global position (handled inside the block's ring
    attention; the learned positional table here is sized for the *global*
    sequence and sliced by the caller-provided offset).  When ``tp_axis``/
    ``tp_size`` are set the module expects the LOCAL parameter shards
    (see module docstring).
    """

    vocab_size: int = 32000
    model_dim: int = 512
    num_heads: int = 4   # head_dim 128 = model_dim/num_heads: at
                         # IDENTICAL FLOPs, head_dim 128 contracts the
                         # attention matmuls over the MXU's full 128-wide
                         # systolic dim and halves per-score VPU overhead
                         # against head_dim 64 (every benchmark
                         # configuration has heads of 128)
    num_kv_heads: Optional[int] = None  # GQA (see TransformerBlock); None = MHA
    num_layers: int = 6
    max_seq_len: int = 2048  # positional-table size under "learned"; under
                             # "rope" only the decode cache-sizing bound
    mlp_ratio: int = 4
    positional: str = "learned"  # "learned" | "rope" (see TransformerBlock)
    seq_axis: Optional[str] = None
    tp_axis: Optional[str] = None
    tp_size: int = 1
    attn_impl: Optional[str] = None
    remat: bool = False  # rematerialize each block in the backward pass,
                         # except what the flash forward handed back (its
                         # output and log-sum-exp, saved by name: O(L) to
                         # keep, O(L^2) to make again).  Activation memory
                         # O(layers) blocks -> one block + the block inputs
                         # + B*L*heads*(2*head_dim+32) bytes a flash layer
                         # (+512 on the chip, which tiles the log-sum-exp's
                         # 8 lanes to 128): the FLOPs-for-HBM trade for
                         # long sequences
    moe_experts: int = 0       # > 0: every block's FFN becomes a Switch MoE
    moe_capacity: int = 0      # (0 = default to 2x the balanced share per
                               # expert; imbalanced routing beyond that
                               # still drops tokens to the residual path)
    moe_top_k: int = 1         # 1 = Switch routing, 2 = GShard-style top-2
    moe_dispatch: str = "auto"  # dispatch path: "dense" | "sorted" | "auto"
    ep_axis: Optional[str] = None
    ep_size: int = 1
    # -- the config-driven block (see TransformerBlock): every default is
    # the GPT-2-style model above
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    head_dim: Optional[int] = None
    qk_norm: object = False    # False | True / "head" | "full"
    attn_gate: bool = False
    pre_norm: bool = True
    post_norm: bool = False
    mlp: str = "gelu"
    mlp_dim: Optional[int] = None
    layer_types: Optional[tuple] = None  # per layer "full" | "sliding" |
                               # "linear" (or the published "full_attention"
                               # / "sliding_attention" / "linear_attention")
                               # | "latent"; None = all full
    sliding_window: Optional[int] = None
    linear_num_heads: int = 0  # the "linear" layers' mixer (TransformerBlock)
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_width: int = 4
    linear_neg_eigval: bool = False
    kv_lora_rank: int = 0      # the "latent" layers' mixer (TransformerBlock)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    rope_layers: str = "all"   # under positional="rope": "all" | "sliding"
                               # (full-attention layers get no positional
                               # signal at all)
    rope_theta: float = 10000.0
    routed_experts: int = 0    # > 0: layers from num_dense_layers on carry
                               # the sigmoid-routed expert layer
                               # (parallel/moe.py::HeldExpertsMLP)
    num_dense_layers: int = 0
    experts_held: Optional[tuple] = None
    routed_top_k: int = 8
    routed_dim: int = 0
    n_shared_experts: int = 1
    route_scale: float = 1.0
    route_balance_coeff: float = 0.0  # the selection bias's step; read by
                               # the training step (``step_hook``), not
                               # by the forward
    tie_word_embeddings: bool = True
    embed_scale: float = 1.0   # the embedding's output times this
    compute_dtype: jnp.dtype = jnp.bfloat16

    @staticmethod
    def sown_collections(config) -> tuple:
        """What a forward of this configuration sows: the Switch layer a
        loss term (``aux_loss``) that a plain ``apply`` would lose, the
        routed expert layer only its assignment counts (its balancing adds
        no loss term)."""
        out = ()
        if config.get("moe_experts"):
            out += ("aux_loss", "router_stats")
        if config.get("routed_experts"):
            out += ("moe_counts",)
        return out

    @staticmethod
    def step_hook(spec):
        if not spec.config.get("routed_experts"):
            return None
        return routed_step_hook(spec)

    def _layer_kinds(self) -> list:
        if self.layer_types is None:
            return ["full"] * self.num_layers
        if len(self.layer_types) != self.num_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, "
                             f"num_layers is {self.num_layers}")
        try:
            return [LAYER_KINDS[t] for t in self.layer_types]
        except KeyError as e:
            raise ValueError(f"layer_types: unknown kind {e.args[0]!r}; "
                             f"known: {sorted(LAYER_KINDS)}") from None

    def setup(self):
        # attribute names ARE the param-tree keys: "embed", "pos_embed",
        # "block_0..N-1" (list attr `block` -> `block_{i}`), "final_norm".
        # parallel/pipeline.py splits on the block_ prefix and shards the
        # rest as replicated "outer" leaves.  NOTE: "final_norm" replaces
        # the compact-era auto-name "LayerNorm_0" — an intentional
        # serialized-format break (no published checkpoints predate it).
        self.embed = nn.Embed(self.vocab_size, self.model_dim, dtype=self.compute_dtype)
        if self.positional == "learned":
            self.pos_embed = self.param(
                "pos_embed", nn.initializers.normal(0.02), (self.max_seq_len, self.model_dim))
        self.block = [
            TransformerBlock(
                model_dim=self.model_dim,
                num_heads=self.num_heads,
                num_kv_heads=self.num_kv_heads,
                mlp_ratio=self.mlp_ratio,
                seq_axis=self.seq_axis,
                tp_axis=self.tp_axis,
                tp_size=self.tp_size,
                attn_impl=self.attn_impl,
                moe_experts=self.moe_experts,
                moe_capacity=self.moe_capacity,
                moe_top_k=self.moe_top_k,
                moe_dispatch=self.moe_dispatch,
                ep_axis=self.ep_axis,
                ep_size=self.ep_size,
                # under rope_layers "sliding" a full-attention layer gets no
                # positional signal at all
                positional=("none" if self.rope_layers == "sliding" and kind != "sliding"
                            else self.positional),
                norm=self.norm, norm_eps=self.norm_eps, head_dim=self.head_dim,
                qk_norm=self.qk_norm, attn_gate=self.attn_gate,
                pre_norm=self.pre_norm, post_norm=self.post_norm,
                mlp=self.mlp, mlp_dim=self.mlp_dim,
                attn_kind=kind, sliding_window=self.sliding_window,
                linear_num_heads=self.linear_num_heads,
                linear_key_dim=self.linear_key_dim,
                linear_value_dim=self.linear_value_dim,
                linear_conv_width=self.linear_conv_width,
                linear_neg_eigval=self.linear_neg_eigval,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_interleave=self.rope_interleave,
                rope_theta=self.rope_theta,
                ffn_kind=("moe" if self.routed_experts and i >= self.num_dense_layers
                          else "dense"),
                routed_experts=self.routed_experts, experts_held=self.experts_held,
                routed_top_k=self.routed_top_k, routed_dim=self.routed_dim,
                n_shared_experts=self.n_shared_experts,
                route_scale=self.route_scale,
                compute_dtype=self.compute_dtype,
            )
            for i, kind in enumerate(self._layer_kinds())
        ]
        if self.rope_layers not in ("all", "sliding"):
            raise ValueError(f"rope_layers must be 'all' or 'sliding', got "
                             f"{self.rope_layers!r}")
        if self.norm == "rmsnorm":
            self.final_norm = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.compute_dtype)
        else:
            self.final_norm = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.compute_dtype)
        if not self.tie_word_embeddings:
            self.lm_head = nn.Dense(self.vocab_size, use_bias=False,
                                    dtype=self.compute_dtype)

    def embed_tokens(self, tokens: jnp.ndarray, pos_offset: int = 0) -> jnp.ndarray:
        """Token (+ learned positional) embedding: [B, L] int32 -> [B, L, E].

        A real bound method (not a free function passed to
        ``apply(method=...)``) so the pipeline-parallel step can run the
        embedding alone against the same param leaves as ``__call__``.
        Under ``positional="rope"`` there is no table — position enters
        through the per-block q/k rotation instead.
        """
        with jax.named_scope("lm.embed"):
            x = self.embed(tokens)
            if self.embed_scale != 1.0:
                x = x * jnp.asarray(self.embed_scale, x.dtype)
            if self.positional != "learned":
                return x
            pos = jnp.arange(tokens.shape[1]) + pos_offset
            return x + self.pos_embed[pos].astype(self.compute_dtype)

    def head(self, x: jnp.ndarray) -> jnp.ndarray:
        """Final norm + unembedding (tied to the embedding unless
        ``tie_word_embeddings`` is off): [B, L, E] -> [B, L, vocab] logits."""
        with jax.named_scope("lm.head"):
            x = self.final_norm(x)
            if not self.tie_word_embeddings:
                # float32 for the loss: a softmax and a mean taken in bfloat16
                # hand back a loss near 10 in steps of 0.0625
                return self.lm_head(x).astype(jnp.float32)
            return self.embed.attend(x.astype(jnp.float32))

    def _trunk(self, tokens: jnp.ndarray, pos_offset: int = 0) -> jnp.ndarray:
        """Embedding + blocks, BEFORE the final norm: [B, L] -> [B, L, E]."""
        x = self.embed_tokens(tokens, pos_offset)
        # pos_offset rides as a DYNAMIC remat arg: under sequence
        # parallelism it is a traced axis_index expression, not a constant
        run = lambda m, y, po: m(y, po)
        if self.remat:
            from distkeras_tpu.ops.flash_attention import FLASH_LSE_NAME, FLASH_OUT_NAME

            # everything of a block is made again in the backward pass but
            # the flash forward's two results: the kernel runs once a layer
            run = nn.remat(run, prevent_cse=True,
                           policy=jax.checkpoint_policies.save_only_these_names(
                               FLASH_OUT_NAME, FLASH_LSE_NAME))
        for blk in self.block:
            x = run(blk, x, pos_offset)
        return x

    def hidden(self, tokens: jnp.ndarray, pos_offset: int = 0) -> jnp.ndarray:
        """Forward WITHOUT the unembed: [B, L] -> final-normed [B, L, E].

        Train-loss entry point: pair with ``ops.losses.unembed_cross_entropy``
        (against ``params['embed']['embedding']``) so the [B, L, vocab]
        float32 logits tensor is computed chunkwise in bfloat16 instead of
        materialized by ``head``'s float32 ``attend`` — kills the
        half-rate f32 unembed matmul and O(B*L*V) activation memory.
        """
        x = self._trunk(tokens, pos_offset)
        with jax.named_scope("lm.head"):
            return self.final_norm(x)

    def __call__(self, tokens: jnp.ndarray, pos_offset: int = 0) -> jnp.ndarray:
        return self.head(self._trunk(tokens, pos_offset))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoutedStats:
    """What the expert layers of one forward hand the training step, a row a
    layer; the window program sums it over its steps and replicas.  Read as
    an array it is the counts, which is all the bias rule asks for."""

    counts: jax.Array    # [expert layers, routed_experts] int32: assignments
    calls: jax.Array     # [expert layers, 2] int32: calls, and those over all T * k rows

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.counts, dtype=dtype)


def _expert_blocks(tree) -> list:
    """Names of the blocks that carry the routed expert layer, in layer order."""
    return sorted((k for k, v in tree.items()
                   if k.startswith("block_") and "experts" in v),
                  key=lambda name: int(name.split("_", 1)[1]))


def routed_step_hook(spec: ModelSpec) -> StepHook:
    """The step hook of a ``transformer_lm`` spec with ``routed_experts``:
    the forward hands back what every expert layer sowed (:class:`RoutedStats`),
    and after each optimizer step the selection bias of each such layer moves
    with its counts by ``parallel/moe.py::bias_update``
    (``route_balance_coeff``).  The bias is a leaf of the parameter tree, so
    it rides pull and commit like any weight."""
    from distkeras_tpu.parallel.moe import bias_update

    module = spec.build()
    coeff = float(spec.config.get("route_balance_coeff", 0.0))
    e = int(spec.config["routed_experts"])
    lo, hi = spec.config.get("experts_held") or (0, e)

    def apply(params, x):
        out, sown = module.apply({"params": params}, x, mutable=["moe_counts"])
        sown = [sown["moe_counts"][b]["experts"] for b in _expert_blocks(sown["moe_counts"])]
        return out, RoutedStats(jnp.stack([s["assignments"][0] for s in sown]),
                                jnp.stack([s["calls"][0] for s in sown]))

    def update(params, stats):
        if not coeff:
            return params
        params = dict(params)
        with jax.named_scope("moe.bias"):
            for n, name in enumerate(_expert_blocks(params)):
                block = dict(params[name])
                experts = dict(block["experts"])
                experts["router_bias"] = bias_update(experts["router_bias"],
                                                     stats.counts[n], coeff)
                block["experts"] = experts
                params[name] = block
        return params

    def publish(stats) -> None:
        counts = np.asarray(stats.counts, dtype=np.int64).reshape(-1, e)   # a row a layer a window
        held = counts[:, lo:hi]
        obs.counter("moe_assignments_total").inc(int(counts.sum()))
        obs.counter("moe_assignments_held_total").inc(int(held.sum()))
        calls = np.asarray(stats.calls, dtype=np.int64).reshape(-1, 2).sum(axis=0)
        obs.counter("moe_layer_calls_total").inc(int(calls[0]))
        obs.counter("moe_layer_calls_full_total").inc(int(calls[1]))
        load = held.sum(axis=0)
        if load.sum():
            obs.gauge("moe_expert_load_max_over_mean").set(float(load.max() / load.mean()))

    return StepHook(apply, update, publish)


def small_lm_spec(vocab_size: int = 1024, model_dim: int = 256, num_heads: int = 2,
                  num_layers: int = 4, max_seq_len: int = 512, seq_axis: Optional[str] = None,
                  tp_axis: Optional[str] = None, remat: bool = False,
                  moe_experts: int = 0, moe_capacity: int = 0,
                  moe_top_k: int = 1, moe_dispatch: str = "auto",
                  num_kv_heads: Optional[int] = None,
                  positional: str = "learned",
                  attn_impl: Optional[str] = None):
    from distkeras_tpu.models.base import ModelSpec

    # num_heads defaults keep head_dim = model_dim/num_heads at 128, the
    # v5e-recommended config (see TransformerLM.num_heads); pass num_heads
    # explicitly when a different head_dim is the point (A/B experiments,
    # tp_size divisibility)
    return ModelSpec(
        name="transformer_lm",
        config={
            "vocab_size": vocab_size,
            "model_dim": model_dim,
            "num_heads": num_heads,
            "num_kv_heads": num_kv_heads,
            "positional": positional,
            "num_layers": num_layers,
            "max_seq_len": max_seq_len,
            "seq_axis": seq_axis,
            "tp_axis": tp_axis,
            "remat": remat,
            "moe_experts": moe_experts,
            "moe_capacity": moe_capacity,
            "moe_top_k": moe_top_k,
            "moe_dispatch": moe_dispatch,
            # None = auto-select per ops.attention.attention (flash on TPU
            # at L >= 2048, device-time validated across head_dim 64/128);
            # "flash"/"dense" pin the kernel for A/B measurement
            "attn_impl": attn_impl,
        },
        input_shape=(max_seq_len,),
        input_dtype="int32",
    )
