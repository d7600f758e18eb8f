"""ResNet-20 (CIFAR variant) — the `BASELINE.json` config-5 model.

Classic 3-stage CIFAR ResNet (He et al. 2015): 6n+2 layers with n=3.
Uses GroupNorm instead of BatchNorm: batch statistics are a cross-replica
dependency that would force an extra collective per norm layer on a TPU
mesh and make the per-replica divergent-weights algorithms (EASGD family)
ill-defined; GroupNorm is batch-independent, so every parallelism mode
sees identical semantics.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu.models.base import register_model


class ResidualBlock(nn.Module):
    channels: int
    strides: int = 1
    compute_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cdt = jnp.dtype(self.compute_dtype or "float32")
        residual = x
        y = nn.Conv(self.channels, (3, 3), strides=(self.strides, self.strides),
                    padding="SAME", use_bias=False, dtype=cdt)(x)
        # flax GroupNorm computes its statistics in float32 regardless of
        # dtype, so bf16 here costs one rounding of the normalized output
        y = nn.GroupNorm(num_groups=min(8, self.channels), dtype=cdt)(y)
        y = nn.relu(y)
        y = nn.Conv(self.channels, (3, 3), padding="SAME", use_bias=False,
                    dtype=cdt)(y)
        y = nn.GroupNorm(num_groups=min(8, self.channels), dtype=cdt)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.channels, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False, dtype=cdt)(x)
        return nn.relu(y + residual)


@register_model("resnet")
class ResNet(nn.Module):
    """CIFAR-style ResNet; depth = 6*blocks_per_stage + 2.

    ``compute_dtype`` follows the family scheme (see models/cnn.py):
    bf16 convs/norms/activations over float32 params, float32 logits."""

    blocks_per_stage: int = 3
    base_channels: int = 16
    num_outputs: int = 10
    compute_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cdt = jnp.dtype(self.compute_dtype or "float32")
        x = x.astype(cdt)
        x = nn.Conv(self.base_channels, (3, 3), padding="SAME", use_bias=False,
                    dtype=cdt)(x)
        x = nn.GroupNorm(num_groups=min(8, self.base_channels), dtype=cdt)(x)
        x = nn.relu(x)
        for stage, ch in enumerate([self.base_channels, self.base_channels * 2, self.base_channels * 4]):
            for block in range(self.blocks_per_stage):
                strides = 2 if (stage > 0 and block == 0) else 1
                x = ResidualBlock(channels=ch, strides=strides,
                                  compute_dtype=self.compute_dtype)(x)
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        return nn.Dense(self.num_outputs, dtype=jnp.float32)(x)


def resnet20_spec(num_outputs: int = 100, compute_dtype: Optional[str] = None):
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(
        name="resnet",
        config={"blocks_per_stage": 3, "base_channels": 16,
                "num_outputs": num_outputs, "compute_dtype": compute_dtype},
        input_shape=(32, 32, 3),
    )
