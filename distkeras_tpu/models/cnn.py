"""Small convnet (the reference's MNIST-CNN / CIFAR-CNN example family).

NHWC layout throughout — XLA's preferred convolution layout on TPU (the
MXU tiles the channel dim onto lanes).
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu.models.base import register_model


@register_model("cnn")
class CNN(nn.Module):
    """Conv-relu-pool blocks then a dense head. Outputs logits.

    ``compute_dtype`` (e.g. ``"bfloat16"``) runs convs/matmuls and
    activations in that dtype with float32 params/optimizer — the LM
    stack's mixed-precision scheme (bfloat16 operands at the MXU's
    native rate; a whole-model cast, parameters included, is a
    different scheme).  The head always emits float32 logits.  ``None``
    keeps float32 (the historical default; parity-tested against bf16)."""

    conv_channels: Sequence[int] = (32, 64)
    kernel_size: int = 3
    dense_size: int = 256
    num_outputs: int = 10
    compute_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cdt = jnp.dtype(self.compute_dtype or "float32")
        x = x.astype(cdt)
        for ch in self.conv_channels:
            x = nn.Conv(ch, (self.kernel_size, self.kernel_size),
                        padding="SAME", dtype=cdt)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(self.dense_size, dtype=cdt)(x))
        return nn.Dense(self.num_outputs, dtype=jnp.float32)(x)


def mnist_cnn_spec(compute_dtype: Optional[str] = None):
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(
        name="cnn",
        config={"conv_channels": (32, 64), "kernel_size": 3, "dense_size": 256,
                "num_outputs": 10, "compute_dtype": compute_dtype},
        input_shape=(28, 28, 1),
    )


def cifar_cnn_spec(num_outputs: int = 10, compute_dtype: Optional[str] = None):
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(
        name="cnn",
        config={"conv_channels": (64, 128, 256), "kernel_size": 3, "dense_size": 512,
                "num_outputs": num_outputs, "compute_dtype": compute_dtype},
        input_shape=(32, 32, 3),
    )
