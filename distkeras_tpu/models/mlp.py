"""MLP architecture (the reference's MNIST-MLP example model family).

Reference parity: the reference's examples built Keras ``Sequential``
Dense stacks (``examples/mnist.py``); here the equivalent is a registered
Flax module so it round-trips through the architecture registry.
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from distkeras_tpu.models.base import register_model


@register_model("mlp")
class MLP(nn.Module):
    """Dense stack: hidden layers with ReLU, linear head (logits out).

    ``compute_dtype`` (e.g. ``"bfloat16"``) runs the hidden matmuls and
    activations in that dtype with float32 params/optimizer — the LM
    stack's mixed-precision scheme (models/transformer.py).  The head
    always emits float32 logits (softmax-CE stability).  ``None`` keeps
    everything float32 (the historical default; parity-tested)."""

    hidden_sizes: Sequence[int] = (500, 500)
    num_outputs: int = 10
    compute_dtype: Optional[str] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cdt = jnp.dtype(self.compute_dtype or "float32")
        x = x.reshape((x.shape[0], -1)).astype(cdt)
        for h in self.hidden_sizes:
            x = nn.relu(nn.Dense(h, dtype=cdt)(x))
        return nn.Dense(self.num_outputs, dtype=jnp.float32)(x)


def mnist_mlp_spec(compute_dtype: Optional[str] = None):
    from distkeras_tpu.models.base import ModelSpec

    return ModelSpec(name="mlp",
                     config={"hidden_sizes": (500, 500), "num_outputs": 10,
                             "compute_dtype": compute_dtype},
                     input_shape=(784,))
