"""Oracle for the fused vocab-head kernels: the plain XLA expression.

The fp32 (T, V) logits, their fp32 log-softmax and the target's entry,
as the model's head computes them off the kernel path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def token_nll_ref(x: Array, w: Array, targets: Array) -> Array:
    """-log softmax(x W^T)[target] per token, fp32; shapes as
    ``ops.token_nll``."""
    logits = jnp.einsum("td,vd->tv", x, w, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
