"""Causal-dot primitives with internal path selection.

``out_i = q_i . sum_{j<=i} k_j^T v_j`` is the aggregation shared by flow
and plain linear attention.  These helpers choose between its cumsum and
chunked-scan XLA realizations from the shapes — call sites pass a chunk
size and get the applicable path.  The Pallas realization is a registry
backend (``pallas_chunk``), chosen by platform at resolution.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.attention.chunked import chunked_causal_dot, chunked_causal_dot_grouped

Array = jax.Array


def causal_dot(q: Array, k: Array, v: Array, chunk_size: int = 128) -> Array:
    """Ungrouped causal dot.  q,k: (..., N, D); v: (..., N, Dv).

    Chunked MXU-friendly scan when N divides by ``chunk_size``; otherwise a
    cumsum fallback (O(N * D * Dv) memory — test-scale only).
    """
    n = q.shape[-2]
    if chunk_size and n % chunk_size == 0 and n > chunk_size:
        return chunked_causal_dot(q, k, v, chunk_size)
    kv = jnp.einsum("...nd,...ne->...nde", k, v)
    kv = jnp.cumsum(kv, axis=-3)
    return jnp.einsum("...nd,...nde->...ne", q, kv)


def causal_dot_grouped(
    qg: Array, k: Array, v: Array, chunk_size: int = 128,
) -> Array:
    """Grouped causal dot sharing the carried state across the GQA group.

    qg: (B,Hkv,G,N,D); k: (B,Hkv,N,D); v: (B,Hkv,N,Dv) -> (B,Hkv,G,N,Dv).
    XLA only: the Pallas realization is the registry's ``pallas_chunk``.
    """
    n = qg.shape[-2]
    if chunk_size and n % chunk_size == 0 and n > chunk_size:
        return chunked_causal_dot_grouped(qg, k, v, chunk_size)
    kv = jnp.einsum("bhnd,bhne->bhnde", k, v)
    kv = jnp.cumsum(kv, axis=2)
    return jnp.einsum("bhgnd,bhnde->bhgne", qg, kv)
