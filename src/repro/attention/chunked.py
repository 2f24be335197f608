"""Chunked causal linear attention — the TPU-native aggregation core.

The paper relies on the sequential CUDA ``causal-dot-product`` kernel of
Katharopoulos et al.  On TPU we replace it with the chunked formulation:
split the sequence into chunks of size C, then for chunk c

    intra_c = tril(Q_c K_c^T) V_c          # dense (C,C)x(C,Dv) matmuls (MXU)
    inter_c = Q_c S_c                      # (C,D)x(D,Dv) matmul
    S_{c+1} = S_c + K_c^T V_c              # carried (D,Dv) state

All operations are 128-alignable matmuls; the carried state is O(D*Dv).
This module is the pure-XLA (lax.scan) primitive behind the ``xla_chunked``
backend; ``repro/kernels/flow_chunk`` is the Pallas kernel with the same
contract (same oracle in its ref.py), wrapped by ``attention/_pallas.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.utils import varying_zeros

Array = jax.Array


def chunked_causal_dot(q: Array, k: Array, v: Array, chunk_size: int) -> Array:
    """out_i = q_i . sum_{j<=i} k_j^T v_j   with q,k: (..., N, D); v: (..., N, Dv).

    N must be divisible by ``chunk_size``.
    """
    *batch, n, d = q.shape
    dv = v.shape[-1]
    c = chunk_size
    assert n % c == 0, f"sequence {n} not divisible by chunk {c}"
    nc = n // c

    qc = q.reshape(*batch, nc, c, d)
    kc = k.reshape(*batch, nc, c, d)
    vc = v.reshape(*batch, nc, c, dv)

    # move chunk axis to front for scan
    perm = (len(batch),) + tuple(range(len(batch))) + (len(batch) + 1, len(batch) + 2)
    qs = jnp.transpose(qc, perm)  # (nc, *batch, c, d)
    ks = jnp.transpose(kc, perm)
    vs = jnp.transpose(vc, perm)

    mask = jnp.tril(jnp.ones((c, c), dtype=q.dtype))

    def step(state, inp):
        qb, kb, vb = inp  # (*batch, c, d/dv)
        scores = jnp.einsum(
            "...id,...jd->...ij", qb, kb, preferred_element_type=jnp.float32
        )
        intra = jnp.einsum(
            "...ij,...je->...ie", scores * mask, vb,
            preferred_element_type=jnp.float32,
        )
        inter = jnp.einsum(
            "...id,...de->...ie", qb, state, preferred_element_type=jnp.float32
        )
        new_state = state + jnp.einsum(
            "...jd,...je->...de", kb, vb, preferred_element_type=jnp.float32
        )
        return new_state, (intra + inter).astype(q.dtype)

    s0 = varying_zeros((*k.shape[:-2], k.shape[-1], v.shape[-1]), k, v)
    _, outs = jax.lax.scan(step, s0, (qs, ks, vs))
    inv = tuple(range(1, len(batch) + 1)) + (0, len(batch) + 1, len(batch) + 2)
    return jnp.transpose(outs, inv).reshape(*batch, n, dv)


def chunked_causal_dot_grouped(
    qg: Array, k: Array, v: Array, chunk_size: int
) -> Array:
    """Grouped-query variant sharing the carried state across the group.

    qg: (B,H,G,N,D); k: (B,H,N,D); v: (B,H,N,Dv) -> (B,H,G,N,Dv).
    """
    b, h, g, n, d = qg.shape
    dv = v.shape[-1]
    c = chunk_size
    assert n % c == 0
    nc = n // c

    qs = jnp.moveaxis(qg.reshape(b, h, g, nc, c, d), 3, 0)  # (nc,B,H,G,c,d)
    ks = jnp.moveaxis(k.reshape(b, h, nc, c, d), 2, 0)  # (nc,B,H,c,d)
    vs = jnp.moveaxis(v.reshape(b, h, nc, c, dv), 2, 0)

    mask = jnp.tril(jnp.ones((c, c), dtype=qg.dtype))

    def step(state, inp):
        qb, kb, vb = inp
        scores = jnp.einsum(
            "bhgid,bhjd->bhgij", qb, kb, preferred_element_type=jnp.float32
        )
        intra = jnp.einsum(
            "bhgij,bhje->bhgie", scores * mask, vb,
            preferred_element_type=jnp.float32,
        )
        inter = jnp.einsum(
            "bhgid,bhde->bhgie", qb, state, preferred_element_type=jnp.float32
        )
        new_state = state + jnp.einsum(
            "bhjd,bhje->bhde", kb, vb, preferred_element_type=jnp.float32
        )
        return new_state, (intra + inter).astype(qg.dtype)

    s0 = varying_zeros((b, h, d, dv), k, v)
    _, outs = jax.lax.scan(step, s0, (qs, ks, vs))
    return jnp.moveaxis(outs, 0, 3).reshape(b, h, g, n, dv)
