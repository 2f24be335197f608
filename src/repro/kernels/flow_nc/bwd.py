"""Pallas TPU kernel: backward pass of the fused non-causal sink side.

The forward kernel (``flow_nc.py``) keeps the whole per-row chain

    phi = sigmoid(q_i);  I = (phi+eps).(k_sum+eps);  I_hat = (phi+eps).(ko_sum+eps)
    out_i = sigmoid(I_hat * scale) * ((phi / I) @ kv)

in VMEM.  The backward recomputes that chain from the same residuals
(q, k_sum, ko_sum, kv — no (N, .) intermediate is ever saved) and reduces
the cotangents:

    dq_i     per row (streamed, blocked over N like the forward)
    dk_sum   = sum_i dI_i     * (phi_i + eps)        (key-side reduction)
    dko_sum  = sum_i dI_hat_i * (phi_i + eps)        (key-side reduction)
    dkv      = (phi / I)^T @ (g * alloc)             (key-side reduction)

The three reductions accumulate across the sequential N-block grid axis in
revisited output blocks (initialized at block 0), so one pass over q/g
produces every cotangent — the op stays memory-roofline-optimal in reverse.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _bwd_kernel(q_ref, ksum_ref, kosum_ref, kv_ref, g_ref,
                dq_ref, dksum_ref, dkosum_ref, dkv_ref, *,
                eps: float, sink_scale: float):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        dksum_ref[...] = jnp.zeros_like(dksum_ref)
        dkosum_ref[...] = jnp.zeros_like(dkosum_ref)
        dkv_ref[...] = jnp.zeros_like(dkv_ref)

    q = q_ref[0]  # (Nb, D)
    k_sum = ksum_ref[0].astype(jnp.float32)  # (1, D)
    ko_sum = kosum_ref[0].astype(jnp.float32)  # (1, D)
    kv = kv_ref[0].astype(jnp.float32)  # (D, Dv)
    g = g_ref[0].astype(jnp.float32)  # (Nb, Dv)

    # --- recompute the forward chain (same ops as the fwd kernel) ---
    phi = jax.nn.sigmoid(q.astype(jnp.float32))
    incoming = jnp.sum((phi + eps) * (k_sum + eps), axis=-1, keepdims=True)
    conserved = jnp.sum((phi + eps) * (ko_sum + eps), axis=-1, keepdims=True)
    alloc = jax.nn.sigmoid(conserved * sink_scale)
    q_in = phi / incoming  # (Nb, D)
    agg = jax.lax.dot_general(
        q_in, kv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Nb, Dv)

    # --- reverse the chain ---
    dagg = g * alloc  # (Nb, Dv)
    dalloc = jnp.sum(g * agg, axis=-1, keepdims=True)  # (Nb, 1)

    dq_in = jax.lax.dot_general(
        dagg, kv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Nb, D)
    dincoming = -jnp.sum(dq_in * q_in, axis=-1, keepdims=True) / incoming
    dconserved = dalloc * alloc * (1.0 - alloc) * sink_scale

    dphi = (
        dq_in / incoming
        + dincoming * (k_sum + eps)
        + dconserved * (ko_sum + eps)
    )
    dq_ref[0] = (dphi * phi * (1.0 - phi)).astype(dq_ref.dtype)

    # --- key-side cotangent reductions (accumulated across N blocks) ---
    dksum_ref[0] += jnp.sum(dincoming * (phi + eps), axis=0, keepdims=True)
    dkosum_ref[0] += jnp.sum(dconserved * (phi + eps), axis=0, keepdims=True)
    dkv_ref[0] += jax.lax.dot_general(
        q_in, dagg, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (D, Dv)


def flow_nc_qside_bwd_call(
    q: Array, k_sum: Array, ko_sum: Array, kv: Array, g: Array, *,
    n_sinks: int, m_sources: int, eps: float = 1e-6,
    block: int = 256, interpret: bool = False,
) -> tuple[Array, Array, Array, Array]:
    """Cotangents of ``flow_nc_qside_call`` w.r.t. (q, k_sum, ko_sum, kv).

    q: (BH, N, D); k_sum/ko_sum: (BH, D); kv: (BH, D, Dv); g: (BH, N, Dv).
    """
    bh, n, d = q.shape
    dv = kv.shape[-1]
    nb = min(block, n)
    while n % nb:
        nb //= 2
    grid = (bh, n // nb)

    def fixed(b, c):  # revisited accumulator block, every grid step
        return (b, 0, 0)

    dq, dksum, dkosum, dkv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, eps=eps, sink_scale=float(n_sinks) / float(m_sources)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nb, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, d), fixed),
            pl.BlockSpec((1, 1, d), fixed),
            pl.BlockSpec((1, d, dv), fixed),
            pl.BlockSpec((1, nb, dv), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nb, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, d), fixed),
            pl.BlockSpec((1, 1, d), fixed),
            pl.BlockSpec((1, d, dv), fixed),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, d, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(q, k_sum[:, None, :], ko_sum[:, None, :], kv, g)
    return (
        dq,
        dksum[:, 0, :].astype(k_sum.dtype),
        dkosum[:, 0, :].astype(ko_sum.dtype),
        dkv.astype(kv.dtype),
    )
