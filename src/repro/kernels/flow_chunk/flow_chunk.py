"""Pallas TPU kernel: chunked causal linear/flow aggregation.

Computes  out_i = q_i . sum_{j<=i} k_j^T v_j  (the causal dot product at the
heart of causal Flow-Attention, paper Alg. 2) in the chunked MXU form:

    per chunk c:  intra = tril(Q_c K_c^T) V_c      (C,C)x(C,Dv) MXU matmuls
                  inter = Q_c S                     (C,D)x(D,Dv)
                  S    += K_c^T V_c                 carried in VMEM scratch

Grid = (batch*kv_heads, n_chunks): the chunk axis iterates sequentially on
TPU, so the (D, Dv) fp32 state lives in VMEM scratch across chunks — the
HBM traffic is exactly one read of q/k/v and one write of out (roofline-
optimal for this op).  Grouped queries (GQA) share the carried state: q has
an extra leading G axis, k/v are per kv head.

Block shapes are (G, C, D) / (C, D) panels with C=chunk, D=head_dim — both
MXU-aligned when C, D are multiples of 128 (enforced by ops.py padding).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(q_ref, k_ref, v_ref, o_ref, state_ref, *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0]  # (G, C, D)
    k = k_ref[0]  # (C, D)
    v = v_ref[0]  # (C, Dv)

    scores = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, C, C)
    mask = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    intra = jax.lax.dot_general(
        (scores * mask).astype(v.dtype), v, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, C, Dv)
    inter = jax.lax.dot_general(
        q.astype(jnp.float32), state_ref[...], (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, C, Dv)
    o_ref[0] = (intra + inter).astype(o_ref.dtype)
    state_ref[...] += jax.lax.dot_general(
        k, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (D, Dv)


def flow_chunk_call(
    q: Array, k: Array, v: Array, *, chunk: int = 128, interpret: bool = False
) -> Array:
    """q: (BH, G, N, D); k: (BH, N, D); v: (BH, N, Dv) -> (BH, G, N, Dv)."""
    bh, g, n, d = q.shape
    dv = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, g, chunk, d), lambda b, c: (b, 0, c, 0)),
            pl.BlockSpec((1, chunk, d), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, chunk, dv), lambda b, c: (b, 0, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, n, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(q, k, v)
