"""Pallas TPU kernel: backward pass of the chunked causal aggregation.

Forward (``flow_chunk.py``) computes ``out[g, i] = q[g, i] . S_i`` with
``S_i = sum_{j<=i} k_j^T v_j``.  Differentiating w.r.t. the three inputs:

    dq[g, i] = sum_{j<=i} (g[g, i] . v_j) k_j            (causal, like fwd)
    dk[j]    = sum_{g, i>=j} (g[g, i] . v_j) q[g, i]     (REVERSE causal)
    dv[j]    = sum_{g, i>=j} (q[g, i] . k_j) g[g, i]     (REVERSE causal)

``dq`` has exactly the forward structure with (k, v) roles swapped, so it
reuses the forward kernel: ``dq = flow_chunk_call(g, v, k)`` (the carried
state accumulates ``v^T k = S^T``).  ``dk``/``dv`` share one REVERSE chunked
scan implemented here: the grid walks chunks last-to-first (via the block
index map) carrying the (D, Dv) reverse state

    U = sum_{i in later chunks, g} q[g, i]^T g[g, i]

in VMEM scratch, mirroring the forward carry.  Intra-chunk terms recompute
the (G, C, C) score panels from q/k/v/g — nothing sequence-length-sized is
ever materialized in HBM, exactly like the forward pass.

Grid = (batch*kv_heads, n_chunks); the chunk axis iterates sequentially on
TPU so the reverse carry is sound; HBM traffic is one read of q/k/v/g and
one write of dk/dv.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _dkv_kernel(q_ref, k_ref, v_ref, g_ref, dk_ref, dv_ref, u_ref, *,
                chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        u_ref[...] = jnp.zeros_like(u_ref)

    f32 = jnp.float32
    grp = q_ref.shape[1]
    # (G, C, X) panels flattened to (G*C, X): Mosaic contracts 2-D operands
    # only, and summing over (g, i) is then one leading-dim contraction
    q = q_ref[0].astype(f32).reshape(grp * chunk, -1)  # (G*C, D)
    k = k_ref[0].astype(f32)  # (C, D)
    v = v_ref[0].astype(f32)  # (C, Dv)
    g = g_ref[0].astype(f32).reshape(grp * chunk, -1)  # (G*C, Dv)

    # mask[i, j] = 1 where i >= j: the transpose-time image of the fwd tril
    mask = jnp.tril(jnp.ones((chunk, chunk), f32))

    def masked(scores):  # (G*C, C) scores -> masked to i >= j per group
        return (scores.reshape(grp, chunk, chunk) * mask).reshape(
            grp * chunk, chunk)

    def contract_rows(a, b):  # sum over the (g, i) rows: a^T b
        return jax.lax.dot_general(
            a, b, (((0,), (0,)), ((), ())), preferred_element_type=f32)

    # dk intra: scores_gv[(g, i), j] = g[g, i] . v[j], masked to i >= j,
    # contracted against q over (g, i)
    scores_gv = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (G*C, C)
    dk = contract_rows(masked(scores_gv), q)  # (C_j, D)

    # dv intra: scores_qk[(g, i), j] = q[g, i] . k[j], masked, against g
    scores_qk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (G*C, C)
    dv = contract_rows(masked(scores_qk), g)  # (C_j, Dv)

    # inter-chunk terms from the reverse carry U (later chunks only)
    u = u_ref[...]  # (D, Dv)
    dk += jax.lax.dot_general(
        v, u, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (C, D): dk[j] += U @ v[j]
    dv += jax.lax.dot_general(
        k, u, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # (C, Dv): dv[j] += U^T k[j]

    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    # fold this chunk into the carry before stepping to the EARLIER chunk
    u_ref[...] += contract_rows(q, g)  # (D, Dv)


def flow_chunk_dkv_call(
    q: Array, k: Array, v: Array, g: Array, *, chunk: int = 128,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Reverse-scan dk/dv for the chunked causal aggregation.

    q: (BH, G, N, D); k: (BH, N, D); v: (BH, N, Dv); g: (BH, G, N, Dv)
    -> dk (BH, N, D), dv (BH, N, Dv).
    """
    bh, grp, n, d = q.shape
    dv_dim = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk

    def rev(b, c):
        return (b, nc - 1 - c, 0)

    def rev_g(b, c):
        return (b, 0, nc - 1 - c, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, grp, chunk, d), rev_g),
            pl.BlockSpec((1, chunk, d), rev),
            pl.BlockSpec((1, chunk, dv_dim), rev),
            pl.BlockSpec((1, grp, chunk, dv_dim), rev_g),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d), rev),
            pl.BlockSpec((1, chunk, dv_dim), rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, d), k.dtype),
            jax.ShapeDtypeStruct((bh, n, dv_dim), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, dv_dim), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(q, k, v, g)
    return dk, dv


def flow_chunk_dkv_ref(q, k, v, g):
    """Pure-jnp oracle for the reverse-causal dk/dv.

    q: (BH, G, N, D); k: (BH, N, D); v: (BH, N, Dv); g: (BH, G, N, Dv).
    """
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    n = q.shape[2]
    mask = jnp.tril(jnp.ones((n, n), jnp.float32))  # (i, j): i >= j
    sgv = jnp.einsum("bgie,bje->bgij", gf, vf) * mask
    dk = jnp.einsum("bgij,bgid->bjd", sgv, qf)
    sqk = jnp.einsum("bgid,bjd->bgij", qf, kf) * mask
    dv = jnp.einsum("bgij,bgie->bje", sqk, gf)
    return dk.astype(k.dtype), dv.astype(v.dtype)
