"""Pallas TPU kernel: the ENTIRE non-causal Flow-Attention pair, one launch.

``flow_nc.py`` fuses only the sink side and leaves the key-side reductions
(k_sum, src_out, ko_sum, qi_sum, competition reweighting, the (D, Dv)
``kv`` matmul) to XLA — a second pass over K/V plus five kernel launches.
This kernel runs the whole pipeline in ONE ``pallas_call`` with a phased
sequential grid per (batch*head):

    phase A (P1 steps):   ksum += sum phi(K_j);  qsum += sum phi(Q_j)
    phase B (P1 steps):   kosum += sum phi(K_j) * src_out      (needs qsum)
                          qisum += sum phi(Q_j) * sink_in      (needs ksum)
    phase C (nbm steps):  e = exp(clip(cons_src)); z += sum e
                          kvacc += phi(K_j)^T (V_j * e)        (needs qisum)
    phase D (nbn steps):  out_j = sigmoid(I_hat * n/m)
                                  * ((phi(Q_j)/I_j) @ kvacc) * (m / z)

P1 = max(nbm, nbn) so phases A/B stream the q- and k-side blocks in
lockstep.  The competition softmax is applied with a DEFERRED normalizer:
phase C accumulates the unnormalized ``e``-weighted kv plus ``z = sum e``
and phase D multiplies by ``m / z`` — exact (not approximate) because
``cons_src`` is clipped to [-1, 1], so no max-subtraction is needed, and
``kv`` enters the output linearly.  With ``use_comp=False`` e == 1, z == m
and the factor collapses to exactly 1.  Like ``flow_nc.py`` the kernel
hard-codes sigmoid phi and sigmoid allocation (the PallasNC contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _blocks(n: int, block: int) -> int:
    nb = min(block, n)
    while n % nb:
        nb //= 2
    return nb


def _kernel(q_ref, k_ref, v_ref, o_ref, ksum, qsum, kosum, qisum, zacc,
            kvacc, *, p1: int, nbm: int, nbn: int, m: int, eps: float,
            sink_scale: float, use_comp: bool):
    j = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        for ref in (ksum, qsum, kosum, qisum, zacc, kvacc):
            ref[...] = jnp.zeros_like(ref)

    # ---- phase A: plain sums -------------------------------------------
    @pl.when(j < min(p1, nbm))
    def _a_k():
        pk = jax.nn.sigmoid(k_ref[0].astype(f32))
        ksum[...] += jnp.sum(pk, axis=0, keepdims=True)

    @pl.when(j < min(p1, nbn))
    def _a_q():
        pq = jax.nn.sigmoid(q_ref[0].astype(f32))
        qsum[...] += jnp.sum(pq, axis=0, keepdims=True)

    # ---- phase B: conservation sums (need the phase-A totals) ----------
    @pl.when(jnp.logical_and(p1 <= j, j < p1 + nbm))
    def _b_k():
        pk = jax.nn.sigmoid(k_ref[0].astype(f32))
        src_out = 1.0 / jnp.sum(
            (pk + eps) * (qsum[...] + eps), axis=-1, keepdims=True
        )
        kosum[...] += jnp.sum(pk * src_out, axis=0, keepdims=True)

    @pl.when(jnp.logical_and(p1 <= j, j < p1 + nbn))
    def _b_q():
        pq = jax.nn.sigmoid(q_ref[0].astype(f32))
        sink_in = 1.0 / jnp.sum(
            (pq + eps) * (ksum[...] + eps), axis=-1, keepdims=True
        )
        qisum[...] += jnp.sum(pq * sink_in, axis=0, keepdims=True)

    # ---- phase C: competition-weighted kv + deferred normalizer --------
    @pl.when(jnp.logical_and(2 * p1 <= j, j < 2 * p1 + nbm))
    def _c():
        pk = jax.nn.sigmoid(k_ref[0].astype(f32))
        vf = v_ref[0].astype(f32)
        if use_comp:
            cons_src = jnp.clip(
                jnp.sum((pk + eps) * (qisum[...] + eps), axis=-1,
                        keepdims=True),
                -1.0,
                1.0,
            )
            e = jnp.exp(cons_src)  # in [1/e, e]: deferred softmax is exact
        else:
            e = jnp.ones((pk.shape[0], 1), f32)
        zacc[...] += jnp.sum(e, axis=0, keepdims=True)
        kvacc[...] += jax.lax.dot_general(
            pk, vf * e, (((0,), (0,)), ((), ())), preferred_element_type=f32
        )

    # ---- phase D: sink side over the finished kv -----------------------
    @pl.when(2 * p1 + nbm <= j)
    def _d():
        pq = jax.nn.sigmoid(q_ref[0].astype(f32))
        incoming = jnp.sum(
            (pq + eps) * (ksum[...] + eps), axis=-1, keepdims=True
        )
        conserved = jnp.sum(
            (pq + eps) * (kosum[...] + eps), axis=-1, keepdims=True
        )
        alloc = jax.nn.sigmoid(conserved * sink_scale)
        agg = jax.lax.dot_general(
            pq / incoming, kvacc[...], (((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )
        scale = float(m) / zacc[...]  # softmax normalizer, applied once
        o_ref[0] = (agg * alloc * scale).astype(o_ref.dtype)


def flow_nc_fused_call(
    q: Array, k: Array, v: Array, *, eps: float = 1e-6, block: int = 256,
    use_comp: bool = True, interpret: bool = False,
) -> Array:
    """q: (BH, NQ, D) raw; k: (BH, M, D); v: (BH, M, Dv) -> (BH, NQ, Dv).

    NQ counts sinks (G*N after GQA grouping); ``sink_scale = NQ / M``
    matches the pipeline's allocation normalization.
    """
    bh, nq, d = q.shape
    m = k.shape[1]
    dv = v.shape[-1]
    bq = _blocks(nq, block)
    bm = _blocks(m, block)
    nbn = nq // bq
    nbm = m // bm
    p1 = max(nbm, nbn)
    steps = 2 * p1 + nbm + nbn

    def qmap(b, j):
        jj = jnp.where(j < p1, j,
                       jnp.where(j < 2 * p1, j - p1, j - (2 * p1 + nbm)))
        return (b, jnp.clip(jj, 0, nbn - 1), 0)

    def kmap(b, j):
        jj = jnp.where(j < p1, j, jnp.where(j < 2 * p1, j - p1, j - 2 * p1))
        return (b, jnp.clip(jj, 0, nbm - 1), 0)

    def omap(b, j):
        # pinned to block 0 until phase D starts; the first D step
        # overwrites block 0 before the index ever advances
        return (b, jnp.maximum(j - (2 * p1 + nbm), 0), 0)

    return pl.pallas_call(
        functools.partial(
            _kernel, p1=p1, nbm=nbm, nbn=nbn, m=m, eps=eps,
            sink_scale=float(nq) / float(m), use_comp=use_comp,
        ),
        grid=(bh, steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bm, d), kmap),
            pl.BlockSpec((1, bm, dv), kmap),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), omap),
        out_shape=jax.ShapeDtypeStruct((bh, nq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((d, dv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(q, k, v)
