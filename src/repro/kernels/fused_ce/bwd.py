"""Pallas TPU kernel: backward of the fused vocab-head cross-entropy.

With ``s = x W^T`` and ``nll = lse(s) - s[target]`` (``fused_ce.py``),
the logits' cotangent is ``g = (softmax(s) - onehot(target)) * gscale``,
``gscale`` the NLL's cotangent per token.  Grid (token blocks, vocab
blocks), every program independent: the (tm, tv) tile of ``s`` is
recomputed on the MXU from ``x``, ``W`` and the forward's ``lse``, and
``g`` is written once in ``x``'s dtype (bf16).  ``dx = g W`` and ``dW =
g^T x`` are left to XLA's matmuls (``ops.py``), which feed the MXU the
same bf16 operands the f32 cotangent of the unfused head is rounded to
at default precision.  No fp32 (T, V) tensor reaches HBM; columns past
``v`` read 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_ce.fused_ce import (
    VMEM_LIMIT,
    _cols,
    _logits,
    _operand_specs,
    _row_spec,
)

Array = jax.Array


def _bwd_kernel(tgt_ref, lse_ref, gs_ref, x_ref, w_ref, g_ref,
                *, tv: int, v: int, masked: bool):
    j = pl.program_id(1)
    s = _logits(x_ref, w_ref)
    cols = _cols(s.shape, j, tv)
    p = jnp.exp(s - lse_ref[...])
    g = jnp.where(cols == tgt_ref[...], p - 1.0, p) * gs_ref[...]
    if masked:
        g = jnp.where(cols < v, g, 0.0)
    g_ref[...] = g.astype(g_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tv", "v", "interpret"))
def fused_ce_bwd_call(x: Array, w: Array, targets: Array, lse: Array,
                      gscale: Array, *, tm: int, tv: int, v: int,
                      interpret: bool = False) -> Array:
    """The logits' cotangent ``(softmax - onehot) * gscale``, (T, Vp) in
    ``x``'s dtype; ``lse`` and ``gscale`` are (T, 1) fp32, the rest as
    ``fused_ce_fwd_call`` takes them.  Padded columns read 0."""
    t, d = x.shape
    vp = w.shape[0]
    kernel = functools.partial(_bwd_kernel, tv=tv, v=v, masked=vp != v)
    size = x.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid=(t // tm, vp // tv),
        in_specs=[_row_spec(tm), _row_spec(tm), _row_spec(tm),
                  *_operand_specs(tm, tv, d)],
        out_specs=pl.BlockSpec((tm, tv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, vp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * vp * d, transcendentals=t * vp,
            bytes_accessed=(t * d + (t // tm) * vp * d + t * vp) * size
            + 12 * t),
        name="fused_ce_bwd",
        interpret=interpret,
    )(targets, lse, gscale, x, w)
