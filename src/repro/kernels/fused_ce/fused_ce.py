"""Pallas TPU kernels: the vocab head's projection fused with its softmax.

The head of a language model scores every token against every vocabulary
row, ``s = x W^T`` (T, V), and training needs only ``nll = lse(s) -
s[target]`` per token.  Written out in fp32, ``s`` and its log-softmax
are (T, V) tensors of 4 bytes an element: at 49,152 tokens and a 32,768
vocabulary, 6.4 GB each, read and written several times a step.  These
kernels never write them.

Forward (``fused_ce_fwd_call``), grid (token blocks, vocab blocks): each
program computes one (tm, tv) tile of ``s`` on the MXU (bf16 operands,
fp32 accumulation) and folds it into a running max and sum of
exponentials kept in VMEM scratch (the online softmax); the target's
logit is picked from the tile by comparing a vocabulary iota with the
targets.  The vocab axis is the reduction ("arbitrary"); after its last
block the program writes ``lse`` and the target logit, (T, 1) fp32 each.

The backward is ``bwd.py``.  Vocabulary columns past ``v`` (padding up
to a whole tile) are masked out of ``lse``; rows past the true token
count are padding that the caller slices off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

# scoped VMEM this kernel and ``bwd.py``'s may use: v5e has 128 MiB a
# core, and the default scope (16 MiB) is too small for a (512, 2048)
# fp32 tile and its temporaries
VMEM_LIMIT = 64 * 1024 * 1024


def _logits(x_ref, w_ref) -> Array:
    """This program's (tm, tv) fp32 logits tile: x W^T on the MXU."""
    return jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _cols(shape, j: Array, tv: int) -> Array:
    """Vocabulary index of each element of a (tm, tv) tile."""
    return j * tv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _fwd_kernel(tgt_ref, x_ref, w_ref, lse_ref, tl_ref, m_sc, l_sc, t_sc,
                *, tv: int, v: int, masked: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        t_sc[...] = jnp.zeros_like(t_sc)

    s = _logits(x_ref, w_ref)
    cols = _cols(s.shape, j, tv)
    if masked:
        s = jnp.where(cols < v, s, -jnp.inf)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_sc[...] = (l_sc[...] * jnp.exp(m_prev - m_new)
                 + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True))
    m_sc[...] = m_new
    t_sc[...] += jnp.sum(jnp.where(cols == tgt_ref[...], s, 0.0), axis=1,
                         keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        lse_ref[...] = m_sc[...] + jnp.log(l_sc[...])
        tl_ref[...] = t_sc[...]


def _row_spec(tm: int) -> pl.BlockSpec:
    """A (tm, 1) block of a per-token column."""
    return pl.BlockSpec((tm, 1), lambda i, j: (i, 0))


def _operand_specs(tm: int, tv: int, d: int) -> list:
    """The x (tm, d) and W (tv, d) blocks of program (i, j)."""
    return [pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tv, d), lambda i, j: (j, 0))]


@functools.partial(jax.jit, static_argnames=("tm", "tv", "v", "interpret"))
def fused_ce_fwd_call(x: Array, w: Array, targets: Array, *, tm: int,
                      tv: int, v: int, interpret: bool = False):
    """(lse, target logit), each (T, 1) fp32, of ``x`` (T, d) against the
    vocabulary rows ``w`` (Vp, d), ``targets`` (T, 1) int32.

    T and Vp are whole multiples of ``tm`` and ``tv``; columns from ``v``
    on are padding.
    """
    t, d = x.shape
    vp = w.shape[0]
    f32 = jnp.float32
    col = jax.ShapeDtypeStruct((t, 1), f32)
    kernel = functools.partial(_fwd_kernel, tv=tv, v=v, masked=vp != v)
    return pl.pallas_call(
        kernel,
        grid=(t // tm, vp // tv),
        in_specs=[_row_spec(tm), *_operand_specs(tm, tv, d)],
        out_specs=[_row_spec(tm), _row_spec(tm)],
        out_shape=[col, col],
        scratch_shapes=[pltpu.VMEM((tm, 1), f32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * t * vp * d, transcendentals=t * vp,
            bytes_accessed=(t * d + (t // tm) * vp * d) * x.dtype.itemsize
            + 12 * t),
        name="fused_ce_fwd",
        interpret=interpret,
    )(targets, x, w)
