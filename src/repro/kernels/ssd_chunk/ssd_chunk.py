"""Pallas TPU kernel: Mamba-2 SSD chunk scan (decay-gated linear attention).

State-space duality makes the SSD recurrence a *decay-weighted* version of
the flow_chunk kernel (DESIGN.md §5 / kernels family note):

    per chunk c, per head h:
      cum    = cumsum(dt * A)                          in-chunk log decays
      intra  = ((C B^T) * exp(cum_i - cum_j) * tril) @ (dt*x)
      inter  = exp(cum_i) * (C @ S)
      S      = exp(cum_total) * S + (B * exp(cum_total - cum_j))^T (dt*x)

Grid = (batch*heads, n_chunks); the (P, N_state) fp32 state is carried in
VMEM scratch across the sequential chunk axis, exactly like flow_chunk.
B/C are per-position state projections (shared across heads upstream;
ops.py pre-broadcasts per head so the kernel stays head-local).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _ssd_step(h, x, dt, bm, cm, *, chunk: int):
    """One SSD chunk, pure jnp: (h_in (P,S), x (C,P), dt (C,1), bm/cm
    (C,S)) -> (h_out, y (C,P)).  Shared verbatim by the forward kernel and
    the ``jax.vjp`` pull inside the backward kernel (``bwd.py``), so the
    two passes can never drift apart.  The in-chunk cumsum is a tril
    matmul — ``jnp.cumsum`` has no in-kernel transpose rule."""
    f32 = jnp.float32
    ltri = jnp.tril(jnp.ones((chunk, chunk), f32))
    cum = jax.lax.dot_general(
        ltri, dt, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # (C, 1) inclusive log decay
    diff = cum - cum.T  # (C, C): cum_i - cum_j (<= 0 on the valid triangle)
    # clamp BEFORE exp: masked upper-triangle entries are large-positive and
    # exp() of them is inf — inf * 0 would poison the result with NaNs
    decay = jnp.exp(jnp.minimum(diff, 0.0)) * ltri
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (C, C) = C_i . B_j
    # x arrives pre-scaled by dt (ops.py): xdt_j = softplus(dt_j) * x_j
    intra = jax.lax.dot_general(
        scores * decay, x, (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
    )  # (C, P)
    inter = jax.lax.dot_general(
        cm, h, (((1,), (1,)), ((), ())), preferred_element_type=f32
    ) * jnp.exp(cum)  # (C, P) — state is (P, S)
    y = intra + inter

    seg = jnp.exp(cum[-1:] - cum)  # (C, 1) decay from j to chunk end
    h_new = h * jnp.exp(cum[-1]) + jax.lax.dot_general(
        x * seg, bm, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # (P, S)
    return h_new, y


def _kernel(x_ref, dt_ref, b_ref, c_ref, o_ref, state_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    h_new, y = _ssd_step(
        state_ref[...],
        x_ref[0].astype(jnp.float32),
        dt_ref[0].astype(jnp.float32),
        b_ref[0].astype(jnp.float32),
        c_ref[0].astype(jnp.float32),
        chunk=chunk,
    )
    o_ref[0] = y.astype(o_ref.dtype)
    state_ref[...] = h_new


def _kernel_hins(x_ref, dt_ref, b_ref, c_ref, o_ref, hins_ref, state_ref,
                 *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    # record the carry ENTERING this chunk — the backward kernel's boundary
    # residual (suffix reconstruction a la flow_fused is impossible here:
    # dividing exp(-50)-decayed totals back out is catastrophic)
    hins_ref[0, 0] = state_ref[...]
    h_new, y = _ssd_step(
        state_ref[...],
        x_ref[0].astype(jnp.float32),
        dt_ref[0].astype(jnp.float32),
        b_ref[0].astype(jnp.float32),
        c_ref[0].astype(jnp.float32),
        chunk=chunk,
    )
    o_ref[0] = y.astype(o_ref.dtype)
    state_ref[...] = h_new


def ssd_chunk_call(
    x: Array, dta: Array, b: Array, c: Array, *, chunk: int = 128,
    interpret: bool = False, return_hins: bool = False,
):
    """x: (BH, N, P) pre-scaled by dt; dta: (BH, N, 1) = dt*A (log decays);
    b, c: (BH, N, S).  Returns y: (BH, N, P); with ``return_hins`` also the
    (BH, n_chunks, P, S) carry-in states (training-path residuals)."""
    bh, n, p = x.shape
    s = b.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk
    in_specs = [
        pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, chunk, s), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, chunk, s), lambda i, j: (i, j, 0)),
    ]
    y_spec = pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0))
    y_shape = jax.ShapeDtypeStruct((bh, n, p), x.dtype)
    common = dict(
        grid=(bh, nc),
        in_specs=in_specs,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )
    if not return_hins:
        return pl.pallas_call(
            functools.partial(_kernel, chunk=chunk),
            out_specs=y_spec,
            out_shape=y_shape,
            scratch_shapes=[pltpu.VMEM((p, s), jnp.float32)],
            **common,
        )(x, dta, b, c)
    return pl.pallas_call(
        functools.partial(_kernel_hins, chunk=chunk),
        out_specs=[y_spec, pl.BlockSpec((1, 1, p, s), lambda i, j: (i, j, 0, 0))],
        out_shape=[y_shape, jax.ShapeDtypeStruct((bh, nc, p, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, s), jnp.float32)],
        **common,
    )(x, dta, b, c)
