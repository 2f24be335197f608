"""Reverse-scan Pallas backward for the SSD chunk kernel.

The forward's only extra residual is the (BH, n_chunks, P, S) carry-IN
state per chunk (``ssd_chunk_call(..., return_hins=True)``) — O(N/C * P*S),
nothing (B, H, N)-sized.  Boundary states must be SAVED rather than
reconstructed: unlike the flow kernels' monotone nonnegative sums, the SSD
carry is decay-contracted (``h_out = h_in * exp(cum_total) + ...`` with
``cum_total`` as low as -50 in practice), so dividing the decay back out of
a final total is catastrophically ill-conditioned.

Walking chunks back-to-front with the (P, S) state cotangent ``dh`` carried
in VMEM scratch, each step pulls ``jax.vjp`` of the SAME ``_ssd_step`` the
forward ran: ``(dh_in, dx, ddt, dbm, dcm) = pull((dh_carry, g_chunk))``.
``dh`` starts at zero — the forward discards the final state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssd_chunk import _ssd_step

Array = jax.Array


def _bwd_kernel(x_ref, dt_ref, b_ref, c_ref, hin_ref, g_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, dh, *, chunk: int):
    r = pl.program_id(1)

    @pl.when(r == 0)
    def _init():
        dh[...] = jnp.zeros_like(dh)  # final state is discarded upstream

    f32 = jnp.float32
    _, pull = jax.vjp(
        functools.partial(_ssd_step, chunk=chunk),
        hin_ref[0, 0],
        x_ref[0].astype(f32),
        dt_ref[0].astype(f32),
        b_ref[0].astype(f32),
        c_ref[0].astype(f32),
    )
    dh_in, dx, ddt, dbm, dcm = pull((dh[...], g_ref[0].astype(f32)))
    dx_ref[0] = dx.astype(dx_ref.dtype)
    ddt_ref[0] = ddt.astype(ddt_ref.dtype)
    db_ref[0] = dbm.astype(db_ref.dtype)
    dc_ref[0] = dcm.astype(dc_ref.dtype)
    dh[...] = dh_in


def ssd_chunk_bwd_call(
    x: Array, dta: Array, b: Array, c: Array, hins: Array, g: Array, *,
    chunk: int = 128, interpret: bool = False,
):
    """Gradients of ``ssd_chunk_call`` w.r.t. (x, dta, b, c).

    hins: (BH, n_chunks, P, S) carry-in states from the forward;
    g: (BH, N, P) output cotangent.  Returns (dx, ddta, db, dc)."""
    bh, n, p = x.shape
    s = b.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk

    def rev(b_, r):
        return (b_, nc - 1 - r, 0)

    def rev_h(b_, r):
        return (b_, nc - 1 - r, 0, 0)

    x_spec = pl.BlockSpec((1, chunk, p), rev)
    dt_spec = pl.BlockSpec((1, chunk, 1), rev)
    s_spec = pl.BlockSpec((1, chunk, s), rev)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(bh, nc),
        in_specs=[
            x_spec, dt_spec, s_spec, s_spec,
            pl.BlockSpec((1, 1, p, s), rev_h),
            x_spec,
        ],
        out_specs=[x_spec, dt_spec, s_spec, s_spec],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dta.shape, dta.dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((p, s), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(x, dta, b, c, hins, g)
