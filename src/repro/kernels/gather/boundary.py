"""Pallas boundary-history gather for packed prefill admission.

rglru/ssd packed prefill needs each row's last ``k-1`` conv inputs
*before* its own boundary ``lengths[i]`` — the decode conv history.  The
XLA form zero-pads the whole (B, N, W) stream and runs a
``take_along_axis`` gather; on the serving hot path that is an extra
(B, N+k-1, W) materialization just to read k-1 rows per batch element.

The kernel reads the raw stream once.  Tap ``j`` of row ``b`` lives at
raw position ``lengths[b] - (k-1) + j``, which is NEGATIVE for rows
shorter than the window — a single ``pl.ds`` window starting there would
wrap, so each tap is loaded at its index clipped into range and then
zero-masked where the true index is below zero (the fresh-conv left
pad).  ``k`` is tiny (conv_width <= 4 in every config), so the per-tap
python loop unrolls to a handful of loads.

``boundary_gather_xla`` is the XLA form, for platforms without the kernel;
the caller picks one by platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(lens_ref, x_ref, o_ref, *, k: int):
    b = pl.program_id(0)
    n = x_ref.shape[1]
    start = lens_ref[b] - (k - 1)
    taps = []
    for jj in range(k - 1):
        idx = start + jj
        row = x_ref[:, pl.ds(jnp.clip(idx, 0, n - 1), 1), :]  # (1, 1, W)
        taps.append(jnp.where(idx >= 0, row, jnp.zeros_like(row)))
    o_ref[...] = jnp.concatenate(taps, axis=1).astype(o_ref.dtype)


def boundary_gather_xla(xb: Array, lengths: Array, k: int) -> Array:
    """``boundary_gather`` as an XLA pad + ``take_along_axis``."""
    bsz, _, w = xb.shape
    pad = jnp.zeros((bsz, k - 1, w), xb.dtype)
    xp = jnp.concatenate([pad, xb], axis=1)
    idx = lengths.astype(jnp.int32)[:, None] + jnp.arange(k - 1)[None, :]
    return jnp.take_along_axis(xp, idx[..., None], axis=1)


def boundary_gather(xb: Array, lengths: Array, k: int, *,
                    interpret: bool = False) -> Array:
    """xb: (B, N, W); lengths: (B,) int.  Returns (B, k-1, W): row i's
    trailing ``k-1`` inputs before position ``lengths[i]``, zero-filled on
    the left exactly like a fresh causal-conv pad.  ``interpret`` runs the
    kernel in the Pallas interpreter (off-TPU)."""
    bsz, n, w = xb.shape
    lens = lengths.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bsz,),
        in_specs=[pl.BlockSpec((1, n, w), lambda b, lens_: (b, 0, 0))],
        out_specs=pl.BlockSpec((1, k - 1, w), lambda b, lens_: (b, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, k - 1, w), xb.dtype),
        interpret=interpret,
    )(lens, xb)
