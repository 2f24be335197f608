"""Jit'd wrapper for the SSD chunk Pallas kernel.

``ssd_chunk_dot`` is the differentiable entry: its custom VJP runs the
reverse-scan Pallas backward (``bwd.py``) off the chunk-boundary carry-in
residuals, so TPU training of hybrid (ssd + attention) stacks no longer
needs an XLA fallback.  The upstream softplus/pre-scale/head-broadcast in
``ssd_scan_pallas`` stays plain XLA and differentiates natively.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd_chunk.ssd_chunk import ssd_chunk_call


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_chunk_dot(x: jax.Array, dta: jax.Array, b: jax.Array, c: jax.Array,
                  chunk: int, interpret: bool) -> jax.Array:
    """Differentiable ``ssd_chunk_call``.

    x: (BH, N, P) pre-scaled; dta: (BH, N, 1); b/c: (BH, N, S) -> (BH, N, P).
    ``chunk`` and ``interpret`` are static (non-differentiable) arguments.
    """
    return ssd_chunk_call(x, dta, b, c, chunk=chunk, interpret=interpret)


def _ssd_fwd(x, dta, b, c, chunk, interpret):
    y, hins = ssd_chunk_call(x, dta, b, c, chunk=chunk, interpret=interpret,
                             return_hins=True)
    return y, (x, dta, b, c, hins)


def _ssd_bwd(chunk, interpret, residuals, g):
    from repro.kernels.ssd_chunk.bwd import ssd_chunk_bwd_call

    x, dta, b, c, hins = residuals
    return ssd_chunk_bwd_call(x, dta, b, c, hins, g, chunk=chunk,
                              interpret=interpret)


ssd_chunk_dot.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    xh: jax.Array, dt: jax.Array, bmat: jax.Array, cmat: jax.Array,
    a: jax.Array, *, chunk: int = 128, interpret: bool = False,
) -> jax.Array:
    """Head-batched SSD scan.

    xh: (B, N, H, P); dt: (B, N, H) fp32 (softplus already applied);
    bmat/cmat: (B, N, S) shared across heads; a: (H,) negative.
    Returns y: (B, N, H, P) fp32 (without the D-skip term).
    ``interpret`` runs the kernel in the Pallas interpreter (off-TPU).
    """
    bsz, n, h, p = xh.shape
    s = bmat.shape[-1]
    c = min(chunk, n)
    while n % c:
        c //= 2

    x = (xh.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)
    x = x.reshape(bsz * h, n, p)
    dta = (dt * a[None, None, :]).transpose(0, 2, 1).reshape(bsz * h, n, 1)
    bm = jnp.broadcast_to(bmat[:, None], (bsz, h, n, s)).reshape(bsz * h, n, s)
    cm = jnp.broadcast_to(cmat[:, None], (bsz, h, n, s)).reshape(bsz * h, n, s)

    y = ssd_chunk_dot(x, dta, bm, cm, c, interpret)
    return y.reshape(bsz, h, n, p).transpose(0, 2, 1, 3)
