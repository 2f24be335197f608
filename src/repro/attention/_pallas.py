"""Jit'd wrappers around the raw Pallas kernels in ``repro/kernels``.

Shape policing + chunk adjustment live here so the kernels themselves stay
pure grid/block code.  On TPU the compiled kernels keep the carried state in
VMEM; ``interpret=True`` runs them in the Pallas interpreter, which the
registry asks for only when a Pallas backend is selected explicitly
off-TPU.  Calls route through the
``attention/vjp.py`` custom-VJP rules, so ``jax.grad`` through these
wrappers runs the Pallas backward kernels instead of raising.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.attention.fused import effective_chunk, padded_len
from repro.attention.vjp import flow_chunk_dot


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def chunked_causal_dot_pallas(
    qg: jax.Array, k: jax.Array, v: jax.Array, *, chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """qg: (B, H, G, N, D); k: (B, H, N, D); v: (B, H, N, Dv).

    Non-chunk-multiple N is zero-padded to the next chunk multiple and the
    result sliced back — zero k/v rows contribute nothing to the causal
    aggregation, so no masking is needed inside the kernel.
    """
    b, h, g, n, d = qg.shape
    dv = v.shape[-1]
    c = effective_chunk(n, chunk)
    n_pad = padded_len(n, c)

    def pad(x):
        if x.shape[-2] == n_pad:
            return x
        width = [(0, 0)] * x.ndim
        width[-2] = (0, n_pad - x.shape[-2])
        return jnp.pad(x, width)

    out = flow_chunk_dot(
        pad(qg.reshape(b * h, g, n, d)),
        pad(k.reshape(b * h, n, d)),
        pad(v.reshape(b * h, n, dv)),
        c,
        interpret,
    )
    return out[:, :, :n].reshape(b, h, g, n, dv)
