"""jit-facing wrapper: fused strict-causal Flow-Attention + boundary state.

Grouping, chunk padding and FlowState assembly live here; the Pallas grid
only ever sees flat (BH, G, N, D) chunk-multiple arrays.  The dense path
(``lengths=None``) routes through the ``flow_fused_dot`` custom-vjp rule in
``attention/vjp.py`` so training gets the reverse-scan Pallas backward; the
packed path (per-row ``lengths``) is forward-only serving prefill.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def _pad_chunk(x, n_pad: int):
    n = x.shape[-2]
    if n_pad == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, n_pad - n)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit, static_argnames=("cfg", "return_state", "interpret")
)
def flow_fused_forward(
    q: Array, k: Array, v: Array, cfg, *,
    return_state: bool = False, lengths: Optional[Array] = None,
    interpret: bool = False,
):
    """Strict-causal Flow-Attention via the fused Pallas kernel.

    q: (B, Hq, N, D); k/v: (B, Hkv, N, D/Dv) — already expand_kv'd to the
    grouped layout contract (Hq divisible by Hkv).  ``lengths`` (B,) int32
    selects the forward-only packed path whose returned state is each
    row's boundary FlowState.  Non-chunk-multiple N is padded and masked,
    never shrunk to degenerate chunks.  ``interpret`` runs the kernels in
    the Pallas interpreter (off-TPU).
    """
    # lazy: this package must import before repro.attention finishes
    from repro.attention.recurrent import FlowState
    from repro.core.flow_attention import _group, _ungroup

    b, hq, n, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    grp = hq // hkv
    qg = _group(q, hkv)  # (B, Hkv, G, N, D)

    c = max(1, min(cfg.chunk_size, n))
    n_pad = -(-n // c) * c
    qf = _pad_chunk(qg.reshape(b * hkv, grp, n, d), n_pad)
    kf = _pad_chunk(k.reshape(b * hkv, n, d), n_pad)
    vf = _pad_chunk(v.reshape(b * hkv, n, dv), n_pad)

    if lengths is None:
        from repro.attention.vjp import flow_fused_dot  # lazy: cycle

        out, sums = flow_fused_dot(
            qf, kf, vf, n, c, cfg.eps, cfg.phi, cfg.use_allocation,
            interpret,
        )
        t = jnp.full((b,), n, jnp.int32)
    else:
        from .flow_fused import flow_fused_call

        t = jnp.clip(lengths.astype(jnp.int32), 1, n)
        lens = jnp.broadcast_to(t[:, None], (b, hkv)).reshape(b * hkv)
        out, sums = flow_fused_call(
            qf, kf, vf, lens, chunk=c, eps=cfg.eps, phi=cfg.phi,
            use_alloc=cfg.use_allocation, interpret=interpret,
        )
    out = _ungroup(
        out[:, :, :n].reshape(b, hkv, grp, n, dv)
    )
    if not return_state:
        return out, None
    q_sum, k_sum, ko_sum, qi_sum, z, s = sums
    state = FlowState(
        t=t,
        q_sum=q_sum.reshape(b, hkv, d),
        k_sum=k_sum.reshape(b, hkv, d),
        ko_sum=ko_sum.reshape(b, hkv, d),
        qi_sum=qi_sum.reshape(b, hkv, d),
        z=z.reshape(b, hkv),
        s=s.reshape(b, hkv, d, dv),
    )
    return out, state
