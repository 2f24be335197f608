"""Jit'd wrapper: full fused non-causal Flow-Attention in ONE Pallas launch.

The whole pair — key-side reductions, competition reweighting, the (D, Dv)
``kv`` matmul and the sink side — runs as the phased single-kernel
``fused.py`` grid: one read of q and one of k/v, no XLA round-trips for the
intermediate reductions.  Matches
``repro.core.flow_attention.flow_attention_nc`` (shared-GQA semantics) and
is tested against it.

The op routes through the ``attention/vjp.py`` ``flow_nc_fused`` custom-VJP
rule: the backward differentiates the decomposed key-side math in XLA while
the dominant sink-side stream still pulls through the ``flow_nc_qside``
Pallas backward kernel.
"""
from __future__ import annotations

import functools

import jax

from repro.core.flow_attention import FlowConfig, _group


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def flow_attention_nc_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array,
    cfg: FlowConfig = FlowConfig(), *, interpret: bool = False,
) -> jax.Array:
    """q: (B,Hq,N,D); k,v: (B,Hkv,M,*) -> (B,Hq,N,Dv).

    ``interpret`` runs the kernel in the Pallas interpreter (off-TPU).
    """
    b, hq, n, d = q.shape
    hkv, m = k.shape[1], k.shape[2]
    g = hq // hkv
    dv = v.shape[-1]
    qg = _group(q, hkv)  # raw q; phi applied inside the kernel

    # lazy import keeps the kernels package importable without a cycle
    # through repro.attention
    from repro.attention.vjp import flow_nc_fused

    out = flow_nc_fused(
        qg.reshape(b * hkv, g * n, d),
        k.reshape(b * hkv, m, d),
        v.reshape(b * hkv, m, dv),
        cfg.eps,
        256,
        cfg.use_competition,
        interpret,
    )
    return out.reshape(b, hkv, g, n, dv).reshape(b, hq, n, dv)
