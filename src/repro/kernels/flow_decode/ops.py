"""Jit'd wrapper for the batched decode kernel: FlowState in, FlowState out.

Reshapes the (B, Hkv, ...) state pool and the (B, Hq, 1, D) token into the
kernel's flattened (BH, ...) layout, launches one grid over every
(slot, kv head) pair, and reassembles the ``FlowState``.  ``interpret``
runs the kernel in the Pallas interpreter; the registry sets it only for a
Pallas backend selected explicitly off-TPU.  GQA grouping
("shared" mode) is native: the G query heads of a kv group ride along as
the kernel's G axis; "expand" mode is handled by the backend expanding kv
heads before calling (G becomes 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.attention.recurrent import FlowState
from repro.core.flow_attention import FlowConfig
from repro.kernels.flow_decode.flow_decode import flow_decode_call

Array = jax.Array


def _row_t(t: Array, hkv: int) -> Array:
    """(B,) per-slot counts -> (B*Hkv,) per-(slot, head) row counts."""
    return jnp.repeat(t.astype(jnp.int32), hkv)


def _rows(q: Array, k: Array, v: Array):
    """The token's q/k/v in the kernel's row layout: (BH, G, D),
    (BH, 1, D), (BH, 1, Dv)."""
    b, hq, _, d = q.shape
    hkv = k.shape[1]
    return (q[:, :, 0].reshape(b * hkv, hq // hkv, d),
            k.reshape(b * hkv, 1, d), v.reshape(b * hkv, 1, v.shape[-1]))


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def flow_decode_step(
    state: FlowState, q: Array, k: Array, v: Array, cfg: FlowConfig,
    *, interpret: bool = False,
) -> tuple[FlowState, Array]:
    """Advance one token for every slot.

    q: (B, Hq, 1, D); k: (B, Hkv, 1, D); v: (B, Hkv, 1, Dv).
    Returns (new_state, out (B, Hq, 1, Dv)).
    """
    b, hq, one, d = q.shape
    assert one == 1, "decode_step consumes exactly one position"
    hkv = k.shape[1]
    dv = v.shape[-1]
    bh = b * hkv

    t = state.t + 1  # (B,) int32, per-slot position counts
    qg, k2, v2 = _rows(q, k, v)
    rows = lambda x: x.reshape(bh, 1, -1)  # noqa: E731 — (BH, 1, X) rows

    out, k_sum, q_sum, ko_sum, qi_sum, z, s = flow_decode_call(
        _row_t(t, hkv), qg, k2, v2,
        rows(state.k_sum), rows(state.q_sum), rows(state.ko_sum),
        rows(state.qi_sum), rows(state.z), state.s.reshape(bh, d, dv),
        eps=cfg.eps, phi=cfg.phi, use_allocation=cfg.use_allocation,
        interpret=interpret,
    )
    new_state = FlowState(
        t=t,
        q_sum=q_sum.reshape(b, hkv, d),
        k_sum=k_sum.reshape(b, hkv, d),
        ko_sum=ko_sum.reshape(b, hkv, d),
        qi_sum=qi_sum.reshape(b, hkv, d),
        z=z.reshape(b, hkv),
        s=s.reshape(b, hkv, d, dv),
    )
    return new_state, out.reshape(b, hq, 1, dv).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def flow_decode_q_step(
    pool, q: Array, k: Array, v: Array, cfg: FlowConfig,
    *, interpret: bool = False,
):
    """Advance one token for every slot of a *quantized* FlowState pool.

    ``pool`` — a ``serving.quant.QuantizedPool`` whose payload/scale
    trees are FlowState-typed (head granularity, ``z`` exempt).  The
    low-bit payloads go straight into the kernel, which dequantizes in
    VMEM, accumulates in fp32 and requantizes with a fresh per-(slot,
    head) amax on the in-place write.  Returns (new_pool, out).
    """
    from repro.kernels.flow_decode.quant import flow_decode_q_call

    assert pool.granularity == "head" and pool.exempt == ("z",), (
        "flow_decode_q_step expects the serving FlowState pool recipe "
        f"(head granularity, z exempt); got {pool.granularity!r}/"
        f"{pool.exempt!r}")
    st, sc = pool.payload, pool.scale
    b, hq, one, d = q.shape
    assert one == 1, "decode_step consumes exactly one position"
    hkv = k.shape[1]
    dv = v.shape[-1]
    bh = b * hkv

    t = st.t + 1  # (B,) int32, per-slot position counts
    qg, k2, v2 = _rows(q, k, v)
    rows = lambda x: x.reshape(bh, 1, -1)  # noqa: E731 — (BH, 1, X) rows

    out, pays, s_pay, scs, s_sc, z = flow_decode_q_call(
        _row_t(t, hkv), qg, k2, v2,
        (rows(st.k_sum), rows(st.q_sum), rows(st.ko_sum), rows(st.qi_sum)),
        st.s.reshape(bh, d, dv),
        (rows(sc.k_sum), rows(sc.q_sum), rows(sc.ko_sum), rows(sc.qi_sum)),
        rows(sc.s), rows(st.z),
        eps=cfg.eps, phi=cfg.phi, use_allocation=cfg.use_allocation,
        qmax=pool.spec.qmax, is_int=pool.spec.name == "int8",
        interpret=interpret,
    )
    new_payload = FlowState(
        t=t,
        q_sum=pays[1].reshape(b, hkv, d),
        k_sum=pays[0].reshape(b, hkv, d),
        ko_sum=pays[2].reshape(b, hkv, d),
        qi_sum=pays[3].reshape(b, hkv, d),
        z=z.reshape(b, hkv),
        s=s_pay.reshape(b, hkv, d, dv),
    )
    new_scale = FlowState(
        t=sc.t,  # unit scales for the integer / exempt leaves carry over
        q_sum=scs[1].reshape(b, hkv, 1),
        k_sum=scs[0].reshape(b, hkv, 1),
        ko_sum=scs[2].reshape(b, hkv, 1),
        qi_sum=scs[3].reshape(b, hkv, 1),
        z=sc.z,
        s=s_sc.reshape(b, hkv, 1, 1),
    )
    return (pool.with_state(new_payload, new_scale),
            out.reshape(b, hq, 1, dv).astype(q.dtype))
