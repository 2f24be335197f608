"""Pallas TPU kernel: one quantized Flow-Attention decode step.

The quantized serving pools (``serving/quant.py``) store the FlowState's
four flow sums and the (D, Dv) aggregation panel as int8 / fp8 payloads
with one fp32 scale per (slot, kv head) leaf.  This kernel keeps the low
bit-width all the way to VMEM: each program loads its pair's *payload*
rows from HBM (1/4 the bytes of the fp32 pool), dequantizes in VMEM,
runs the identical fp32 recurrence as ``flow_decode.py``, then
requantizes with a fresh per-program amax before the in-place write.
HBM traffic per step is therefore one low-bit read + one low-bit write
of the pool — the bandwidth saving IS the speedup, since this op is
purely memory-bound.

Same aliasing contract as the full-precision kernel: every payload and
scale input aliases its output, so the pool updates in place and a
decode step allocates nothing per token.

The competition normalizer ``z`` stays raw fp32 (it is a monotone
running sum — quantizing it would accumulate rounding into every future
denominator); it is (BH, 1, 1), so its bytes are noise next to the panel.

Layout is the full-precision kernel's: per-row vectors and scales carry a
unit middle axis ((BH, 1, D), (BH, 1, 1)) so each one-row block's last two
dims equal the array's, and ``t`` arrives by scalar prefetch in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flow_attention import phi_map

Array = jax.Array
_SCALE_EPS = 1e-12  # matches serving.quant's amax floor


def _requant(x, qmax: float, is_int: bool, dtype):
    """Fresh-amax quantize of one state leaf inside the program."""
    amax = jnp.max(jnp.abs(x))
    sc = jnp.maximum(amax, _SCALE_EPS) / qmax
    y = x / sc
    if is_int:
        payload = jnp.clip(jnp.rint(y), -qmax, qmax).astype(dtype)
    else:
        payload = jnp.clip(y, -qmax, qmax).astype(dtype)
    return payload, sc


def _kernel(t_ref, q_ref, k_ref, v_ref,
            ksum_p, qsum_p, kosum_p, qisum_p, s_p,
            ksum_s, qsum_s, kosum_s, qisum_s, s_s, z_ref,
            out_ref,
            ksum_po, qsum_po, kosum_po, qisum_po, s_po,
            ksum_so, qsum_so, kosum_so, qisum_so, s_so, z_o,
            *, g: int, eps: float, phi: str, use_allocation: bool,
            qmax: float, is_int: bool):
    tf = t_ref[pl.program_id(0)].astype(jnp.float32)  # t+1 for this row

    # dequantize this (slot, head)'s state in VMEM: payload * scale
    deq = lambda p_ref, s_ref: p_ref[0].astype(jnp.float32) * s_ref[0]  # noqa: E731
    ksum = deq(ksum_p, ksum_s)  # (1, D)
    qsum = deq(qsum_p, qsum_s)
    kosum = deq(kosum_p, kosum_s)
    qisum = deq(qisum_p, qisum_s)
    s_in = deq(s_p, s_s)  # (D, Dv)

    phi_q = phi_map(q_ref[0].astype(jnp.float32), phi)  # (G, D)
    phi_k = phi_map(k_ref[0].astype(jnp.float32), phi)  # (1, D)
    vf = v_ref[0].astype(jnp.float32)  # (1, Dv)

    normal_k = tf  # sources seen so far
    normal_q = tf * g  # sinks seen so far (G per position)

    # fp32 accumulation, term for term the full-precision kernel's math
    k_sum = ksum + phi_k  # (1, D)
    q_sum = qsum + jnp.sum(phi_q, axis=0, keepdims=True)

    sink_in = normal_k / jax.lax.dot_general(
        phi_q + eps, k_sum + eps, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, 1)
    src_out = normal_q / jnp.sum((phi_k + eps) * (q_sum + eps))  # scalar

    ko_sum = kosum + phi_k * src_out
    cons_sink = jax.lax.dot_general(
        phi_q + eps, ko_sum + eps, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / normal_q  # (G, 1)

    q_in = phi_q * sink_in  # value-normalized queries (G, D)
    qi_sum = qisum + jnp.sum(q_in, axis=0, keepdims=True)
    cons_src = jnp.sum((phi_k + eps) * (qi_sum + eps)) / normal_k
    cons_src = jnp.clip(cons_src, -1.0, 1.0)

    alloc = jax.nn.sigmoid(cons_sink) if use_allocation else 1.0

    e = jnp.exp(cons_src)  # bounded in [1/e, e] by the clamp
    z = z_ref[0] + e  # (1, 1)
    s = s_in + jax.lax.dot_general(
        phi_k, vf * e, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (D, Dv)

    agg = jax.lax.dot_general(
        q_in, s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, Dv)
    out_ref[0] = (agg * (normal_k / z[0, 0]) * alloc).astype(out_ref.dtype)

    # requantize each leaf with a fresh amax before the in-place write
    for val, p_out, s_out in (
        (k_sum, ksum_po, ksum_so), (q_sum, qsum_po, qsum_so),
        (ko_sum, kosum_po, kosum_so), (qi_sum, qisum_po, qisum_so),
    ):
        payload, sc = _requant(val, qmax, is_int, p_out.dtype)
        p_out[0] = payload
        s_out[0] = jnp.reshape(sc, (1, 1))
    s_payload, s_sc = _requant(s, qmax, is_int, s_po.dtype)
    s_po[0] = s_payload
    s_so[0] = jnp.reshape(s_sc, (1, 1))
    z_o[0] = z


def flow_decode_q_call(
    t: Array, q: Array, k: Array, v: Array,
    sum_payloads, s_payload: Array, sum_scales, s_scale: Array, z: Array,
    *, eps: float, phi: str, use_allocation: bool,
    qmax: float, is_int: bool, interpret: bool = False,
):
    """One quantized decode step over the flattened (BH) state pool.

    ``sum_payloads`` / ``sum_scales`` — 4-tuples (k, q, ko, qi order);
    payloads (BH, 1, D) low-bit, scales (BH, 1, 1) f32, s payload
    (BH, D, Dv), s scale (BH, 1, 1), z (BH, 1, 1) raw f32; ``t`` (BH,)
    int32 and q/k/v as in ``flow_decode_call``.  Returns
    (out, (payloads...), s_payload, (scales...), s_scale, z) with every
    state buffer updated in place (aliased).
    """
    bh, g, d = q.shape
    dv = v.shape[-1]
    row = lambda b, t: (b, 0, 0)  # noqa: E731 — one (slot, head) row
    qdt = sum_payloads[0].dtype
    f32 = jnp.float32
    pay_specs = [pl.BlockSpec((1, 1, d), row)] * 4 + [
        pl.BlockSpec((1, d, dv), row)]
    pay_shapes = [jax.ShapeDtypeStruct((bh, 1, d), qdt)] * 4 + [
        jax.ShapeDtypeStruct((bh, d, dv), qdt)]
    sc_specs = [pl.BlockSpec((1, 1, 1), row)] * 5
    sc_shapes = [jax.ShapeDtypeStruct((bh, 1, 1), f32)] * 5
    z_spec = pl.BlockSpec((1, 1, 1), row)
    res = pl.pallas_call(
        functools.partial(_kernel, g=g, eps=eps, phi=phi,
                          use_allocation=use_allocation,
                          qmax=qmax, is_int=is_int),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, g, d), row),
                pl.BlockSpec((1, 1, d), row),
                pl.BlockSpec((1, 1, dv), row),
                *pay_specs, *sc_specs, z_spec,
            ],
            out_specs=[pl.BlockSpec((1, g, dv), row), *pay_specs, *sc_specs,
                       z_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, g, dv), q.dtype), *pay_shapes,
                   *sc_shapes, jax.ShapeDtypeStruct((bh, 1, 1), f32)],
        # payload inputs 4..8 -> outputs 1..5, scale inputs 9..13 ->
        # outputs 6..10, z input 14 -> output 11: the whole quantized
        # pool updates in place
        input_output_aliases={4: 1, 5: 2, 6: 3, 7: 4, 8: 5, 9: 6, 10: 7,
                              11: 8, 12: 9, 13: 10, 14: 11},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(t.astype(jnp.int32), q, k, v, *sum_payloads, s_payload, *sum_scales,
      s_scale, z)
    return (res[0], tuple(res[1:5]), res[5], tuple(res[6:10]), res[10],
            res[11])
