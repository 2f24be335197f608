"""Pallas TPU kernel: one batched Flow-Attention decode step.

Serving's hot loop advances every live slot's O(d^2) recurrent ``FlowState``
by one token (paper Alg. 2 position t+1, the recurrence in
``repro/attention/recurrent.py``).  This kernel runs the WHOLE slot pool in
one grid launch: grid = (slots * Hkv,), one program per (slot, kv head),
with that pair's entire state — the (D, Dv) aggregation panel plus the four
(D,) flow sums and the competition normalizer — resident in VMEM for the
duration of the program.  HBM traffic is one read + one write of the state
pool and one read of q/k/v per step, which is the information-theoretic
floor for this op.

State arrays are aliased input->output (``input_output_aliases``) so the
pool updates in place: a decode step allocates nothing per token, which is
what lets the serving Worker keep thousands of slots device-resident.

Shapes (BH = slots * Hkv, G = grouped query heads per kv head).  Per-row
vectors carry a unit middle axis so every block's last two dims equal the
array's, as the TPU tiling rule requires of a one-row block:

    t           (BH,)    i32  position count AFTER this token (t+1);
                              scalar prefetch, so it lives in SMEM
    q           (BH, G, D)    raw (pre-phi) grouped queries
    k           (BH, 1, D)    raw key
    v           (BH, 1, Dv)   value
    k/q/ko/qi_sum (BH, 1, D) f32 running flow sums     (aliased in-place)
    z           (BH, 1, 1) f32 competition normalizer  (aliased in-place)
    s           (BH, D, Dv) f32 aggregation state      (aliased in-place)
    out         (BH, G, Dv)   attention output for this token

The math mirrors ``recurrent.decode_step`` term for term (including eps
placement and the official [-1, 1] clamp); tests assert parity over long
slot-churn traces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.flow_attention import phi_map

Array = jax.Array


def _kernel(t_ref, q_ref, k_ref, v_ref, ksum_ref, qsum_ref, kosum_ref,
            qisum_ref, z_ref, s_ref,
            out_ref, ksum_o, qsum_o, kosum_o, qisum_o, z_o, s_o,
            *, g: int, eps: float, phi: str, use_allocation: bool):
    tf = t_ref[pl.program_id(0)].astype(jnp.float32)  # t+1 for this row

    phi_q = phi_map(q_ref[0].astype(jnp.float32), phi)  # (G, D)
    phi_k = phi_map(k_ref[0].astype(jnp.float32), phi)  # (1, D)
    vf = v_ref[0].astype(jnp.float32)  # (1, Dv)

    normal_k = tf  # sources seen so far
    normal_q = tf * g  # sinks seen so far (G per position)

    k_sum = ksum_ref[0] + phi_k  # (1, D)
    q_sum = qsum_ref[0] + jnp.sum(phi_q, axis=0, keepdims=True)

    sink_in = normal_k / jax.lax.dot_general(
        phi_q + eps, k_sum + eps, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, 1)
    src_out = normal_q / jnp.sum((phi_k + eps) * (q_sum + eps))  # scalar

    ko_sum = kosum_ref[0] + phi_k * src_out
    cons_sink = jax.lax.dot_general(
        phi_q + eps, ko_sum + eps, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) / normal_q  # (G, 1)

    q_in = phi_q * sink_in  # value-normalized queries (G, D)
    qi_sum = qisum_ref[0] + jnp.sum(q_in, axis=0, keepdims=True)
    cons_src = jnp.sum((phi_k + eps) * (qi_sum + eps)) / normal_k
    cons_src = jnp.clip(cons_src, -1.0, 1.0)

    alloc = jax.nn.sigmoid(cons_sink) if use_allocation else 1.0

    e = jnp.exp(cons_src)  # bounded in [1/e, e] by the clamp
    z = z_ref[0] + e  # (1, 1)
    s = s_ref[0] + jax.lax.dot_general(
        phi_k, vf * e, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (D, Dv)

    agg = jax.lax.dot_general(
        q_in, s, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, Dv)
    out_ref[0] = (agg * (normal_k / z[0, 0]) * alloc).astype(out_ref.dtype)

    for ref, val in zip((ksum_o, qsum_o, kosum_o, qisum_o, z_o, s_o),
                        (k_sum, q_sum, ko_sum, qi_sum, z, s)):
        ref[0] = val


def flow_decode_call(
    t: Array, q: Array, k: Array, v: Array,
    k_sum: Array, q_sum: Array, ko_sum: Array, qi_sum: Array,
    z: Array, s: Array,
    *, eps: float, phi: str, use_allocation: bool, interpret: bool = False,
):
    """One decode step over the flattened (BH = slots*Hkv) state pool.

    Shapes as in the module docstring.  Returns (out (BH, G, Dv), k_sum,
    q_sum, ko_sum, qi_sum, z, s) with the six state arrays updated in
    place (aliased buffers).
    """
    bh, g, d = q.shape
    dv = v.shape[-1]
    row = lambda b, t: (b, 0, 0)  # noqa: E731 — one (slot, head) row
    state_specs = [pl.BlockSpec((1, 1, d), row)] * 4 + [
        pl.BlockSpec((1, 1, 1), row), pl.BlockSpec((1, d, dv), row)]
    f32 = jnp.float32
    state_shapes = [jax.ShapeDtypeStruct((bh, 1, d), f32)] * 4 + [
        jax.ShapeDtypeStruct((bh, 1, 1), f32),
        jax.ShapeDtypeStruct((bh, d, dv), f32)]
    return pl.pallas_call(
        functools.partial(_kernel, g=g, eps=eps, phi=phi,
                          use_allocation=use_allocation),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh,),
            in_specs=[
                pl.BlockSpec((1, g, d), row),
                pl.BlockSpec((1, 1, d), row),
                pl.BlockSpec((1, 1, dv), row),
                *state_specs,
            ],
            out_specs=[pl.BlockSpec((1, g, dv), row), *state_specs],
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, g, dv), q.dtype), *state_shapes],
        # state inputs 4..9 alias state outputs 1..6: the pool is updated
        # in place, no per-token allocation
        input_output_aliases={4: 1, 5: 2, 6: 3, 7: 4, 8: 5, 9: 6},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(t.astype(jnp.int32), q, k, v, k_sum, q_sum, ko_sum, qi_sum, z, s)
