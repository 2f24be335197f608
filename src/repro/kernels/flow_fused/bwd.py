"""Reverse-scan Pallas backward for the fused strict-causal kernel.

The forward saves NOTHING (B, H, N)-sized: residuals are q/k/v (re-read),
``lens``, and the six FINAL carry totals.  Walking chunks back-to-front,
each step first reconstructs the carry that ENTERED the chunk as

    carry_in = total - suffix - own_increment

where ``suffix`` accumulates the increments of the chunks already visited
(i.e. later in forward order) in VMEM scratch, and the chunk's own
increments are recomputed in dependency order (k/q sums are carry-free;
sink_in/src_out then unlock the ko/qi/z/s increments).  With the carry-in
in hand, ``jax.vjp`` of the SAME ``_chunk_step`` the forward ran pulls the
output cotangent plus the carried state cotangent back onto (carry_in,
q, k, v) — so forward and backward can never drift apart.  The six state
cotangents (for the FlowState outputs) seed the carried cotangent at the
last chunk.  All reconstruction is exact up to fp32 reassociation: the
four flow sums are sums of nonnegative phi terms, e is clip-bounded to
[1/e, e], so the subtractions lose no significant bits at chunked scales.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flow_fused import (_chunk_step, _phi as phi_map, _positions,
                         state_specs)

Array = jax.Array


def _bwd_kernel(
    lens_ref, q_ref, k_ref, v_ref,
    tq_ref, tk_ref, tko_ref, tqi_ref, tz_ref, ts_ref,
    go_ref, gq_ref, gk_ref, gko_ref, gqi_ref, gz_ref, gs_ref,
    dq_ref, dk_ref, dv_ref,
    q_suf, k_suf, ko_suf, qi_suf, z_suf, s_suf,
    dq_c, dk_c, dko_c, dqi_c, dz_c, ds_c,
    *, nc: int, chunk: int, eps: float, phi: str, use_alloc: bool,
    grp: int,
):
    r = pl.program_id(1)
    ci = nc - 1 - r  # forward chunk index

    @pl.when(r == 0)
    def _init():
        for ref in (q_suf, k_suf, ko_suf, qi_suf, z_suf, s_suf):
            ref[...] = jnp.zeros_like(ref)
        # carried state cotangent starts from the FlowState output grads
        for c_ref, g_ref in zip((dq_c, dk_c, dko_c, dqi_c, dz_c, ds_c),
                                (gq_ref, gk_ref, gko_ref, gqi_ref, gz_ref,
                                 gs_ref)):
            c_ref[...] = g_ref[0]

    f32 = jnp.float32
    pos, valid = _positions(lens_ref, ci, chunk)
    ltri = jnp.tril(jnp.ones((chunk, chunk), f32))
    normal_k = pos
    normal_q = pos * float(grp)

    def csum(x):
        return jax.lax.dot_general(
            ltri, x, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )

    qc = q_ref[0].astype(f32)
    kc = k_ref[0].astype(f32)
    vc = v_ref[0].astype(f32)
    pq = phi_map(qc, phi) * valid
    pk = phi_map(kc, phi) * valid

    # --- reconstruct the carry that entered this chunk ------------------
    k_inc = jnp.sum(pk, axis=0, keepdims=True)  # (1, D)
    q_inc = jnp.sum(pq.sum(axis=0), axis=0, keepdims=True)
    k_run = tk_ref[0] - k_suf[...] - k_inc
    q_run = tq_ref[0] - q_suf[...] - q_inc
    k_csum = k_run + csum(pk)
    q_csum = q_run + csum(pq.sum(axis=0))
    sink_in = normal_k[None] / jnp.sum(
        (pq + eps) * (k_csum[None] + eps), axis=-1, keepdims=True
    )
    src_out = normal_q / jnp.sum(
        (pk + eps) * (q_csum + eps), axis=-1, keepdims=True
    )
    ko_inc = jnp.sum(pk * src_out, axis=0, keepdims=True)
    qi_inc = jnp.sum(
        (pq * sink_in).sum(axis=0), axis=0, keepdims=True
    )
    ko_run = tko_ref[0] - ko_suf[...] - ko_inc
    qi_run = tqi_ref[0] - qi_suf[...] - qi_inc
    qi_csum = qi_run + csum((pq * sink_in).sum(axis=0))
    cons_src = jnp.clip(
        jnp.sum((pk + eps) * (qi_csum + eps), axis=-1, keepdims=True)
        / normal_k,
        -1.0,
        1.0,
    )
    e = jnp.exp(cons_src) * valid
    z_inc = jnp.sum(e, axis=0, keepdims=True)  # (1, 1)
    z_run = tz_ref[0] - z_suf[...] - z_inc
    s_inc = jax.lax.dot_general(
        pk, vc * e, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )
    s_run = ts_ref[0] - s_suf[...] - s_inc

    # suffix now absorbs this chunk for the next (earlier) reverse step
    q_suf[...] += q_inc
    k_suf[...] += k_inc
    ko_suf[...] += ko_inc
    qi_suf[...] += qi_inc
    z_suf[...] += z_inc
    s_suf[...] += s_inc

    # --- pull cotangents through the forward chunk step -----------------
    runs_in = (q_run, k_run, ko_run, qi_run, z_run, s_run)

    def step(runs, qx, kx, vx):
        return _chunk_step(
            runs, qx, kx, vx, pos=pos, valid=valid, ltri=ltri, eps=eps,
            phi=phi, use_alloc=use_alloc, grp=grp,
        )

    _, pull = jax.vjp(step, runs_in, qc, kc, vc)
    d_carry = (dq_c[...], dk_c[...], dko_c[...], dqi_c[...], dz_c[...],
               ds_c[...])
    d_runs_in, dq, dk, dv = pull((d_carry, go_ref[0].astype(f32)))

    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dq_c[...] = d_runs_in[0]
    dk_c[...] = d_runs_in[1]
    dko_c[...] = d_runs_in[2]
    dqi_c[...] = d_runs_in[3]
    dz_c[...] = d_runs_in[4]
    ds_c[...] = d_runs_in[5]


def flow_fused_bwd_call(
    q: Array, k: Array, v: Array, lens: Array, totals, g_out: Array,
    g_sums, *, chunk: int = 128, eps: float = 1e-6, phi: str = "sigmoid",
    use_alloc: bool = True, interpret: bool = False,
):
    """Gradients of ``flow_fused_call`` w.r.t. (q, k, v).

    ``totals``/``g_sums`` are the six forward state outputs and their
    cotangents, each (BH, D) / (BH, 1) / (BH, D, Dv) f32.  ``lens`` (BH,)
    int32 rides in SMEM by scalar prefetch.  Returns
    (dq, dk, dv) with the primal dtypes.
    """
    bh, grp, n, d = q.shape
    dv_dim = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk
    f32 = jnp.float32

    def rev_g(b, r, lens):
        return (b, 0, nc - 1 - r, 0)

    def rev(b, r, lens):
        return (b, nc - 1 - r, 0)

    def as_rows(sums):  # (BH, D)/(BH, 1) -> the kernel's (BH, 1, X) rows
        return [x if x.ndim == 3 else x.reshape(bh, 1, x.shape[-1])
                for x in sums]

    state = state_specs(d, dv_dim, lambda b, r, lens: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nc=nc, chunk=chunk, eps=eps,
                          phi=phi, use_alloc=use_alloc, grp=grp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nc),
            in_specs=[
                pl.BlockSpec((1, grp, chunk, d), rev_g),
                pl.BlockSpec((1, chunk, d), rev),
                pl.BlockSpec((1, chunk, dv_dim), rev),
                *state,
                pl.BlockSpec((1, grp, chunk, dv_dim), rev_g),
                *state,
            ],
            out_specs=[
                pl.BlockSpec((1, grp, chunk, d), rev_g),
                pl.BlockSpec((1, chunk, d), rev),
                pl.BlockSpec((1, chunk, dv_dim), rev),
            ],
            scratch_shapes=2 * ([pltpu.VMEM((1, d), f32)] * 4 + [
                pltpu.VMEM((1, 1), f32), pltpu.VMEM((d, dv_dim), f32)]),
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(lens.astype(jnp.int32), q, k, v, *as_rows(totals), g_out,
      *as_rows(g_sums))
