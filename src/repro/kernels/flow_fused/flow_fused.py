"""Pallas TPU kernel: the WHOLE strict-causal Flow-Attention pipeline.

This kernel runs paper Alg. 2 (the math of ``attention/fused.py``) as one
scan over the Pallas grid whose carry is the O(d^2) ``FlowState``.  Per
(batch*kv_head, chunk) grid step the kernel computes

    k/q running sums -> sink_in, src_out          (chunk cumsums + carry)
    ko/qi running sums -> cons_sink, cons_src     (conservation, Eq. 7)
    e = exp(clip(cons_src)); z += cumsum(e)       (cumulative competition)
    out_c = [tril(Q'_c K_c^T) (V_c e) + Q'_c S] * (pos/z) * alloc
    S += K_c^T (V_c e)

with the six running quantities — four (1, D) flow sums, the (1, 1)
competition normalizer ``z`` and the (D, Dv) aggregation state ``S`` —
carried in VMEM scratch across the sequential chunk axis.  HBM traffic is
one read of q/k/v and one write of out plus the O(d^2) state outputs;
every intermediate is chunk-sized.  Chunk-local inclusive cumsums are
``tril @ x`` matmuls so the identical step function differentiates cleanly
under ``jax.vjp`` inside the backward kernel (``bwd.py``).

Per-row validity is a (BH,) int32 ``lens`` scalar-prefetch operand (SMEM):
positions past a row's
length contribute ZERO to phi_q/phi_k/e, so every running sum freezes at
the boundary and the final carry IS that row's boundary ``FlowState`` —
one mechanism serves both tail padding (awkward lengths) and right-padded
packed prefill, with no gathers anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _phi(x, kind: str):
    # local mirror of core.flow_attention.phi_map: this module must stay
    # import-light (attention/vjp.py loads it mid-way through the
    # repro.attention package init); parity with the core map is pinned by
    # tests/test_flow_fused.py across all three kinds
    if kind == "sigmoid":
        return jax.nn.sigmoid(x)
    if kind == "elu1":
        return jax.nn.elu(x) + 1.0
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown phi {kind!r}")


def state_specs(d: int, dv: int, index_map):
    """Blocks of the six per-row FlowState pieces, in carry order.

    The four flow sums travel as (BH, 1, D) and ``z`` as (BH, 1, 1) so each
    block's last two dims equal the array's (the TPU tiling rule); ``s``
    is (BH, D, Dv).  ``index_map`` maps grid indices to (row, 0, 0).
    """
    return ([pl.BlockSpec((1, 1, d), index_map)] * 4
            + [pl.BlockSpec((1, 1, 1), index_map),
               pl.BlockSpec((1, d, dv), index_map)])


def state_shapes(bh: int, d: int, dv: int):
    """f32 array shapes matching ``state_specs``."""
    f32 = jnp.float32
    return ([jax.ShapeDtypeStruct((bh, 1, d), f32)] * 4
            + [jax.ShapeDtypeStruct((bh, 1, 1), f32),
               jax.ShapeDtypeStruct((bh, d, dv), f32)])


def _chunk_step(runs, qc, kc, vc, *, pos, valid, ltri, eps: float, phi: str,
                use_alloc: bool, grp: int):
    """One fused chunk of paper Alg. 2 (strict-causal), pure jnp.

    ``runs`` is the carried state BEFORE this chunk:
        (q_run (1,D), k_run (1,D), ko_run (1,D), qi_run (1,D),
         z_run (1,1), s (D,Dv))
    qc: (G, C, D) raw queries; kc: (C, D); vc: (C, Dv); pos: (C, 1) f32
    1-based global positions; valid: (C, 1) f32 in-row mask; ltri: (C, C)
    lower-triangular ones.  Returns (new_runs, out (G, C, Dv)).

    The forward kernel runs this with scratch refs as ``runs``; the
    backward kernel re-runs it under ``jax.vjp`` per reverse chunk, so it
    must stay a pure function of its arguments.
    """
    q_run, k_run, ko_run, qi_run, z_run, s = runs
    f32 = jnp.float32
    pq = _phi(qc.astype(f32), phi) * valid  # (G, C, D); masked past end
    pk = _phi(kc.astype(f32), phi) * valid  # (C, D)
    vf = vc.astype(f32)  # (C, Dv)
    normal_k = pos  # sources seen up to position i   (C, 1)
    normal_q = pos * float(grp)  # sinks seen (G per position)

    def csum(x):  # chunk-local inclusive cumsum as a tril matmul
        return jax.lax.dot_general(
            ltri, x, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )

    # (1) flows from carried sums + chunk-local inclusive cumsums
    k_csum = k_run + csum(pk)  # (C, D)
    q_csum = q_run + csum(pq.sum(axis=0))  # (C, D)
    sink_in = normal_k[None] / jnp.sum(
        (pq + eps) * (k_csum[None] + eps), axis=-1, keepdims=True
    )  # (G, C, 1)
    src_out = normal_q / jnp.sum(
        (pk + eps) * (q_csum + eps), axis=-1, keepdims=True
    )  # (C, 1)

    # (2) conservation refinement
    ko_csum = ko_run + csum(pk * src_out)  # (C, D)
    cons_sink = jnp.sum(
        (pq + eps) * (ko_csum[None] + eps), axis=-1, keepdims=True
    ) / normal_q[None]  # (G, C, 1)
    qi_csum = qi_run + csum((pq * sink_in).sum(axis=0))  # (C, D)
    cons_src = jnp.clip(
        jnp.sum((pk + eps) * (qi_csum + eps), axis=-1, keepdims=True)
        / normal_k,
        -1.0,
        1.0,
    )  # (C, 1)

    # (3) cumulative competition + allocation.  e is masked so z freezes at
    # each row's boundary along with the sums.
    if use_alloc:
        alloc = jax.nn.sigmoid(cons_sink)
    else:
        alloc = jnp.ones_like(cons_sink)
    e = jnp.exp(cons_src) * valid  # in [1/e, e]: no running-max needed
    z = z_run + csum(e)  # (C, 1)
    v_w = vf * e  # (C, Dv)

    # (4) aggregation: intra-chunk tril matmul + carried (D, Dv) state.
    # The dots run on (G*C, .) panels: Mosaic contracts 2-D operands only,
    # and the backward's jax.vjp transposes them into 2-D dots too.
    g, c, d = pq.shape
    q_in = (pq * sink_in).reshape(g * c, d)
    scores = jax.lax.dot_general(
        q_in, pk, (((1,), (1,)), ((), ())), preferred_element_type=f32
    )  # (G*C, C)
    scores = (scores.reshape(g, c, c) * ltri).reshape(g * c, c)
    intra = jax.lax.dot_general(
        scores, v_w, (((1,), (0,)), ((), ())), preferred_element_type=f32,
    )  # (G*C, Dv)
    inter = jax.lax.dot_general(
        q_in, s, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # (G*C, Dv)
    out = ((intra + inter).reshape(g, c, -1) * (normal_k / z)[None]
           * alloc)

    new_runs = (
        q_csum[-1:],
        k_csum[-1:],
        ko_csum[-1:],
        qi_csum[-1:],
        z[-1:],
        s + jax.lax.dot_general(
            pk, v_w, (((0,), (0,)), ((), ())), preferred_element_type=f32
        ),
    )
    return new_runs, out


def _positions(lens_ref, ci, chunk: int):
    """(pos, valid), each (C, 1) f32: 1-based global positions of chunk
    ``ci`` and the in-row mask against this row's SMEM length."""
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) + 1
    valid = (pos <= lens_ref[pl.program_id(0)]).astype(jnp.float32)
    return pos.astype(jnp.float32), valid


def _fwd_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, qs_ref, ks_ref,
                kos_ref, qis_ref, zo_ref, so_ref, q_run, k_run, ko_run,
                qi_run, z_run, s_run, *, chunk: int, eps: float, phi: str,
                use_alloc: bool, grp: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        for ref in (q_run, k_run, ko_run, qi_run, z_run, s_run):
            ref[...] = jnp.zeros_like(ref)

    pos, valid = _positions(lens_ref, ci, chunk)
    ltri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))

    runs = (q_run[...], k_run[...], ko_run[...], qi_run[...], z_run[...],
            s_run[...])
    new_runs, out = _chunk_step(
        runs, q_ref[0], k_ref[0], v_ref[0], pos=pos, valid=valid, ltri=ltri,
        eps=eps, phi=phi, use_alloc=use_alloc, grp=grp,
    )
    o_ref[0] = out.astype(o_ref.dtype)
    for ref, val in zip((q_run, k_run, ko_run, qi_run, z_run, s_run),
                        new_runs):
        ref[...] = val
    # state outputs: fixed blocks, rewritten every chunk — the final
    # (sequential) write is the boundary FlowState
    for ref, val in zip((qs_ref, ks_ref, kos_ref, qis_ref, zo_ref, so_ref),
                        new_runs):
        ref[0] = val


def flow_fused_call(
    q: Array, k: Array, v: Array, lens: Array, *, chunk: int = 128,
    eps: float = 1e-6, phi: str = "sigmoid", use_alloc: bool = True,
    interpret: bool = False,
):
    """Fused strict-causal Flow-Attention over a chunk-padded batch.

    q: (BH, G, N, D) raw; k: (BH, N, D); v: (BH, N, Dv); lens: (BH,) int32
    per-row valid lengths (1 <= lens <= N); N % chunk == 0.
    Returns (out (BH, G, N, Dv),
             (q_sum, k_sum, ko_sum, qi_sum) each (BH, D) f32,
             z (BH, 1) f32, s (BH, D, Dv) f32) — the boundary FlowState
    pieces, frozen at each row's own length.
    """
    bh, grp, n, d = q.shape
    dv = v.shape[-1]
    assert n % chunk == 0, (n, chunk)
    nc = n // chunk
    f32 = jnp.float32
    state = state_specs(d, dv, lambda b, c, lens: (b, 0, 0))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, eps=eps, phi=phi,
                          use_alloc=use_alloc, grp=grp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nc),
            in_specs=[
                pl.BlockSpec((1, grp, chunk, d),
                             lambda b, c, lens: (b, 0, c, 0)),
                pl.BlockSpec((1, chunk, d), lambda b, c, lens: (b, c, 0)),
                pl.BlockSpec((1, chunk, dv), lambda b, c, lens: (b, c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, grp, chunk, dv),
                             lambda b, c, lens: (b, 0, c, 0)),
                *state,
            ],
            scratch_shapes=[pltpu.VMEM((1, d), f32)] * 4 + [
                pltpu.VMEM((1, 1), f32), pltpu.VMEM((d, dv), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((bh, grp, n, dv), q.dtype),
                   *state_shapes(bh, d, dv)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(lens.astype(jnp.int32), q, k, v)
    out, q_sum, k_sum, ko_sum, qi_sum, z, s = outs
    return out, (q_sum.reshape(bh, d), k_sum.reshape(bh, d),
                 ko_sum.reshape(bh, d), qi_sum.reshape(bh, d),
                 z.reshape(bh, 1), s)
