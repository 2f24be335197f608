"""Pallas page-table gather for the paged-KV serving hot path.

``layers/attention.py`` decode used to materialize the logical per-slot
cache with an XLA gather ``kc[page_table]`` followed by a transpose +
reshape — three HBM round-trips over the whole gathered cache per decode
step.  Here the page table rides the grid as a scalar-prefetch operand:
block ``(b, j)`` of the output is fetched straight from pool page
``table[b, j]``, already laid out as the (B, Hkv, MP*page, D) sequence
the attention kernel wants.  One pass, no transpose.

Sentinel page ids (== num_pages) clip into an arbitrary real page, same
as the XLA gather's clamp; callers mask the tail via ``kv_len``.  The
``*_xla`` functions are that XLA gather, for platforms without the kernel;
the caller picks one by platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _kernel(tbl_ref, k_ref, v_ref, ko_ref, vo_ref):
    del tbl_ref  # only consumed by the index maps
    ko_ref[...] = k_ref[...]
    vo_ref[...] = v_ref[...]


def _flat_pages(pool: Array, table: Array) -> Array:
    """XLA gather of ``table``'s (clamped) pages as (B, Hkv, MP*page, X)."""
    p, hkv, page, x = pool.shape
    b, mp = table.shape
    g = pool[jnp.clip(table, 0, p - 1)]  # (B, MP, Hkv, page, X)
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mp * page, x)


def paged_gather_xla(kc: Array, vc: Array, table: Array):
    """``paged_gather`` as a plain XLA gather (same clamped semantics)."""
    return _flat_pages(kc, table), _flat_pages(vc, table)


def paged_gather_quant_xla(kc: Array, vc: Array, ks: Array, vs: Array,
                           table: Array, *, out_dtype):
    """``paged_gather_quant`` as XLA: dequantize the pools, then gather."""
    def deq(pool, spool):
        return (pool.astype(jnp.float32) * spool).astype(out_dtype)
    return paged_gather_xla(deq(kc, ks), deq(vc, vs), table)


def paged_gather(kc: Array, vc: Array, table: Array, *,
                 interpret: bool = False) -> tuple[Array, Array]:
    """Gather pool pages into per-slot sequences.

    kc/vc: (P, Hkv, page, D|Dv) pools; table: (B, MP) int32 page ids.
    Returns (kg, vg) shaped (B, Hkv, MP*page, D|Dv).  ``interpret`` runs
    the kernel in the Pallas interpreter (off-TPU)."""
    p, hkv, page, d = kc.shape
    dv = vc.shape[-1]
    b, mp = table.shape

    def src(b_, j, tbl):
        return (jnp.clip(tbl[b_, j], 0, p - 1), 0, 0, 0)

    def dst(b_, j, tbl):
        return (b_, 0, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, page, d), src),
            pl.BlockSpec((1, hkv, page, dv), src),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, page, d), dst),
            pl.BlockSpec((1, hkv, page, dv), dst),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, mp * page, d), kc.dtype),
            jax.ShapeDtypeStruct((b, hkv, mp * page, dv), vc.dtype),
        ],
        interpret=interpret,
    )(table.astype(jnp.int32), kc, vc)


def _kernel_quant(tbl_ref, k_ref, v_ref, ks_ref, vs_ref, ko_ref, vo_ref):
    del tbl_ref  # only consumed by the index maps
    # dequantize inline: the HBM read is the low-bit payload plus one
    # scale column per token row; fp32 multiply happens in VMEM
    ko_ref[...] = (k_ref[...].astype(jnp.float32)
                   * ks_ref[...]).astype(ko_ref.dtype)
    vo_ref[...] = (v_ref[...].astype(jnp.float32)
                   * vs_ref[...]).astype(vo_ref.dtype)


def paged_gather_quant(kc: Array, vc: Array, ks: Array, vs: Array,
                       table: Array, *, out_dtype,
                       interpret: bool = False) -> tuple[Array, Array]:
    """Gather + dequantize quantized pool pages into per-slot sequences.

    kc/vc: (P, Hkv, page, D|Dv) low-bit payload pools; ks/vs:
    (P, Hkv, page, 1) fp32 per-token scales (token granularity: appended
    rows are quantized once and never re-rounded).  Returns (kg, vg)
    shaped (B, Hkv, MP*page, D|Dv) in ``out_dtype`` — the dense cache the
    attention math wants, materialized from ~1/4 the HBM bytes.
    """
    p, hkv, page, d = kc.shape
    dv = vc.shape[-1]
    b, mp = table.shape

    def src(b_, j, tbl):
        return (jnp.clip(tbl[b_, j], 0, p - 1), 0, 0, 0)

    def dst(b_, j, tbl):
        return (b_, 0, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, mp),
        in_specs=[
            pl.BlockSpec((1, hkv, page, d), src),
            pl.BlockSpec((1, hkv, page, dv), src),
            pl.BlockSpec((1, hkv, page, 1), src),
            pl.BlockSpec((1, hkv, page, 1), src),
        ],
        out_specs=[
            pl.BlockSpec((1, hkv, page, d), dst),
            pl.BlockSpec((1, hkv, page, dv), dst),
        ],
    )
    return pl.pallas_call(
        _kernel_quant,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, mp * page, d), out_dtype),
            jax.ShapeDtypeStruct((b, hkv, mp * page, dv), out_dtype),
        ],
        interpret=interpret,
    )(table.astype(jnp.int32), kc, vc, ks, vs)
