"""The registered shape grid the kernel auditor traces every wrapper over.

Each :class:`GridEntry` names one ``pl.pallas_call`` wrapper, a loader
returning the callable, example array shapes (kept deliberately small —
tracing is abstract, nothing executes), and the static kwargs that take
the kernel branch (``interpret=True`` where a wrapper would otherwise
fall back to XLA off-TPU).  :data:`VJP_ENTRIES` registers the
``custom_vjp`` forward rules with larger sequence lengths so the
residual byte budget actually bites on a saved primal output.

Adding a kernel?  Add a grid row — the auditor refuses silently-skipped
coverage by checking every ``pl.pallas_call`` under ``src/repro/kernels``
appears in some traced entry.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

__all__ = ["GridEntry", "VjpEntry", "GRID", "VJP_ENTRIES"]


@dataclasses.dataclass(frozen=True)
class GridEntry:
    """One (wrapper, example shapes, statics) cell of the audit grid."""

    name: str
    load: Callable[[], Callable]
    args: Callable[[], tuple]
    kwargs: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class VjpEntry:
    """One ``custom_vjp`` forward rule + shapes for the residual budget."""

    name: str
    load: Callable[[], Callable]
    args: Callable[[], tuple]
    statics: tuple = ()
    seq_len: int = 1024


def _z(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


# shared tiny shapes: BH=2 flattened batch*kv-heads, one query group,
# N=256 tokens (two 128-chunks), D=Dv=64, SSD P=64/S=16, pages of 8
_BH, _G, _N, _D, _DV = 2, 1, 256, 64, 64


def _flow_chunk():
    from repro.kernels.flow_chunk.flow_chunk import flow_chunk_call
    return flow_chunk_call


def _flow_chunk_dkv():
    from repro.kernels.flow_chunk.bwd import flow_chunk_dkv_call
    return flow_chunk_dkv_call


def _flow_fused():
    from repro.kernels.flow_fused.flow_fused import flow_fused_call
    return flow_fused_call


def _flow_fused_bwd():
    from repro.kernels.flow_fused.bwd import flow_fused_bwd_call
    return flow_fused_bwd_call


def _flow_decode():
    from repro.kernels.flow_decode.flow_decode import flow_decode_call
    return flow_decode_call


def _flow_decode_q():
    from repro.kernels.flow_decode.quant import flow_decode_q_call
    return flow_decode_q_call


def _flow_nc_qside():
    from repro.kernels.flow_nc.flow_nc import flow_nc_qside_call
    return flow_nc_qside_call


def _flow_nc_qside_bwd():
    from repro.kernels.flow_nc.bwd import flow_nc_qside_bwd_call
    return flow_nc_qside_bwd_call


def _flow_nc_fused():
    from repro.kernels.flow_nc.fused import flow_nc_fused_call
    return flow_nc_fused_call


def _ssd_chunk():
    from repro.kernels.ssd_chunk.ssd_chunk import ssd_chunk_call
    return ssd_chunk_call


def _ssd_chunk_bwd():
    from repro.kernels.ssd_chunk.bwd import ssd_chunk_bwd_call
    return ssd_chunk_bwd_call


def _paged_gather():
    from repro.kernels.gather.paged import paged_gather
    return paged_gather


def _paged_gather_quant():
    from repro.kernels.gather.paged import paged_gather_quant
    return paged_gather_quant


def _boundary_gather():
    from repro.kernels.gather.boundary import boundary_gather

    def call(xb, lengths, *, interpret):  # k is a static python int
        return boundary_gather(xb, lengths, 4, interpret=interpret)
    return call


def _fused_ce_fwd():
    from repro.kernels.fused_ce.fused_ce import fused_ce_fwd_call
    return fused_ce_fwd_call


def _fused_ce_bwd():
    from repro.kernels.fused_ce.bwd import fused_ce_bwd_call
    return fused_ce_bwd_call


def _qkv():
    return (_z((_BH, _G, _N, _D)), _z((_BH, _N, _D)), _z((_BH, _N, _DV)))


def _fused_sums():
    return (_z((_BH, _D)), _z((_BH, _D)), _z((_BH, _D)), _z((_BH, _D)),
            _z((_BH, 1)), _z((_BH, _D, _DV)))


def _decode_token():  # t, q, k, v in the decode kernels' row layout
    return (_z((_BH,), jnp.int32), _z((_BH, _G, _D)), _z((_BH, 1, _D)),
            _z((_BH, 1, _DV)))


def _decode_args():
    return (*_decode_token(),
            _z((_BH, 1, _D)), _z((_BH, 1, _D)), _z((_BH, 1, _D)),
            _z((_BH, 1, _D)), _z((_BH, 1, 1)), _z((_BH, _D, _DV)))


def _decode_q_args():
    pay = tuple(_z((_BH, 1, _D), jnp.int8) for _ in range(4))
    sc = tuple(_z((_BH, 1, 1)) for _ in range(4))
    return (*_decode_token(), pay, _z((_BH, _D, _DV), jnp.int8), sc,
            _z((_BH, 1, 1)), _z((_BH, 1, 1)))


def _paged_pools():
    p, hkv, page = 6, 2, 8
    return (_z((p, hkv, page, _D), jnp.bfloat16),
            _z((p, hkv, page, _DV), jnp.bfloat16),
            _z((2, 3), jnp.int32))


def _paged_pools_quant():
    p, hkv, page = 6, 2, 8
    return (_z((p, hkv, page, _D), jnp.int8),
            _z((p, hkv, page, _DV), jnp.int8),
            _z((p, hkv, page, 1)), _z((p, hkv, page, 1)),
            _z((2, 3), jnp.int32))


# the vocab head: T=64 tokens against a 512-row vocabulary whose last 128
# rows are padding, in (32, 256) tiles
_CE_T, _CE_V = 64, 512


def _ce_args():
    return (_z((_CE_T, _D), jnp.bfloat16), _z((_CE_V, _D), jnp.bfloat16),
            _z((_CE_T, 1), jnp.int32))


_CE_STATICS = dict(tm=32, tv=256, v=384, interpret=True)

_FLOW_STATICS = dict(eps=1e-6, phi="sigmoid", use_allocation=True)

GRID: tuple[GridEntry, ...] = (
    GridEntry("flow_chunk_call", _flow_chunk, _qkv,
              dict(chunk=128, interpret=True)),
    GridEntry("flow_chunk_dkv_call", _flow_chunk_dkv,
              lambda: (*_qkv(), _z((_BH, _G, _N, _DV))),
              dict(chunk=128, interpret=True)),
    GridEntry("flow_fused_call", _flow_fused,
              lambda: (*_qkv(), _z((_BH,), jnp.int32)),
              dict(chunk=128, interpret=True)),
    GridEntry("flow_fused_bwd_call", _flow_fused_bwd,
              lambda: (*_qkv(), _z((_BH,), jnp.int32), _fused_sums(),
                       _z((_BH, _G, _N, _DV)), _fused_sums()),
              dict(chunk=128, interpret=True)),
    GridEntry("flow_decode_call", _flow_decode, _decode_args,
              dict(interpret=True, **_FLOW_STATICS)),
    GridEntry("flow_decode_q_call (int8)", _flow_decode_q, _decode_q_args,
              dict(qmax=127.0, is_int=True, interpret=True, **_FLOW_STATICS)),
    GridEntry("flow_nc_qside_call", _flow_nc_qside,
              lambda: (_z((_BH, _N, _D)), _z((_BH, _D)), _z((_BH, _D)),
                       _z((_BH, _D, _DV))),
              dict(n_sinks=_N, m_sources=_N, block=256, interpret=True)),
    GridEntry("flow_nc_qside_bwd_call", _flow_nc_qside_bwd,
              lambda: (_z((_BH, _N, _D)), _z((_BH, _D)), _z((_BH, _D)),
                       _z((_BH, _D, _DV)), _z((_BH, _N, _DV))),
              dict(n_sinks=_N, m_sources=_N, block=256, interpret=True)),
    GridEntry("flow_nc_fused_call", _flow_nc_fused,
              lambda: (_z((_BH, _N, _D)), _z((_BH, _N, _D)),
                       _z((_BH, _N, _DV))),
              dict(block=256, interpret=True)),
    GridEntry("ssd_chunk_call", _ssd_chunk,
              lambda: (_z((_BH, _N, 64)), _z((_BH, _N, 1)),
                       _z((_BH, _N, 16)), _z((_BH, _N, 16))),
              dict(chunk=128, interpret=True, return_hins=True)),
    GridEntry("ssd_chunk_bwd_call", _ssd_chunk_bwd,
              lambda: (_z((_BH, _N, 64)), _z((_BH, _N, 1)),
                       _z((_BH, _N, 16)), _z((_BH, _N, 16)),
                       _z((_BH, 2, 64, 16)), _z((_BH, _N, 64))),
              dict(chunk=128, interpret=True)),
    GridEntry("paged_gather", _paged_gather, _paged_pools,
              dict(interpret=True)),
    GridEntry("paged_gather_quant", _paged_gather_quant, _paged_pools_quant,
              dict(out_dtype=jnp.bfloat16, interpret=True)),
    GridEntry("boundary_gather", _boundary_gather,
              lambda: (_z((2, _N, 8)), _z((2,), jnp.int32)),
              dict(interpret=True)),
    GridEntry("fused_ce_fwd_call", _fused_ce_fwd, _ce_args, _CE_STATICS),
    GridEntry("fused_ce_bwd_call", _fused_ce_bwd,
              lambda: (*_ce_args(), _z((_CE_T, 1)), _z((_CE_T, 1))),
              _CE_STATICS),
)


def _vjp_chunk():
    from repro.attention.vjp import _flow_chunk_fwd
    return _flow_chunk_fwd


def _vjp_fused():
    from repro.attention.vjp import _flow_fused_fwd
    return _flow_fused_fwd


def _vjp_nc():
    from repro.attention.vjp import _flow_nc_fwd
    return _flow_nc_fwd


def _vjp_nc_fused():
    from repro.attention.vjp import _flow_nc_fused_fwd
    return _flow_nc_fused_fwd


def _vjp_fused_ce():
    from repro.kernels.fused_ce.ops import _nll_fwd
    return _nll_fwd


def _vjp_ssd():
    from repro.kernels.ssd_chunk.ops import _ssd_fwd
    return _ssd_fwd


_NB = 1024  # residual-budget sequence length: big enough that saving the
# primal output or an (N, N) matrix overflows the byte budget

VJP_ENTRIES: tuple[VjpEntry, ...] = (
    VjpEntry("flow_chunk_dot", _vjp_chunk,
             lambda: (_z((_BH, _G, _NB, _D)), _z((_BH, _NB, _D)),
                      _z((_BH, _NB, _DV))),
             statics=(128, True), seq_len=_NB),
    VjpEntry("flow_fused_dot", _vjp_fused,
             lambda: (_z((_BH, _G, _NB, _D)), _z((_BH, _NB, _D)),
                      _z((_BH, _NB, _DV))),
             statics=(_NB, 128, 1e-6, "sigmoid", True, True), seq_len=_NB),
    VjpEntry("flow_nc_qside", _vjp_nc,
             lambda: (_z((_BH, _NB, _D)), _z((_BH, _D)), _z((_BH, _D)),
                      _z((_BH, _D, _DV))),
             statics=(_NB, _NB, 1e-6, 256, True), seq_len=_NB),
    VjpEntry("flow_nc_fused", _vjp_nc_fused,
             lambda: (_z((_BH, _NB, _D)), _z((_BH, _NB, _D)),
                      _z((_BH, _NB, _DV))),
             statics=(1e-6, 256, True, True), seq_len=_NB),
    VjpEntry("ssd_chunk_dot", _vjp_ssd,
             lambda: (_z((_BH, _NB, 64)), _z((_BH, _NB, 1)),
                      _z((_BH, _NB, 16)), _z((_BH, _NB, 16))),
             statics=(128, True), seq_len=_NB),
    VjpEntry("fused_ce_nll", _vjp_fused_ce,
             lambda: (_z((_NB, _D), jnp.bfloat16), _z((_NB, _D), jnp.bfloat16),
                      _z((_NB,), jnp.int32)),
             statics=(True,), seq_len=_NB),
)
