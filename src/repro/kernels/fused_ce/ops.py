"""Differentiable per-token NLL of the vocab head through the fused kernels.

``token_nll(x, w, targets)`` is ``-log_softmax(x W^T)[target]`` per token,
computed by ``fused_ce.py`` without an fp32 (T, V) tensor in HBM: the
forward writes only ``lse`` and the target logit, and the backward
(``jax.custom_vjp``) recomputes each logits tile from the saved ``x``,
``W`` and ``lse``, writes the logits' cotangent once in bf16, and hands
``dx = g W`` and ``dW = g^T x`` to XLA.

Tiles come from the shapes (``tiles``); T and V that are not whole
multiples of them are padded here and sliced back.  Both rules run inside
the ``step.head.fused`` scope, so a device trace attributes the kernels
and the backward's matmuls to the head (``repro/scopes.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels.fused_ce.bwd import fused_ce_bwd_call
from repro.kernels.fused_ce.fused_ce import fused_ce_fwd_call

Array = jax.Array

# the widest tiles: a (512, 2048) fp32 logits tile is 4 MiB of VMEM, and
# its temporaries several more, within ``fused_ce.VMEM_LIMIT``
TM, TV = 512, 2048


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def tiles(t: int, v: int) -> tuple[int, int]:
    """(tm, tv) for T tokens and a V-row vocabulary: the widest tiles,
    narrowed to the shapes (tm a multiple of 16, bf16's sublane tile; tv
    of 128, the lane width)."""
    return min(TM, _round_up(t, 16)), min(TV, _round_up(v, 128))


def _pad_rows(a: Array, n: int) -> Array:
    return a if a.shape[0] == n else jnp.pad(
        a, [(0, n - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _nll(x, w, targets, interpret):
    return _nll_fwd(x, w, targets, interpret)[0]


def _nll_fwd(x, w, targets, interpret):
    (t, _), v = x.shape, w.shape[0]
    tm, tv = tiles(t, v)
    tp, vp = _round_up(t, tm), _round_up(v, tv)
    with jax.named_scope(scopes.HEAD_FUSED):
        tgt = _pad_rows(targets.astype(jnp.int32)[:, None], tp)
        lse, tgt_logit = fused_ce_fwd_call(
            _pad_rows(x, tp), _pad_rows(w, vp), tgt, tm=tm, tv=tv, v=v,
            interpret=interpret)
        nll = (lse - tgt_logit)[:t, 0]
    return nll, (x, w, targets, lse)


def _nll_bwd(interpret, res, gnll):
    x, w, targets, lse = res
    (t, _), v = x.shape, w.shape[0]
    tm, tv = tiles(t, v)
    tp, vp = _round_up(t, tm), _round_up(v, tv)
    with jax.named_scope(scopes.HEAD_FUSED):
        xp, wp = _pad_rows(x, tp), _pad_rows(w, vp)
        g = fused_ce_bwd_call(
            xp, wp, _pad_rows(targets.astype(jnp.int32)[:, None], tp), lse,
            _pad_rows(gnll.astype(jnp.float32)[:, None], tp),
            tm=tm, tv=tv, v=v, interpret=interpret)
        dx = jnp.dot(g, wp, preferred_element_type=jnp.float32)
        dw = jax.lax.dot_general(g, xp, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return dx[:t].astype(x.dtype), dw[:v].astype(w.dtype), None


_nll.defvjp(_nll_fwd, _nll_bwd)


def token_nll(x: Array, w: Array, targets: Array, *,
              interpret: bool = False) -> Array:
    """-log softmax(x W^T)[target] per token, fp32.

    x: (T, d) hidden states; w: (V, d) vocabulary rows in x's dtype;
    targets: (T,) int.  Differentiable in ``x`` and ``w``.  ``interpret``
    runs the kernels in the Pallas interpreter (off TPU).
    """
    assert x.ndim == 2 and w.ndim == 2 and x.shape[1] == w.shape[1], (
        x.shape, w.shape)
    assert targets.shape == x.shape[:1], (targets.shape, x.shape)
    return _nll(x, w, targets, interpret)
