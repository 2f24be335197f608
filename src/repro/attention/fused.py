"""Fused strict-causal Flow-Attention, chunk-parallel: no sequential loop.

Paper Alg. 2 (strict-causal variant) needs five running sums along the
sequence — the flow normalizers ``k_sum``/``q_sum``/``ko_sum``/``qi_sum``
and the cumulative competition ``z`` — plus the running (D, Dv) state
``S = sum_j K_j^T V_w,j``.  Every one of them is a prefix sum, and a prefix
sum splits over chunks of C tokens into a chunk-local prefix plus an
exclusive prefix over the chunk totals.  So the sequence is reshaped to
(nc, C) and the chunk axis stays a batch axis of every op:

    for all chunks at once (chunk-local prefix + exclusive chunk prefix):
      k/q running sums -> sink_in, src_out
      ko/qi running sums -> cons_sink, cons_src     (conservation, Eq. 7)
      e = exp(clip(cons_src)); z = running sum of e (cumulative competition)
      v_w = V * e
      S_c = exclusive prefix over chunks of K_c^T v_w,c    (B,H,nc,D,Dv)
      out_c = [tril(Q'_c K_c^T) v_w,c + Q'_c S_c] * (pos/z) * alloc

The heavy ops are batched (C,C)x(C,Dv) and (C,D)x(D,Dv) matmuls over all
chunks; nothing runs chunk after chunk, forward or backward.  The returned
state is the totals — the last position's sums and the sum of the chunk
states — which is exactly the decode ``FlowState``, so prefill hands serving
its state for free.

The four running sums, the flows before their division or clip, ``z``
and the chunk states are tagged ``scopes.FLOW_SUMS`` (``checkpoint_name``,
an identity): a per-layer checkpoint whose policy names the tag keeps them
for the backward instead of recomputing the prefix matmuls and the flows'
contractions; outside such a checkpoint the tag does nothing.
``flow_sums_bytes`` counts what the tag keeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro import scopes
from repro.core.flow_attention import FlowConfig, _group, _ungroup, phi_map
from repro.attention.recurrent import FlowState

Array = jax.Array


def effective_chunk(n: int, chunk_size: int) -> int:
    """Chunk size actually used for a length-``n`` sequence: ``chunk_size``
    capped at ``n``.  Non-multiple lengths are handled by padding to the
    next chunk multiple and masking the tail (see ``padded_len``) — the old
    power-of-two shrink degraded to one-token chunks for odd/prime N."""
    return max(1, min(chunk_size, n))


def padded_len(n: int, chunk: int) -> int:
    """``n`` rounded up to the next multiple of ``chunk``."""
    return -(-n // chunk) * chunk


def flow_sums_bytes(b: int, h: int, n: int, d: int, dv: int,
                    chunk_size: int) -> int:
    """Device bytes that keeping one layer's ``scopes.FLOW_SUMS`` residuals
    costs at a (b, h, n, d) -> dv call: twice their fp32 count, which is
    four running sums (b, h, n_pad, d), five per-token values (b, h,
    n_pad) and the chunk states (b, h, nc, d, dv).  The factor is
    measured: at the paper LM's 6 x 8192 tokens keeping them added 5.53 GB
    of temporaries to the step compiled for a v5e over six layers, against
    2.77 GB counted.  With grouped kv heads it is an upper bound."""
    c = effective_chunk(n, chunk_size)
    n_pad = padded_len(n, c)
    return 2 * 4 * b * h * (4 * n_pad * d + 5 * n_pad
                            + n_pad // c * d * dv)


def _keep(x: Array) -> Array:
    return checkpoint_name(x, scopes.FLOW_SUMS)


def _pad_seq(x: Array, n_pad: int, axis: int) -> Array:
    if x.shape[axis] == n_pad:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n_pad - x.shape[axis])
    return jnp.pad(x, pad)


def _local_prefix(x: Array, axis: int) -> Array:
    """Inclusive running sum along ``axis`` (a chunk's tokens), as a
    lower-triangular matmul on the MXU.  ``Precision.HIGHEST`` keeps it the
    fp32 sum a cumsum gives: at the default precision the TPU would round
    ``x`` to bf16 first."""
    tri = jnp.tri(x.shape[axis], dtype=jnp.float32)
    y = jnp.einsum("...j,ij->...i", jnp.moveaxis(x, axis, -1), tri,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.moveaxis(y, -1, axis)


def _exclusive(x: Array, axis: int) -> Array:
    """Sum of the entries before each one along ``axis`` (the chunks)."""
    x = jnp.cumsum(x, axis=axis)
    zero = jnp.zeros_like(jax.lax.index_in_dim(x, 0, axis))
    return jnp.concatenate([zero, jax.lax.slice_in_dim(x, 0, -1, axis=axis)],
                           axis=axis)


def _prefix(x: Array, axis: int) -> Array:
    """Inclusive running sum over the flattened (chunk ``axis``, token
    ``axis + 1``) pair: a chunk-local prefix plus the exclusive prefix of
    the chunk totals."""
    local = _local_prefix(x, axis + 1)
    totals = jax.lax.index_in_dim(local, -1, axis + 1, keepdims=True)
    return local + _exclusive(totals, axis)


def fused_causal_forward(
    q: Array,
    k: Array,
    v: Array,
    cfg: FlowConfig,
    *,
    return_state: bool = False,
    lengths: Array | None = None,
):
    """Strict-causal Flow-Attention, all chunks at once.

    q: (B, Hq, N, D); k: (B, Hkv, N, D); v: (B, Hkv, N, Dv); N == M.
    Requires ``strict_causal`` and ``use_competition`` (the cumulative
    softmax is what admits the O(d^2) state).  GQA-expand must be applied by
    the caller (see ``pipeline.expand_kv``); this function implements shared
    semantics over whatever kv heads it is given.

    ``lengths`` (B,) selects packed-prefill semantics: positions past each
    row's length contribute zero phi/e, so every running sum freezes at the
    boundary and the returned totals are that row's boundary ``FlowState`` —
    the same masking that makes non-chunk-multiple N a pad-and-mask, not a
    degenerate-chunk, problem.
    """
    assert cfg.strict_causal and cfg.use_competition, (
        "fused path implements the strict-causal cumulative competition"
    )
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    dv = v.shape[-1]
    assert k.shape[2] == n, "causal flow attention requires N == M"

    c = effective_chunk(n, cfg.chunk_size)
    n_pad = padded_len(n, c)
    nc = n_pad // c

    if lengths is None:
        t = jnp.full((b,), n, jnp.int32)
    else:
        t = jnp.clip(lengths.astype(jnp.int32), 1, n)
    # (B, n_pad) validity: padding tail and packed positions both masked
    row_ok = (
        jnp.arange(n_pad, dtype=jnp.int32)[None, :] < t[:, None]
    ).astype(jnp.float32)

    phi_q = phi_map(_pad_seq(q, n_pad, 2).astype(jnp.float32), cfg.phi)
    phi_k = phi_map(_pad_seq(k, n_pad, 2).astype(jnp.float32), cfg.phi)
    phi_q = phi_q * row_ok[:, None, :, None]
    phi_k = phi_k * row_ok[:, None, :, None]
    vf = _pad_seq(v, n_pad, 2).astype(jnp.float32)

    # chunk the sequence axis in place: every op batches over the chunks
    qs = _group(phi_q, hkv).reshape(b, hkv, -1, nc, c, d)  # (B,H,G,nc,c,d)
    g = qs.shape[2]
    ks = phi_k.reshape(b, hkv, nc, c, d)
    vs = vf.reshape(b, hkv, nc, c, dv)
    # 1-based global positions: sources seen up to each position, and the
    # sinks seen (G per position)
    normal_k = (jnp.arange(n_pad, dtype=jnp.float32) + 1.0).reshape(nc, c)
    normal_q = normal_k * g
    ok = row_ok.reshape(b, 1, nc, c)

    # (1) flows from the running source/sink sums
    k_csum = _keep(_prefix(ks, 2))  # (B,H,nc,c,d)
    q_csum = _keep(_prefix(qs.sum(axis=2), 2))
    sink_in = normal_k / _keep(jnp.einsum("bhgncd,bhncd->bhgnc", qs + eps,
                                          k_csum + eps))
    src_out = normal_q / _keep(jnp.einsum("bhncd,bhncd->bhnc", ks + eps,
                                          q_csum + eps))

    # (2) conservation refinement
    ko_csum = _keep(_prefix(ks * src_out[..., None], 2))
    cons_sink = _keep(jnp.einsum("bhgncd,bhncd->bhgnc", qs + eps,
                                 ko_csum + eps) / normal_q)
    qi_csum = _keep(_prefix((qs * sink_in[..., None]).sum(axis=2), 2))
    cons_src = jnp.clip(_keep(
        jnp.einsum("bhncd,bhncd->bhnc", ks + eps, qi_csum + eps) / normal_k),
        -1.0, 1.0,
    )

    # (3) cumulative competition + allocation
    if cfg.use_allocation:
        alloc = jax.nn.sigmoid(cons_sink)
    else:
        alloc = jnp.ones_like(cons_sink)
    # e masked past each row's boundary so z freezes with the sums
    e = jnp.exp(cons_src) * ok  # in [1/e, e] while valid
    z = _keep(_prefix(e, 2))  # (B,H,nc,c)
    v_w = vs * e[..., None]

    # (4) aggregation: intra-chunk tril matmul + the (D,Dv) state of every
    # earlier chunk
    f32 = jnp.float32
    q_in = qs * sink_in[..., None]
    scores = jnp.einsum("bhgnid,bhnjd->bhgnij", q_in, ks,
                        preferred_element_type=f32)
    mask = jnp.tril(jnp.ones((c, c), f32))
    intra = jnp.einsum("bhgnij,bhnje->bhgnie", scores * mask, v_w,
                       preferred_element_type=f32)
    states = jnp.einsum("bhnjd,bhnje->bhnde", ks, v_w,
                        preferred_element_type=f32)  # (B,H,nc,D,Dv)
    s_before = _keep(_exclusive(states, 2))
    inter = jnp.einsum("bhgnid,bhnde->bhgnie", q_in, s_before,
                       preferred_element_type=f32)
    out = (intra + inter) * (normal_k / z)[:, :, None, ..., None]
    out = (out * alloc[..., None]).astype(out_dtype)
    out = _ungroup(out.reshape(b, hkv, g, n_pad, dv))[:, :, :n]
    if not return_state:
        return out
    # the totals: running sums at the last (padded) position, which the
    # masking froze at each row's boundary
    state = FlowState(
        t=t,
        q_sum=q_csum[:, :, -1, -1],
        k_sum=k_csum[:, :, -1, -1],
        ko_sum=ko_csum[:, :, -1, -1],
        qi_sum=qi_csum[:, :, -1, -1],
        z=z[:, :, -1, -1],
        s=s_before[:, :, -1] + states[:, :, -1],
    )
    return out, state
