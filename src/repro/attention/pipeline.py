"""Unfused Flow-Attention math shared by the XLA / Pallas-dot backends.

The normalizer algebra (paper Eq. 4/7/8, Alg. 2) is identical across
execution strategies; what differs is how the causal aggregation
``out_i = q'_i . sum_{j<=i} phiK_j^T V_hat_j`` is realized.  ``causal_forward``
therefore takes the aggregation as a ``dot_fn`` argument — backends inject
cumsum, chunked-scan or Pallas dots without duplicating the flow math.

The fused strict-causal path (normalizers + competition + aggregation,
chunk-parallel) lives in ``attention/fused.py``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.flow_attention import FlowConfig, _group, _ungroup, phi_map

Array = jax.Array
DotFn = Callable[[Array, Array, Array], Array]


def expand_kv(q: Array, k: Array, v: Array, cfg: FlowConfig):
    """Apply ``gqa_mode="expand"`` by broadcasting kv heads to query heads."""
    hq, hkv = q.shape[1], k.shape[1]
    if cfg.gqa_mode == "expand" and hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def nc_forward(q: Array, k: Array, v: Array, cfg: FlowConfig) -> Array:
    """Non-causal Flow-Attention (paper Eq. 4/7/8), pure XLA.

    q: (B, Hq, N, D); k: (B, Hkv, M, D); v: (B, Hkv, M, Dv) with Hkv | Hq.
    Returns (B, Hq, N, Dv).
    """
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, n, d = q.shape
    k, v = expand_kv(q, k, v, cfg)
    hkv, m = k.shape[1], k.shape[2]

    phi_q = phi_map(q.astype(jnp.float32), cfg.phi)  # (B,Hq,N,D)
    phi_k = phi_map(k.astype(jnp.float32), cfg.phi)  # (B,Hkv,M,D)
    vf = v.astype(jnp.float32)

    qg = _group(phi_q, hkv)  # (B,Hkv,G,N,D)

    # (1) incoming / outgoing flows (Eq. 4 + official eps placement)
    k_sum = phi_k.sum(axis=2)  # (B,Hkv,D)
    q_sum = qg.sum(axis=(2, 3))  # (B,Hkv,D) — sums over group+positions
    sink_in = 1.0 / jnp.einsum("bhgnd,bhd->bhgn", qg + eps, k_sum + eps)  # I^-1
    src_out = 1.0 / jnp.einsum("bhmd,bhd->bhm", phi_k + eps, q_sum + eps)  # O^-1

    # (2) conservation refinement (Eq. 7)
    ko_sum = (phi_k * src_out[..., None]).sum(axis=2)  # (B,Hkv,D)
    cons_sink = jnp.einsum("bhgnd,bhd->bhgn", qg + eps, ko_sum + eps)  # I_hat
    qi_sum = (qg * sink_in[..., None]).sum(axis=(2, 3))  # (B,Hkv,D)
    cons_src = jnp.einsum("bhmd,bhd->bhm", phi_k + eps, qi_sum + eps)  # O_hat
    cons_src = jnp.clip(cons_src, -1.0, 1.0)  # official stability clamp

    # (3) competition & allocation (Eq. 8, official n/m scalings)
    n_sinks = qg.shape[2] * n  # G*N sinks per kv head (shared mode)
    if cfg.use_competition:
        comp = jax.nn.softmax(cons_src, axis=-1) * float(m)  # (B,Hkv,M)
        v_hat = vf * comp[..., None]
    else:
        v_hat = vf
    if cfg.use_allocation:
        alloc = jax.nn.sigmoid(cons_sink * (float(n_sinks) / float(m)))
    else:
        alloc = jnp.ones_like(cons_sink)

    # (4) linear aggregation: (phiQ * I^-1) @ (phiK^T @ V_hat)
    kv = jnp.einsum("bhmd,bhme->bhde", phi_k, v_hat)  # (B,Hkv,D,Dv)
    agg = jnp.einsum("bhgnd,bhde->bhgne", qg * sink_in[..., None], kv)
    out = agg * alloc[..., None]
    return _ungroup(out).astype(out_dtype)


def causal_verify(state, q: Array, k: Array, v: Array, cfg: FlowConfig,
                  dot_fn: DotFn | None = None):
    """Score a drafted window of n tokens in one chunked pass from ``state``.

    The speculative-decoding verifier: continues the strict-causal recurrence
    from a boundary ``FlowState`` over ``n = k_draft + 1`` candidate
    positions, producing every position's output AND every position's
    boundary state in a single pass — the inclusive cumsums that the chunked
    scan computes anyway ARE the per-position states, so accept-prefix
    rollback is a gather, not a recompute.

    q: (B, Hq, n, D); k: (B, Hkv, n, D); v: (B, Hkv, n, Dv) with per-row
    start offsets taken from ``state.t`` (continuous batching: slots verify
    at heterogeneous depths).  Requires ``strict_causal`` competition, like
    every state-producing op.

    Returns ``(out, traj)`` where ``out`` is (B, Hq, n, Dv) — position j is
    bit-identical (up to fp32 reassociation) to what ``decode_step`` would
    emit after consuming tokens 1..j — and ``traj`` is a trajectory
    ``FlowState`` whose leaves carry an extra position axis at index 1
    (``t``: (B,n); sums: (B,n,Hkv,D); ``z``: (B,n,Hkv); ``s``:
    (B,n,Hkv,D,Dv)).  Select the accepted boundary with
    ``recurrent.select_state(traj, accepted_idx)``.

    ``dot_fn`` is accepted for registry-signature symmetry but unused: the
    window is tiny (a handful of drafted tokens), so the in-window
    aggregation is always realized as a cumsum of rank-1 updates against the
    carried ``s`` panel.
    """
    del dot_fn  # in-window aggregation is cumsum-sized by construction
    from repro.attention.recurrent import FlowState
    from repro.serving.quant import QuantizedPool, dequantize_state

    if isinstance(state, QuantizedPool):
        # quantized slot pools verify in full precision: one boundary
        # dequantize here, and the caller (mixer verify_step) carries the
        # pool's recipe alongside the fp32 trajectory so rollback
        # requantizes exactly once at the accepted boundary
        state = dequantize_state(state)
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, n, d = q.shape
    assert k.shape[2] == n, "verify_step requires N == M over the window"
    assert cfg.strict_causal and cfg.use_competition, (
        "verify_step continues a recurrent state: requires strict_causal "
        "competition"
    )
    k, v = expand_kv(q, k, v, cfg)
    hkv = k.shape[1]

    phi_q = phi_map(q.astype(jnp.float32), cfg.phi)
    phi_k = phi_map(k.astype(jnp.float32), cfg.phi)
    vf = v.astype(jnp.float32)

    qg = _group(phi_q, hkv)  # (B,Hkv,G,n,D)
    g = qg.shape[2]

    # per-row position counts continue from the carried state.t
    t_traj = state.t[:, None] + jnp.arange(1, n + 1, dtype=jnp.int32)  # (B,n)
    counts = t_traj.astype(jnp.float32)
    normal_k = counts[:, None, :]  # (B,1,n) sources seen so far
    normal_q = normal_k * g  # sinks seen so far (G per position)

    # (1) incoming / outgoing flows: in-window cumsums offset by the carry
    k_csum = state.k_sum[:, :, None, :] + jnp.cumsum(phi_k, axis=2)
    q_csum = state.q_sum[:, :, None, :] + jnp.cumsum(qg.sum(axis=2), axis=2)
    sink_in = normal_k[:, :, None, :] / jnp.einsum(
        "bhgnd,bhnd->bhgn", qg + eps, k_csum + eps)
    src_out = normal_q / jnp.einsum(
        "bhnd,bhnd->bhn", phi_k + eps, q_csum + eps)

    # (2) conservation refinement
    ko_csum = state.ko_sum[:, :, None, :] + jnp.cumsum(
        phi_k * src_out[..., None], axis=2)
    cons_sink = jnp.einsum(
        "bhgnd,bhnd->bhgn", qg + eps, ko_csum + eps) / normal_q[:, :, None, :]
    qi_csum = state.qi_sum[:, :, None, :] + jnp.cumsum(
        (qg * sink_in[..., None]).sum(axis=2), axis=2)
    cons_src = jnp.einsum(
        "bhnd,bhnd->bhn", phi_k + eps, qi_csum + eps) / normal_k
    cons_src = jnp.clip(cons_src, -1.0, 1.0)

    # (3) competition & allocation
    if cfg.use_allocation:
        alloc = jax.nn.sigmoid(cons_sink)
    else:
        alloc = jnp.ones_like(cons_sink)
    e = jnp.exp(cons_src)  # (B,Hkv,n)
    z = state.z[:, :, None] + jnp.cumsum(e, axis=-1)
    v_w = vf * e[..., None]

    # (4) aggregation against the per-position state panel: the window is a
    # handful of tokens, so materializing the (B,Hkv,n,D,Dv) trajectory is
    # cheaper than any blocked dot — and rollback needs it anyway.
    s_traj = state.s[:, :, None] + jnp.cumsum(
        jnp.einsum("bhnd,bhne->bhnde", phi_k, v_w), axis=2)
    q_in = qg * sink_in[..., None]
    agg = jnp.einsum("bhgnd,bhnde->bhgne", q_in, s_traj)
    scale = normal_k[:, :, None, :, None] / z[:, :, None, :, None]
    out = agg * scale * alloc[..., None]

    traj = FlowState(
        t=t_traj,
        q_sum=q_csum.swapaxes(1, 2),
        k_sum=k_csum.swapaxes(1, 2),
        ko_sum=ko_csum.swapaxes(1, 2),
        qi_sum=qi_csum.swapaxes(1, 2),
        z=z.swapaxes(1, 2),
        s=s_traj.swapaxes(1, 2),
    )
    return _ungroup(out).astype(out_dtype), traj


def causal_forward(
    q: Array,
    k: Array,
    v: Array,
    cfg: FlowConfig,
    dot_fn: DotFn,
    *,
    return_state: bool = False,
    lengths: Array | None = None,
):
    """Causal Flow-Attention (paper Alg. 2) with an injected aggregation.

    q: (B, Hq, N, D); k: (B, Hkv, N, D); v: (B, Hkv, N, Dv); N == M.
    ``dot_fn(qg, k, v)`` computes the grouped causal dot
    (B,Hkv,G,N,D) x (B,Hkv,N,D) x (B,Hkv,N,Dv) -> (B,Hkv,G,N,Dv).
    With ``return_state=True`` (requires ``strict_causal``) also returns the
    O(d^2) recurrent ``FlowState`` that decode continues from.

    ``lengths`` (B,) serves right-padded packed prompts: causality means
    padding can never leak into earlier positions, so each row's TRUE state
    is simply the cumulative quantities gathered at its own boundary
    ``lengths[i]-1`` instead of at N-1 (the padded tail is sliced off by a
    mask for the non-cumulative ``s`` panel).  Outputs at padded positions
    are garbage by construction; callers gather their own boundary.
    """
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, n, d = q.shape
    assert k.shape[2] == n, "causal flow attention requires N == M"
    if return_state:
        assert cfg.strict_causal and cfg.use_competition, (
            "recurrent decode state requires strict_causal competition"
        )
    assert lengths is None or return_state, (
        "per-row lengths only affect the returned FlowState"
    )
    k, v = expand_kv(q, k, v, cfg)
    hkv = k.shape[1]

    phi_q = phi_map(q.astype(jnp.float32), cfg.phi)
    phi_k = phi_map(k.astype(jnp.float32), cfg.phi)
    vf = v.astype(jnp.float32)

    qg = _group(phi_q, hkv)  # (B,Hkv,G,N,D)
    g = qg.shape[2]

    # position count ("normal" in the official code).  With G grouped query
    # heads each position contributes G sinks.
    pos = jnp.arange(1, n + 1, dtype=jnp.float32)  # (N,)
    normal_q = pos * g  # sinks seen up to i
    normal_k = pos  # sources seen up to j

    # (1) incoming / outgoing flows from inclusive cumsums
    k_csum = jnp.cumsum(phi_k, axis=2)  # (B,Hkv,N,D)
    q_csum = jnp.cumsum(qg.sum(axis=2), axis=2)  # (B,Hkv,N,D) summed over group
    sink_in = 1.0 / jnp.einsum("bhgnd,bhnd->bhgn", qg + eps, k_csum + eps)
    sink_in = sink_in * normal_k  # official: rescale by count of sources
    src_out = 1.0 / jnp.einsum("bhnd,bhnd->bhn", phi_k + eps, q_csum + eps)
    src_out = src_out * normal_q

    # (2) conservation refinement
    ko_csum = jnp.cumsum(phi_k * src_out[..., None], axis=2)
    cons_sink = (
        jnp.einsum("bhgnd,bhnd->bhgn", qg + eps, ko_csum + eps) / normal_q
    )
    qi_csum = jnp.cumsum((qg * sink_in[..., None]).sum(axis=2), axis=2)
    cons_src = (
        jnp.einsum("bhnd,bhnd->bhn", phi_k + eps, qi_csum + eps) / normal_k
    )
    cons_src = jnp.clip(cons_src, -1.0, 1.0)

    # (3) competition & allocation
    if cfg.use_allocation:
        alloc = jax.nn.sigmoid(cons_sink)  # (B,Hkv,G,N)
    else:
        alloc = jnp.ones_like(cons_sink)

    q_in = qg * sink_in[..., None]  # value-normalized queries
    if not cfg.use_competition:
        agg = dot_fn(q_in, phi_k, vf)
        out = agg * alloc[..., None]
        return _ungroup(out).astype(out_dtype)

    if cfg.strict_causal:
        # cumulative softmax: weight_{i,j} = exp(cs_j)/Z_i * normal_k_i
        e = jnp.exp(cons_src)  # bounded in [1/e, e] by the clamp
        z = jnp.cumsum(e, axis=-1)  # (B,Hkv,N)
        v_w = vf * e[..., None]
        agg = dot_fn(q_in, phi_k, v_w)
        scale = (normal_k / z)[:, :, None, :, None]  # (B,Hkv,1,N,1)
        out = agg * scale * alloc[..., None]
        if return_state:
            from repro.attention.recurrent import FlowState

            if lengths is None:
                t = jnp.full((b,), n, dtype=jnp.int32)
                gat = lambda a: a[:, :, -1, :]  # noqa: E731
                z_at = z[:, :, -1]
                k_mask = phi_k
            else:
                t = lengths.astype(jnp.int32)
                li = jnp.maximum(t, 1) - 1  # (B,) boundary index per row
                gat = lambda a: jnp.take_along_axis(  # noqa: E731
                    a, li[:, None, None, None], axis=2
                )[:, :, 0, :]
                z_at = jnp.take_along_axis(z, li[:, None, None], axis=2)[:, :, 0]
                valid = (jnp.arange(n) < t[:, None]).astype(jnp.float32)
                k_mask = phi_k * valid[:, None, :, None]
            state = FlowState(
                t=t,
                q_sum=gat(q_csum),
                k_sum=gat(k_csum),
                ko_sum=gat(ko_csum),
                qi_sum=gat(qi_csum),
                z=z_at,
                s=jnp.einsum(
                    "bhnd,bhne->bhde", k_mask, v_w,
                    preferred_element_type=jnp.float32,
                ),
            )
            return _ungroup(out).astype(out_dtype), state
    else:
        # paper-faithful: softmax over the full length, scaled by N
        comp = jax.nn.softmax(cons_src, axis=-1) * float(n)  # (B,Hkv,N)
        v_hat = vf * comp[..., None]
        agg = dot_fn(q_in, phi_k, v_hat)
        out = agg * alloc[..., None]
    return _ungroup(out).astype(out_dtype)
