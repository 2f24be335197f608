"""Context-parallel Flow-Attention backends: shard-local strategy + glue.

Beyond-paper distributed optimization (DESIGN.md §7.2): the only cross-token
coupling in Flow-Attention is through *global sums* of d-vectors / (d x dv)
matrices, so sharding the sequence axis over devices costs collectives of
O(d^2) bytes — independent of sequence length.  Softmax attention in the
same regime needs the full O(n*d) KV exchange (ring attention).

This module expresses that as two registry backends instead of hand-built
call-site math:

* ``cp_nc``     — non-causal glue: the six flow sums become ``psum``s.
* ``cp_causal`` — strict-causal glue: cumulative sums become a local cumsum
  plus an ``all_gather`` of per-device partials and a local exclusive
  prefix (a distributed Blelloch scan over tiny tensors).  Provides
  ``prefill`` and ``prefill_packed`` too: every ``FlowState`` field is a
  prefix sum, so the per-row boundary state is one masked ``psum`` per
  field — seq-parallel serving admission resolves through the same door as
  everything else.

Each backend wraps a *shard-local inner strategy* in the collective glue.
For ``cp_causal`` the inner strategy is the grouped causal aggregation dot
of any registered backend exposing ``causal_dot_fn`` (``xla_cumsum``,
``xla_chunked``, ``pallas_chunk``) — resolved over shard-local shapes by
``ShardSpec.inner`` (``"auto"`` prefers the Pallas kernel on TPU exactly
like unsharded resolution).  ``cp_nc``'s shard-local work is a fixed set of
einsums between the psums; it has no injectable inner (``pallas_nc`` fuses
the *global* sums inside its kernel and cannot run shard-local), and says
so when an inner is pinned.

Both backends run their math inside ``jax.shard_map`` over
``ShardSpec.mesh`` with the sequence axis sharded over ``ShardSpec.axis``
(batch optionally over ``ShardSpec.batch_axis``, heads replicated).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.flow_attention import FlowConfig, _group, _ungroup, phi_map
from repro.attention import pipeline
from repro.attention.recurrent import FlowState
from repro.attention.registry import (
    Backend,
    ResolutionError,
    ShapeInfo,
    ShardSpec,
    get_backend,
    list_backends,
    run_kwargs,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Inner (shard-local) strategy resolution
# ---------------------------------------------------------------------------
def resolve_inner(cfg: FlowConfig, local_shapes: ShapeInfo, platform: str,
                  shard: ShardSpec) -> Backend:
    """Pick the shard-local causal aggregation strategy for ``cp_causal``.

    Candidates are the registered backends exposing ``causal_dot_fn``
    (the grouped causal dot is the only piece of the math that differs
    between execution strategies — the flow algebra is shared).  ``auto``
    walks them in registry preference order against the SHARD-LOCAL
    shapes, so e.g. ``pallas_chunk`` volunteers on TPU and the chunk-size
    divisibility is judged on the local sequence length.
    """
    inner = shard.inner or "auto"
    explicit = inner != "auto"
    names = [inner] if explicit else [
        n for n in list_backends() if hasattr(get_backend(n), "causal_dot_fn")
    ]
    rejections = []
    for name in names:
        try:
            be = get_backend(name)
        except ValueError as err:
            raise ResolutionError(str(err), ((name, str(err)),)) from None
        if not hasattr(be, "causal_dot_fn"):
            rejections.append((name, "no shard-local causal dot (cannot be "
                                     "a context-parallel inner strategy)"))
            continue
        ok, why = be.supports(cfg, local_shapes, platform, op="forward",
                              explicit=explicit)
        if ok:
            return be
        rejections.append((name, why))
    raise ResolutionError(
        f"no shard-local inner strategy for context-parallel causal flow "
        f"(local {local_shapes}):\n  "
        + "\n  ".join(f"{n}: {w}" for n, w in rejections),
        rejections,
    )


# ---------------------------------------------------------------------------
# Non-causal shard body: pure psum of flow sums
# ---------------------------------------------------------------------------
def _nc_shard_body(q: Array, k: Array, v: Array, cfg: FlowConfig,
                   axis_name: str) -> Array:
    """Sequence-parallel non-causal Flow-Attention (runs inside shard_map).

    q: (B,Hq,Nl,D); k: (B,Hkv,Ml,D); v: (B,Hkv,Ml,Dv) — local shards.
    Collective volume: 5 psums of (B,Hkv,D) + 1 psum of (B,Hkv,D,Dv) + scalars.
    """
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, nl, d = q.shape
    hkv, ml = k.shape[1], k.shape[2]
    psize = jax.lax.psum(1, axis_name)
    n_tot = nl * psize
    m_tot = ml * psize

    phi_q = phi_map(q.astype(jnp.float32), cfg.phi)
    phi_k = phi_map(k.astype(jnp.float32), cfg.phi)
    vf = v.astype(jnp.float32)
    qg = _group(phi_q, hkv)

    k_sum = jax.lax.psum(phi_k.sum(axis=2), axis_name)  # (B,Hkv,D)
    q_sum = jax.lax.psum(qg.sum(axis=(2, 3)), axis_name)
    sink_in = 1.0 / jnp.einsum("bhgnd,bhd->bhgn", qg + eps, k_sum + eps)
    src_out = 1.0 / jnp.einsum("bhmd,bhd->bhm", phi_k + eps, q_sum + eps)

    ko_sum = jax.lax.psum((phi_k * src_out[..., None]).sum(axis=2), axis_name)
    cons_sink = jnp.einsum("bhgnd,bhd->bhgn", qg + eps, ko_sum + eps)
    qi_sum = jax.lax.psum((qg * sink_in[..., None]).sum(axis=(2, 3)), axis_name)
    cons_src = jnp.clip(
        jnp.einsum("bhmd,bhd->bhm", phi_k + eps, qi_sum + eps), -1.0, 1.0
    )

    n_sinks = qg.shape[2] * n_tot
    if cfg.use_competition:
        # clamp bounds exp() — distributed softmax needs no running max
        e = jnp.exp(cons_src)
        z = jax.lax.psum(e.sum(axis=-1), axis_name)  # (B,Hkv)
        v_hat = vf * (e / z[..., None] * float(m_tot))[..., None]
    else:
        v_hat = vf
    if cfg.use_allocation:
        alloc = jax.nn.sigmoid(cons_sink * (float(n_sinks) / float(m_tot)))
    else:
        alloc = jnp.ones_like(cons_sink)

    kv = jax.lax.psum(
        jnp.einsum("bhmd,bhme->bhde", phi_k, v_hat), axis_name
    )  # (B,Hkv,D,Dv) — THE collective: O(d^2), independent of sequence length
    agg = jnp.einsum("bhgnd,bhde->bhgne", qg * sink_in[..., None], kv)
    return _ungroup(agg * alloc[..., None]).astype(out_dtype)


# ---------------------------------------------------------------------------
# Causal shard body: all_gather of per-device partials + local excl. prefix
# ---------------------------------------------------------------------------
def _prefix(partials: Array, idx: Array) -> Array:
    """Exclusive prefix over the leading (device) axis, select own entry."""
    csum = jnp.cumsum(partials, axis=0)
    excl = csum - partials  # exclusive prefix per device
    return excl[idx]


def _causal_shard_body(q: Array, k: Array, v: Array, cfg: FlowConfig,
                       axis_name: str, dot_fn, *, lengths: Array | None = None,
                       return_state: bool = False):
    """Sequence-parallel strictly-causal Flow-Attention (inside shard_map).

    Device p holds positions [p*Nl, (p+1)*Nl).  Cross-device coupling is the
    exclusive prefix of six small per-device partial sums; collective volume
    O(P * d^2) — independent of sequence length.  ``dot_fn`` is the
    shard-local grouped causal aggregation (injected inner strategy).

    ``return_state`` additionally returns the per-row boundary ``FlowState``
    (at ``lengths[i]-1``, or the final position when ``lengths`` is None):
    every state field is a prefix sum of per-position contributions, so the
    boundary value is one masked local sum + psum per field.
    """
    assert cfg.strict_causal, "context-parallel causal requires strict_causal"
    out_dtype = q.dtype
    eps = cfg.eps
    b, hq, nl, d = q.shape
    hkv = k.shape[1]
    idx = jax.lax.axis_index(axis_name)
    psize = jax.lax.psum(1, axis_name)

    phi_q = phi_map(q.astype(jnp.float32), cfg.phi)
    phi_k = phi_map(k.astype(jnp.float32), cfg.phi)
    vf = v.astype(jnp.float32)
    qg = _group(phi_q, hkv)
    g = qg.shape[2]

    # global positions of the local shard
    pos = (idx * nl + jnp.arange(1, nl + 1)).astype(jnp.float32)
    normal_q = pos * g
    normal_k = pos

    def dist_cumsum(x: Array) -> Array:
        """Inclusive cumsum along axis=2 of a sequence-sharded tensor."""
        local = jnp.cumsum(x, axis=2)
        part = jax.lax.all_gather(x.sum(axis=2), axis_name)  # (P, B, H, ...)
        return local + _prefix(part, idx)[:, :, None]

    k_csum = dist_cumsum(phi_k)
    q_csum = dist_cumsum(qg.sum(axis=2))
    sink_in = normal_k / jnp.einsum("bhgnd,bhnd->bhgn", qg + eps, k_csum + eps)
    src_out = normal_q / jnp.einsum("bhnd,bhnd->bhn", phi_k + eps, q_csum + eps)

    ko_csum = dist_cumsum(phi_k * src_out[..., None])
    cons_sink = jnp.einsum("bhgnd,bhnd->bhgn", qg + eps, ko_csum + eps) / normal_q
    qi_csum = dist_cumsum((qg * sink_in[..., None]).sum(axis=2))
    cons_src = jnp.clip(
        jnp.einsum("bhnd,bhnd->bhn", phi_k + eps, qi_csum + eps) / normal_k,
        -1.0,
        1.0,
    )

    alloc = jax.nn.sigmoid(cons_sink) if cfg.use_allocation else jnp.ones_like(cons_sink)
    e = jnp.exp(cons_src)
    z_local = jnp.cumsum(e, axis=-1)
    z_part = jax.lax.all_gather(e.sum(axis=-1), axis_name)
    z = z_local + _prefix(z_part, idx)[..., None]  # (B,Hkv,Nl)

    v_w = vf * e[..., None]
    # local causal dot (the inner strategy) + carried inter-device state
    q_in = qg * sink_in[..., None]
    local = dot_fn(q_in, phi_k, v_w)
    s_part = jax.lax.all_gather(
        jnp.einsum("bhnd,bhne->bhde", phi_k, v_w), axis_name
    )  # (P,B,Hkv,D,Dv)
    s_prev = _prefix(s_part, idx)
    inter = jnp.einsum("bhgnd,bhde->bhgne", q_in, s_prev)
    agg = local + inter

    out = agg * (normal_k / z)[:, :, None, :, None] * alloc[..., None]
    out = _ungroup(out).astype(out_dtype)
    if not return_state:
        return out

    # Boundary FlowState: each field is the prefix sum of per-position
    # contributions at each row's own boundary, i.e. a masked sum over
    # global positions < t — one (B,H,D)-sized psum per field.
    if lengths is None:
        t = jnp.full((b,), nl * psize, dtype=jnp.int32)
    else:
        t = lengths.astype(jnp.int32)
    pos0 = idx * nl + jnp.arange(nl)  # 0-based global positions, local shard
    valid = (pos0[None, :] < t[:, None]).astype(jnp.float32)  # (B, Nl)
    vmask = valid[:, None, :, None]  # broadcast over (B, Hkv, Nl, D)

    def masked_psum(contrib: Array) -> Array:
        return jax.lax.psum((contrib * vmask).sum(axis=2), axis_name)

    state = FlowState(
        t=t,
        q_sum=masked_psum(qg.sum(axis=2)),
        k_sum=masked_psum(phi_k),
        ko_sum=masked_psum(phi_k * src_out[..., None]),
        qi_sum=masked_psum((qg * sink_in[..., None]).sum(axis=2)),
        z=jax.lax.psum((e * valid[:, None, :]).sum(axis=-1), axis_name),
        s=jax.lax.psum(
            jnp.einsum("bhnd,bhne->bhde", phi_k * vmask, v_w), axis_name
        ),
    )
    return out, state


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------
class _ContextParallel(Backend):
    """Shared shard plumbing for the collective-glue backends."""

    shard_only = True

    def _check_shard(self, op: str, shard: ShardSpec | None, shapes, platform):
        if shard is None:
            return ("context-parallel glue requires a sharded ExecutionPlan "
                    "(no ShardSpec in this resolution)")
        if shard.mesh is None:
            return "ShardSpec has no mesh bound (resolution cannot place collectives)"
        if shard.axis not in dict(shard.mesh.shape):
            return (f"mesh has no axis {shard.axis!r} "
                    f"(axes: {tuple(dict(shard.mesh.shape))})")
        size = shard.axis_size
        if size < 2:
            return (f"axis {shard.axis!r} has size {size} — nothing to "
                    "shard (resolve without a ShardSpec instead)")
        if shapes is not None:
            if shapes.n % size:
                return (f"N={shapes.n} not divisible by the {size}-way "
                        f"axis {shard.axis!r}")
            if shapes.m % size:
                return (f"M={shapes.m} not divisible by the {size}-way "
                        f"axis {shard.axis!r}")
        return None

    def _specs(self, shard: ShardSpec):
        bax = shard.batch_axis
        return P(bax, None, shard.axis, None), P(bax)

    def _shard_shapes(self, q, k, v, cfg, shard):
        """(expanded qkv, local ShapeInfo) — kv expanded for gqa_mode="expand"
        BEFORE sharding so the shard body always runs shared-group math."""
        k, v = pipeline.expand_kv(q, k, v, cfg)
        size = shard.axis_size
        sh = ShapeInfo.from_qkv(q, k, v)
        local = dataclasses.replace(sh, n=sh.n // size, m=sh.m // size)
        return k, v, local


class ContextParallelNC(_ContextParallel):
    """Non-causal Flow-Attention with the sequence axis sharded over a mesh
    axis: the six global flow sums become psums of O(d^2) bytes each."""

    provides = frozenset({"forward"})
    differentiable = frozenset({"forward"})
    shardable = frozenset({"forward"})

    def shard_support(self, op="forward", shard=None, *, cfg=None, shapes=None,
                      platform=None):
        if op not in self.shardable:
            return False, f"does not provide sharded {op}"
        why = self._check_shard(op, shard, shapes, platform)
        if why:
            return False, why
        if shard.inner != "auto":
            return False, (
                "non-causal glue has no injectable inner strategy (the "
                "shard-local work is fixed einsums between psums; "
                f"pallas_nc fuses global sums in-kernel) — got inner="
                f"{shard.inner!r}"
            )
        return True, f"psum glue over {shard.describe()}"

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        if cfg.causal:
            return False, "non-causal glue (use cp_causal for causal plans)"
        return True, "sharded non-causal flow"

    def forward(self, q, k, v, cfg, *, shard: ShardSpec):
        k, v, _ = self._shard_shapes(q, k, v, cfg, shard)
        spec, _ = self._specs(shard)

        @functools.partial(jax.shard_map, mesh=shard.mesh,
                           in_specs=(spec, spec, spec), out_specs=spec)
        def wrapped(ql, kl, vl):
            return _nc_shard_body(ql, kl, vl, cfg, shard.axis)

        return wrapped(q, k, v)


class ContextParallelCausal(_ContextParallel):
    """Strict-causal Flow-Attention with the sequence axis sharded: local
    cumsums + an all_gather/exclusive-prefix of per-device partials, around
    a resolvable shard-local aggregation strategy (``ShardSpec.inner``).

    Provides ``prefill``/``prefill_packed``: the boundary ``FlowState`` is
    six masked psums, so seq-parallel serving admission is exact."""

    provides = frozenset({"forward", "prefill", "prefill_packed"})
    differentiable = frozenset({"forward", "prefill", "prefill_packed"})
    shardable = frozenset({"forward", "prefill", "prefill_packed"})

    def shard_support(self, op="forward", shard=None, *, cfg=None, shapes=None,
                      platform=None):
        if op not in self.shardable:
            return False, f"does not provide sharded {op}"
        why = self._check_shard(op, shard, shapes, platform)
        if why:
            return False, why
        if cfg is not None and shapes is not None and shard.axis_size:
            hkv = shapes.hq if cfg.gqa_mode == "expand" else shapes.hkv
            local = dataclasses.replace(shapes, hkv=hkv,
                                        n=shapes.n // shard.axis_size,
                                        m=shapes.m // shard.axis_size)
            try:
                inner = resolve_inner(cfg, local, platform
                                      or jax.default_backend(), shard)
            except ResolutionError as err:
                return False, f"no shard-local inner strategy: {err.rejections}"
            return True, (f"all_gather+prefix glue over {shard.describe()}, "
                          f"inner={inner.name}")
        return True, f"all_gather+prefix glue over {shard.describe()}"

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        if not cfg.causal:
            return False, "causal glue (use cp_nc for non-causal plans)"
        if shapes.n != shapes.m:
            return False, f"causal requires N == M, got N={shapes.n} M={shapes.m}"
        if not (cfg.strict_causal and cfg.use_competition):
            return False, ("no collective glue for causal: the distributed "
                           "prefix exists for the strict-causal cumulative "
                           "competition only")
        return True, "sharded strict-causal flow"

    # ------------------------------------------------------------------
    def _wrapped(self, q, k, v, cfg, shard: ShardSpec, *, packed: bool,
                 return_state: bool):
        k, v, local = self._shard_shapes(q, k, v, cfg, shard)
        platform = jax.default_backend()
        inner = resolve_inner(cfg, local, platform, shard)
        dot_fn = inner.causal_dot_fn(cfg, **run_kwargs(inner, platform))
        spec, bspec = self._specs(shard)
        state_spec = FlowState(t=bspec, q_sum=bspec, k_sum=bspec,
                               ko_sum=bspec, qi_sum=bspec, z=bspec, s=bspec)
        out_specs = (spec, state_spec) if return_state else spec
        in_specs = (spec, spec, spec) + ((bspec,) if packed else ())

        @functools.partial(jax.shard_map, mesh=shard.mesh, in_specs=in_specs,
                           out_specs=out_specs)
        def wrapped(ql, kl, vl, *rest):
            lengths = rest[0] if rest else None
            return _causal_shard_body(ql, kl, vl, cfg, shard.axis, dot_fn,
                                      lengths=lengths,
                                      return_state=return_state)

        return wrapped, (q, k, v)

    def forward(self, q, k, v, cfg, *, shard: ShardSpec):
        wrapped, args = self._wrapped(q, k, v, cfg, shard, packed=False,
                                      return_state=False)
        return wrapped(*args)

    def prefill(self, q, k, v, cfg, *, lengths=None, shard: ShardSpec):
        wrapped, args = self._wrapped(q, k, v, cfg, shard,
                                      packed=lengths is not None,
                                      return_state=True)
        if lengths is not None:
            return wrapped(*args, jnp.asarray(lengths, jnp.int32))
        return wrapped(*args)
