"""Unified attention layer: flow (the paper) / softmax / linear / local.

One weight structure per arch; ``cfg.attention.kind`` switches the mechanism
(Flow-Attention is a drop-in replacement — no extra parameters, paper §4.3).

Modes:
  * ``full``     — whole sequence, no cache (train / encoder).
  * ``prefill``  — whole prompt, returns a decode cache.
  * ``decode``   — one token + cache.

Caches:
  * flow/linear  — O(d^2) recurrent state (``repro/attention/recurrent.py``),
                   constant in context length: why `long_500k` decode is cheap.

Flow execution (which kernel/scan realizes the math) is resolved by the
``repro/attention`` backend registry from one ``ExecutionPlan`` built at
module-construction time (``plan_of``) — mesh/axis sharding, packed
admission and the paged-cache option ride the plan instead of per-call
kwargs; this layer never names an execution path.
  * softmax      — dense KV cache (B, Hkv, L, D) written at position t.
  * local        — ring-buffer KV cache of window size W.
  * MLA+softmax  — compressed latent cache (B, L, kv_lora+rope) with the
                   absorbed-matmul decode form (DeepSeek-V2 §2.1).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import attention as flow_backend
from repro.attention import BoundExecutor, ExecutionPlan, ShardSpec, init_state
from repro.config import ModelConfig
from repro.core.flow_attention import FlowConfig, phi_map
from repro.layers import mixer as mixer_lib
from repro.layers.linear import dense, dense_init
from repro.layers.rope import apply_mrope, apply_rope
from repro.serving import quant as quant_lib
from repro.serving.paged import PagedKVCache, PagedSpec, pages_for
from repro.utils import KeySeq

Array = jax.Array


class KVCache(NamedTuple):
    k: Array  # (B, Hkv, L, D)
    v: Array  # (B, Hkv, L, Dv)
    pos: Array  # (B,) int32 — tokens written per slot


class LinearState(NamedTuple):
    s: Array  # (B, Hkv, D, Dv)
    z: Array  # (B, Hkv, D)
    pos: Array  # (B,)


class MLACache(NamedTuple):
    c_kv: Array  # (B, L, kv_lora)
    k_rope: Array  # (B, L, rope_dim)
    pos: Array  # (B,)


def flow_cfg_of(cfg: ModelConfig, causal: bool) -> FlowConfig:
    a = cfg.attention
    return FlowConfig(
        phi=a.phi,
        causal=causal,
        strict_causal=a.strict_causal,
        use_competition=a.use_competition,
        use_allocation=a.use_allocation,
        chunk_size=a.chunk_size,
        gqa_mode=a.gqa_mode,
        backend=a.backend,
    )


def plan_of(cfg: ModelConfig, *, causal: bool = True,
            shard: ShardSpec | None = None, paged=None, packed: bool = False,
            needs_grad: bool = False, platform: str | None = None,
            speculate_k: int = 0,
            state_dtype: str | None = None) -> ExecutionPlan:
    """Build the model-level ``ExecutionPlan`` ONCE (engine/step
    construction time) instead of re-threading backend pins / ``paged=`` /
    mesh axes as per-call kwargs.  ``flow`` is derived from
    ``cfg.attention``; layers re-derive it per block anyway (hybrid stacks
    flip ``causal``/kind per slot), so the plan's job is carrying the
    execution context: shard placement, packed admission, paged caches,
    gradient needs, the speculative verify window (``speculate_k``), and
    the serving state-pool dtype (``state_dtype``: None/"bf16"/"fp32"
    keep full precision, "int8"/"fp8" quantize every pool)."""
    return ExecutionPlan(flow=flow_cfg_of(cfg, causal), shard=shard,
                         paged=paged, packed=packed, needs_grad=needs_grad,
                         platform=platform, speculate_k=speculate_k,
                         state_dtype=state_dtype)


@functools.lru_cache(maxsize=64)
def _local_cfg(cfg: ModelConfig) -> ModelConfig:
    # hybrid archs run "local" pattern slots as local sliding-window
    # attention under softmax mode, and as flow attention in flow mode
    # (the paper's replacement)
    if cfg.attention.kind == "flow":
        return cfg
    att = dataclasses.replace(cfg.attention, kind="local")
    return dataclasses.replace(cfg, attention=att)


def dataclass_replace_attn(cfg: ModelConfig, kind: str) -> ModelConfig:
    """Narrow a model config to one attention pattern slot ("attn"/"local")."""
    if kind == "local":
        return _local_cfg(cfg)
    return cfg


def _flow_executor(cfg: ModelConfig, causal: bool,
                   plan: ExecutionPlan | None) -> BoundExecutor:
    """Executor for one attention block: the block's FlowConfig (from
    ``cfg.attention`` + this call's causality) under the plan's execution
    context.  With no plan this is exactly the legacy per-call behavior."""
    fc = flow_cfg_of(cfg, causal)
    if plan is None:
        return BoundExecutor(ExecutionPlan(flow=fc))
    return BoundExecutor(dataclasses.replace(plan, flow=fc))


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    d, hd = cfg.d_model, cfg.dim_head
    nq, nkv = cfg.n_heads, cfg.kv_heads
    if cfg.mla is not None:
        m = cfg.mla
        qdim = nq * (m.nope_head_dim + m.rope_head_dim)
        p = {
            "kv_down": dense_init(ks(), d, m.kv_lora_rank + m.rope_head_dim),
            "kv_up": dense_init(
                ks(), m.kv_lora_rank, nq * (m.nope_head_dim + m.v_head_dim)
            ),
            "wo": dense_init(ks(), nq * m.v_head_dim, d),
        }
        if m.q_lora_rank:
            p["q_down"] = dense_init(ks(), d, m.q_lora_rank)
            p["q_up"] = dense_init(ks(), m.q_lora_rank, qdim)
        else:
            p["wq"] = dense_init(ks(), d, qdim)
        return p
    return {
        "wq": dense_init(ks(), d, nq * hd),
        "wk": dense_init(ks(), d, nkv * hd),
        "wv": dense_init(ks(), d, nkv * hd),
        "wo": dense_init(ks(), nq * hd, d),
    }


def _split_heads(x: Array, n_heads: int) -> Array:
    b, n, _ = x.shape
    return x.reshape(b, n, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x: Array) -> Array:
    b, h, n, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)


# ---------------------------------------------------------------------------
# QKV projections (standard + MLA)
# ---------------------------------------------------------------------------
def _project_qkv(params, x: Array, cfg: ModelConfig, positions):
    """Returns per-head q, k, v with positional encoding applied."""
    if cfg.mla is not None:
        return _project_qkv_mla(params, x, cfg, positions)
    from repro.distribution.act_sharding import constrain_heads

    q = constrain_heads(_split_heads(dense(params["wq"], x), cfg.n_heads))
    k = constrain_heads(_split_heads(dense(params["wk"], x), cfg.kv_heads))
    v = constrain_heads(_split_heads(dense(params["wv"], x), cfg.kv_heads))
    q, k = _apply_positions(q, k, cfg, positions)
    return q, k, v


def _apply_positions(q, k, cfg: ModelConfig, positions):
    if positions is None or cfg.rope in ("none", "learned"):
        return q, k
    if cfg.rope == "rope":
        return (
            apply_rope(q, positions, theta=cfg.rope_theta),
            apply_rope(k, positions, theta=cfg.rope_theta),
        )
    if cfg.rope == "mrope":
        return (
            apply_mrope(q, positions, cfg.mrope_sections, theta=cfg.rope_theta),
            apply_mrope(k, positions, cfg.mrope_sections, theta=cfg.rope_theta),
        )
    raise ValueError(cfg.rope)


def _project_qkv_mla(params, x: Array, cfg: ModelConfig, positions):
    """DeepSeek-V2 MLA, decompressed form: per-head q/k = [nope | rope]."""
    m = cfg.mla
    nq = cfg.n_heads
    if m.q_lora_rank:
        q = dense(params["q_up"], dense(params["q_down"], x))
    else:
        q = dense(params["wq"], x)
    q = _split_heads(q, nq)  # (B, H, N, nope+rope)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)

    ckv = dense(params["kv_down"], x)  # (B, N, kv_lora + rope)
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    kv = dense(params["kv_up"], c_kv)  # (B, N, nq*(nope+v))
    kv = _split_heads(kv, nq)
    k_nope, v = jnp.split(kv, [m.nope_head_dim], axis=-1)
    k_rope = k_rope[:, None]  # single shared rope head (B,1,N,rope)

    if positions is not None and cfg.rope != "none":
        q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)
        k_rope = apply_rope(k_rope, positions, theta=cfg.rope_theta)
    k_rope = jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], m.rope_head_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    return q, k, v


# ---------------------------------------------------------------------------
# Mechanisms
# ---------------------------------------------------------------------------
def _softmax_attn(q, k, v, *, causal: bool, softcap: float = 0.0,
                  q_offset: int | Array = 0, kv_len: Array | None = None) -> Array:
    """GQA softmax attention; O(n*m).  q:(B,Hq,N,D) k,v:(B,Hkv,M,*)."""
    b, hq, n, d = q.shape
    hkv, m = k.shape[1], k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, n, d)
    logits = jnp.einsum(
        "bhgnd,bhmd->bhgnm", qg, k, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    if causal:
        qpos = jnp.arange(n) + q_offset
        mask = qpos[:, None] >= jnp.arange(m)[None, :]
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if kv_len is not None:
        valid = jnp.arange(m)[None, :] < kv_len
        logits = jnp.where(valid[:, None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgnm,bhme->bhgne", w, v)
    return out.reshape(b, hq, n, -1)


def _local_attn(q, k, v, *, window: int, softcap: float = 0.0) -> Array:
    """Sliding-window causal attention (band mask), O(n*W) via chunking."""
    b, hq, n, d = q.shape
    if n <= window:
        return _softmax_attn(q, k, v, causal=True, softcap=softcap)
    # chunk into window-sized blocks; each attends to itself + previous block
    hkv = k.shape[1]
    w = window
    assert n % w == 0, f"seq {n} must be divisible by window {w}"
    nc = n // w
    def pad(t):
        return jnp.concatenate([jnp.zeros_like(t[:, :, :w]), t], axis=2)

    kp, vp = pad(k), pad(v)
    qc = q.reshape(b, hq, nc, w, d)
    kc = jnp.stack([kp[:, :, i * w : (i + 2) * w] for i in range(nc)], axis=2)
    vc = jnp.stack([vp[:, :, i * w : (i + 2) * w] for i in range(nc)], axis=2)
    g = hq // hkv
    qg = qc.reshape(b, hkv, g, nc, w, d)
    logits = jnp.einsum(
        "bhgcnd,bhcmd->bhgcnm", qg, kc, preferred_element_type=jnp.float32
    ) * (d**-0.5)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(w)[:, None] + w  # position within [prev | cur] band
    kpos = jnp.arange(2 * w)[None, :]
    mask = (qpos >= kpos) & (kpos > qpos - w)
    first = jnp.arange(2 * w)[None, :] >= w  # first chunk's "prev" is padding
    mask0 = mask & first
    cmask = jnp.where(
        (jnp.arange(nc) == 0)[:, None, None], mask0[None], mask[None]
    )  # (nc, w, 2w)
    logits = jnp.where(cmask[None, None, None], logits, -1e30)
    wts = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgcnm,bhcme->bhgcne", wts, vc)
    return out.reshape(b, hq, n, -1)


def _linear_attn(q, k, v, *, causal: bool, phi: str = "elu1",
                 chunk_size: int = 128, eps: float = 1e-6) -> Array:
    """Katharopoulos et al. linear attention — the paper's ablation baseline."""
    b, hq, n, d = q.shape
    hkv = k.shape[1]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    pq = phi_map(q.astype(jnp.float32), phi)
    pk = phi_map(k.astype(jnp.float32), phi)
    vf = v.astype(jnp.float32)
    if causal:
        num = flow_backend.causal_dot(pq, pk, vf, chunk_size)
        den = jnp.einsum("bhnd,bhnd->bhn", pq, jnp.cumsum(pk, axis=2))
    else:
        kv = jnp.einsum("bhmd,bhme->bhde", pk, vf)
        num = jnp.einsum("bhnd,bhde->bhne", pq, kv)
        den = jnp.einsum("bhnd,bhd->bhn", pq, pk.sum(axis=2))
    return (num / (den[..., None] + eps)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer entry points
# ---------------------------------------------------------------------------
def attention(
    params,
    x: Array,
    cfg: ModelConfig,
    *,
    causal: bool,
    positions: Array | None = None,
    kv_input: Array | None = None,  # cross-attention memory (enc-dec)
    plan: ExecutionPlan | None = None,
) -> Array:
    """Full-sequence attention (train / encode).  x: (B, N, d_model)."""
    kind = cfg.attention.kind
    from repro.distribution.act_sharding import constrain_heads

    src = x if kv_input is None else kv_input
    if cfg.mla is None:
        q = constrain_heads(_split_heads(dense(params["wq"], x), cfg.n_heads))
        k = constrain_heads(_split_heads(dense(params["wk"], src), cfg.kv_heads))
        v = constrain_heads(_split_heads(dense(params["wv"], src), cfg.kv_heads))
        if kv_input is None:
            q, k = _apply_positions(q, k, cfg, positions)
    else:
        assert kv_input is None, "MLA cross-attention not used by any arch"
        q, k, v = _project_qkv_mla(params, x, cfg, positions)

    if kind == "flow":
        out = _flow_executor(cfg, causal, plan).forward(q, k, v)
    elif kind == "softmax":
        out = _softmax_attn(q, k, v, causal=causal, softcap=cfg.attention.softcap)
    elif kind == "local":
        out = _local_attn(q, k, v, window=cfg.attention.window,
                          softcap=cfg.attention.softcap)
    elif kind == "linear":
        out = _linear_attn(q, k, v, causal=causal, phi="elu1",
                           chunk_size=cfg.attention.chunk_size)
    else:
        raise ValueError(kind)
    return dense(params["wo"], _merge_heads(out))


def _attn_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16, *, paged: PagedSpec | None = None):
    """Decode-cache for one layer.

    ``paged`` switches standard softmax KV layers to a ``PagedKVCache``
    pool (see ``repro/serving/paged.py``); model-level callers carry the
    spec on their ``ExecutionPlan`` and ``lm.init_caches`` unfolds it.
    Flow/linear states and the bounded local ring buffer are unaffected,
    and MLA keeps its compressed dense cache (already ~an order of
    magnitude smaller than raw KV).
    """
    kind = cfg.attention.kind
    hd, nkv = cfg.dim_head, cfg.kv_heads
    if (paged is not None and kind == "softmax" and cfg.mla is None):
        p = paged.num_pages or batch * pages_for(max_len, paged.page_size)
        return PagedKVCache(
            k=jnp.zeros((p, nkv, paged.page_size, hd), dtype),
            v=jnp.zeros((p, nkv, paged.page_size, hd), dtype),
            pos=jnp.zeros((batch,), jnp.int32),
        )
    if cfg.mla is not None:
        m = cfg.mla
        if kind == "flow":
            return init_state(batch, cfg.n_heads, m.nope_head_dim + m.rope_head_dim,
                              m.v_head_dim)
        return MLACache(
            c_kv=jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            k_rope=jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
            pos=jnp.zeros((batch,), jnp.int32),
        )
    if kind == "flow":
        return init_state(batch, nkv, hd, hd)
    if kind == "linear":
        return LinearState(
            s=jnp.zeros((batch, cfg.n_heads, hd, hd), jnp.float32),
            z=jnp.zeros((batch, cfg.n_heads, hd), jnp.float32),
            pos=jnp.zeros((batch,), jnp.int32),
        )
    win = cfg.attention.window if kind == "local" else max_len
    cache_len = min(win, max_len) if kind == "local" else max_len
    return KVCache(
        k=jnp.zeros((batch, nkv, cache_len, hd), dtype),
        v=jnp.zeros((batch, nkv, cache_len, hd), dtype),
        pos=jnp.zeros((batch,), jnp.int32),
    )


def _attention_decode(
    params,
    x: Array,
    cache,
    cfg: ModelConfig,
    *,
    positions: Array | None = None,
    page_table: Array | None = None,
    plan: ExecutionPlan | None = None,
):
    """One-token decode.  x: (B, 1, d_model) -> (out, new_cache).

    ``page_table`` (B, pages_per_slot) maps slots to pool pages when
    ``cache`` is a ``PagedKVCache`` (ignored otherwise); sentinel entries
    (== num_pages) drop writes and read masked-off garbage.
    """
    kind = cfg.attention.kind
    if cfg.mla is not None and kind != "flow":
        return _mla_decode_absorbed(params, x, cache, cfg, positions)

    q, k, v = _project_qkv(params, x, cfg, positions)

    pool = cache if isinstance(cache, quant_lib.QuantizedPool) else None
    store = pool.payload if pool is not None else cache
    if isinstance(store, PagedKVCache):
        return _paged_decode(params, q, k, v, cache, cfg, page_table,
                             mixer_lib.plan_platform(plan))

    if kind == "flow":
        # quantized pools pass straight through: the registry decode op is
        # quant-aware (pallas_decode dequantizes/requantizes in-kernel,
        # recurrent around the fp32 update)
        ex = _flow_executor(cfg, True, plan)
        new_state, out = ex.decode_step(cache, q, k, v)
        return dense(params["wo"], _merge_heads(out)), new_state
    if kind == "linear":
        st = quant_lib.dequantize_state(pool) if pool is not None else cache
        pq = phi_map(q.astype(jnp.float32), "elu1")[:, :, 0]
        pk = phi_map(k.astype(jnp.float32), "elu1")[:, :, 0]
        if cfg.n_heads != cfg.kv_heads:
            rep = cfg.n_heads // cfg.kv_heads
            pk = jnp.repeat(pk, rep, axis=1)
            vv = jnp.repeat(v, rep, axis=1)
        else:
            vv = v
        s = st.s + jnp.einsum("bhd,bhe->bhde", pk, vv[:, :, 0].astype(jnp.float32))
        z = st.z + pk
        num = jnp.einsum("bhd,bhde->bhe", pq, s)
        den = jnp.einsum("bhd,bhd->bh", pq, z) + 1e-6
        out = (num / den[..., None])[:, :, None].astype(x.dtype)
        new_state = LinearState(s, z, st.pos + 1)
        if pool is not None:
            # constant-size state, fully rewritten: requantize whole with a
            # fresh per-(slot, head) amax
            new_state = quant_lib.quantize_like(pool, new_state)
        return dense(params["wo"], _merge_heads(out)), new_state

    # softmax / local: write to (ring) cache then attend.  pos is per
    # slot, so writes scatter at each row's own index (continuous batching).
    t = store.pos  # (B,)
    b = x.shape[0]
    cache_len = store.k.shape[2]
    idx = t % cache_len if kind == "local" else jnp.minimum(t, cache_len - 1)
    rows = jnp.arange(b)
    if pool is not None:
        # append-only per-token quantization: this token's K/V rows get
        # their own scale and land in payload + scale pools by the same
        # scatter; prior positions are never re-rounded
        kq, ks = quant_lib.quantize_leaf(k[:, :, 0], pool.spec, "token")
        vq, vs = quant_lib.quantize_leaf(v[:, :, 0], pool.spec, "token")
        kc = store.k.at[rows, :, idx].set(kq)
        vc = store.v.at[rows, :, idx].set(vq)
        ksc = pool.scale.k.at[rows, :, idx].set(ks)
        vsc = pool.scale.v.at[rows, :, idx].set(vs)
        ka = (kc.astype(jnp.float32) * ksc).astype(q.dtype)
        va = (vc.astype(jnp.float32) * vsc).astype(q.dtype)
        new_cache = pool.with_state(KVCache(kc, vc, t + 1),
                                    KVCache(ksc, vsc, pool.scale.pos))
    else:
        kc = store.k.at[rows, :, idx].set(k[:, :, 0].astype(store.k.dtype))
        vc = store.v.at[rows, :, idx].set(v[:, :, 0].astype(store.v.dtype))
        ka, va = kc, vc
        new_cache = KVCache(kc, vc, t + 1)
    kv_len = jnp.minimum(t + 1, cache_len)  # (B,)
    out = _softmax_attn(
        q, ka, va, causal=False, softcap=cfg.attention.softcap,
        kv_len=kv_len[:, None],
    )
    return dense(params["wo"], _merge_heads(out)), new_cache


def _paged_decode(params, q, k, v, cache, cfg: ModelConfig,
                  page_table: Array | None, platform: str):
    """Softmax decode on the paged pool: scatter this token's K/V into the
    slot's current page, attend over the gathered page sequence.

    ``cache`` may be a ``QuantizedPool`` over a ``PagedKVCache``: the
    token's rows quantize once on append (per-token scales scatter into a
    mirrored scale pool) and the page-table gather dequantizes inline
    (``paged_gather_quant``).  On TPU the page-table gathers are Pallas
    kernels writing the (B, Hkv, MP*page, D) layout directly; on any other
    ``platform`` they are plain XLA gathers."""
    # flowlint: disable=FL001 -- utility gathers below the registry; the plan's platform picks one
    from repro.kernels import gather

    on_tpu = platform == "tpu"
    assert page_table is not None, "paged decode requires the page table"
    pool = cache if isinstance(cache, quant_lib.QuantizedPool) else None
    store = pool.payload if pool is not None else cache
    b = q.shape[0]
    t = store.pos  # (B,)
    page = store.k.shape[2]
    max_pages = page_table.shape[1]
    rows = jnp.arange(b)
    # clamp the POSITION (not just the page index) so writes past the slot
    # capacity land on the last in-page offset — mirroring the dense
    # end-of-cache clamp instead of wrapping onto attended context
    tc = jnp.minimum(t, max_pages * page - 1)  # (B,)
    pid = page_table[rows, tc // page]  # (B,)
    off = tc % page
    # sentinel pids are out of range: the scatter drops them (dead slots)
    if pool is not None:
        kq, ks = quant_lib.quantize_leaf(k[:, :, 0], pool.spec, "token")
        vq, vs = quant_lib.quantize_leaf(v[:, :, 0], pool.spec, "token")
        kc = store.k.at[pid, :, off].set(kq)
        vc = store.v.at[pid, :, off].set(vq)
        ksc = pool.scale.k.at[pid, :, off].set(ks)
        vsc = pool.scale.v.at[pid, :, off].set(vs)
        gather_q = (gather.paged_gather_quant if on_tpu
                    else gather.paged_gather_quant_xla)
        kg, vg = gather_q(kc, vc, ksc, vsc, page_table, out_dtype=q.dtype)
        new_cache = pool.with_state(PagedKVCache(kc, vc, t + 1),
                                    PagedKVCache(ksc, vsc, pool.scale.pos))
    else:
        kc = store.k.at[pid, :, off].set(k[:, :, 0].astype(store.k.dtype))
        vc = store.v.at[pid, :, off].set(v[:, :, 0].astype(store.v.dtype))
        # logical per-slot cache = its pages in table order; sentinel
        # gathers clamp into garbage that kv_len masks off
        gather_fn = gather.paged_gather if on_tpu else gather.paged_gather_xla
        kg, vg = gather_fn(kc, vc, page_table)
        new_cache = PagedKVCache(kc, vc, t + 1)
    kv_len = jnp.minimum(t + 1, max_pages * page)  # (B,)
    out = _softmax_attn(
        q, kg, vg, causal=False, softcap=cfg.attention.softcap,
        kv_len=kv_len[:, None],
    )
    return dense(params["wo"], _merge_heads(out)), new_cache


def _mla_decode_absorbed(params, x, cache, cfg: ModelConfig, positions):
    """MLA decode on the compressed cache (absorbed matmuls, DeepSeek-V2).

    ``cache`` may be a ``QuantizedPool`` over an ``MLACache``: the token's
    latent row quantizes once on append (per-token scale) and the whole
    cache dequantizes for the absorbed matmuls."""
    m = cfg.mla
    nq = cfg.n_heads
    b = x.shape[0]
    pool = cache if isinstance(cache, quant_lib.QuantizedPool) else None
    store = pool.payload if pool is not None else cache
    if m.q_lora_rank:
        q = dense(params["q_up"], dense(params["q_down"], x))
    else:
        q = dense(params["wq"], x)
    q = _split_heads(q, nq)  # (B,H,1,nope+rope)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)

    ckv_t = dense(params["kv_down"], x)  # (B,1,kv_lora+rope)
    c_t, krope_t = jnp.split(ckv_t, [m.kv_lora_rank], axis=-1)
    if positions is not None and cfg.rope != "none":
        q_rope = apply_rope(q_rope, positions, theta=cfg.rope_theta)
        krope_t = apply_rope(krope_t[:, None], positions, theta=cfg.rope_theta)[:, 0]

    t = store.pos  # (B,)
    rows = jnp.arange(b)
    idx = jnp.minimum(t, store.c_kv.shape[1] - 1)
    if pool is not None:
        cq, cs = quant_lib.quantize_leaf(c_t[:, 0], pool.spec, "token")
        rq, rs = quant_lib.quantize_leaf(krope_t[:, 0], pool.spec, "token")
        c_store = store.c_kv.at[rows, idx].set(cq)
        r_store = store.k_rope.at[rows, idx].set(rq)
        c_sc = pool.scale.c_kv.at[rows, idx].set(cs)
        r_sc = pool.scale.k_rope.at[rows, idx].set(rs)
        c_kv = (c_store.astype(jnp.float32) * c_sc).astype(x.dtype)
        k_rope = (r_store.astype(jnp.float32) * r_sc).astype(x.dtype)
        new_cache = pool.with_state(
            MLACache(c_store, r_store, t + 1),
            MLACache(c_sc, r_sc, pool.scale.pos))
    else:
        c_kv = store.c_kv.at[rows, idx].set(c_t[:, 0].astype(store.c_kv.dtype))
        k_rope = store.k_rope.at[rows, idx].set(
            krope_t[:, 0].astype(store.k_rope.dtype)
        )
        new_cache = MLACache(c_kv, k_rope, t + 1)

    # absorb kv_up into the query:  W_up maps kv_lora -> H*(nope+v)
    w_up = params["kv_up"]["w"].reshape(m.kv_lora_rank, nq, m.nope_head_dim + m.v_head_dim)
    w_uk = w_up[:, :, : m.nope_head_dim]  # (lora, H, nope)
    w_uv = w_up[:, :, m.nope_head_dim :]  # (lora, H, v)
    q_abs = jnp.einsum("bhnd,lhd->bhnl", q_nope, w_uk.astype(q_nope.dtype))
    scores = jnp.einsum(
        "bhnl,bml->bhnm", q_abs, c_kv.astype(q_abs.dtype),
        preferred_element_type=jnp.float32,
    )
    scores += jnp.einsum(
        "bhnd,bmd->bhnm", q_rope, k_rope.astype(q_rope.dtype),
        preferred_element_type=jnp.float32,
    )
    scores = scores * ((m.nope_head_dim + m.rope_head_dim) ** -0.5)
    valid = jnp.arange(c_kv.shape[1])[None, :] <= t[:, None]
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(c_kv.dtype)
    ctx = jnp.einsum("bhnm,bml->bhnl", w, c_kv)  # (B,H,1,lora)
    out = jnp.einsum("bhnl,lhe->bhne", ctx, w_uv.astype(ctx.dtype))
    return dense(params["wo"], _merge_heads(out)), new_cache


def _attention_prefill(
    params, x: Array, cfg: ModelConfig, max_len: int, *,
    positions: Array | None = None, lengths: Array | None = None,
    plan: ExecutionPlan | None = None,
):
    """Prompt prefill returning (out, cache) for subsequent decode.

    ``lengths`` (B,) serves a right-padded batch of prompts in one call
    (the engine's packed admission): causality keeps every true position
    exact, per-row cache state lands at each row's own boundary, and
    outputs at padded positions are garbage the caller never reads.  Local
    attention's ring buffer has no per-row packed form and rejects it.
    """
    kind = cfg.attention.kind
    b, n, _ = x.shape
    q, k, v = _project_qkv(params, x, cfg, positions)
    if kind == "flow":
        ex = _flow_executor(cfg, True, plan)
        out, state = ex.prefill(q, k, v, lengths=lengths)
        return dense(params["wo"], _merge_heads(out)), state
    pos0 = (jnp.full((b,), n, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))
    if kind == "linear":
        out = _linear_attn(q, k, v, causal=True, chunk_size=cfg.attention.chunk_size)
        hq = cfg.n_heads
        if hq != cfg.kv_heads:
            k = jnp.repeat(k, hq // cfg.kv_heads, axis=1)
            v = jnp.repeat(v, hq // cfg.kv_heads, axis=1)
        pk = phi_map(k.astype(jnp.float32), "elu1")
        if lengths is not None:
            pk = pk * (jnp.arange(n) < lengths[:, None]
                       ).astype(jnp.float32)[:, None, :, None]
        s = jnp.einsum("bhnd,bhne->bhde", pk, v.astype(jnp.float32))
        z = pk.sum(axis=2)
        return dense(params["wo"], _merge_heads(out)), LinearState(s, z, pos0)
    if kind == "local":
        if lengths is not None:
            # callers reach this only by skipping resolution: the mixer
            # registry reports local as non-packable and admission consults
            # that capability instead of crashing mid-prefill
            raise mixer_lib.MixerResolutionError(
                "local attention cannot satisfy packed prefill — missing "
                "capability packable: per-row ring alignment is "
                "length-dependent",
                (("local", "packable", "per-row ring alignment"),),
            )
        out = _local_attn(q, k, v, window=cfg.attention.window,
                          softcap=cfg.attention.softcap)
        w = min(cfg.attention.window, max_len)
        # keep the last `w` positions in the ring buffer, aligned to n % w
        kc = jnp.zeros((b, cfg.kv_heads, w, cfg.dim_head), k.dtype)
        vc = jnp.zeros_like(kc)
        take = min(w, n)
        ks_, vs_ = k[:, :, -take:], v[:, :, -take:]
        start = (n - take) % w
        rolled_idx = (start + jnp.arange(take)) % w
        kc = kc.at[:, :, rolled_idx].set(ks_)
        vc = vc.at[:, :, rolled_idx].set(vs_)
        return dense(params["wo"], _merge_heads(out)), KVCache(
            kc, vc, jnp.full((b,), n, jnp.int32)
        )
    # softmax: dense cache
    out = _softmax_attn(q, k, v, causal=True, softcap=cfg.attention.softcap)
    if cfg.mla is not None:
        # recompute compressed latents for the cache (cheap: one matmul)
        ckv = dense(params["kv_down"], x)
        c_kv, k_rope = jnp.split(ckv, [cfg.mla.kv_lora_rank], axis=-1)
        if positions is not None and cfg.rope != "none":
            k_rope = apply_rope(k_rope[:, None], positions, theta=cfg.rope_theta)[:, 0]
        pad = max_len - n
        c_kv = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
        k_rope = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
        # cache precision follows the activations: bf16 serving keeps bf16
        # caches, fp32 parity tests get exact hand-off
        return dense(params["wo"], _merge_heads(out)), MLACache(
            c_kv.astype(x.dtype), k_rope.astype(x.dtype), pos0,
        )
    pad = max_len - n
    kc = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(x.dtype)
    vc = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))).astype(x.dtype)
    return dense(params["wo"], _merge_heads(out)), KVCache(kc, vc, pos0)


# ---------------------------------------------------------------------------
# SequenceMixer registration + legacy-name shims
# ---------------------------------------------------------------------------
class AttentionMixer(mixer_lib.Mixer):
    """The unified attention layer ("attn" pattern slots) as a registered
    sequence mixer.  ``cfg.attention.kind`` still switches the mechanism
    (flow/softmax/linear/MLA); the mixer protocol only owns the lifecycle."""

    params_field = "attn"

    def _cfg(self, cfg: ModelConfig) -> ModelConfig:
        return cfg

    def packable(self, cfg):
        sub = self._cfg(cfg)
        if sub.attention.kind == "local":
            return False, ("local ring buffers have no per-row packed form "
                           "(ring alignment is length-dependent)")
        return True, "per-row boundary caches from one padded causal call"

    def paged_capable(self, cfg):
        sub = self._cfg(cfg)
        if sub.mla is not None:
            return False, ("MLA keeps its compressed dense latent cache "
                           "(~an order smaller than raw KV)")
        if sub.attention.kind == "softmax":
            return True, "dense KV cache pages into the pool"
        if sub.attention.kind == "local":
            return False, "bounded ring buffer (nothing to page)"
        return False, ("constant-size O(d^2) recurrent state "
                       "(nothing to page)")

    def differentiable(self, cfg, platform):
        return True, ("gradient capability is judged per execution strategy "
                      "by the attention backend registry (needs_grad plans)")

    def verify_capable(self, cfg):
        sub = self._cfg(cfg)
        if sub.attention.kind == "local":
            return False, ("ring buffer overwrites history: a rejected "
                           "draft cannot be rolled back")
        if sub.attention.kind == "flow":
            return True, ("registry verify op: one carry-in pass, "
                          "trajectory FlowState rollback")
        if sub.attention.kind == "linear":
            return True, "trajectory rollback over scanned decode"
        return True, ("positional cache: rollback is per-slot position "
                      "arithmetic (stale writes are masked/overwritten)")

    def quant_capable(self, cfg, platform, dtype):
        sub = self._cfg(cfg)
        if sub.attention.kind == "local":
            return False, ("bounded window ring stays full-precision "
                           "(window-sized cache: negligible bytes to win, "
                           "and ring realignment would re-round history)")
        ok, why = quant_lib.platform_support(dtype, platform)
        if not ok:
            return False, why
        kind = sub.attention.kind
        if kind == "flow":
            return True, f"quantized FlowState pool ({why})"
        if kind == "linear":
            return True, f"dequantize/requantize around the O(d^2) update ({why})"
        if sub.mla is not None:
            return True, f"per-token quantized latent rows ({why})"
        return True, f"per-token quantized KV rows ({why})"

    def init_params(self, key, cfg):
        return attn_init(key, self._cfg(cfg))

    def forward(self, params, x, cfg, *, positions=None, plan=None):
        return attention(params, x, self._cfg(cfg), causal=True,
                         positions=positions, plan=plan)

    def state_init(self, cfg, batch, max_len, *, dtype=None, plan=None):
        paged = plan.paged if plan is not None else None
        # the plan's state_dtype outranks the activation dtype for pool
        # storage: bf16/fp32 override the cache dtype directly, int8/fp8
        # additionally wrap the fresh state in a QuantizedPool
        sd = quant_lib.state_dtype_of(plan)
        cache_dtype = {"bf16": jnp.bfloat16, "fp32": jnp.float32}.get(
            sd, dtype or jnp.bfloat16)
        st = _attn_cache_init(self._cfg(cfg), batch, max_len, cache_dtype,
                              paged=paged)
        return quant_lib.maybe_quantize(st, plan)

    def prefill(self, params, x, cfg, max_len, *, positions=None, plan=None):
        return _attention_prefill(params, x, self._cfg(cfg), max_len,
                                  positions=positions, plan=plan)

    def prefill_packed(self, params, x, cfg, max_len, lengths, *,
                       positions=None, plan=None):
        return _attention_prefill(params, x, self._cfg(cfg), max_len,
                                  positions=positions, lengths=lengths,
                                  plan=plan)

    def decode_step(self, params, x, state, cfg, *, positions=None,
                    page_table=None, plan=None):
        return _attention_decode(params, x, state, self._cfg(cfg),
                                 positions=positions, page_table=page_table,
                                 plan=plan)

    def verify_step(self, params, x, state, cfg, *, positions=None,
                    page_table=None, plan=None):
        sub = self._cfg(cfg)
        kind = sub.attention.kind
        if kind == "local":
            raise mixer_lib.MixerResolutionError(
                "local attention cannot satisfy speculative verify — "
                "missing capability verify_capable: ring buffer overwrites "
                "history",
                (("local", "verify_capable", "ring overwrite"),),
            )
        if kind == "flow":
            # one chunked carry-in pass through the registry verify op:
            # per-position outputs plus the trajectory FlowState (window
            # axis at index 1) in a single device call
            q, k, v = _project_qkv(params, x, sub, positions)
            ex = _flow_executor(sub, True, plan)
            out, traj = ex.verify_step(state, q, k, v)
            if isinstance(state, quant_lib.QuantizedPool):
                # the verify pass dequantized once at entry; carry the
                # fp32 trajectory with the pool's recipe so rollback
                # quantizes exactly once at the accepted boundary
                traj = quant_lib.QuantTraj(traj, state.spec,
                                           state.granularity, state.exempt)
            return dense(params["wo"], _merge_heads(out)), traj
        if kind == "linear":
            # constant-size state: the generic scanned-decode trajectory
            return super().verify_step(params, x, state, cfg,
                                       positions=positions,
                                       page_table=page_table, plan=plan)
        # softmax / MLA / paged: positional caches roll back by position
        # arithmetic, so stacking n cache snapshots would waste O(n * L)
        # memory — decode the window sequentially and keep only the final
        # cache as the pending state
        outs = []
        st = state
        for j in range(x.shape[1]):
            pos_j = None if positions is None else positions[..., j:j + 1]
            y, st = self.decode_step(params, x[:, j:j + 1], st, cfg,
                                     positions=pos_j, page_table=page_table,
                                     plan=plan)
            outs.append(y)
        return jnp.concatenate(outs, axis=1), st

    def select_verified(self, pending, accepted, n, cfg, *, plan=None):
        sub = self._cfg(cfg)
        kind = sub.attention.kind
        if isinstance(pending, quant_lib.QuantTraj):
            # flow verify kept the trajectory fp32: gather the accepted
            # boundary first, THEN quantize — the rollback's single
            # boundary requantization
            boundary = mixer_lib.select_from_trajectory(pending.traj,
                                                        accepted)
            return pending.quantize(boundary)
        if kind in ("flow", "linear"):
            return super().select_verified(pending, accepted, n, cfg,
                                           plan=plan)
        # positional caches (KVCache / MLACache / PagedKVCache): the window
        # wrote n tokens at positions pos-n..pos-1; accepting a+1 of them
        # rewinds pos so future decodes overwrite the stale tail, and
        # kv_len masking keeps it invisible until then
        if isinstance(pending, quant_lib.QuantizedPool):
            # quantized positional pools rewind the payload's pos; scales
            # are per-token and get overwritten with the stale tail
            acc = accepted.astype(pending.payload.pos.dtype)
            pay = pending.payload._replace(
                pos=pending.payload.pos - (n - acc - 1))
            return pending.with_state(pay, pending.scale)
        acc = accepted.astype(pending.pos.dtype)
        return pending._replace(pos=pending.pos - (n - acc - 1))


class LocalSlotMixer(AttentionMixer):
    """"local" pattern slots (RecurrentGemma): local sliding-window
    attention under softmax mode, flow attention in flow mode — the narrow
    happens here so call sites never re-derive it."""

    def _cfg(self, cfg: ModelConfig) -> ModelConfig:
        return _local_cfg(cfg)


mixer_lib.register_mixer("attn", AttentionMixer())
mixer_lib.register_mixer("local", LocalSlotMixer())


attn_cache_init = mixer_lib.make_legacy_shim(
    "attention", "attn_cache_init", _attn_cache_init, "attn", "state_init")
attention_prefill = mixer_lib.make_legacy_shim(
    "attention", "attention_prefill", _attention_prefill, "attn", "prefill")
attention_decode = mixer_lib.make_legacy_shim(
    "attention", "attention_decode", _attention_decode, "attn",
    "decode_step")
