"""Quantized serving state pools: round-trip bounds, engine parity, gating.

The int8/fp8 pools (``serving/quant.py``) store every serving state as a
low-bit payload plus fp32 per-(slot, head) (or per-token, for positional
caches) scales.  Tests pin:

  * the leaf round-trip error bound (one half-LSB of the group's amax),
  * greedy argmax parity of the int8 engine against the fp32 engine over
    slot churn / re-admission, packed prefill and speculative rollback —
    prompts use a seed with no near-tied argmaxes (int8 rounding is
    ~1e-3 relative; a random-init smoke model has occasional 4e-4 logit
    ties that flip under ANY rounding, which is noise, not a bug),
  * named capability rejection at both registries (backend + mixer),
  * the quantized Pallas decode kernel and dequantizing paged gather in
    interpret mode against the XLA oracles,
  * the >= 3x pool-bytes saving the whole feature exists for.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.attention import recurrent
from repro.attention.registry import ResolutionError, ShapeInfo, resolve
from repro.configs import get_smoke_config
from repro.core.flow_attention import FlowConfig
from repro.layers.attention import KVCache, plan_of
from repro.layers.mixer import MixerResolutionError, resolve_mixer
from repro.models import lm
from repro.serving.engine import Engine, PagedSpec, Request
from repro.serving.quant import (
    dequantize_state,
    maybe_quantize,
    pool_bytes,
    quantize_leaf,
    quantize_state,
    spec_of,
)


# ---------------------------------------------------------------------------
# Leaf / state round trips
# ---------------------------------------------------------------------------
def test_leaf_round_trip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 2, 32)) * 5.0
    for gran in ("head", "token"):
        payload, scale = quantize_leaf(x, spec_of("int8"), gran)
        assert payload.dtype == jnp.int8
        deq = payload.astype(jnp.float32) * scale
        # rint quantization: error <= half an LSB = scale / 2 per group
        err = np.abs(np.asarray(deq - x))
        bound = np.broadcast_to(np.asarray(scale) * 0.5 + 1e-6, x.shape)
        assert (err <= bound).all()


def test_flow_state_round_trip_preserves_exempt_and_int_leaves():
    st = recurrent.init_state(3, 2, 16, 16)
    st = jax.tree.map(
        lambda a: (jax.random.normal(jax.random.PRNGKey(a.size), a.shape)
                   .astype(a.dtype) if jnp.issubdtype(a.dtype, jnp.floating)
                   else a + 7), st)
    pool = quantize_state(st, spec_of("int8"), granularity="head",
                          exempt=("z",))
    assert pool.payload.t.dtype == st.t.dtype  # integer passthrough
    assert pool.payload.z.dtype == st.z.dtype  # exempt leaf stays raw
    assert pool.payload.s.dtype == jnp.int8
    deq = dequantize_state(pool)
    np.testing.assert_array_equal(np.asarray(deq.t), np.asarray(st.t))
    np.testing.assert_array_equal(np.asarray(deq.z), np.asarray(st.z))
    # quantized leaves: within half an LSB of their per-(slot, head) amax
    for name in ("q_sum", "k_sum", "ko_sum", "qi_sum", "s"):
        a, b = np.asarray(getattr(deq, name)), np.asarray(getattr(st, name))
        sc = np.asarray(getattr(pool.scale, name))
        assert (np.abs(a - b) <= np.broadcast_to(sc * 0.5 + 1e-6,
                                                 a.shape)).all()


def test_maybe_quantize_is_identity_without_quant_plan():
    st = recurrent.init_state(2, 2, 8, 8)
    cfg = get_smoke_config("flowformer_lm")
    assert maybe_quantize(st, plan_of(cfg)) is st
    assert maybe_quantize(st, None) is st
    pool = maybe_quantize(st, plan_of(cfg, state_dtype="int8"))
    assert pool is not st and pool.exempt == ("z",)


# ---------------------------------------------------------------------------
# Engine parity: int8 pools vs fp32 pools, greedy argmax identical
# ---------------------------------------------------------------------------
def _generate(cfg, params, state_dtype, *, paged=None, spec_k=0,
              slots=2, n_req=4, max_new=6, seed=1):
    plan = plan_of(cfg, packed=True, state_dtype=state_dtype, paged=paged)
    eng = Engine(params, cfg, slots=slots, max_len=96, plan=plan,
                 dtype=jnp.float32, paged=paged, speculate_k=spec_k)
    rng = np.random.default_rng(seed)
    for i in range(n_req):
        eng.submit(Request(
            uid=i,
            prompt=rng.integers(0, cfg.vocab_size, 6 + 3 * i).astype(np.int32),
            max_new_tokens=max_new))
    done = eng.run()
    assert len(done) == n_req
    return [r.generated for r in sorted(done, key=lambda r: r.uid)]


def test_engine_int8_flow_matches_fp32_over_churn():
    """4 requests through 2 slots: packed install, decode, retirement and
    re-admission into a previously-used (stale-payload) slot."""
    cfg = get_smoke_config("flowformer_lm")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    assert (_generate(cfg, params, "int8")
            == _generate(cfg, params, None))


@pytest.mark.parametrize("kind", ["softmax", "mla", "linear"])
def test_engine_int8_positional_pools_match_fp32(kind):
    cfg = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind=kind))
    params = lm.init(jax.random.PRNGKey(0), cfg)
    assert (_generate(cfg, params, "int8")
            == _generate(cfg, params, None))


def test_engine_int8_paged_matches_fp32():
    cfg = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    params = lm.init(jax.random.PRNGKey(0), cfg)
    pg = PagedSpec(page_size=16)
    assert (_generate(cfg, params, "int8", paged=pg)
            == _generate(cfg, params, None, paged=pg))


def test_engine_int8_speculative_matches_fp32_plain():
    """Greedy speculation commits identical tokens to plain decode; the
    int8 speculative engine exercises the QuantTraj rollback (gather the
    accepted boundary fp32, quantize exactly once)."""
    cfg = get_smoke_config("flowformer_lm")
    params = lm.init(jax.random.PRNGKey(0), cfg)
    assert (_generate(cfg, params, "int8", spec_k=3)
            == _generate(cfg, params, None))


def test_engine_int8_hybrid_stack_matches_fp32():
    from repro.config import RGLRUConfig

    base = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(
        base, n_layers=3, pattern=("rglru", "rglru", "attn"),
        rglru=RGLRUConfig(conv_width=4, lru_width=0, n_blocks=4))
    params = lm.init(jax.random.PRNGKey(0), cfg)
    assert (_generate(cfg, params, "int8")
            == _generate(cfg, params, None))


# ---------------------------------------------------------------------------
# Capability gating: named rejections, never a silent dequantize
# ---------------------------------------------------------------------------
def test_registry_rejects_fp8_off_tpu():
    cfg = FlowConfig(causal=True, strict_causal=True, use_competition=True)
    shapes = ShapeInfo(b=2, hq=4, hkv=4, n=1, m=1, d=16, dv=16)
    with pytest.raises(ResolutionError, match="TPU-only"):
        resolve(cfg, shapes, "cpu", op="decode", quant="fp8")
    # int8 decode resolves everywhere (recurrent's deq->fp32->req path)
    be = resolve(cfg, shapes, "cpu", op="decode", quant="int8")
    assert be.quant_capable("cpu", "int8", op="decode")[0]


def test_mixer_rejects_unquantizable_local_rings():
    cfg = get_smoke_config("recurrentgemma_9b")
    cfg = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    assert "local" in cfg.pattern
    plan = plan_of(cfg, state_dtype="int8")
    with pytest.raises(MixerResolutionError, match="quant_capable"):
        resolve_mixer("local", cfg, plan)


# ---------------------------------------------------------------------------
# Kernels (interpret mode): quantized decode + dequantizing paged gather
# ---------------------------------------------------------------------------
def test_flow_decode_q_step_matches_dequantized_oracle():
    from repro.kernels.flow_decode import flow_decode_q_step

    b, hq, hkv, d, dv = 3, 4, 2, 16, 16
    cfg = FlowConfig(causal=True, strict_causal=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    st = recurrent.init_state(b, hkv, d, dv)
    st = st._replace(
        t=jnp.array([3, 1, 5], jnp.int32),
        q_sum=jax.random.normal(keys[0], st.q_sum.shape) * 2,
        k_sum=jax.random.normal(keys[1], st.k_sum.shape) * 2,
        ko_sum=jax.random.normal(keys[2], st.ko_sum.shape),
        qi_sum=jax.random.normal(keys[3], st.qi_sum.shape),
        z=jnp.abs(jax.random.normal(keys[4], st.z.shape)) + 1.0,
        s=jax.random.normal(keys[5], st.s.shape) * 3,
    )
    pool = quantize_state(st, spec_of("int8"), granularity="head",
                          exempt=("z",))
    q = jax.random.normal(keys[6], (b, hq, 1, d), jnp.float32)
    k = jax.random.normal(keys[7], (b, hkv, 1, d), jnp.float32)
    v = jax.random.normal(keys[0], (b, hkv, 1, dv), jnp.float32)

    new_pool, out = flow_decode_q_step(pool, q, k, v, cfg, interpret=True)
    # oracle: identical fp32 math from the dequantized carry-in
    ref_state, ref_out = recurrent.decode_step(
        dequantize_state(pool), q, k, v, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-4)
    deq = dequantize_state(new_pool)
    np.testing.assert_array_equal(np.asarray(deq.t), np.asarray(ref_state.t))
    np.testing.assert_allclose(np.asarray(deq.z), np.asarray(ref_state.z),
                               rtol=1e-5, atol=1e-5)
    for name in ("q_sum", "k_sum", "ko_sum", "qi_sum", "s"):
        a = np.asarray(getattr(deq, name))
        r = np.asarray(getattr(ref_state, name))
        sc = np.asarray(getattr(new_pool.scale, name))
        # within one LSB of the kernel's fresh per-(slot, head) scale
        assert (np.abs(a - r) <= np.broadcast_to(sc + 1e-5, a.shape)).all(), \
            name


def test_paged_gather_quant_interpret_matches_xla():
    import functools

    from repro.kernels import gather

    p, hkv, page, d = 6, 2, 8, 16
    kc = jax.random.normal(jax.random.PRNGKey(0), (p, hkv, page, d))
    vc = jax.random.normal(jax.random.PRNGKey(1), (p, hkv, page, d))
    kq, ks = quantize_leaf(kc, spec_of("int8"), "token")
    vq, vs = quantize_leaf(vc, spec_of("int8"), "token")
    table = jnp.array([[0, 3, 6], [5, 1, 6]], jnp.int32)  # 6 == sentinel

    kernel = functools.partial(gather.paged_gather_quant, interpret=True)
    kernel_fp = functools.partial(gather.paged_gather, interpret=True)
    # the XLA gathers AND the Pallas kernels
    for gather_q, gather_fp in ((gather.paged_gather_quant_xla,
                                 gather.paged_gather_xla),
                                (kernel, kernel_fp)):
        kg, vg = gather_q(kq, vq, ks, vs, table, out_dtype=jnp.float32)
        assert kg.shape == (2, hkv, 3 * page, d)
        # dequantized gather == full-precision gather of the dequantized
        # pool (same clamped page semantics)
        kd = kq.astype(jnp.float32) * ks
        vd = vq.astype(jnp.float32) * vs
        rk, rv = gather_fp(kd, vd, table)
        np.testing.assert_allclose(np.asarray(kg), np.asarray(rk), atol=1e-6)
        np.testing.assert_allclose(np.asarray(vg), np.asarray(rv), atol=1e-6)


# ---------------------------------------------------------------------------
# The capacity claim: >= 3x pool bytes saved
# ---------------------------------------------------------------------------
def test_int8_pools_at_least_3x_smaller():
    cfg = get_smoke_config("flowformer_lm")
    full = lm.init_caches(cfg, 8, 256, plan=plan_of(cfg), dtype=jnp.bfloat16)
    q8 = lm.init_caches(cfg, 8, 256, plan=plan_of(cfg, state_dtype="int8"),
                        dtype=jnp.bfloat16)
    assert pool_bytes(full) >= 3 * pool_bytes(q8), (
        pool_bytes(full), pool_bytes(q8))

    # dense softmax KV pools shrink too (the KVCache payload dominates)
    sm = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    full = lm.init_caches(sm, 8, 256, plan=plan_of(sm), dtype=jnp.bfloat16)
    q8 = lm.init_caches(sm, 8, 256, plan=plan_of(sm, state_dtype="int8"),
                        dtype=jnp.bfloat16)
    assert pool_bytes(full) >= 1.5 * pool_bytes(q8)


def test_state_dtype_bf16_fp32_override_cache_storage():
    cfg = get_smoke_config("flowformer_lm")
    sm = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    for sd, expect in (("bf16", jnp.bfloat16), ("fp32", jnp.float32)):
        caches = lm.init_caches(sm, 2, 64, plan=plan_of(sm, state_dtype=sd),
                                dtype=jnp.bfloat16)
        kv = next(c for c in caches if isinstance(c, KVCache))
        assert kv.k.dtype == expect, sd
