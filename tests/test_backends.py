"""Backend registry: parity across execution strategies + resolution rules.

Every registered backend must produce the same Flow-Attention (within fp32
reassociation tolerance) wherever it self-reports applicable; resolution
must be deterministic and explain itself when nothing applies.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention
from repro.attention import FlowConfig, ShapeInfo
from repro.core.reference import flow_attention_causal_ref, flow_attention_nc_ref

from conftest import assert_close


def _qkv(key, b, hq, hkv, n, d, dv=None):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, dv or d)))


def _applicable(cfg, q, k, v, op="forward"):
    be = attention.get_backend(cfg.backend)
    ok, _ = be.supports(cfg, ShapeInfo.from_qkv(q, k, v), jax.default_backend(),
                        op=op, explicit=True)
    return ok


CAUSAL_BACKENDS = ("xla_cumsum", "xla_chunked", "pallas_chunk",
                   "pallas_fused", "fused_causal", "recurrent")
NC_BACKENDS = ("xla_cumsum", "pallas_nc")


# ---------------------------------------------------------------------------
# parity: every applicable backend agrees with the quadratic oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", CAUSAL_BACKENDS)
@pytest.mark.parametrize("strict", [False, True])
@pytest.mark.parametrize("gqa", ["shared", "expand"])
def test_causal_backend_parity(backend, strict, gqa):
    q, k, v = _qkv(0, 2, 4, 2, 64, 16)
    cfg = FlowConfig(causal=True, strict_causal=strict, chunk_size=16,
                     gqa_mode=gqa, backend=backend)
    if not _applicable(cfg, q, k, v):
        pytest.skip(f"{backend} not applicable: strict={strict} gqa={gqa}")
    out = attention.forward(q, k, v, cfg)
    ref = flow_attention_causal_ref(q, k, v, cfg)
    assert_close(out, ref, rtol=2e-3, atol=1e-4)


@pytest.mark.parametrize("backend", NC_BACKENDS)
@pytest.mark.parametrize("gqa", ["shared", "expand"])
def test_nc_backend_parity(backend, gqa):
    q, k, v = _qkv(1, 2, 4, 2, 48, 16)
    cfg = FlowConfig(gqa_mode=gqa, backend=backend)
    if not _applicable(cfg, q, k, v):
        pytest.skip(f"{backend} not applicable: gqa={gqa}")
    out = attention.forward(q, k, v, cfg)
    ref = flow_attention_nc_ref(q, k, v, cfg)
    assert_close(out, ref, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("backend", CAUSAL_BACKENDS)
def test_expand_equals_shared_at_g1(backend):
    """With Hq == Hkv the two GQA modes are the same computation."""
    q, k, v = _qkv(2, 1, 2, 2, 32, 8)
    base = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                      backend=backend)
    if not _applicable(base, q, k, v):
        pytest.skip(f"{backend} not applicable")
    a = attention.forward(q, k, v, dataclasses.replace(base, gqa_mode="shared"))
    b = attention.forward(q, k, v, dataclasses.replace(base, gqa_mode="expand"))
    assert_close(a, b, rtol=1e-4, atol=1e-5)


def _with_fused_lengths(backends, n, lengths):
    """Each backend at length ``n``, then ``fused_causal`` again at each of
    ``lengths`` (under one chunk of 16, no multiple of it)."""
    return ([pytest.param(b, n, id=b) for b in backends]
            + [pytest.param("fused_causal", m, id=f"fused_causal-n{m}")
               for m in lengths])


@pytest.mark.parametrize("backend,n", _with_fused_lengths(
    ["xla_cumsum", "xla_chunked", "fused_causal", "pallas_fused",
     "recurrent"], 32, [9, 37]))
def test_prefill_state_parity(backend, n):
    """All prefill-capable backends hand decode the same FlowState."""
    q, k, v = _qkv(3, 1, 4, 2, n, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend=backend)
    if not _applicable(cfg, q, k, v, op="prefill"):
        pytest.skip(f"{backend} prefill not applicable")
    out, state = attention.prefill(q, k, v, cfg)
    ref_out, ref_state = attention.get_backend("xla_cumsum").prefill(q, k, v, cfg)
    assert_close(out, ref_out, rtol=1e-3, atol=1e-4)
    for f in state._fields:
        assert_close(getattr(state, f).astype(jnp.float32),
                     getattr(ref_state, f).astype(jnp.float32),
                     rtol=1e-3, atol=1e-4, msg=f"state field {f}")
    # ...and decode continues identically from it
    q1, k1, v1 = _qkv(4, 1, 4, 2, 1, 8)
    s_a, o_a = attention.decode_step(state, q1, k1, v1, cfg)
    s_b, o_b = attention.decode_step(ref_state, q1, k1, v1, cfg)
    assert_close(o_a, o_b, rtol=1e-3, atol=1e-4)


def test_ablation_flags_respected_by_auto():
    """use_competition=False still resolves and matches the oracle."""
    q, k, v = _qkv(5, 1, 2, 2, 64, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     use_competition=False)
    out = attention.forward(q, k, v, cfg)
    ref = flow_attention_causal_ref(q, k, v, cfg)
    assert_close(out, ref, rtol=2e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# resolution rules
# ---------------------------------------------------------------------------
def test_auto_resolution_is_deterministic_cpu():
    q, k, v = _qkv(6, 1, 2, 2, 64, 8)
    sh = ShapeInfo.from_qkv(q, k, v)
    strict = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    assert attention.resolve(strict, sh, "cpu").name == "fused_causal"
    paper = FlowConfig(causal=True, strict_causal=False, chunk_size=16)
    assert attention.resolve(paper, sh, "cpu").name == "xla_chunked"
    nochunk = FlowConfig(causal=True, strict_causal=True, chunk_size=0)
    assert attention.resolve(nochunk, sh, "cpu").name == "xla_cumsum"
    assert attention.resolve(FlowConfig(), sh, "cpu").name == "xla_cumsum"


def test_auto_resolution_prefers_pallas_on_tpu():
    q, k, v = _qkv(7, 1, 2, 2, 64, 8)
    sh = ShapeInfo.from_qkv(q, k, v)
    strict = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    assert attention.resolve(strict, sh, "tpu").name == "pallas_fused"
    # non-strict causal: the fused kernel's contract fails, chunked wins
    paper = FlowConfig(causal=True, strict_causal=False, chunk_size=16)
    assert attention.resolve(paper, sh, "tpu").name == "pallas_chunk"
    assert attention.resolve(FlowConfig(), sh, "tpu").name == "pallas_nc"
    # legacy family selectors
    xla = dataclasses.replace(strict, backend="xla")
    assert attention.resolve(xla, sh, "tpu").name == "fused_causal"


def test_named_backend_raises_with_reason():
    q, k, v = _qkv(8, 1, 2, 2, 33, 8)  # 33: not chunkable
    sh = ShapeInfo.from_qkv(q, k, v)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend="xla_chunked")
    with pytest.raises(ValueError, match="not chunkable"):
        attention.resolve(cfg, sh, "cpu")
    with pytest.raises(ValueError, match="unknown"):
        attention.resolve(dataclasses.replace(cfg, backend="nope"), sh, "cpu")


def test_pinned_forward_backend_never_blocks_decode():
    """A forward-only pin falls back to auto for decode (serving keeps working)."""
    b, hkv, d = 1, 2, 8
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend="xla_chunked")
    state = attention.init_state(b, hkv, d, d)
    q1, k1, v1 = _qkv(9, b, 4, hkv, 1, d)
    state, out = attention.decode_step(state, q1, k1, v1, cfg)
    assert out.shape == (b, 4, 1, d)


def test_explain_covers_all_backends():
    q, k, v = _qkv(10, 1, 2, 2, 64, 8)
    rows = attention.explain(FlowConfig(causal=True, strict_causal=True),
                             ShapeInfo.from_qkv(q, k, v), "cpu")
    assert {r[0] for r in rows} == set(attention.list_backends())
    assert all(isinstance(r[2], str) and r[2] for r in rows)


def test_register_backend_duplicate_rejected():
    with pytest.raises(ValueError, match="already registered"):
        attention.register_backend("xla_cumsum",
                                   attention.get_backend("xla_cumsum"))


# ---------------------------------------------------------------------------
# batched decode kernel (pallas_decode)
# ---------------------------------------------------------------------------
def test_pallas_decode_resolution_order():
    """pallas_decode resolves ahead of recurrent for decode on TPU and
    never volunteers off-TPU (interpret must be pinned explicitly)."""
    sh = ShapeInfo(b=4, hq=4, hkv=2, n=1, m=1, d=16, dv=16)
    cfg = FlowConfig(causal=True, strict_causal=True)
    assert attention.resolve(cfg, sh, "tpu", op="decode").name == "pallas_decode"
    assert attention.resolve(cfg, sh, "cpu", op="decode").name == "recurrent"
    # the legacy pallas family pin selects it explicitly (interpret off-TPU)
    pinned = dataclasses.replace(cfg, backend="pallas")
    assert attention.resolve(pinned, sh, "cpu", op="decode").name == "pallas_decode"
    # forward auto-resolution is untouched by the decode-only backend
    fwd = ShapeInfo(b=1, hq=2, hkv=2, n=64, m=64, d=8, dv=8)
    assert attention.resolve(cfg, fwd, "tpu").name == "pallas_fused"


@pytest.mark.parametrize("gqa", ["shared", "expand"])
def test_pallas_decode_matches_recurrent_with_churn(gqa):
    """64+ decode steps of slot churn: the batched kernel tracks the
    recurrent oracle through periodic per-slot state re-installs (the
    engine's admit/retire pattern)."""
    b, hq, hkv, d, dv = 3, 4, 2, 16, 8
    base = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                      gqa_mode=gqa)
    cfg_r = dataclasses.replace(base, backend="recurrent")
    cfg_p = dataclasses.replace(base, backend="pallas_decode")
    n_state = hq if gqa == "expand" else hkv
    st_r = st_p = attention.init_state(b, n_state, d, dv)
    for step in range(68):
        q, k, v = _qkv(1000 + step, b, hq, hkv, 1, d, dv)
        st_r, o_r = attention.decode_step(st_r, q, k, v, cfg_r)
        st_p, o_p = attention.decode_step(st_p, q, k, v, cfg_p)
        assert_close(o_p, o_r, rtol=1e-4, atol=1e-5, msg=f"step {step}")
        if step % 16 == 7:  # churn: install a fresh prefill state into a slot
            qp, kp, vp = _qkv(2000 + step, 1, hq, hkv, 32, d, dv)
            _, fresh = attention.prefill(qp, kp, vp, base)
            slot = step % b
            put = lambda dst, src: dst.at[slot].set(  # noqa: E731
                src[0].astype(dst.dtype))
            st_r = jax.tree.map(put, st_r, fresh)
            st_p = jax.tree.map(put, st_p, fresh)
    for f in st_r._fields:
        assert_close(getattr(st_p, f), getattr(st_r, f), rtol=1e-4, atol=1e-5,
                     msg=f"state field {f}")


# ---------------------------------------------------------------------------
# packed prefill (prefill_packed op)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,n", _with_fused_lengths(
    ["xla_cumsum", "xla_chunked", "pallas_chunk", "fused_causal",
     "pallas_fused"], 32, [12, 37]))
def test_prefill_packed_matches_per_row_prefill(backend, n):
    """A right-padded batch prefilled in one call hands decode the same
    per-row FlowState as prefilling each prompt alone (causality keeps
    padding out of every prefix)."""
    b, hq, hkv, d = 3, 4, 2, 8
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend=backend)
    q, k, v = _qkv(11, b, hq, hkv, n, d)
    if not _applicable(cfg, q, k, v, op="prefill_packed"):
        pytest.skip(f"{backend} prefill_packed not applicable")
    lens = [min(19, n), n, 7]
    out_p, st_p = attention.prefill(q, k, v, cfg, lengths=jnp.asarray(lens))
    assert np.asarray(st_p.t).tolist() == lens
    ref_cfg = dataclasses.replace(cfg, backend="xla_cumsum")  # any length
    for i, li in enumerate(lens):
        sl = slice(i, i + 1)
        out_i, st_i = attention.prefill(q[sl, :, :li], k[sl, :, :li],
                                        v[sl, :, :li], ref_cfg)
        assert_close(out_p[sl, :, :li], out_i, rtol=1e-3, atol=1e-4,
                     msg=f"row {i} outputs")
        for f in st_i._fields:
            assert_close(getattr(st_p, f)[sl], getattr(st_i, f),
                         rtol=1e-3, atol=1e-4, msg=f"row {i} state {f}")


def test_prefill_packed_falls_back_past_pinned_fused():
    """A pinned fused_causal serves packed admission natively: boundary
    masking freezes each row's carry at its own length (no gathers)."""
    q, k, v = _qkv(12, 2, 2, 2, 16, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend="fused_causal")
    out, state = attention.prefill(q, k, v, cfg,
                                   lengths=jnp.asarray([9, 16]))
    assert np.asarray(state.t).tolist() == [9, 16]
