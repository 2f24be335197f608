"""pallas_fused kernel family (interpret=True): forward/backward parity vs
the chunk-parallel XLA pipeline, packed boundary states, padding,
resolution."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention
from repro.attention import FlowConfig, ShapeInfo
from repro.attention.fused import (effective_chunk, fused_causal_forward,
                                   padded_len)
from repro.core.flow_attention import _group, phi_map
from repro.kernels.flow_fused import (flow_fused_call, flow_fused_forward,
                                      flow_fused_ref)
from repro.kernels.flow_fused.bwd import flow_fused_bwd_call
from repro.kernels.flow_fused.flow_fused import _phi

from conftest import assert_close


def _inputs(key, bh, g, n, d, dv):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (bh, g, n, d)),
            jax.random.normal(ks[1], (bh, n, d)),
            jax.random.normal(ks[2], (bh, n, dv)))


def _qkv(key, b, hq, hkv, n, d, dv=None):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, dv or d)))


# ---------------------------------------------------------------------------
# kernel vs jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [8, 16, 32, 64])
@pytest.mark.parametrize("masked", [False, True])
def test_flow_fused_kernel_matches_ref(chunk, masked):
    """Chunk sweep (VMEM block sizes) x full/ragged lens: out + every
    boundary-state sum."""
    bh, g, n, d, dv = 3, 2, 64, 16, 8
    q, k, v = _inputs(chunk, bh, g, n, d, dv)
    lens = jnp.array([19, 64, 7]) if masked else jnp.full((bh,), n)
    out, sums = flow_fused_call(q, k, v, lens, chunk=chunk, interpret=True)
    ref_out, ref_sums = flow_fused_ref(q, k, v, lens)
    assert_close(out, ref_out, rtol=1e-3, atol=1e-4)
    for got, want, name in zip(
            sums, ref_sums, ["q_sum", "k_sum", "ko_sum", "qi_sum", "z", "s"]):
        assert_close(got, want, rtol=1e-3, atol=1e-4, msg=name)


@pytest.mark.parametrize("phi", ["sigmoid", "elu1", "relu"])
def test_flow_fused_phi_kinds(phi):
    """The kernel's import-light ``_phi`` copy must track the core
    ``phi_map`` for every kind, and the kernel must agree with the oracle
    under each."""
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32))
    assert_close(_phi(x, phi), phi_map(x, phi), rtol=1e-6, atol=1e-7)
    bh, g, n, d = 2, 1, 32, 8
    q, k, v = _inputs(7, bh, g, n, d, d)
    lens = jnp.full((bh,), n)
    out, _ = flow_fused_call(q, k, v, lens, chunk=16, phi=phi, interpret=True)
    ref_out, _ = flow_fused_ref(q, k, v, lens, phi=phi)
    assert_close(out, ref_out, rtol=1e-3, atol=1e-4)


def test_flow_fused_ref_matches_fused_causal():
    """The oracle itself reproduces the production chunk-parallel pipeline,
    state included (shared-GQA semantics)."""
    b, hq, hkv, n, d = 2, 4, 2, 64, 16
    q, k, v = _qkv(3, b, hq, hkv, n, d)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    want, st = fused_causal_forward(q, k, v, cfg, return_state=True)
    g = hq // hkv
    qg = _group(q, hkv).reshape(b * hkv, g, n, d)
    lens = jnp.full((b * hkv,), n)
    out, sums = flow_fused_ref(qg.astype(jnp.float32),
                               k.reshape(b * hkv, n, d),
                               v.reshape(b * hkv, n, d), lens)
    assert_close(out.reshape(b, hkv, g, n, d), _group(want, hkv),
                 rtol=1e-3, atol=1e-4)
    q_sum, k_sum, ko_sum, qi_sum, z, s = sums
    assert_close(q_sum.reshape(b, hkv, d), st.q_sum, rtol=1e-3, atol=1e-4)
    assert_close(k_sum.reshape(b, hkv, d), st.k_sum, rtol=1e-3, atol=1e-4)
    assert_close(ko_sum.reshape(b, hkv, d), st.ko_sum, rtol=1e-3, atol=1e-4)
    assert_close(qi_sum.reshape(b, hkv, d), st.qi_sum, rtol=1e-3, atol=1e-4)
    assert_close(z.reshape(b, hkv), st.z, rtol=1e-3, atol=1e-4)
    assert_close(s.reshape(b, hkv, d, d), st.s, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("masked", [False, True])
def test_flow_fused_bwd_matches_vjp_of_ref(masked):
    """Reverse-scan backward vs jax.vjp of the oracle, cotangents on the
    output AND all six state sums (no (B,H,N)-sized residuals on path)."""
    bh, g, n, d, dv, chunk = 2, 2, 48, 8, 8, 16
    q, k, v = _inputs(9, bh, g, n, d, dv)
    lens = jnp.array([37, 11]) if masked else jnp.full((bh,), n)
    ks = jax.random.split(jax.random.PRNGKey(10), 7)
    g_out = jax.random.normal(ks[0], (bh, g, n, dv))
    out, sums = flow_fused_call(q, k, v, lens, chunk=chunk, interpret=True)
    g_sums = tuple(jax.random.normal(kk, s.shape)
                   for kk, s in zip(ks[1:], sums))
    dq, dk, dv_ = flow_fused_bwd_call(q, k, v, lens, sums, g_out, g_sums,
                                      chunk=chunk, interpret=True)
    _, pull = jax.vjp(lambda q_, k_, v_: flow_fused_ref(q_, k_, v_, lens),
                      q, k, v)
    rq, rk, rv = pull((g_out, g_sums))
    assert_close(dq, rq, rtol=2e-3, atol=1e-4, msg="dq")
    assert_close(dk, rk, rtol=2e-3, atol=1e-4, msg="dk")
    assert_close(dv_, rv, rtol=2e-3, atol=1e-4, msg="dv")


# ---------------------------------------------------------------------------
# wrapper: padding, grads, packed boundary states, decode hand-off
# ---------------------------------------------------------------------------
def test_effective_chunk_pads_instead_of_shrinking():
    """Awkward N keeps a real chunk size (pad + mask), never a degenerate
    power-of-two shrink down to chunk=1."""
    assert effective_chunk(97, 32) == 32
    assert padded_len(97, 32) == 128
    assert effective_chunk(5, 32) == 5
    q, k, v = _qkv(13, 2, 2, 2, 97, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=32,
                     backend="fused_causal")
    out = attention.forward(q, k, v, cfg)
    ref = attention.forward(q, k, v,
                            dataclasses.replace(cfg, backend="xla_cumsum"))
    assert_close(out, ref, rtol=1e-3, atol=1e-4)


def test_flow_fused_forward_odd_n_grads():
    """n=60 (non-chunk-multiple): padded forward + grads track the XLA
    pipeline within the grad-parity bounds."""
    b, hq, hkv, n, d = 2, 4, 2, 60, 8
    q, k, v = _qkv(17, b, hq, hkv, n, d)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)

    def loss_fused(q_, k_, v_):
        out, st = flow_fused_forward(q_, k_, v_, cfg, return_state=True,
                                     interpret=True)
        return jnp.sum(out ** 2) + jnp.sum(st.s), out

    def loss_ref(q_, k_, v_):
        out, st = fused_causal_forward(q_, k_, v_, cfg, return_state=True)
        return jnp.sum(out ** 2) + jnp.sum(st.s), out

    (la, out_a), ga = jax.value_and_grad(loss_fused, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    (lb, out_b), gb = jax.value_and_grad(loss_ref, (0, 1, 2),
                                         has_aux=True)(q, k, v)
    assert_close(out_a, out_b, rtol=1e-3, atol=1e-4)
    for a, b_, name in zip(ga, gb, ["dq", "dk", "dv"]):
        assert_close(a, b_, rtol=3e-3, atol=1e-3, msg=name)


def test_fused_causal_lowers_to_no_loop():
    """Chunk-parallel: neither the forward, the prefill (state returned) nor
    their gradient lowers to a loop over the chunks."""
    q, k, v = _qkv(31, 2, 4, 2, 100, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    lens = jnp.array([100, 41])

    def fwd(q_, k_, v_):
        return fused_causal_forward(q_, k_, v_, cfg)

    def prefill(q_, k_, v_):
        return fused_causal_forward(q_, k_, v_, cfg, return_state=True,
                                    lengths=lens)

    def loss(q_, k_, v_):
        out, st = prefill(q_, k_, v_)
        return jnp.sum(out ** 2) + sum(jnp.sum(x) for x in st[1:])

    for name, f in [("forward", fwd), ("prefill", prefill),
                    ("grad", jax.grad(loss, (0, 1, 2)))]:
        text = jax.jit(f).lower(q, k, v).as_text()
        assert "while" not in text, f"{name} lowers to a loop"


def test_flow_fused_packed_prefill_to_decode_handoff():
    """Packed pallas_fused prefill boundary states feed decode directly:
    one decode step on top matches a longer xla_cumsum prefill."""
    b, h, n, d = 3, 2, 16, 8
    lens = [9, 16, 4]
    q, k, v = _qkv(21, b, h, h, n + 1, d)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=8,
                     backend="pallas_fused")
    _, st = attention.prefill(q[:, :, :n], k[:, :, :n], v[:, :, :n], cfg,
                              lengths=jnp.asarray(lens))
    assert np.asarray(st.t).tolist() == lens
    li = jnp.asarray(lens)
    pick = lambda x: jnp.take_along_axis(  # noqa: E731
        x, li[:, None, None, None], axis=2)
    dec_cfg = dataclasses.replace(cfg, backend="recurrent")
    st2, o = attention.decode_step(st, pick(q), pick(k), pick(v), dec_cfg)
    ref_cfg = dataclasses.replace(cfg, backend="xla_cumsum")
    for i, l_i in enumerate(lens):
        sl = slice(i, i + 1)
        qi = jnp.concatenate([q[sl, :, :l_i], pick(q)[sl]], axis=2)
        ki = jnp.concatenate([k[sl, :, :l_i], pick(k)[sl]], axis=2)
        vi = jnp.concatenate([v[sl, :, :l_i], pick(v)[sl]], axis=2)
        out_i, st_i = attention.prefill(qi, ki, vi, ref_cfg)
        assert_close(o[sl], out_i[:, :, -1:], rtol=2e-3, atol=1e-4,
                     msg=f"row {i} decode output")
        for f in st_i._fields:
            assert_close(getattr(st2, f)[sl], getattr(st_i, f),
                         rtol=2e-3, atol=1e-4, msg=f"row {i} state {f}")


def test_resolution_prefers_pallas_fused_only_when_strict():
    sh = ShapeInfo(b=2, hq=4, hkv=2, n=64, m=64, d=16, dv=16)
    strict = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    assert attention.resolve(strict, sh, "tpu").name == "pallas_fused"
    paper = dataclasses.replace(strict, strict_causal=False)
    assert attention.resolve(paper, sh, "tpu").name == "pallas_chunk"
    dec = ShapeInfo(b=2, hq=4, hkv=2, n=1, m=1, d=16, dv=16)
    assert attention.resolve(strict, dec, "tpu",
                             op="decode").name != "pallas_fused"
