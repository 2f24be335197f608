"""Gradient correctness for every registered Flow-Attention backend.

The Pallas backends differentiate through the custom VJP rules in
``attention/vjp.py`` (backward passes are Pallas kernels); the XLA/scan
backends differentiate natively.  Wherever a backend self-reports
applicable, ``jax.grad`` through it must match the ``xla_cumsum``
reference within fp32 reassociation tolerance, and spot-checked finite
differences must agree with the analytic gradient.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention
from repro.attention import FlowConfig, ResolutionError, ShapeInfo


def _qkv(key, b, hq, hkv, n, d, dv=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d), dtype),
            jax.random.normal(ks[1], (b, hkv, n, d), dtype),
            jax.random.normal(ks[2], (b, hkv, n, dv or d), dtype))


def _applicable(cfg, q, k, v, op="forward"):
    be = attention.get_backend(cfg.backend)
    if be.shard_only:
        # context-parallel glue resolves only for sharded ExecutionPlans;
        # its grad parity runs on an 8-device mesh in test_context_parallel.py
        return False
    ok, _ = be.supports(cfg, ShapeInfo.from_qkv(q, k, v),
                        jax.default_backend(), op=op, explicit=True)
    return ok


def _grads(cfg, q, k, v, op="forward", lengths=None):
    def loss(q, k, v):
        if op == "prefill":
            out, state = attention.prefill(q, k, v, cfg, lengths=lengths)
            if lengths is not None:  # outputs past a row's end are padding
                ok = jnp.arange(q.shape[2]) < lengths[:, None]
                out = out * ok[:, None, :, None]
            return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.sum(state.s)
        out = attention.forward(q, k, v, cfg)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _assert_grads_close(got, want, *, rtol=3e-3, atol=1e-3):
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"d{name} mismatch")


# ---------------------------------------------------------------------------
# jax.grad parity vs the XLA reference, every registered backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", attention.list_backends())
@pytest.mark.parametrize("causal", [True, False])
def test_grad_parity_vs_reference(backend, causal):
    q, k, v = _qkv(0, 2, 4, 2, 64, 16)
    cfg = FlowConfig(causal=causal, strict_causal=causal, chunk_size=16,
                     backend=backend)
    if not _applicable(cfg, q, k, v):
        pytest.skip(f"{backend} not applicable: causal={causal}")
    ref_cfg = dataclasses.replace(cfg, backend="xla_cumsum")
    _assert_grads_close(_grads(cfg, q, k, v), _grads(ref_cfg, q, k, v))


@pytest.mark.parametrize("backend,n,lengths", [
    *(pytest.param(b, 32, None, id=b) for b in
      ["pallas_chunk", "fused_causal", "pallas_fused", "xla_chunked"]),
    # fused_causal again under one chunk of 16, at no multiple of it, and
    # packed (rows ending at 23, 37 and 5)
    pytest.param("fused_causal", 9, None, id="fused_causal-n9"),
    pytest.param("fused_causal", 37, None, id="fused_causal-n37"),
    pytest.param("fused_causal", 37, (23, 37, 5), id="fused_causal-packed"),
])
def test_grad_parity_through_prefill(backend, n, lengths):
    """Gradients flow through the (out, FlowState) prefill op too."""
    q, k, v = _qkv(1, 1 if lengths is None else len(lengths), 4, 2, n, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend=backend)
    op = "prefill" if lengths is None else "prefill_packed"
    if not _applicable(cfg, q, k, v, op=op):
        pytest.skip(f"{backend} {op} not applicable")
    lens = None if lengths is None else jnp.asarray(lengths)
    ref_cfg = dataclasses.replace(cfg, backend="xla_cumsum")
    _assert_grads_close(_grads(cfg, q, k, v, op="prefill", lengths=lens),
                        _grads(ref_cfg, q, k, v, op="prefill", lengths=lens))


@pytest.mark.parametrize("backend,causal", [("pallas_chunk", True),
                                            ("pallas_nc", False)])
def test_grad_bf16_matches_reference_scale(backend, causal):
    """bf16 inputs: gradient parity at a scale-aware bound (elementwise rtol
    is meaningless for near-zero entries)."""
    q, k, v = _qkv(2, 2, 2, 2, 64, 16, dtype=jnp.bfloat16)
    cfg = FlowConfig(causal=causal, strict_causal=causal, chunk_size=16,
                     backend=backend)
    if not _applicable(cfg, q, k, v):
        pytest.skip(f"{backend} not applicable")
    ref_cfg = dataclasses.replace(cfg, backend="xla_cumsum")
    for name, a, b in zip("qkv", _grads(cfg, q, k, v),
                          _grads(ref_cfg, q, k, v)):
        af = np.asarray(a, np.float32)
        bf = np.asarray(b, np.float32)
        scale = max(np.abs(bf).max(), 1e-6)
        assert np.abs(af - bf).max() <= 0.05 * scale, (
            f"d{name}: {np.abs(af - bf).max()} vs scale {scale}"
        )


# ---------------------------------------------------------------------------
# finite-difference spot checks on the Pallas custom VJPs
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,causal", [("pallas_chunk", True),
                                            ("pallas_nc", False),
                                            ("fused_causal", True)])
def test_grad_finite_differences(backend, causal):
    """Directional derivative g . u ~= (f(x + h*u) - f(x - h*u)) / 2h."""
    q, k, v = _qkv(3, 1, 2, 2, 32, 8)
    cfg = FlowConfig(causal=causal, strict_causal=causal, chunk_size=8,
                     backend=backend)
    if not _applicable(cfg, q, k, v):
        pytest.skip(f"{backend} not applicable")

    def loss(args):
        q, k, v = args
        return jnp.sum(attention.forward(q, k, v, cfg) ** 2)

    args = (q, k, v)
    grads = jax.grad(loss)(args)
    ks = jax.random.split(jax.random.PRNGKey(99), 3)
    u = tuple(jax.random.normal(kk, a.shape) for kk, a in zip(ks, args))
    h = 1e-2
    plus = loss(jax.tree.map(lambda a, b: a + h * b, args, u))
    minus = loss(jax.tree.map(lambda a, b: a - h * b, args, u))
    fd = (plus - minus) / (2.0 * h)
    analytic = sum(jnp.vdot(g, d) for g, d in zip(grads, u))
    np.testing.assert_allclose(float(analytic), float(fd), rtol=2e-2,
                               atol=2e-2)


# ---------------------------------------------------------------------------
# capability reporting + resolution
# ---------------------------------------------------------------------------
def test_all_builtin_backends_declare_gradients():
    """Everything registered ships a VJP (or is natively differentiable):
    resolve(needs_grad=True) must behave exactly like plain resolve."""
    q, k, v = _qkv(4, 1, 2, 2, 64, 8)
    sh = ShapeInfo.from_qkv(q, k, v)
    for cfg in (FlowConfig(causal=True, strict_causal=True, chunk_size=16),
                FlowConfig()):
        plain = attention.resolve(cfg, sh, "cpu")
        trained = attention.resolve_for_training(cfg, sh, "cpu")
        assert trained.name == plain.name
    for name in attention.list_backends():
        if name.startswith("_test"):  # doubles registered by other tests
            continue
        be = attention.get_backend(name)
        assert be.differentiable <= be.provides, name
        # every training-reachable op ships gradients; inference-only ops
        # (the serving decode kernel) may stay forward-only by design
        assert be.provides & {"forward", "prefill"} <= be.differentiable, name


class _FwdOnly(attention.Backend):
    """Test double: applicable when pinned, but no VJP rule."""

    provides = frozenset({"forward"})

    def supports(self, cfg, shapes, platform, *, op="forward",
                 explicit=False):
        if not explicit:
            return False, "test-only backend (pin explicitly)"
        return True, "ok"

    def forward(self, q, k, v, cfg):  # pragma: no cover - never resolved
        raise AssertionError("must not run under needs_grad resolution")


@pytest.fixture
def fwd_only_backend():
    """Register a forward-only test double; unregister on teardown so the
    process-global registry stays pristine for other tests."""
    from repro.attention import registry

    name = "_test_fwd_only"
    attention.register_backend(name, _FwdOnly())
    yield name
    registry._REGISTRY.pop(name)
    registry._ORDER.remove(name)


def test_non_differentiable_backend_rejected_with_reason(fwd_only_backend):
    q, k, v = _qkv(5, 1, 2, 2, 64, 8)
    sh = ShapeInfo.from_qkv(q, k, v)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend=fwd_only_backend)
    # forward-only pin resolves fine without gradients...
    assert attention.resolve(cfg, sh, "cpu").name == fwd_only_backend
    # ...and fails fast, naming the missing VJP, when gradients are required
    with pytest.raises(ResolutionError, match="no VJP rule for forward"):
        attention.resolve_for_training(cfg, sh, "cpu")
    try:
        attention.resolve_for_training(cfg, sh, "cpu")
    except ResolutionError as err:
        names = [n for n, _ in err.rejections]
        assert fwd_only_backend in names


def test_resolution_error_lists_every_candidate_reason():
    """The structured rejection list names each backend's own reason —
    what the benchmark sweep and CI logs print."""
    q, k, v = _qkv(6, 1, 2, 2, 33, 8)  # 33: nothing chunkable
    sh = ShapeInfo.from_qkv(q, k, v)
    cfg = FlowConfig(causal=False, strict_causal=False, chunk_size=16,
                     backend="xla_chunked")
    with pytest.raises(ResolutionError) as exc_info:
        attention.resolve(cfg, sh, "cpu")
    err = exc_info.value
    assert err.rejections == (("xla_chunked", "causal-only backend"),)
    assert "xla_chunked: causal-only backend" in str(err)
