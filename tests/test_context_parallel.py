"""Context-parallel backends + ExecutionPlan API.

Sharded resolution must bind the cp_* collective-glue backends and match
the unsharded ``xla_cumsum`` oracle to fp32 tolerance (forward, grad, and
packed-prefill boundary states) on a forced 8-device CPU mesh; the old
per-call signatures must keep working as warn-once deprecation shims.

Multi-device cases run in subprocesses (jax locks the device count at
first init — same contract as tests/test_sharding.py).
"""
import json
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import attention
from repro.attention import ExecutionPlan, FlowConfig, ShapeInfo, ShardSpec

from conftest import assert_close
from repro.launch.mesh import make_mesh
from test_sharding import run_with_devices


# ---------------------------------------------------------------------------
# 8-device parity: cp_nc / cp_causal vs the unsharded xla_cumsum oracle
# ---------------------------------------------------------------------------
def test_cp_backends_match_unsharded_oracle():
    code = textwrap.dedent("""
        import dataclasses, json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro import attention
        from repro.attention import (ExecutionPlan, FlowConfig, ShapeInfo,
                                     ShardSpec)

        mesh = make_mesh((8,), ("seq",))
        B, H, Hkv, N, D = 2, 4, 2, 128, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, Hkv, N, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, N, D))
        shard = ShardSpec(axis="seq", mesh=mesh)
        shapes = ShapeInfo.from_qkv(q, k, v)

        def oracle(cfg):
            return attention.resolve(ExecutionPlan(
                flow=dataclasses.replace(cfg, backend="xla_cumsum")))

        out = {}

        # resolve() on a sharded plan returns the context-parallel backends
        nc_cfg = FlowConfig()
        c_cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=8)
        nc_plan = ExecutionPlan(flow=nc_cfg, shard=shard, shapes=shapes)
        c_plan = ExecutionPlan(flow=c_cfg, shard=shard, shapes=shapes)
        ex_nc = attention.resolve(nc_plan)
        ex_c = attention.resolve(c_plan)
        out["nc_backend"] = ex_nc.backend("forward").name
        out["c_backend"] = ex_c.backend("forward").name
        out["pf_backend"] = ex_c.backend("prefill_packed").name

        def maxerr(a, b):
            return float(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32)).max())

        # forward parity
        out["nc_fwd"] = maxerr(jax.jit(ex_nc.forward)(q, k, v),
                               oracle(nc_cfg).forward(q, k, v))
        out["c_fwd"] = maxerr(jax.jit(ex_c.forward)(q, k, v),
                              oracle(c_cfg).forward(q, k, v))

        # grad parity (the glue declares differentiable and must be)
        def sq(fn):
            return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()
        for name, ex, cfg in (("nc", ex_nc, nc_cfg), ("c", ex_c, c_cfg)):
            gs = jax.grad(sq(ex.forward), argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(sq(oracle(cfg).forward), argnums=(0, 1, 2))(q, k, v)
            out[f"{name}_grad"] = max(maxerr(a, b) for a, b in zip(gs, gr))

        # prefill (full-length + packed boundary states)
        o_p, st_p = ex_c.prefill(q, k, v)
        o_r, st_r = oracle(c_cfg).prefill(q, k, v)
        out["pf_out"] = maxerr(o_p, o_r)
        out["pf_state"] = max(
            maxerr(getattr(st_p, f), getattr(st_r, f)) for f in st_p._fields)
        lens = jnp.asarray([37, 128])
        _, st_pk = ex_c.prefill(q, k, v, lengths=lens)
        _, st_rk = oracle(c_cfg).prefill(q, k, v, lengths=lens)
        out["packed_t"] = [int(x) for x in st_pk.t]
        out["packed_state"] = max(
            maxerr(getattr(st_pk, f), getattr(st_rk, f))
            for f in st_pk._fields)

        # explain(plan): shard axis + per-backend shard_support verdicts
        report = str(attention.explain(c_plan))
        out["explain_has_axis"] = "axis 'seq' (8-way)" in report
        out["explain_has_glue_reason"] = "no collective glue" in report
        out["explain_binds_cp"] = "OK  cp_causal" in report

        # the deprecated make_context_parallel shim still executes (+warns)
        import warnings
        from repro.core.context_parallel import make_context_parallel
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            fn = make_context_parallel(mesh, c_cfg, seq_axis="seq")
        out["shim_warned"] = any(
            issubclass(x.category, DeprecationWarning) for x in w)
        out["shim_fwd"] = maxerr(jax.jit(fn)(q, k, v),
                                 oracle(c_cfg).forward(q, k, v))
        print(json.dumps(out))
    """)
    res = json.loads(run_with_devices(code, 8).strip().splitlines()[-1])
    assert res["nc_backend"] == "cp_nc", res
    assert res["c_backend"] == "cp_causal", res
    assert res["pf_backend"] == "cp_causal", res
    for key in ("nc_fwd", "c_fwd", "pf_out", "pf_state", "packed_state",
                "shim_fwd"):
        assert res[key] < 1e-3, (key, res)
    for key in ("nc_grad", "c_grad"):
        assert res[key] < 5e-3, (key, res)
    assert res["packed_t"] == [37, 128], res
    assert res["explain_has_axis"] and res["explain_has_glue_reason"], res
    assert res["explain_binds_cp"] and res["shim_warned"], res


def test_cp_inner_strategy_is_resolvable_and_pinnable():
    """ShardSpec.inner pins the shard-local strategy; an impossible inner
    (chunk too large for the local length) rejects with its own reason."""
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro import attention
        from repro.attention import (ExecutionPlan, FlowConfig, ShapeInfo,
                                     ShardSpec)

        mesh = make_mesh((8,), ("seq",))
        B, H, N, D = 1, 2, 128, 8
        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, N, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, H, N, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, H, N, D))
        cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=8)
        shapes = ShapeInfo.from_qkv(q, k, v)
        out = {}

        ref = attention.resolve(ExecutionPlan(flow=cfg)).forward(q, k, v)
        for inner in ("auto", "xla_chunked", "xla_cumsum"):
            plan = ExecutionPlan(flow=cfg, shapes=shapes, shard=ShardSpec(
                axis="seq", mesh=mesh, inner=inner))
            o = attention.resolve(plan).forward(q, k, v)
            out[inner] = float(jnp.abs(o - ref).max())

        # local N = 16, so a pinned chunked inner with chunk 16 cannot chunk
        big = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
        plan = ExecutionPlan(flow=big, shapes=shapes, shard=ShardSpec(
            axis="seq", mesh=mesh, inner="xla_chunked"))
        try:
            attention.resolve(plan)
            out["pinned_inner_rejects"] = False
        except attention.ResolutionError as err:
            out["pinned_inner_rejects"] = any(
                "inner" in why for _, why in err.rejections)
        print(json.dumps(out))
    """)
    res = json.loads(run_with_devices(code, 8).strip().splitlines()[-1])
    for inner in ("auto", "xla_chunked", "xla_cumsum"):
        assert res[inner] < 1e-3, res
    assert res["pinned_inner_rejects"], res


# ---------------------------------------------------------------------------
# Mesh-aware resolution rules (single device is enough)
# ---------------------------------------------------------------------------
def _qkv(key, b, hq, hkv, n, d):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return (jax.random.normal(ks[0], (b, hq, n, d)),
            jax.random.normal(ks[1], (b, hkv, n, d)),
            jax.random.normal(ks[2], (b, hkv, n, d)))


def test_sharded_rejections_name_missing_glue():
    """Every single-device backend refuses a sharded plan with a "no
    collective glue" reason carried in ResolutionError.rejections."""
    mesh = make_mesh((1,), ("model",))
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    shapes = ShapeInfo(b=1, hq=2, hkv=2, n=64, m=64, d=8, dv=8)
    with pytest.raises(attention.ResolutionError) as ei:
        attention.resolve(cfg, shapes, "cpu",
                          shard=ShardSpec(axis="model", mesh=mesh))
    rej = dict(ei.value.rejections)
    assert "no collective glue" in rej["xla_cumsum"]
    assert "no collective glue" in rej["fused_causal"]
    # the glue itself refuses a 1-way axis (nothing to shard)
    assert "size 1" in rej["cp_causal"]


def test_cp_backends_refuse_unsharded_plans():
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16,
                     backend="cp_causal")
    shapes = ShapeInfo(b=1, hq=2, hkv=2, n=64, m=64, d=8, dv=8)
    with pytest.raises(attention.ResolutionError, match="sharded"):
        attention.resolve(cfg, shapes, "cpu")


def test_explain_plan_requires_shapes_and_prints_unsharded():
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    plan = ExecutionPlan(flow=cfg)
    with pytest.raises(ValueError, match="shapes"):
        attention.explain(plan)
    report = str(attention.explain(plan.with_shapes(
        ShapeInfo(b=1, hq=2, hkv=2, n=64, m=64, d=8, dv=8))))
    assert "unsharded" in report and "cp_causal" in report


# ---------------------------------------------------------------------------
# Deprecation shims: old signatures still work and warn once
# ---------------------------------------------------------------------------
def test_legacy_signatures_work_and_warn_once():
    from repro.attention import api

    q, k, v = _qkv(0, 1, 4, 2, 32, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    ex = attention.resolve(ExecutionPlan(flow=cfg))

    api._reset_deprecation_warnings()
    # first call per signature warns ...
    with pytest.warns(DeprecationWarning, match="ExecutionPlan"):
        out = attention.forward(q, k, v, cfg)
    with pytest.warns(DeprecationWarning, match="ExecutionPlan"):
        out_p, state = attention.prefill(q, k, v, cfg)
    q1, k1, v1 = _qkv(1, 1, 4, 2, 1, 8)
    with pytest.warns(DeprecationWarning, match="ExecutionPlan"):
        state2, out_d = attention.decode_step(state, q1, k1, v1, cfg)

    # ... the second does not ...
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out_again = attention.forward(q, k, v, cfg)
        attention.prefill(q, k, v, cfg)
        attention.decode_step(state, q1, k1, v1, cfg)
    assert not any(issubclass(x.category, DeprecationWarning) for x in w), w

    # ... and results are identical to the plan-first spelling
    assert_close(out, ex.forward(q, k, v))
    assert_close(out_again, out)
    ref_p, ref_state = ex.prefill(q, k, v)
    assert_close(out_p, ref_p)
    for f in state._fields:
        assert_close(getattr(state, f), getattr(ref_state, f), msg=f)
    _, ref_d = ex.decode_step(ref_state, q1, k1, v1)
    assert_close(out_d, ref_d)

    # passing the plan in the cfg position is the supported spelling: silent
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert_close(attention.forward(q, k, v, ExecutionPlan(flow=cfg)), out)
    assert not any(issubclass(x.category, DeprecationWarning) for x in w), w


def test_worker_plan_built_once_at_construction():
    """The serving Worker folds paged/packed into ONE plan at __init__."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving.worker import Worker
    from repro.serving.paged import PagedSpec

    cfg = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(cfg, n_layers=1)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    w = Worker(params, cfg, slots=2, max_len=32)
    assert w.plan.packed == w.packable
    assert w.plan.paged is None  # flow stacks have no pageable layers
    sm = dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    w2 = Worker(lm.init(jax.random.PRNGKey(0), sm), sm, slots=2, max_len=32,
                paged=PagedSpec(page_size=8))
    assert w2.plan.paged is not None and w2.plan.paged.page_size == 8


def test_prefill_packed_via_plan_matches_per_row():
    """Plan-first packed prefill (plan.packed + runtime lengths) matches
    per-row prefill — the executor routes to the prefill_packed op."""
    q, k, v = _qkv(2, 3, 4, 2, 32, 8)
    cfg = FlowConfig(causal=True, strict_causal=True, chunk_size=16)
    ex = attention.resolve(ExecutionPlan(flow=cfg, packed=True))
    lens = [19, 32, 7]
    out_p, st_p = ex.prefill(q, k, v, lengths=jnp.asarray(lens))
    assert np.asarray(st_p.t).tolist() == lens
    for i, li in enumerate(lens):
        sl = slice(i, i + 1)
        out_i, st_i = ex.prefill(q[sl, :, :li], k[sl, :, :li], v[sl, :, :li])
        assert_close(out_p[sl, :, :li], out_i, rtol=1e-3, atol=1e-4,
                     msg=f"row {i}")
        for f in st_i._fields:
            assert_close(getattr(st_p, f)[sl], getattr(st_i, f),
                         rtol=1e-3, atol=1e-4, msg=f"row {i} state {f}")
