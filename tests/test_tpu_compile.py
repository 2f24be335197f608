"""Ahead-of-time compiles of the main-path kernels for a TPU v5e chip.

The flow kernels of flowformer-lm's serving and training path, its vocab
head's fused cross-entropy and the paged-decode gathers compile here.  The ``ssd_chunk`` kernel and the packed
hybrid boundary gather do not compile for v5e yet and are not covered.

Interpret mode runs a kernel's math but not the chip's compiler, which
refuses blocks off the (8, 128) tiling, contractions it cannot lower and
kernels over the VMEM budget.  These tests compile each main-path kernel
for one v5e chip of a described (not attached) ``v5e:2x2`` topology at
flowformer-lm widths (``configs/flowformer_lm.py``: batch 8, 8 heads,
sequence 512, head dim 64, chunk 128), and check that the compiled program
holds the Pallas kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest workers import every
test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.attention import FlowState, chunked_causal_dot_pallas
from repro.core.flow_attention import FlowConfig
from repro.kernels.flow_decode import flow_decode_q_step, flow_decode_step
from repro.kernels.flow_fused import flow_fused_forward

B, H, N, D, CHUNK = 8, 8, 512, 64, 128
CFG = FlowConfig(causal=True, strict_causal=True, chunk_size=CHUNK)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described v5e chip, with the persistent
    compilation cache off: an AOT compile for an absent chip can be
    written to the cache but never read back."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; return the compiled HLO."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _qkv(sharding, dtype=jnp.bfloat16):
    s = jax.ShapeDtypeStruct((B, H, N, D), dtype, sharding=sharding)
    return s, s, s


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "lengths"])
def test_flow_fused_forward_compiles(one_chip, packed):
    q, k, v = _qkv(one_chip)
    if packed:
        lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
        _compile(lambda q, k, v, n: flow_fused_forward(
            q, k, v, CFG, return_state=True, lengths=n), q, k, v, lens)
    else:
        _compile(lambda q, k, v: flow_fused_forward(q, k, v, CFG)[0],
                 q, k, v)


def test_flow_fused_grad_compiles(one_chip):
    def loss(q, k, v):
        out, _ = flow_fused_forward(q, k, v, CFG)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2)), *_qkv(one_chip))


def test_flow_fused_grad_compiles_data_parallel(topo, one_chip):
    """Four-way data parallel: the compiler cannot partition a Pallas
    kernel, so the executor must run it per device under the step's mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import attention
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices[:4])
    ex = attention.resolve(attention.ExecutionPlan(
        flow=CFG, needs_grad=True, platform="tpu"))

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jnp.sum(ex.forward(q, k, v).astype(jnp.float32) ** 2)

    s = jax.ShapeDtypeStruct((B, H, N, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    assert ex.backend("forward", attention.ShapeInfo(
        b=B, hq=H, hkv=H, n=N, m=N, d=D, dv=D)).name == "pallas_fused"
    _compile(jax.grad(loss, argnums=(0, 1, 2)), s, s, s)


def _state(slots, sharding):
    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return FlowState(t=jax.ShapeDtypeStruct((slots,), jnp.int32,
                                            sharding=sharding),
                     q_sum=sds(slots, H, D), k_sum=sds(slots, H, D),
                     ko_sum=sds(slots, H, D), qi_sum=sds(slots, H, D),
                     z=sds(slots, H), s=sds(slots, H, D, D))


def _token(slots, sharding):
    s = jax.ShapeDtypeStruct((slots, H, 1, D), jnp.bfloat16,
                             sharding=sharding)
    return s, s, s


@pytest.mark.parametrize("slots", [8, 64])
def test_flow_decode_compiles(one_chip, slots):
    _compile(lambda st, q, k, v: flow_decode_step(st, q, k, v, CFG),
             _state(slots, one_chip), *_token(slots, one_chip))


def test_flow_decode_int8_compiles(one_chip):
    from repro.serving.quant import quantize_state, spec_of

    pool = jax.eval_shape(
        functools.partial(quantize_state, spec=spec_of("int8"),
                          granularity="head", exempt=("z",)),
        _state(8, None))
    pool = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        pool)
    _compile(lambda p, q, k, v: flow_decode_q_step(p, q, k, v, CFG),
             pool, *_token(8, one_chip))


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flow_chunk_compiles(one_chip, grad):
    qg = jax.ShapeDtypeStruct((B, H, 1, N, D), jnp.bfloat16,
                              sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, H, N, D), jnp.bfloat16, sharding=one_chip)

    def dot(qg, k, v):
        return chunked_causal_dot_pallas(qg, k, v, chunk=CHUNK)

    if grad:
        def loss(qg, k, v):
            return jnp.sum(dot(qg, k, v).astype(jnp.float32) ** 2)

        text = _compile(jax.grad(loss, argnums=(0, 1, 2)), qg, kv, kv)
        # forward, the dq pass (the forward kernel) and the dk/dv scan
        assert text.count("tpu_custom_call") >= 3
    else:
        _compile(dot, qg, kv, kv)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_gather_compiles(one_chip, quant):
    """The page-table gather of paged softmax decode: B slots of N tokens
    in pages of 16."""
    from repro.kernels.gather import paged_gather, paged_gather_quant

    page = 16
    pages, per_slot = B * N // page, N // page
    table = jax.ShapeDtypeStruct((B, per_slot), jnp.int32, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((pages, H, page, D),
                                jnp.int8 if quant else jnp.bfloat16,
                                sharding=one_chip)
    if quant:
        scale = jax.ShapeDtypeStruct((pages, H, page, 1), jnp.float32,
                                     sharding=one_chip)
        _compile(lambda k, v, ks, vs, t: paged_gather_quant(
            k, v, ks, vs, t, out_dtype=jnp.bfloat16),
            pool, pool, scale, scale, table)
    else:
        _compile(paged_gather, pool, pool, table)


def test_fused_ce_compiles(one_chip):
    """The vocab head's fused cross-entropy, forward and backward, at the
    training cell's widths: 6 x 8192 tokens, d 512, a 32768-row
    vocabulary.  Its kernels carry the head's scopes, so a device trace
    attributes them, and no fp32 (tokens x vocab) buffer is left."""
    import re

    from repro import scopes
    from repro.kernels.fused_ce import token_nll

    t, d, v = 6 * 8192, 512, 32768

    def loss(x, table, targets):
        with jax.named_scope(scopes.HEAD):
            return jnp.mean(token_nll(x, table.astype(x.dtype), targets))

    x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct((v, d), jnp.float32, sharding=one_chip)
    targets = jax.ShapeDtypeStruct((t,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, table, targets).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 2, "one forward and one backward kernel"
    for ln in kernels:
        path = re.search(r'op_name="([^"]*)"', ln).group(1)
        parts = set()
        for c in path.split("/"):
            while (m := re.match(r"^[\w\-]+\((.*)\)$", c)) is not None:
                c = m.group(1)
            parts.add(c)
        assert {scopes.HEAD, scopes.HEAD_FUSED} <= parts, path
    assert f"f32[{t},{v}]" not in text
    # the backward's bf16 cotangent is the one (tokens x vocab) buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * t * v


def test_train_step_keeps_flow_sums(topo, one_chip, monkeypatch):
    """The training cell's step: the paper LM, 6 x 8192 tokens, attention
    on ``fused_causal``, the vocab head on its fused kernel as on a TPU.
    Its per-layer checkpoint keeps the flow mixer's running sums, so the
    backward's recompute of the mixer holds none of their chunk-local
    prefix matmuls (``attention/fused._local_prefix``, ``...j,ij->...i``),
    and the kept sums leave the step's temporaries under 11 GB of the
    chip's 16."""
    import dataclasses

    from jax.sharding import Mesh

    from repro.config import ShapeSpec
    from repro.configs import get_config
    from repro.core import losses
    from repro.launch.steps import build_train_step

    fused = losses._fused
    monkeypatch.setattr(losses, "_fused",
                        lambda h, s, p, backend: fused(h, s, p, "tpu"))
    base = get_config("flowformer_lm")
    cfg = dataclasses.replace(
        base, max_seq_len=8192, remat=True, scan_layers=True,
        attention=dataclasses.replace(base.attention, backend="fused_causal"))
    shape = ShapeSpec("train-8k", 8192, 6, "train")
    step, state_shape, _, _ = build_train_step(
        cfg, shape, Mesh([[topo.devices[0]]], ("data", "model")),
        train_overrides={"fused_value_grad": True})
    tok = jax.ShapeDtypeStruct((6, 8192), jnp.int32)
    compiled = step.lower(state_shape, {"inputs": tok,
                                        "targets": tok}).compile()
    text = compiled.as_text()
    prefix = "step.mixer/...j,ij->...i/"
    assert f'{prefix}dot_general"' in text  # the forward computes them
    assert f"rematted_computation/{prefix}" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= 11e9
