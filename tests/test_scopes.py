"""The train step's named scopes reach the compiled program's op metadata.

The program's own train step (``launch.steps.build_train_step``) for a
tiny flowformer LM with a scanned, rematerialised layer stack on the
``fused_causal`` strategy is compiled on the CPU; every scope of
``repro/scopes.py`` must name ops there, the mixer's and the FFN's in the
forward, the backward and the recomputation alike.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import scopes
from repro.config import ShapeSpec
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import RunPlan, build_train_step

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_WRAPPED = re.compile(r"^([\w\-]+)\((.*)\)$")


@pytest.fixture(scope="module")
def op_names():
    cfg = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(
        cfg, scan_layers=True, remat=True,
        attention=dataclasses.replace(cfg.attention, backend="fused_causal",
                                      chunk_size=32))
    shape = ShapeSpec("t", 64, 2, "train")
    mesh = make_mesh((1, 1), ("data", "model"))
    step, state_shape, _, _ = build_train_step(
        cfg, shape, mesh, RunPlan.choose(cfg, shape, mesh),
        train_overrides={"fused_value_grad": True})
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    text = step.lower(state_shape, {"inputs": tok, "targets": tok}
                      ).compile().as_text()
    # a merged instruction's metadata joins its paths with ";"
    return [p for n in _OP_NAME.findall(text) for p in n.split(";")]


def _unwrap(component: str) -> tuple[str, list[str]]:
    """(the component inside its wrappers, the wrappers outermost first):
    ``transpose(jvp(step.ffn))`` -> (``step.ffn``, [transpose, jvp])."""
    wrappers = []
    while (m := _WRAPPED.match(component)) is not None:
        wrappers.append(m.group(1))
        component = m.group(2)
    return component, wrappers


def _scopes_of(path: str) -> set:
    return {c for c in (_unwrap(x)[0] for x in path.split("/"))
            if c in scopes.ALL}


def test_every_scope_names_ops(op_names):
    named = set().union(*map(_scopes_of, op_names))
    assert named == set(scopes.ALL)


@pytest.mark.parametrize("scope", [scopes.MIXER, scopes.FFN])
def test_scope_in_forward_backward_and_recompute(op_names, scope):
    paths = [p for p in op_names if scope in _scopes_of(p)]
    assert any("transpose(" not in p for p in paths)  # forward
    assert any("transpose(" in p and "rematted_computation" not in p
               for p in paths)  # backward
    assert any("rematted_computation" in p for p in paths)  # recompute


def test_no_generated_component_is_a_scope(op_names):
    """Only ``jax.named_scope`` makes a component equal to a scope name:
    wrapped, it sits under transforms only (never ``jit(...)`` of a
    function), and nothing else JAX writes carries the ``step.`` prefix."""
    transforms = {"jvp", "transpose", "vmap"}
    for path in op_names:
        for component in path.split("/"):
            inner, wrappers = _unwrap(component)
            if inner in scopes.ALL:
                assert set(wrappers) <= transforms, path
            else:
                assert not inner.startswith("step."), path
