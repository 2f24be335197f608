"""The plain reference against the program's own fp32 forward, and the
benchmark's weights against the program's tree."""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import flow_lm
from bench.weights import make_weights
from bench.tests.conftest import TINY

CASES = {
    "gqa_swiglu_rms": TINY,
    "mha_gelu_layernorm": dict(TINY, n_kv_heads=4, act="gelu",
                               norm="layernorm", n_layers=3),
}


INTERFACE = {"forward": ["params", "tokens", "model", "quant"],
             "adamw_steps": ["params", "batches", "model", "opt", "quant"]}
REFERENCES = sorted(p.stem for p in (harness.BENCH / "reference").glob("*.py")
                    if p.stem != "__init__")


@pytest.mark.parametrize("name", REFERENCES)
def test_every_reference_exports_the_interface(name):
    mod = harness.reference({"reference": name})
    for fn, params in INTERFACE.items():
        assert list(inspect.signature(getattr(mod, fn)).parameters) == params
        assert inspect.signature(getattr(mod, fn)).parameters[
            "quant"].default is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_matches_program_fp32(name):
    from repro.models import lm

    model = CASES[name]
    cfg = harness.model_config(dict(model, attention=dict(
        model["attention"], backend="xla_cumsum")))
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    params = make_weights(shapes, 7)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 96)),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = lm.forward(params, toks, cfg, dtype=jnp.float32)
    got = flow_lm.forward(params, toks, model)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-4 * scale


def test_weights_follow_the_program_tree():
    from repro.models import lm

    cfg = harness.model_config(TINY)
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    params = make_weights(shapes, 3)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for p, s in zip(jax.tree.leaves(params), jax.tree.leaves(shapes)):
        assert p.shape == s.shape and p.dtype == s.dtype
    again = make_weights(shapes, 3)
    other = make_weights(shapes, 2**40 + 3)
    leaf = lambda t: np.asarray(t["embed"]["table"])  # noqa: E731
    assert np.array_equal(leaf(params), leaf(again))
    assert not np.array_equal(leaf(params), leaf(other))


def test_reference_is_causal():
    model = TINY
    from repro.models import lm

    cfg = harness.model_config(model)
    params = make_weights(jax.eval_shape(
        lambda: lm.init(jax.random.PRNGKey(0), cfg)), 1)
    toks = np.random.default_rng(1).integers(0, 512, (1, 64)).astype(np.int32)
    longer = np.concatenate([toks, toks[:, :32]], axis=1)
    a = flow_lm.forward(params, jnp.asarray(toks), model)
    b = flow_lm.forward(params, jnp.asarray(longer), model)[:, :64]
    assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(a).max())
