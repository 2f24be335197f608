"""What the language model's per-layer checkpoint keeps for the backward.

``models/lm.py`` wraps each layer (or scanned period) in ``jax.checkpoint``
with a policy chosen from the batch's shape: the flow mixer's running sums,
flows and chunk states (tagged ``scopes.FLOW_SUMS`` in
``attention/fused.py``) are kept while every flow layer's fit
``lm.KEEP_BYTES``; above it nothing is kept and the backward recomputes
the whole layer, as it did before there was a choice.  Keeping them
changes no value: the gradients are those of the full recompute and of no
checkpoint at all.  Mixers that tag nothing give the same program either
way.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import lm


def _flow_lm(**over):
    cfg = get_smoke_config("flowformer_lm")
    cfg = dataclasses.replace(
        cfg, n_layers=2, remat=True,
        attention=dataclasses.replace(cfg.attention, backend="fused_causal",
                                      chunk_size=16))
    return dataclasses.replace(cfg, **over)


def _batch(cfg, b, n, seed=0):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (b, n), 0,
                             cfg.vocab_size)
    return {"inputs": tok, "targets": jnp.roll(tok, -1, axis=1)}


def _value_and_grad(cfg, dtype=jnp.float32):
    """A fresh jitted loss and gradient, so that each call traces anew
    and reads ``lm.KEEP_BYTES`` as it stands."""
    loss = functools.partial(lm.loss_fn, cfg=cfg, dtype=dtype)
    return jax.jit(jax.value_and_grad(lambda p, b: loss(p, b)[0]))


def _fall_back(monkeypatch):
    """Every shape over the budget: the checkpoint keeps nothing."""
    monkeypatch.setattr(lm, "KEEP_BYTES", -1)


@pytest.mark.parametrize("scan", [True, False], ids=["scan", "blocks"])
@pytest.mark.parametrize("against", ["full_remat", "no_remat"])
def test_kept_sums_give_the_same_gradients(monkeypatch, scan, against):
    """Two flow layers on ``fused_causal`` at n 37 (chunks of 16, so the
    tail is padded and masked), through either checkpoint site."""
    cfg = _flow_lm(scan_layers=scan)
    b, n = 2, 37
    assert lm._remat_policy(cfg, b, n) is not None
    params = lm.init(jax.random.PRNGKey(1), cfg)
    batch = _batch(cfg, b, n)
    kept_loss, kept = _value_and_grad(cfg)(params, batch)
    if against == "no_remat":
        cfg = dataclasses.replace(cfg, remat=False)
    else:
        _fall_back(monkeypatch)
    want_loss, want = _value_and_grad(cfg)(params, batch)
    np.testing.assert_allclose(kept_loss, want_loss, rtol=0, atol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(kept)
    assert len(leaves) == len(jax.tree.leaves(want))
    for (path, a), w in zip(leaves, jax.tree.leaves(want)):
        np.testing.assert_allclose(a, w, rtol=0, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


def _highest_dots(cfg, b, n):
    """``Precision.HIGHEST`` dots in the compiled gradient step: the
    running sums' chunk-local prefixes (``attention/fused._local_prefix``)
    and nothing else of the model."""
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    batch = jax.eval_shape(lambda: _batch(cfg, b, n))
    text = _value_and_grad(cfg).lower(params, batch).compile().as_text()
    return text.count("operand_precision={highest,highest}")


def test_kept_sums_are_not_recomputed(monkeypatch):
    """Kept, each layer's five prefix sums are computed once in the
    forward and transposed once in the backward; recomputed, the backward
    computes them a second time."""
    cfg = _flow_lm(scan_layers=False)
    kept = _highest_dots(cfg, 2, 37)
    _fall_back(monkeypatch)
    recomputed = _highest_dots(cfg, 2, 37)
    assert kept == 2 * (5 + 5)
    assert recomputed == kept + 2 * 5


@pytest.mark.parametrize("b,n,keeps", [
    pytest.param(6, 8192, True, id="cell-6x8192"),
    pytest.param(2, 65536, False, id="over-2x65536"),
])
def test_policy_from_shape(b, n, keeps):
    """The paper LM as the training cell runs it (six flow layers, 8 heads
    of 64, chunks of 128): at 6 x 8192 tokens the kept set costs 5.53 GB
    and is kept; at 2 x 65536 the four running sums alone are 4 x 2 x 8 x
    65536 x 64 x 4 B = 1.07 GB a layer, and the set 14.7 GB over six
    layers, over ``lm.KEEP_BYTES``: nothing is kept."""
    cfg = dataclasses.replace(
        get_config("flowformer_lm"), max_seq_len=n, remat=True,
        attention=dataclasses.replace(get_config("flowformer_lm").attention,
                                      backend="fused_causal"))
    assert (lm._remat_policy(cfg, b, n) is not None) == keeps


def _untagged(name):
    if name == "softmax":
        cfg = get_smoke_config("flowformer_lm")
        return dataclasses.replace(
            cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))
    if name in ("xla_chunked", "pallas_fused"):
        return _flow_lm(attention=dataclasses.replace(
            _flow_lm().attention, backend=name))
    if name == "ssd":
        return get_smoke_config("mamba2_1p3b")
    # rglru beside local softmax slots (in flow mode those run flow
    # attention), and routed experts beside softmax attention
    cfg = get_smoke_config({"rglru": "recurrentgemma_9b",
                            "moe": "granite_moe_3b_a800m"}[name])
    return dataclasses.replace(
        cfg, attention=dataclasses.replace(cfg.attention, kind="softmax"))


@pytest.mark.parametrize("name", ["softmax", "xla_chunked", "pallas_fused",
                                  "rglru", "ssd", "moe"])
def test_untagged_mixers_lower_to_the_same_program(monkeypatch, name):
    """Every mixer but ``fused_causal`` tags nothing: the step's gradient
    lowers to the same text with the policy as without it."""
    cfg = dataclasses.replace(_untagged(name), remat=True)
    b, n = 2, 64
    assert lm._remat_policy(cfg, b, n) is not None
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    batch = jax.eval_shape(lambda: _batch(cfg, b, n))
    with_policy = _value_and_grad(cfg, jnp.bfloat16).lower(
        params, batch).as_text()
    _fall_back(monkeypatch)
    assert lm._remat_policy(cfg, b, n) is None
    without = _value_and_grad(cfg, jnp.bfloat16).lower(
        params, batch).as_text()
    assert with_policy == without
