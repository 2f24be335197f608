"""The vocab head's fused projection + cross-entropy (``kernels/fused_ce``).

The kernels run in the Pallas interpreter on the CPU.  Two comparisons
for each case: the kernel's per-token NLL and its gradients in ``x`` and
``W`` against the oracle ``ref.py``, and ``lm.loss_fn`` with its head
steered onto the kernel against ``lm.loss_fn`` on the plain expression.
The tiles are narrowed (in the test) so that the small shapes still span
several token and vocabulary tiles and pad both.

Off the kernel's conditions (the CPU, fp32 compute, a logit softcap, a
mesh of two devices) ``lm.loss_fn`` traces no Pallas kernel and gives the
plain expression's loss and gradients bit for bit.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.extend.core import jaxpr_as_fun

from repro.configs import get_smoke_config
from repro.core import losses
from repro.kernels.fused_ce import ops, token_nll, token_nll_ref
from repro.models import lm


@dataclasses.dataclass(frozen=True)
class Case:
    b: int  # rows
    n: int  # tokens a row
    vocab: int
    tiles: tuple  # (TM, TV) for the test
    mask: bool = False  # zeros in the loss mask
    tied: bool = False  # the head is the input embedding's table
    scale: float = 1.0  # the loss scaled: each token's NLL cotangent != 1


CASES = {
    # 111 tokens in 32-token tiles, 1000 rows in 256-row tiles: both padded
    "ragged": Case(3, 37, 1000, (32, 256)),
    "mask": Case(2, 64, 512, (32, 256), mask=True),
    "tied": Case(2, 48, 640, (32, 128), tied=True),
    "gscale": Case(2, 40, 384, (16, 128), mask=True, scale=3.7),
}


def _cfg(case: Case, **kw):
    cfg = get_smoke_config("flowformer_lm")
    return dataclasses.replace(
        cfg, vocab_size=case.vocab, tie_embeddings=case.tied, n_layers=1,
        attention=dataclasses.replace(cfg.attention, backend="fused_causal",
                                      chunk_size=32), **kw)


def _batch(case: Case, key):
    k1, k2 = jax.random.split(key)
    tok = jax.random.randint(k1, (case.b, case.n), 0, case.vocab)
    mask = jnp.ones((case.b, case.n), jnp.float32)
    if case.mask:
        mask = (jax.random.uniform(k2, mask.shape) > 0.3).astype(jnp.float32)
        mask = mask.at[0].set(0.0)  # a whole row left out
    return {"inputs": tok, "targets": jnp.roll(tok, -1, axis=1),
            "mask": mask}


def _steer_to_kernel(monkeypatch, tiles):
    """Take the kernel path on the CPU: its backend condition read as a
    TPU's, the kernels in the interpreter, the tiles narrowed."""
    fused = losses._fused
    monkeypatch.setattr(losses, "_fused",
                        lambda h, s, p, backend: fused(h, s, p, "tpu"))
    monkeypatch.setattr(losses, "fused_token_nll",
                        functools.partial(token_nll, interpret=True))
    monkeypatch.setattr(ops, "TM", tiles[0])
    monkeypatch.setattr(ops, "TV", tiles[1])


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("name", list(CASES))
def test_fused_ce_matches_reference(monkeypatch, name):
    case = CASES[name]
    _steer_to_kernel(monkeypatch, case.tiles)
    key = jax.random.PRNGKey(17)
    kx, kw, kb, kp = jax.random.split(key, 4)
    t, d = case.b * case.n, 128
    batch = _batch(case, kb)
    x = jax.random.normal(kx, (t, d), jnp.float32).astype(jnp.bfloat16)
    w = (0.5 * jax.random.normal(kw, (case.vocab, d))).astype(jnp.bfloat16)
    tgt = batch["targets"].reshape(t)
    weight = case.scale * batch["mask"].reshape(t) / batch["mask"].sum()

    def loss(nll_fn, x, w):
        return jnp.sum(nll_fn(x, w, tgt) * weight)

    kern = functools.partial(token_nll, interpret=True)
    # the NLL: the same bf16 products summed in fp32 in another order,
    # and an online log-sum-exp over vocabulary tiles against one pass;
    # fp32 rounding of values near log(V) ~ 7
    np.testing.assert_allclose(kern(x, w, tgt), token_nll_ref(x, w, tgt),
                               rtol=1e-5, atol=1e-5)
    got = jax.grad(functools.partial(loss, kern), (0, 1))(x, w)
    want = jax.grad(functools.partial(loss, token_nll_ref), (0, 1))(x, w)
    # gradients: the kernel rounds the logits' cotangent to bf16 before
    # the matmuls (as the MXU does at default precision), the CPU's
    # reference does not; both round the result to bf16. Two bf16
    # roundings, 2^-8 each at worst, bound the relative error
    for g, r in zip(got, want):
        assert _rel(g, r) < 2 ** -7

    # lm.loss_fn on the kernel against lm.loss_fn on the plain expression
    cfg = _cfg(case)
    params = lm.init(kp, cfg)

    def model_loss(p):
        return case.scale * lm.loss_fn(p, batch, cfg)[0]

    lk, gk = jax.value_and_grad(model_loss)(params)
    monkeypatch.setattr(losses, "_fused", lambda *a: False)
    lx, gx = jax.value_and_grad(model_loss)(params)
    # the loss: fp32 rounding of a mean of NLLs near log(V)
    np.testing.assert_allclose(lk, lx, rtol=1e-5)
    head = "embed" if case.tied else "head"
    for path, g in jax.tree_util.tree_leaves_with_path(gk):
        r = gx
        for k in path:
            r = r[k.key if hasattr(k, "key") else k.idx]
        # the head's bf16 rounding of the cotangent, carried back through
        # the layers' bf16 activations: within a few bf16 ulps in norm
        tol = 2 ** -7 if head in jax.tree_util.keystr(path) else 2 ** -6
        assert _rel(g, r) < tol, (jax.tree_util.keystr(path), _rel(g, r))


def _parent_loss(params, batch, cfg, dtype):
    """``lm.loss_fn``'s loss as the plain expression computes it."""
    logits, aux = lm.forward(params, batch["inputs"], cfg, dtype=dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["targets"][..., None],
                               axis=-1)[..., 0]
    mask = batch["mask"]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0) + aux


def _two_devices():
    from jax.sharding import AbstractMesh, AxisType

    return jax.sharding.use_abstract_mesh(
        AbstractMesh((2, 1), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto)))


@pytest.mark.parametrize("off", ["cpu", "f32", "softcap", "two_devices"])
def test_loss_fn_off_kernel_is_plain_expression(monkeypatch, off):
    case = Case(2, 32, 512, (16, 128), mask=True)
    if off != "cpu":  # every other condition of the kernel holds
        _steer_to_kernel(monkeypatch, case.tiles)
    cfg = _cfg(case, logit_softcap=30.0 if off == "softcap" else 0.0)
    dtype = jnp.float32 if off == "f32" else jnp.bfloat16
    params = lm.init(jax.random.PRNGKey(3), cfg)
    batch = _batch(case, jax.random.PRNGKey(4))

    vg = jax.value_and_grad(lambda p: lm.loss_fn(p, batch, cfg,
                                                 dtype=dtype)[0])
    if off == "two_devices":
        with _two_devices():
            closed = jax.make_jaxpr(vg)(params)
    else:
        closed = jax.make_jaxpr(vg)(params)
    assert "pallas_call" not in str(closed.jaxpr)

    got = jaxpr_as_fun(closed)(*jax.tree.leaves(params))
    want = jax.tree.leaves(jax.value_and_grad(
        lambda p: _parent_loss(p, batch, cfg, dtype))(params))
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_loss_fn_on_kernel_traces_it(monkeypatch):
    """The steering above reaches the kernel where every condition holds
    (else the test above would pass for nothing)."""
    case = Case(2, 32, 512, (16, 128))
    _steer_to_kernel(monkeypatch, case.tiles)
    cfg = _cfg(case)
    params = lm.init(jax.random.PRNGKey(3), cfg)
    batch = _batch(case, jax.random.PRNGKey(4))
    closed = jax.make_jaxpr(jax.grad(
        lambda p: lm.loss_fn(p, batch, cfg)[0]))(params)
    text = str(closed.jaxpr)
    assert "fused_ce_fwd" in text and "fused_ce_bwd" in text
