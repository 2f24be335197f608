"""Opt-in activation sharding constraints (§Perf iteration 2).

Baseline behaviour (no context set) lets XLA's SPMD propagation choose
activation shardings; on several cells it picks feature-sharded residuals
with per-layer all-gathers.  When a policy is activated, model code pins
the residual stream to

    (batch over DP axes, sequence replicated-or-SP, features replicated)

at block boundaries, which turns the per-layer resharding traffic into the
canonical TP pattern (reduce-scatter/all-gather around the two matmul pairs
only).  Thread-local so baseline and optimized variants of the same model
can be lowered in one process.
"""
from __future__ import annotations

import contextlib
import threading
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = threading.local()


def _current():
    return getattr(_STATE, "residual", None)


@contextlib.contextmanager
def activation_sharding(residual_spec: P, mesh):
    """Enable activation constraints within the block (trace time).

    residual_spec's leading axes give (batch, seq) placement; derived specs:
      residual    (B, N, d)        -> (batch, seq, None)
      ffn hidden  (B, N, f)        -> (batch, seq, "model")   TP hidden
      heads       (B, H, N, D)     -> (batch, "model", seq, None) head TP
    """
    prev = _current()
    _STATE.residual = (residual_spec, mesh)
    try:
        yield
    finally:
        _STATE.residual = prev


def _constrain(x: jax.Array, dims: list) -> jax.Array:
    cur = _current()
    if cur is None:
        return x
    _, mesh = cur
    dims = dims[: x.ndim] + [None] * (x.ndim - len(dims))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*dims)))


def constrain_residual(x: jax.Array) -> jax.Array:
    """Pin a (B, N, d) residual activation; no-op when policy inactive."""
    cur = _current()
    if cur is None:
        return x
    spec, _ = cur
    return _constrain(x, list(spec))


def constrain_ffn_hidden(x: jax.Array) -> jax.Array:
    """Pin a (B, N, f) FFN hidden activation: hidden dim over "model".

    Without this, XLA's SPMD propagation has been observed to replicate the
    FFN hidden (full-width fp32 activation-gradient all-reduces per layer —
    the dominant §Perf baseline pathology)."""
    cur = _current()
    if cur is None:
        return x
    spec, _ = cur
    batch_axis = list(spec)[0] if len(list(spec)) else None
    seq_axis = list(spec)[1] if len(list(spec)) > 1 else None
    if seq_axis == "model":
        seq_axis = None  # hidden TP and seq SP both want "model": prefer TP
    return _constrain(x, [batch_axis, seq_axis, "model"])


def constrain_heads(x: jax.Array) -> jax.Array:
    """Pin a (B, H, N, D) per-head activation: heads over "model"."""
    cur = _current()
    if cur is None:
        return x
    spec, mesh = cur
    batch_axis = list(spec)[0] if len(list(spec)) else None
    if x.shape[1] % mesh.shape.get("model", 1):
        return x  # kv heads may not divide the axis: leave to XLA
    return _constrain(x, [batch_axis, "model", None, None])
