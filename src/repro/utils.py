"""Small shared utilities: parameter init, pytree helpers, dtype policies.

The framework is pure JAX (no flax/haiku): parameters are nested dicts of
jnp arrays ("param pytrees"), and every layer exposes
``init(key, cfg) -> params`` and ``apply(params, x, ...) -> y`` functions.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


# ---------------------------------------------------------------------------
# RNG helpers
# ---------------------------------------------------------------------------
class KeySeq:
    """Splittable stream of PRNG keys: ``ks = KeySeq(key); k1 = ks()``."""

    def __init__(self, key: jax.Array | int):
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self._key = key

    def __call__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def split(self, n: int) -> list[jax.Array]:
        self._key, *subs = jax.random.split(self._key, n + 1)
        return list(subs)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def trunc_normal(key, shape, stddev: float = 0.02, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def lecun_normal(key, shape, in_axis: int = -2, dtype=jnp.float32):
    fan_in = shape[in_axis] if len(shape) >= 2 else shape[0]
    return jax.random.normal(key, shape, dtype) * (1.0 / math.sqrt(fan_in))


def scaled_init(key, shape, scale: float, fan_in: int, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * (scale / math.sqrt(fan_in))


# ---------------------------------------------------------------------------
# Pytree helpers
# ---------------------------------------------------------------------------
def varying_zeros(shape, *like: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Zeros of ``shape`` varying over the ``shard_map`` manual axes that
    any of ``like`` varies over (a scan carry's type must match the
    updates it absorbs); plain zeros outside ``shard_map``."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    z = jnp.zeros(shape, dtype)
    return jax.lax.pcast(z, tuple(sorted(vma)), to="varying") if vma else z


def device_summary() -> str:
    """The devices JAX runs on, as the banner lines print them."""
    devs = jax.devices()
    return (f"platform={devs[0].platform} device_kind={devs[0].device_kind}"
            f" devices={len(devs)}")


def tree_size(tree: PyTree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree: PyTree) -> int:
    return sum(int(np.prod(x.shape)) * dtype_bytes(x.dtype) for x in jax.tree.leaves(tree))


def tree_cast(tree: PyTree, dtype) -> PyTree:
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def flatten_dict(d: dict, prefix: str = "") -> Iterator[tuple[str, Any]]:
    for k, v in d.items():
        path = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            yield from flatten_dict(v, path)
        else:
            yield path, v


def global_norm(tree: PyTree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


# ---------------------------------------------------------------------------
# Dtype accounting
# ---------------------------------------------------------------------------
#: Canonical HLO-mnemonic -> bytes-per-element table.  This is THE byte
#: table: ``launch.hlo_analysis`` parses optimized HLO against its keys,
#: ``serving.quant.pool_bytes`` and the ``repro.analysis`` kernel auditor
#: account device buffers through :func:`dtype_bytes`.  Keeping one copy
#: means a new dtype (fp8 variants, fp4, ...) lands everywhere at once.
HLO_DTYPE_BYTES: dict[str, int] = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def dtype_bytes(dtype) -> int:
    """Bytes per element of ``dtype``.

    Accepts an HLO mnemonic (``"f32"``, ``"bf16"``, ``"f8e4m3fn"``), a
    numpy/jax dtype object, or any string ``np.dtype`` understands
    (``"int8"``).  fp8 dtypes resolve through ``ml_dtypes`` itemsize.
    """
    if isinstance(dtype, str) and dtype in HLO_DTYPE_BYTES:
        return HLO_DTYPE_BYTES[dtype]
    return int(np.dtype(dtype).itemsize)


# ---------------------------------------------------------------------------
# Dtype policy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Precision:
    """Mixed-precision policy.

    * ``param_dtype``   — dtype parameters are stored in for compute.
    * ``compute_dtype`` — dtype of activations / matmul inputs.
    * ``accum_dtype``   — dtype of matmul accumulation and of all flow
      normalizers (always fp32: the conservation ratios divide small sums).
    """

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32

    @staticmethod
    def bf16() -> "Precision":
        return Precision(jnp.bfloat16, jnp.bfloat16, jnp.float32)

    @staticmethod
    def fp32() -> "Precision":
        return Precision(jnp.float32, jnp.float32, jnp.float32)


def pretty_count(n: int | float) -> str:
    for unit, div in (("T", 1e12), ("B", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(n) >= div:
            return f"{n / div:.2f}{unit}"
    return str(n)
