"""Device meshes.

These are FUNCTIONS, not module constants — importing this module never
touches jax device state.

Every mesh here has ``Auto`` axes: the sharding rules
(``with_sharding_constraint`` in ``launch/steps.py``, the context-parallel
``shard_map`` glue) are written for the compiler to propagate shardings,
whereas ``jax.make_mesh`` defaults to ``Explicit`` axes.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """Mesh with ``Auto`` axes over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_fleet_meshes(prefill: int, decode: int, devices=None):
    """Per-group 1-D meshes for disaggregated (prefill/decode) serving.

    Carves the host's devices into DISJOINT groups when there are enough
    (CI's fleet leg forces 8 CPU devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on smaller
    hosts the groups degrade gracefully — prefill and decode at least on
    separate devices when two exist, everything on one device otherwise
    — so the fleet subsystem stays functional (and testable) anywhere.
    A group smaller than its worker count is oversubscribed round-robin
    by the fleet router.
    """
    import numpy as np

    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) >= prefill + decode:
        p, d = devs[:prefill], devs[prefill:prefill + decode]
    elif len(devs) >= 2:
        p, d = devs[:1], devs[1:]
    else:
        p = d = devs[:1]
    return (jax.sharding.Mesh(np.array(p), ("prefill",)),
            jax.sharding.Mesh(np.array(d), ("decode",)))
