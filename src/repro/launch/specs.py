"""ShapeDtypeStruct input specs for the step builders, per (arch x shape).

No device allocation anywhere: model params/caches come from
``jax.eval_shape`` over the init functions; inputs are explicit
ShapeDtypeStructs.  ``kind``:

  train    — {"inputs", "targets" [, "positions"/"frames"]} for train_step
  prefill  — prompt tokens/frames for the prefill serve_step
  decode   — one token + per-layer caches (flow/recurrent state in flow
             mode; its size is independent of context length — the paper's
             O(d^2) serving state) + position offset
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeSpec

SDS = jax.ShapeDtypeStruct


def train_inputs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, Any]:
    b, n = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {
            "frames": SDS((b, n, cfg.d_model), jnp.bfloat16),
            "inputs": SDS((b, n), jnp.int32),
            "targets": SDS((b, n), jnp.int32),
        }
    batch: dict[str, Any] = {"targets": SDS((b, n), jnp.int32)}
    if cfg.embedding_frontend == "stub":
        batch["inputs"] = SDS((b, n, cfg.d_model), jnp.bfloat16)
    else:
        batch["inputs"] = SDS((b, n), jnp.int32)
    if cfg.rope == "mrope":
        batch["positions"] = SDS((b, 3, n), jnp.int32)
    return batch


def prefill_inputs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, Any]:
    b, n = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {"frames": SDS((b, n, cfg.d_model), jnp.bfloat16)}
    if cfg.embedding_frontend == "stub":
        return {"inputs": SDS((b, n, cfg.d_model), jnp.bfloat16)}
    return {"inputs": SDS((b, n), jnp.int32)}


def decode_inputs(cfg: ModelConfig, shape: ShapeSpec) -> dict[str, Any]:
    b, n = shape.global_batch, shape.seq_len
    from repro.models import encdec as encdec_lib
    from repro.models import lm as lm_lib

    if cfg.family == "encdec":
        caches = jax.eval_shape(
            lambda: encdec_lib.init_dec_caches(cfg, b, n)
        )
        return {
            "token": SDS((b, 1), jnp.int32),
            "memory": SDS((b, n, cfg.d_model), jnp.bfloat16),
            "caches": caches,
            "pos": SDS((), jnp.int32),
        }
    caches = jax.eval_shape(lambda: lm_lib.init_caches(cfg, b, n))
    token = (
        SDS((b, 1, cfg.d_model), jnp.bfloat16)
        if cfg.embedding_frontend == "stub"
        else SDS((b, 1), jnp.int32)
    )
    return {"token": token, "caches": caches, "pos": SDS((), jnp.int32)}


def params_shape(cfg: ModelConfig):
    """Abstract parameter pytree (fp32) without allocating anything."""
    from repro.models import decision, encdec, lm, vision

    key = jax.random.PRNGKey(0)
    if cfg.family == "encdec":
        return jax.eval_shape(lambda: encdec.init(key, cfg))
    if cfg.family == "vision":
        return jax.eval_shape(lambda: vision.init(key, cfg))
    if cfg.family == "decision":
        return jax.eval_shape(
            lambda: decision.init(key, cfg, state_dim=17, action_dim=6)
        )
    return jax.eval_shape(lambda: lm.init(key, cfg))
