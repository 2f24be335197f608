"""The language model's training loss at its vocab head.

``token_nll`` is the per-token negative log-likelihood of the targets
under the head's logits.  Where the program can see that the fused kernel
applies (``repro/kernels/fused_ce``), it runs that: on a TPU, in bf16,
without a logit softcap, on one device.  The kernel computes the same
fp32 softmax and NLL without writing the fp32 (tokens x vocab) logits.
Everywhere else (the CPU, fp32 compute, softcapped heads, meshes of more
than one device) it runs the plain expression: the fp32 logits, their
fp32 log-softmax and the target's entry.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_ce import token_nll as fused_token_nll
from repro.layers.embeddings import unembed

Array = jax.Array


def _fused(hidden: Array, softcap: float, plan, backend: str) -> bool:
    """Whether the fused kernel computes this head: a TPU backend, bf16
    hidden states, no softcap, and neither a sharded plan nor a mesh of
    more than one device (the compiler cannot partition a Pallas
    kernel)."""
    return (backend == "tpu"
            and hidden.dtype == jnp.bfloat16
            and softcap == 0
            and (plan is None or plan.shard is None)
            and jax.sharding.get_abstract_mesh().size <= 1)


def token_nll(hidden: Array, head: dict, targets: Array, *,
              softcap: float = 0.0, plan=None) -> Array:
    """-log softmax(unembed(head, hidden))[target] per token, fp32 (B, N).

    hidden: (B, N, d) final-normed hidden states; head: the head's
    embedding params (``{"table": (V, d)}``); targets: (B, N) int.
    ``plan`` is the step's execution plan (``attention.ExecutionPlan``).
    """
    if _fused(hidden, softcap, plan, jax.default_backend()):
        b, n, d = hidden.shape
        nll = fused_token_nll(hidden.reshape(b * n, d),
                              head["table"].astype(hidden.dtype),
                              targets.reshape(b * n))
        return nll.reshape(b, n)
    logits = unembed(head, hidden, softcap=softcap)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
