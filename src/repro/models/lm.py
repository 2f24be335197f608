"""Decoder-only language model: dense / MoE / hybrid (RG-LRU) / SSM (SSD).

Layer stacking uses ``lax.scan`` over repeats of the block *pattern* (one
period = e.g. ("rglru", "rglru", "attn") for RecurrentGemma) with stacked
parameters, keeping HLO size O(pattern) instead of O(layers); remainder
layers run unrolled.  Remat wraps each period when ``cfg.remat``.

Which mechanism runs a block comes from ``cfg.block_kind`` (the single
source of truth) through the ``repro/layers/mixer`` SequenceMixer registry
— init/forward/state_init/prefill/decode below are single loops over
resolved mixers, never ``if kind ==`` ladders, so hybrid stacks (rglru /
ssd / local slots) serve through exactly the same code path as pure
attention, packed admission included.

Entry points:
  init / forward / loss_fn            training
  init_caches / prefill / decode      serving (flow state or KV cache)
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro import scopes
from repro.config import ModelConfig
from repro.core.losses import token_nll
from repro.layers.embeddings import embed, embedding_init, unembed
from repro.layers.ffn import ffn, ffn_init
from repro.layers.mixer import (
    resolve_layer_mixer,
    resolve_mixer,
    resolve_mixers,
)
from repro.layers.moe import moe, moe_init
from repro.layers.norms import apply_norm, norm_init
from repro.layers.rope import default_mrope_positions, default_positions
from repro.utils import KeySeq

Array = jax.Array


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------
def _block_init(key, kind: str, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    d = cfg.d_model
    mx = resolve_mixer(kind, cfg)
    p = {"norm1": norm_init(d, cfg.norm), mx.params_field: mx.init_params(ks())}
    if cfg.d_ff > 0 and mx.block_ffn:
        p["norm2"] = norm_init(d, cfg.norm)
        if cfg.moe is not None:
            p["moe"] = moe_init(ks(), d, cfg.d_ff, cfg.act, cfg.moe)
        else:
            p["ffn"] = ffn_init(ks(), d, cfg.d_ff, cfg.act)
    return p


def _mixer(params, x, kind: str, cfg: ModelConfig, positions, plan=None):
    mx = resolve_layer_mixer(kind, cfg, plan)
    return mx.forward(params[mx.params_field], x, positions=positions)


def _block_apply(params, x, kind: str, cfg: ModelConfig, positions, plan=None):
    from repro.distribution.act_sharding import constrain_residual

    with jax.named_scope(scopes.NORM):
        h = apply_norm(params["norm1"], x, cfg.norm)
    x = constrain_residual(x + _mixer(params, h, kind, cfg, positions, plan))
    aux = jnp.zeros((), jnp.float32)
    if "ffn" in params or "moe" in params:
        with jax.named_scope(scopes.NORM):
            h = apply_norm(params["norm2"], x, cfg.norm)
        with jax.named_scope(scopes.FFN):
            if "ffn" in params:
                y = ffn(params["ffn"], h, cfg.act)
            else:
                y, aux = moe(params["moe"], h, cfg.act, cfg.moe)
        x = x + y
    return constrain_residual(x), aux


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------
def init(key, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    p: dict[str, Any] = {}
    if cfg.embedding_frontend == "tokens":
        p["embed"] = embedding_init(ks(), cfg.vocab_size, cfg.d_model)
    else:  # stub frontend: inputs are precomputed embeddings
        p["embed"] = embedding_init(ks(), cfg.vocab_size, cfg.d_model)

    period = len(cfg.pattern)
    n_rep, tail = divmod(cfg.n_layers, period)
    if cfg.scan_layers and n_rep > 1:
        p["scan"] = []
        for j, kind in enumerate(cfg.pattern):
            keys = jnp.stack(ks.split(n_rep))
            p["scan"].append(jax.vmap(lambda k: _block_init(k, kind, cfg))(keys))
        p["tail"] = [
            _block_init(ks(), cfg.block_kind(n_rep * period + i), cfg)
            for i in range(tail)
        ]
    else:
        p["blocks"] = [
            _block_init(ks(), cfg.block_kind(i), cfg) for i in range(cfg.n_layers)
        ]
    p["final_norm"] = norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        p["head"] = embedding_init(ks(), cfg.vocab_size, cfg.d_model)
    return p


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _embed_inputs(params, inputs: Array, cfg: ModelConfig, dtype) -> Array:
    if inputs.dtype in (jnp.int32, jnp.int64):
        return embed(params["embed"], inputs, dtype)
    return inputs.astype(dtype)  # stub frontend: precomputed embeddings


def _head_params(params, cfg: ModelConfig) -> dict:
    """The vocab head's table: the input embedding's when tied."""
    return params["embed"] if cfg.tie_embeddings else params["head"]


def _hidden(params, inputs: Array, cfg: ModelConfig, *, positions, dtype,
            plan):
    """The final-normed hidden states (B, N, d) and the aux loss."""
    b = inputs.shape[0]
    n = inputs.shape[1]
    with jax.named_scope(scopes.EMBED):
        x = _embed_inputs(params, inputs, cfg, dtype)
    if positions is None:
        positions = (
            default_mrope_positions(b, n) if cfg.rope == "mrope"
            else default_positions(b, n)
        )

    period = len(cfg.pattern)
    aux_total = jnp.zeros((), jnp.float32)

    if "scan" in params:
        n_rep = jax.tree.leaves(params["scan"][0])[0].shape[0]

        def period_body(x, layer_params):
            aux = jnp.zeros((), jnp.float32)
            for j, kind in enumerate(cfg.pattern):
                x, a = _block_apply(layer_params[j], x, kind, cfg, positions,
                                    plan)
                aux = aux + a
            return x, aux

        if cfg.remat:
            period_body = jax.checkpoint(period_body)

        def scan_body(carry, layer_params):
            x, aux = carry
            x, a = period_body(x, layer_params)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            scan_body, (x, aux_total), tuple(params["scan"])
        )
        for i, bp in enumerate(params["tail"]):
            kind = cfg.block_kind(n_rep * period + i)
            x, a = _block_apply(bp, x, kind, cfg, positions, plan)
            aux_total = aux_total + a
    else:
        for i, bp in enumerate(params["blocks"]):
            kind = cfg.block_kind(i)
            f = functools.partial(_block_apply, kind=kind, cfg=cfg,
                                  positions=positions, plan=plan)
            if cfg.remat:
                f = jax.checkpoint(f)
            x, a = f(bp, x)
            aux_total = aux_total + a

    with jax.named_scope(scopes.NORM):
        x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, aux_total


def forward(
    params,
    inputs: Array,
    cfg: ModelConfig,
    *,
    positions: Array | None = None,
    dtype=jnp.bfloat16,
    plan=None,
):
    """inputs: int tokens (B, N) or stub embeddings (B, N, d).

    ``plan`` (an ``attention.ExecutionPlan``) carries the execution context
    built once at step construction — mesh/axis sharding for context
    parallelism, gradient needs — instead of per-call kwargs.
    Returns (logits (B, N, vocab) fp32, aux_loss scalar)."""
    x, aux = _hidden(params, inputs, cfg, positions=positions, dtype=dtype,
                     plan=plan)
    with jax.named_scope(scopes.HEAD):
        logits = unembed(_head_params(params, cfg), x,
                         softcap=cfg.logit_softcap)
    return logits, aux


def loss_fn(params, batch: dict, cfg: ModelConfig, *, dtype=jnp.bfloat16,
            plan=None):
    """batch: {"inputs": tokens/embeds, "targets": (B,N) int, "mask": (B,N)}."""
    x, aux = _hidden(params, batch["inputs"], cfg, dtype=dtype,
                     positions=batch.get("positions"), plan=plan)
    targets = batch["targets"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    with jax.named_scope(scopes.HEAD):
        nll = token_nll(x, _head_params(params, cfg), targets,
                        softcap=cfg.logit_softcap, plan=plan)
    denom = jnp.maximum(mask.sum(), 1.0)
    ce = (nll * mask).sum() / denom
    loss = ce + aux
    metrics = {"loss": loss, "ce": ce, "aux": aux,
               "ppl": jnp.exp(jnp.minimum(ce, 20.0)), "tokens": mask.sum()}
    return loss, metrics


# ---------------------------------------------------------------------------
# Serving: prefill + decode with per-layer caches
# ---------------------------------------------------------------------------
def init_caches(cfg: ModelConfig, batch: int, max_len: int, *, paged=None,
                plan=None, dtype=None):
    """Per-layer decode caches.  ``paged`` (a ``serving.paged.PagedSpec``,
    or carried by ``plan.paged`` — the plan-first spelling) switches
    standard softmax KV layers to the shared page pool; all other cache
    kinds are unaffected (flow/linear/rglru/ssd states are already
    constant-size, local rings already bounded).  ``dtype`` is the serving
    activation dtype for caches that follow it (dense KV; default
    bfloat16)."""
    if paged is not None and (plan is None or plan.paged is None):
        # legacy facade sugar: fold the bare ``paged=`` spec into the plan
        import dataclasses

        from repro.layers.attention import plan_of

        plan = dataclasses.replace(plan or plan_of(cfg), paged=paged)
    return [mx.state_init(batch, max_len, dtype=dtype)
            for mx in resolve_mixers(cfg, plan)]


def _blocks_list(params, cfg: ModelConfig):
    """Yield per-layer params in order, unstacking scanned groups."""
    if "blocks" in params:
        yield from params["blocks"]
        return
    n_rep = jax.tree.leaves(params["scan"][0])[0].shape[0]
    for r in range(n_rep):
        for j in range(len(cfg.pattern)):
            yield jax.tree.map(lambda x: x[r], params["scan"][j])
    yield from params["tail"]


def prefill(params, inputs: Array, cfg: ModelConfig, max_len: int,
            *, dtype=jnp.bfloat16, lengths: Array | None = None, plan=None):
    """Consume a prompt; return (last-token logits, caches).

    ``lengths`` (B,) int packs several right-padded prompts into ONE call
    (continuous-batching admission): every layer is causal or position-wise
    so padding never leaks into true positions, per-row cache state lands
    at each row's own boundary, and the returned logits are gathered at
    position ``lengths[i]-1`` per row.  Packing requires every layer's
    mixer to report the ``packable`` capability (rglru/ssd scans freeze
    their recurrences at each row's boundary; local rings decline —
    admission consults the flag and falls back per request)."""
    b, n = inputs.shape[0], inputs.shape[1]
    x = _embed_inputs(params, inputs, cfg, dtype)
    positions = (default_mrope_positions(b, n) if cfg.rope == "mrope"
                 else default_positions(b, n))
    caches = []
    mixers = resolve_mixers(cfg, plan)
    for i, bp in enumerate(_blocks_list(params, cfg)):
        mx = mixers[i]
        h = apply_norm(bp["norm1"], x, cfg.norm)
        y, cache = mx.prefill(bp[mx.params_field], h, max_len,
                              positions=positions, lengths=lengths)
        caches.append(cache)
        x = x + y
        if "ffn" in bp:
            x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
        elif "moe" in bp:
            y2, _ = moe(bp["moe"], apply_norm(bp["norm2"], x, cfg.norm),
                        cfg.act, cfg.moe)
            x = x + y2
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = _head_params(params, cfg)
    if lengths is None:
        x_last = x[:, -1:]
    else:  # each row's boundary token, not the padded tail
        li = jnp.maximum(lengths.astype(jnp.int32), 1) - 1
        x_last = jnp.take_along_axis(x, li[:, None, None], axis=1)
    logits = unembed(head, x_last, softcap=cfg.logit_softcap)
    return logits, caches


def decode(params, token: Array, caches, cfg: ModelConfig, pos: Array,
           *, dtype=jnp.bfloat16, page_table: Array | None = None, plan=None):
    """One decode step.  token: (B, 1) int or (B, 1, d) stub embedding.

    pos: () or (B,) int32 — absolute position(s) of this token (per-slot
    under continuous batching).
    page_table: (B, pages_per_slot) int32 slot->page mapping, required when
    the caches are paged (``init_caches`` with a paged plan); one table
    serves every layer (the table is runtime data and stays a call arg —
    the *spec* rides ``plan.paged``).
    Returns (logits (B,1,vocab), new_caches)."""
    b = token.shape[0]
    x = _embed_inputs(params, token, cfg, dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    positions = (
        default_mrope_positions(b, 1, pos) if cfg.rope == "mrope"
        else default_positions(b, 1, pos)
    )
    new_caches = []
    mixers = resolve_mixers(cfg, plan)
    for i, bp in enumerate(_blocks_list(params, cfg)):
        mx = mixers[i]
        h = apply_norm(bp["norm1"], x, cfg.norm)
        y, cache = mx.decode_step(bp[mx.params_field], h, caches[i],
                                  positions=positions,
                                  page_table=page_table)
        new_caches.append(cache)
        x = x + y
        if "ffn" in bp:
            x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
        elif "moe" in bp:
            y2, _ = moe(bp["moe"], apply_norm(bp["norm2"], x, cfg.norm),
                        cfg.act, cfg.moe)
            x = x + y2
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = _head_params(params, cfg)
    logits = unembed(head, x, softcap=cfg.logit_softcap)
    return logits, new_caches


def verify(params, tokens: Array, caches, cfg: ModelConfig, pos: Array,
           *, dtype=jnp.bfloat16, page_table: Array | None = None, plan=None):
    """Score a drafted window of n tokens in one pass (speculative decode).

    tokens: (B, n) int — the last committed token followed by the n-1
    drafted candidates; ``logits[:, j]`` scores the token at position
    ``pos + j + 1``, exactly matching n sequential ``decode`` calls.
    pos: () or (B,) int32 — absolute position of ``tokens[:, 0]`` per slot.
    Returns (logits (B, n, vocab), pending_caches): the pending caches hold
    every layer's post-window verify state (trajectories for constant-size
    states, position-advanced caches for KV layers) — commit the accepted
    prefix with ``select_verified(pending, accepted, n, cfg)``.
    """
    b, n = tokens.shape[0], tokens.shape[1]
    x = _embed_inputs(params, tokens, cfg, dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    positions = (
        default_mrope_positions(b, n, pos) if cfg.rope == "mrope"
        else default_positions(b, n, pos)
    )
    pending = []
    mixers = resolve_mixers(cfg, plan)
    for i, bp in enumerate(_blocks_list(params, cfg)):
        mx = mixers[i]
        h = apply_norm(bp["norm1"], x, cfg.norm)
        y, cache = mx.verify_step(bp[mx.params_field], h, caches[i],
                                  positions=positions,
                                  page_table=page_table)
        pending.append(cache)
        x = x + y
        if "ffn" in bp:
            x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
        elif "moe" in bp:
            y2, _ = moe(bp["moe"], apply_norm(bp["norm2"], x, cfg.norm),
                        cfg.act, cfg.moe)
            x = x + y2
    x = apply_norm(params["final_norm"], x, cfg.norm)
    head = _head_params(params, cfg)
    logits = unembed(head, x, softcap=cfg.logit_softcap)
    return logits, pending


def select_verified(pending, accepted: Array, n: int, cfg: ModelConfig,
                    *, plan=None):
    """Roll every layer's pending verify state to the accepted prefix.

    accepted: (B,) int in [0, n-1] — the per-row index of the last consumed
    window token (``accepted + 1`` tokens advance the state).  Returns
    caches equivalent to having decoded only the accepted tokens.
    """
    mixers = resolve_mixers(cfg, plan)
    return [mx.select_verified(pending[i], accepted, n)
            for i, mx in enumerate(mixers)]
