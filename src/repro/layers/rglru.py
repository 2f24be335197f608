"""RG-LRU recurrent block (Griffin / RecurrentGemma, arXiv:2402.19427).

Real-Gated Linear Recurrent Unit:

    r_t = sigmoid(W_a x_t)            recurrence gate (block-diagonal proj)
    i_t = sigmoid(W_x x_t)            input gate      (block-diagonal proj)
    log a_t = -c * r_t * softplus(Lambda)          (c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

wrapped in the Griffin recurrent block: two input projections, a short
causal depthwise conv on the recurrent branch, GeLU gating on the other,
and an output projection.  The diagonal recurrence runs as a Blelchoch
associative scan (TPU log-depth); decode carries (h, conv ring buffer).

Serving rides the ``repro/layers/mixer`` SequenceMixer registry: this
module registers the ``rglru`` kind, so hybrid stacks prefill/decode
through the same loops as attention — including *packed* prefill, where
per-row boundary states come out of ONE padded associative scan by
freezing the recurrence past each row's boundary (a=1, b=0 ⇒ the carry
stops moving) and gathering each row's trailing conv inputs.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers import mixer as mixer_lib
from repro.layers.linear import dense, dense_init
from repro.utils import KeySeq, lecun_normal

Array = jax.Array
_C = 8.0


class RGLRUState(NamedTuple):
    h: Array  # (B, W) recurrent state
    conv: Array  # (B, conv_width-1, W) trailing inputs for causal conv


def rglru_init(key, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    d = cfg.d_model
    w = cfg.rglru.lru_width or d
    nb = cfg.rglru.n_blocks
    bw = w // nb
    return {
        "w_x": dense_init(ks(), d, w),
        "w_gate": dense_init(ks(), d, w),
        "conv_w": lecun_normal(ks(), (cfg.rglru.conv_width, w)) * 0.1,
        "conv_b": jnp.zeros((w,), jnp.float32),
        "gate_a": lecun_normal(ks(), (nb, bw, bw)),
        "gate_x": lecun_normal(ks(), (nb, bw, bw)),
        # Lambda init so that a = sigmoid(Lambda)^c spans ~(0.9, 0.999)
        "lam": jnp.log(jnp.expm1(
            jnp.linspace(0.9, 0.999, w) ** (-1.0 / _C) - 1.0
        )),
        "w_out": dense_init(ks(), w, d),
    }


def _block_proj(w_blocks: Array, x: Array) -> Array:
    """Block-diagonal projection: x (..., W) with W = nb*bw."""
    nb, bw, _ = w_blocks.shape
    xs = x.reshape(*x.shape[:-1], nb, bw)
    y = jnp.einsum("...nb,nbc->...nc", xs, w_blocks.astype(x.dtype))
    return y.reshape(*x.shape)


def _causal_conv(x: Array, w: Array, b: Array, history: Array | None = None):
    """Depthwise causal conv along time.  x: (B, N, W); w: (K, W)."""
    k = w.shape[0]
    if history is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[-1]), x.dtype)
    else:
        pad = history.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, N+K-1, W)
    y = sum(
        xp[:, i : i + x.shape[1]] * w[i].astype(x.dtype) for i in range(k)
    )
    return y + b.astype(x.dtype), xp[:, -(k - 1) :]


def _rglru_gates(params, xc: Array):
    r = jax.nn.sigmoid(_block_proj(params["gate_a"], xc).astype(jnp.float32))
    i = jax.nn.sigmoid(_block_proj(params["gate_x"], xc).astype(jnp.float32))
    log_a = -_C * r * jax.nn.softplus(params["lam"])  # (B, N, W) fp32
    a = jnp.exp(log_a)
    gated_x = i * xc.astype(jnp.float32)
    # sqrt(1 - a^2) input normalizer (Griffin eq. 5), stable via expm1
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    return a, beta * gated_x


def rglru_block(params, x: Array, cfg: ModelConfig) -> Array:
    """Full-sequence Griffin recurrent block.  x: (B, N, d_model)."""
    xb = dense(params["w_x"], x)
    gb = jax.nn.gelu(dense(params["w_gate"], x))
    xc, _ = _causal_conv(xb, params["conv_w"], params["conv_b"])
    a, b = _rglru_gates(params, xc)

    def combine(p, q):
        a1, b1 = p
        a2, b2 = q
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    h = h.astype(x.dtype)
    return dense(params["w_out"], h * gb)


def _rglru_state_init(cfg: ModelConfig, batch: int) -> RGLRUState:
    w = cfg.rglru.lru_width or cfg.d_model
    return RGLRUState(
        h=jnp.zeros((batch, w), jnp.float32),
        conv=jnp.zeros((batch, cfg.rglru.conv_width - 1, w), jnp.bfloat16),
    )


def _boundary_conv_history(xb: Array, lengths: Array, k: int,
                           plan=None) -> Array:
    """Per-row trailing conv inputs AT each row's boundary.

    xb: (B, N, W); lengths (B,).  Row i's decode conv history is its last
    ``k-1`` inputs *before* position ``lengths[i]`` — zero-filled on the
    left for rows shorter than the window, exactly like a fresh
    ``_causal_conv`` pad.  When the plan runs on TPU this is a Pallas
    per-tap gather reading the raw stream once (no padded-stream
    materialization); on any other platform it is the XLA pad +
    ``take_along_axis``.
    """
    # flowlint: disable=FL001 -- utility gather below the registry; the plan's platform picks it
    from repro.kernels.gather import boundary_gather, boundary_gather_xla

    if mixer_lib.plan_platform(plan) == "tpu":
        return boundary_gather(xb, lengths, k)
    return boundary_gather_xla(xb, lengths, k)


def _rglru_prefill(params, x: Array, cfg: ModelConfig,
                   lengths: Array | None = None, *, plan=None):
    """Prompt prefill; ``lengths`` (B,) packs right-padded prompts into the
    SAME associative scan: gates at positions >= lengths[i] are frozen to
    the identity element (a=1, b=0) so the scan carry — and therefore
    ``h[:, -1]`` — is each row's boundary state, and the conv history is
    gathered at each row's own boundary.  True positions are untouched
    (the scan is causal); padded outputs are garbage the caller never
    reads."""
    xb = dense(params["w_x"], x)
    gb = jax.nn.gelu(dense(params["w_gate"], x))
    xc, hist = _causal_conv(xb, params["conv_w"], params["conv_b"])
    a, b = _rglru_gates(params, xc)
    if lengths is not None:
        pad = (jnp.arange(x.shape[1])[None, :]
               >= lengths.astype(jnp.int32)[:, None])[..., None]  # (B,N,1)
        a = jnp.where(pad, 1.0, a)
        b = jnp.where(pad, 0.0, b)

    def combine(p, q):
        a1, b1 = p
        a2, b2 = q
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    out = dense(params["w_out"], h.astype(x.dtype) * gb)
    if lengths is not None:
        hist = _boundary_conv_history(xb, lengths, cfg.rglru.conv_width,
                                      plan)
    return out, RGLRUState(h=h[:, -1], conv=hist.astype(jnp.bfloat16))


def _rglru_decode(params, x: Array, state: RGLRUState, cfg: ModelConfig):
    """One-token decode.  x: (B, 1, d_model)."""
    xb = dense(params["w_x"], x)
    gb = jax.nn.gelu(dense(params["w_gate"], x))
    xc, hist = _causal_conv(xb, params["conv_w"], params["conv_b"],
                            history=state.conv)
    a, b = _rglru_gates(params, xc)
    h = a[:, 0] * state.h + b[:, 0]
    out = dense(params["w_out"], h[:, None].astype(x.dtype) * gb)
    return out, RGLRUState(h=h, conv=hist.astype(jnp.bfloat16))


# ---------------------------------------------------------------------------
# SequenceMixer registration + legacy-name shims
# ---------------------------------------------------------------------------
class RGLRUMixer(mixer_lib.Mixer):
    """Griffin RG-LRU as a registered sequence mixer."""

    params_field = "rglru"

    def packable(self, cfg):
        return True, ("boundary states via identity-frozen scan gates "
                      "+ per-row conv-history gather")

    def quant_capable(self, cfg, platform, dtype):
        from repro.serving.quant import platform_support

        ok, why = platform_support(dtype, platform)
        if not ok:
            return False, why
        return True, ("dequantize -> fp32 diagonal recurrence -> "
                      f"requantize per step ({why})")

    def init_params(self, key, cfg):
        return rglru_init(key, cfg)

    def forward(self, params, x, cfg, *, positions=None, plan=None):
        return rglru_block(params, x, cfg)

    def state_init(self, cfg, batch, max_len, *, dtype=None, plan=None):
        from repro.serving.quant import maybe_quantize

        return maybe_quantize(_rglru_state_init(cfg, batch), plan)

    def prefill(self, params, x, cfg, max_len, *, positions=None, plan=None):
        return _rglru_prefill(params, x, cfg)

    def prefill_packed(self, params, x, cfg, max_len, lengths, *,
                       positions=None, plan=None):
        return _rglru_prefill(params, x, cfg, lengths=lengths, plan=plan)

    def decode_step(self, params, x, state, cfg, *, positions=None,
                    page_table=None, plan=None):
        from repro.serving.quant import (QuantizedPool, dequantize_state,
                                         quantize_like)

        if isinstance(state, QuantizedPool):
            # constant-size state, fully rewritten per step: fp32 update
            # between a boundary dequantize and a fresh-amax requantize
            out, new = _rglru_decode(params, x, dequantize_state(state), cfg)
            return out, quantize_like(state, new)
        return _rglru_decode(params, x, state, cfg)


mixer_lib.register_mixer("rglru", RGLRUMixer())


rglru_state_init = mixer_lib.make_legacy_shim(
    "rglru", "rglru_state_init", _rglru_state_init, "rglru", "state_init")
rglru_prefill = mixer_lib.make_legacy_shim(
    "rglru", "rglru_prefill", _rglru_prefill, "rglru", "prefill")
rglru_decode = mixer_lib.make_legacy_shim(
    "rglru", "rglru_decode", _rglru_decode, "rglru", "decode_step")
