"""Mamba-2 block via SSD — state-space duality (arXiv:2405.21060).

The SSD recurrence per head (head_dim P, state N):

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t
    y_t = h_t @ C_t + D * x_t

is computed with the chunked dual form (all matmuls, MXU-friendly):

    within chunk:  y_intra = ((C_i . B_j) * exp(cum_i - cum_j) * 1[j<=i]) @ (dt*x)
    across chunks: y_inter = exp(cum_i) * (C_i @ h_prev)
    state update:  h_new   = exp(cum_total) * h_prev + sum_j exp(cum_total - cum_j) (dt_j x_j) outer B_j

Structure intentionally mirrors repro/attention/chunked.py — SSD *is* decay-gated
chunked linear attention (the duality), which is why our Pallas chunk kernel
family covers both (kernels/ssd_chunk).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers import mixer as mixer_lib
from repro.layers.linear import dense, dense_init
from repro.layers.norms import apply_norm, norm_init
from repro.layers.rglru import _boundary_conv_history, _causal_conv
from repro.utils import KeySeq, lecun_normal, varying_zeros

Array = jax.Array


class SSDState(NamedTuple):
    h: Array  # (B, H, P, N) ssm state
    conv: tuple  # per-component (x, B, C) trailing inputs for causal conv


def _dims(cfg: ModelConfig):
    s = cfg.ssd
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    return s, d_in, n_heads


def ssd_init(key, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    s, d_in, nh = _dims(cfg)
    d = cfg.d_model
    lo, hi = s.a_init_range
    a = jnp.exp(
        jax.random.uniform(ks(), (nh,), minval=math.log(lo), maxval=math.log(hi))
    )
    return {
        # separate projections (vs. one fused in_proj) so each shards cleanly
        # over the model axis (heads for z/x/dt; B/C replicated) — see
        # distribution/sharding.py
        "in_z": dense_init(ks(), d, d_in),
        "in_x": dense_init(ks(), d, d_in),
        "in_b": dense_init(ks(), d, s.d_state),
        "in_c": dense_init(ks(), d, s.d_state),
        "in_dt": dense_init(ks(), d, nh),
        "conv_x_w": lecun_normal(ks(), (s.conv_width, d_in)) * 0.1,
        "conv_x_b": jnp.zeros((d_in,), jnp.float32),
        "conv_b_w": lecun_normal(ks(), (s.conv_width, s.d_state)) * 0.1,
        "conv_b_b": jnp.zeros((s.d_state,), jnp.float32),
        "conv_c_w": lecun_normal(ks(), (s.conv_width, s.d_state)) * 0.1,
        "conv_c_b": jnp.zeros((s.d_state,), jnp.float32),
        "a_log": jnp.log(a),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(ks(), (nh,),
                                       minval=math.log(1e-3), maxval=math.log(1e-1)))
        )),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "norm": norm_init(d_in, "rmsnorm"),
        "out_proj": dense_init(ks(), d_in, d),
    }


def _split_in(params, x: Array, cfg: ModelConfig):
    z = dense(params["in_z"], x)
    xh = dense(params["in_x"], x)
    bmat = dense(params["in_b"], x)
    cmat = dense(params["in_c"], x)
    dt = dense(params["in_dt"], x)
    return z, xh, bmat, cmat, dt


def _conv_all(params, xh, bmat, cmat, hist):
    """Depthwise causal conv per component; hist = (hx, hb, hc) or None."""
    hx, hb, hc = (None, None, None) if hist is None else hist
    xh, nx = _causal_conv(xh, params["conv_x_w"], params["conv_x_b"], history=hx)
    bmat, nb = _causal_conv(bmat, params["conv_b_w"], params["conv_b_b"], history=hb)
    cmat, nc = _causal_conv(cmat, params["conv_c_w"], params["conv_c_b"], history=hc)
    return xh, bmat, cmat, (nx, nb, nc)


def _ssd_scan_chunked(xh, dt, bmat, cmat, a, chunk: int):
    """Chunked SSD over (B, N, H, P) inputs.

    xh: (B,N,H,P); dt: (B,N,H) fp32; bmat/cmat: (B,N,S); a: (H,) negative.
    Returns y: (B,N,H,P), final state (B,H,P,S).
    """
    bsz, n, h, p = xh.shape
    sdim = bmat.shape[-1]
    c = min(chunk, n)
    while n % c:
        c //= 2
    nc = n // c

    xr = xh.reshape(bsz, nc, c, h, p)
    dtr = dt.reshape(bsz, nc, c, h)
    br = bmat.reshape(bsz, nc, c, sdim)
    cr = cmat.reshape(bsz, nc, c, sdim)

    def step(hstate, inp):
        xb, dtb, bb, cb = inp  # (B,c,H,P), (B,c,H), (B,c,S), (B,c,S)
        da = dtb * a  # (B,c,H) negative decays
        cum = jnp.cumsum(da, axis=1)  # inclusive
        # intra-chunk: mask_ij = exp(cum_i - cum_j) for j <= i.  Clamp before
        # exp: upper-triangle diffs are large-positive -> exp inf -> NaN grads
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # (B,c,c,H)
        mask = jnp.tril(jnp.ones((c, c), bool))
        decay = jnp.where(mask[None, :, :, None],
                          jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
        scores = jnp.einsum("bis,bjs->bij", cb, bb,
                            preferred_element_type=jnp.float32)
        xdt = xb.astype(jnp.float32) * dtb[..., None]  # (B,c,H,P)
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores[:, :, :, None] * decay, xdt)
        # inter-chunk
        y_inter = jnp.einsum("bis,bhps->bihp", cb, hstate) * jnp.exp(cum)[..., None]
        # state update
        seg = jnp.exp(cum[:, -1:, :] - cum)  # decay from j to chunk end
        h_new = hstate * jnp.exp(cum[:, -1])[:, :, None, None] + jnp.einsum(
            "bjhp,bjs->bhps", xdt * seg[..., None], bb
        )
        return h_new, y_intra + y_inter

    h0 = varying_zeros((bsz, h, p, sdim), xr, dtr, br)
    xs = (jnp.moveaxis(xr, 1, 0), jnp.moveaxis(dtr, 1, 0),
          jnp.moveaxis(br, 1, 0), jnp.moveaxis(cr, 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, n, h, p)
    return y, h_final


def ssd_block(params, x: Array, cfg: ModelConfig, plan=None) -> Array:
    """Full-sequence Mamba-2 block.  x: (B, N, d_model)."""
    out, _ = _ssd_forward(params, x, cfg, state=None, plan=plan)
    return out


def _ssd_forward(params, x: Array, cfg: ModelConfig, state: SSDState | None,
                 lengths: Array | None = None, plan=None):
    """``lengths`` (B,) packs right-padded prompts into ONE chunked scan:
    dt at positions >= lengths[i] is zeroed, so the decay exp(dt*a) is 1
    and the input term dt*x is 0 — the scan-carried state freezes at each
    row's boundary and the final carry IS the per-row boundary state
    (masked exactly like the cp boundary psums).  Conv histories are
    gathered per row from the raw (pre-silu) component streams."""
    s, d_in, nh = _dims(cfg)
    bsz, n, _ = x.shape
    z, xh, bmat, cmat, dt = _split_in(params, x, cfg)
    raw = (xh, bmat, cmat)
    hist = None if state is None else state.conv
    xh, bmat, cmat, new_hist = _conv_all(params, xh, bmat, cmat, hist)
    xh = jax.nn.silu(xh)
    bmat = jax.nn.silu(bmat)
    cmat = jax.nn.silu(cmat)
    xh = xh.reshape(bsz, n, nh, s.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,N,H)
    if lengths is not None:
        live = (jnp.arange(n)[None, :]
                < lengths.astype(jnp.int32)[:, None])  # (B,N)
        dt = dt * live[..., None]
        new_hist = tuple(
            _boundary_conv_history(r, lengths, s.conv_width, plan)
            for r in raw
        )
    a = -jnp.exp(params["a_log"])  # (H,)

    h0 = None if state is None else state.h
    if state is None and mixer_lib.plan_platform(plan) == "tpu":
        # training path on TPU: fused Pallas chunk kernel (state discarded)
        # flowlint: disable=FL001 -- the ssd mixer IS this kernel's provider (no registry tier between)
        from repro.kernels.ssd_chunk import ssd_scan_pallas

        y = ssd_scan_pallas(xh, dt, bmat.astype(jnp.float32),
                            cmat.astype(jnp.float32), a, chunk=s.chunk_size)
        h_final = jnp.zeros((bsz, nh, s.head_dim, s.d_state), jnp.float32)
    else:
        y, h_final = _ssd_scan_chunked_with_init(
            xh, dt, bmat.astype(jnp.float32), cmat.astype(jnp.float32), a,
            s.chunk_size, h0,
        )
    y = y + params["d_skip"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, n, d_in).astype(x.dtype)
    y = apply_norm(params["norm"], y * jax.nn.silu(z), "rmsnorm")
    out = dense(params["out_proj"], y)
    new_state = SSDState(h=h_final, conv=jax.tree.map(lambda t: t.astype(jnp.bfloat16), new_hist))
    return out, new_state


def _ssd_scan_chunked_with_init(xh, dt, bmat, cmat, a, chunk, h0):
    if h0 is None:
        return _ssd_scan_chunked(xh, dt, bmat, cmat, a, chunk)
    # fold initial state in by running the scan then correcting is complex;
    # instead prepend nothing and use recurrence: for prefill-from-state we
    # run the chunked scan with explicit initial carry.
    bsz, n, h, p = xh.shape
    y, hf = _ssd_scan_chunked(xh, dt, bmat, cmat, a, chunk)
    # contribution of initial state decays through all positions:
    cum = jnp.cumsum(dt * a, axis=1)  # (B,N,H)
    y_init = jnp.einsum("bns,bhps->bnhp", cmat, h0) * jnp.exp(cum)[..., None]
    hf = hf + h0 * jnp.exp(cum[:, -1])[:, :, None, None]
    return y + y_init, hf


def _ssd_state_init(cfg: ModelConfig, batch: int) -> SSDState:
    s, d_in, nh = _dims(cfg)
    return SSDState(
        h=jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
        conv=(
            jnp.zeros((batch, s.conv_width - 1, d_in), jnp.bfloat16),
            jnp.zeros((batch, s.conv_width - 1, s.d_state), jnp.bfloat16),
            jnp.zeros((batch, s.conv_width - 1, s.d_state), jnp.bfloat16),
        ),
    )


def _ssd_prefill(params, x: Array, cfg: ModelConfig,
                 lengths: Array | None = None, *, plan=None):
    state = _ssd_state_init(cfg, x.shape[0])
    return _ssd_forward(params, x, cfg, state, lengths=lengths, plan=plan)


def _ssd_decode(params, x: Array, state: SSDState, cfg: ModelConfig):
    """One-token decode via the plain recurrence.  x: (B, 1, d_model)."""
    s, d_in, nh = _dims(cfg)
    bsz = x.shape[0]
    z, xh, bmat, cmat, dt = _split_in(params, x, cfg)
    xh, bmat, cmat, hist = _conv_all(params, xh, bmat, cmat, state.conv)
    xh = jax.nn.silu(xh)
    bmat = jax.nn.silu(bmat)
    cmat = jax.nn.silu(cmat)
    xh = xh.reshape(bsz, nh, s.head_dim)  # (B,H,P)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B,H)
    a = -jnp.exp(params["a_log"])
    decay = jnp.exp(dtv * a)  # (B,H)
    bm = bmat[:, 0].astype(jnp.float32)  # (B,S)
    cm = cmat[:, 0].astype(jnp.float32)
    h = state.h * decay[:, :, None, None] + jnp.einsum(
        "bhp,bs->bhps", xh.astype(jnp.float32) * dtv[..., None], bm
    )
    y = jnp.einsum("bhps,bs->bhp", h, cm)
    y = y + params["d_skip"][None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(bsz, 1, d_in).astype(x.dtype)
    y = apply_norm(params["norm"], y * jax.nn.silu(z), "rmsnorm")
    return dense(params["out_proj"], y), SSDState(h=h, conv=jax.tree.map(lambda t: t.astype(jnp.bfloat16), hist))


# ---------------------------------------------------------------------------
# SequenceMixer registration + legacy-name shims
# ---------------------------------------------------------------------------
class SSDMixer(mixer_lib.Mixer):
    """Mamba-2 SSD as a registered sequence mixer.

    ``block_ffn=False``: the Mamba block IS the whole layer (gated SSM +
    out-projection, no separate FFN sublayer).
    """

    params_field = "ssd"
    block_ffn = False

    def packable(self, cfg):
        return True, ("boundary states via dt-masked chunked scan "
                      "+ per-row conv-history gathers")

    def quant_capable(self, cfg, platform, dtype):
        from repro.serving.quant import platform_support

        ok, why = platform_support(dtype, platform)
        if not ok:
            return False, why
        return True, ("dequantize -> fp32 SSD recurrence -> requantize "
                      f"per step ({why})")

    def differentiable(self, cfg, platform):
        if platform == "tpu":
            return True, (
                "ssd_chunk custom VJP: reverse-scan Pallas backward off "
                "chunk-boundary carry-in residuals"
            )
        return True, "chunked XLA scan is natively differentiable"

    def init_params(self, key, cfg):
        return ssd_init(key, cfg)

    def forward(self, params, x, cfg, *, positions=None, plan=None):
        return ssd_block(params, x, cfg, plan)

    def state_init(self, cfg, batch, max_len, *, dtype=None, plan=None):
        from repro.serving.quant import maybe_quantize

        return maybe_quantize(_ssd_state_init(cfg, batch), plan)

    def prefill(self, params, x, cfg, max_len, *, positions=None, plan=None):
        return _ssd_prefill(params, x, cfg)

    def prefill_packed(self, params, x, cfg, max_len, lengths, *,
                       positions=None, plan=None):
        return _ssd_prefill(params, x, cfg, lengths=lengths, plan=plan)

    def decode_step(self, params, x, state, cfg, *, positions=None,
                    page_table=None, plan=None):
        from repro.serving.quant import (QuantizedPool, dequantize_state,
                                         quantize_like)

        if isinstance(state, QuantizedPool):
            # constant-size state, fully rewritten per step: fp32 update
            # between a boundary dequantize and a fresh-amax requantize
            out, new = _ssd_decode(params, x, dequantize_state(state), cfg)
            return out, quantize_like(state, new)
        return _ssd_decode(params, x, state, cfg)


mixer_lib.register_mixer("ssd", SSDMixer())


ssd_state_init = mixer_lib.make_legacy_shim(
    "ssd", "ssd_state_init", _ssd_state_init, "ssd", "state_init")
ssd_prefill = mixer_lib.make_legacy_shim(
    "ssd", "ssd_prefill", _ssd_prefill, "ssd", "prefill")
ssd_decode = mixer_lib.make_legacy_shim(
    "ssd", "ssd_decode", _ssd_decode, "ssd", "decode_step")
