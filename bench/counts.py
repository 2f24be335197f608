"""Operations and bytes of each kernel call and each model step, from shapes.

Everything here is a function of a model configuration (the ``model`` dict
of a configuration file) and of call shapes; nothing is read from the
program.  FLOPs count a multiply-add as two.  Kernel counts are what the
chunked strict-causal Flow-Attention algorithm needs for the call; work a
kernel repeats by design (the backward's recompute of the forward chunk,
the tril-matmul cumsums) is not counted, so a roofline share computed from
them can only err low.
"""
from __future__ import annotations

F32 = 4


def head_dim(m: dict) -> int:
    """Per-head width: ``head_dim`` when given, else d_model / n_heads."""
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def kv_heads(m: dict) -> int:
    """Key/value heads (``n_kv_heads``, 0 meaning as many as query heads)."""
    return m.get("n_kv_heads") or m["n_heads"]


def layer_matmul_params(m: dict) -> int:
    """Weights one layer multiplies by: q/k/v/o projections and the FFN."""
    d, hd = m["d_model"], head_dim(m)
    attn = 2 * d * m["n_heads"] * hd + 2 * d * kv_heads(m) * hd
    ffn = d * m["d_ff"] * (3 if m["act"] == "swiglu" else 2)
    return attn + ffn


def head_params(m: dict) -> int:
    """Weights of the output projection onto the vocabulary."""
    return m["d_model"] * m["vocab_size"]


def flow_chunk_flops(g: int, c: int, d: int, dv: int) -> int:
    """One (row, kv head) chunk of the forward: scores and intra-chunk
    aggregation (``g*c`` sinks over ``c`` sources), the read of the carried
    (d, dv) state, and its update."""
    return 2 * g * c * c * d + 2 * g * c * c * dv + 2 * g * c * d * dv \
        + 2 * c * d * dv


def _state_bytes(d: int, dv: int) -> int:
    """One (row, kv head) FlowState: four (d,) flow sums, the competition
    normalizer and the (d, dv) aggregation panel, all fp32."""
    return (4 * d + 1 + d * dv) * F32


def flow_fused_fwd(bh: int, g: int, n: int, d: int, dv: int, chunk: int,
                   act_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of one forward ``flow_fused`` call over ``bh`` rows of
    ``n`` (chunk-padded) positions: read q/k/v, write out and the state."""
    flops = bh * (n // chunk) * flow_chunk_flops(g, chunk, d, dv)
    io = (g * n * d + n * d + n * dv + g * n * dv) * act_bytes
    return flops, bh * (io + _state_bytes(d, dv) + 4)


def flow_fused_bwd(bh: int, g: int, n: int, d: int, dv: int, chunk: int,
                   act_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of one backward ``flow_fused`` call: the transpose of
    every forward product (twice its FLOPs); read q/k/v, the output
    cotangent, the state totals and their cotangents, write dq/dk/dv."""
    flops = 2 * flow_fused_fwd(bh, g, n, d, dv, chunk, act_bytes)[0]
    io = (2 * (g * n * d + n * d + n * dv) + g * n * dv) * act_bytes
    return flops, bh * (io + 2 * _state_bytes(d, dv) + 4)


def flow_decode(bh: int, g: int, d: int, dv: int,
                act_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) of one batched decode call over ``bh`` (slot, kv head)
    rows: read and write each row's state, read q/k/v, write out."""
    flops = bh * (2 * d * dv + 2 * g * d * dv + 8 * g * d)
    io = (g * d + d + dv + g * dv) * act_bytes
    return flops, bh * (2 * _state_bytes(d, dv) + io + 4)


def attn_flops_per_token(m: dict) -> float:
    """Forward Flow-Attention FLOPs per position of one layer, chunked."""
    c = m["attention"].get("chunk_size", 128)
    hkv, hd = kv_heads(m), head_dim(m)
    g = m["n_heads"] // hkv
    return hkv * flow_chunk_flops(g, c, hd, hd) / c


def decode_attn_flops_per_token(m: dict) -> float:
    """Forward Flow-Attention FLOPs of one decoded token in one layer."""
    hkv, hd = kv_heads(m), head_dim(m)
    g = m["n_heads"] // hkv
    return flow_decode(hkv, g, hd, hd)[0]


def decode_token_flops(m: dict) -> float:
    """Model FLOPs of one decoded token: every layer, then the head."""
    per_layer = 2 * layer_matmul_params(m) + decode_attn_flops_per_token(m)
    return m["n_layers"] * per_layer + 2 * head_params(m)


def prefill_flops(m: dict, tokens: int, prompts: int) -> float:
    """Model FLOPs of prefilling ``tokens`` real prompt tokens (padding
    excluded) of ``prompts`` prompts; the head runs once per prompt, at its
    last position."""
    per_token = m["n_layers"] * (2 * layer_matmul_params(m)
                                 + attn_flops_per_token(m))
    return tokens * per_token + prompts * 2 * head_params(m)


def train_token_flops(m: dict) -> float:
    """Model FLOPs of one trained token, forward and backward, no recompute:
    6 per matmul weight (head included) plus three times the forward
    attention."""
    weights = m["n_layers"] * layer_matmul_params(m) + head_params(m)
    return 6 * weights + 3 * m["n_layers"] * attn_flops_per_token(m)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time for a call, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
