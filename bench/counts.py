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


def attn_dims(m: dict) -> tuple[int, int, int, int]:
    """(kv heads, query heads a kv head serves, key width, value width) of
    a layer's flow attention.  Latent attention (``mla``) gives each query
    head its own keys, ``nope + rope`` wide, and values ``v_head_dim``
    wide."""
    a = m.get("mla")
    if a:
        return (m["n_heads"], 1, a["nope_head_dim"] + a["rope_head_dim"],
                a["v_head_dim"])
    hkv, hd = kv_heads(m), head_dim(m)
    return hkv, m["n_heads"] // hkv, hd, hd


def attn_params(m: dict) -> int:
    """Projection weights of one attention layer, laid out as the program
    lays them out: q/k/v/o heads, or with ``mla`` the query projection
    (``wq``, or ``q_down`` and ``q_up`` under a query rank), ``kv_down``
    onto the latent and the rotary key, ``kv_up`` from the latent to every
    head's keys and values, and ``wo``."""
    d, h = m["d_model"], m["n_heads"]
    a = m.get("mla")
    if not a:
        hd = head_dim(m)
        return 2 * d * h * hd + 2 * d * kv_heads(m) * hd
    qk, r = a["nope_head_dim"] + a["rope_head_dim"], a["q_lora_rank"]
    q = d * r + r * h * qk if r else d * h * qk
    kv = (d * (a["kv_lora_rank"] + a["rope_head_dim"])
          + a["kv_lora_rank"] * h * (a["nope_head_dim"] + a["v_head_dim"]))
    return q + kv + h * a["v_head_dim"] * d


def ffn_params(m: dict, width: int) -> int:
    """Weights of one feed-forward network ``width`` wide."""
    return m["d_model"] * width * (3 if m["act"] == "swiglu" else 2)


def layer_params(m: dict, i: int) -> float:
    """Weights a token passes through in layer ``i``: attention, then a
    dense FFN ``d_ff`` wide in the first ``n_dense_layers`` layers and in
    every layer of a model without ``moe``, else the expert layer: the
    router over ``moe.router_width`` experts (absent: ``n_experts``), the
    ``n_shared`` experts, and ``top_k * n_experts / router_width`` routed
    ones, the experts held here (``n_experts``) that a token reaches on
    average."""
    e = m.get("moe")
    if not e or i < m.get("n_dense_layers", 0):
        return attn_params(m) + ffn_params(m, m["d_ff"])
    scored = e.get("router_width") or e["n_experts"]
    routed = e["top_k"] * e["n_experts"] / scored
    return (attn_params(m) + m["d_model"] * scored
            + (e["n_shared"] + routed)
            * ffn_params(m, e["d_ff_expert"] or m["d_ff"]))


def layers_params(m: dict) -> float:
    """Weights a token passes through in all layers, the head left out."""
    return sum(layer_params(m, i) for i in range(m["n_layers"]))


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
    hkv, g, dk, dv = attn_dims(m)
    return hkv * flow_chunk_flops(g, c, dk, dv) / c


def decode_attn_flops_per_token(m: dict) -> float:
    """Forward Flow-Attention FLOPs of one decoded token in one layer."""
    hkv, g, dk, dv = attn_dims(m)
    return flow_decode(hkv, g, dk, dv)[0]


def decode_token_flops(m: dict) -> float:
    """Model FLOPs of one decoded token: every layer, then the head."""
    return (2 * layers_params(m)
            + m["n_layers"] * decode_attn_flops_per_token(m)
            + 2 * head_params(m))


def prefill_flops(m: dict, tokens: int, prompts: int) -> float:
    """Model FLOPs of prefilling ``tokens`` real prompt tokens (padding
    excluded) of ``prompts`` prompts; the head runs once per prompt, at its
    last position."""
    per_token = 2 * layers_params(m) + m["n_layers"] * attn_flops_per_token(m)
    return tokens * per_token + prompts * 2 * head_params(m)


def train_token_flops(m: dict) -> float:
    """Model FLOPs of one trained token, forward and backward, no recompute:
    6 per matmul weight it passes (head included) plus three times the
    forward attention."""
    weights = layers_params(m) + head_params(m)
    return 6 * weights + 3 * m["n_layers"] * attn_flops_per_token(m)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time for a call, and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
