"""Plain reference of a decoder-only Flow-Attention language model.

Straight ``jax.numpy`` in float32 at the highest matmul precision, from the
published equations (Wu et al., ICML 2022, Alg. 2, with the strict-causal
cumulative competition the served model uses), and nothing of the program:
no kernels, caches, batching or imports from ``repro``.  It reads the
weights tree the benchmark made (``bench/weights.py``), in the layout the
model consumes: ``embed``, per-layer blocks stacked under ``scan`` (or
listed under ``blocks``/``tail``), ``final_norm`` and ``head``.

Each layer: pre-norm, q/k/v projections, rotary positions on q and k,
sigmoid feature map, grouped-query flow attention where the query heads of
a group share one kv head's sources, output projection, residual; then
pre-norm feed-forward (SwiGLU or tanh-GELU) and residual.

Causal flow attention over positions i = 1..n (G query heads per kv head,
eps 1e-6; sums over j <= i):

    I_i  = n_k(i) / ((phiQ_i + eps) . (sum phiK_j + eps))       n_k(i) = i
    O_j  = n_q(j) / ((phiK_j + eps) . (sum_g phiQ_j + eps))     n_q(j) = G j
    Ih_i = (phiQ_i + eps) . (sum phiK_j O_j + eps) / n_q(i)
    Oh_j = clip((phiK_j + eps) . (sum_g phiQ_j I_j + eps) / n_k(j), -1, 1)
    out_i = sigmoid(Ih_i) * n_k(i) / (sum e_j)
            * sum_j ((phiQ_i I_i) . phiK_j) e_j V_j,     e_j = exp(Oh_j)

The aggregation is computed as a masked product in blocks of query rows,
so the reference stays exact and fits at the served lengths.

``quant="fp8"`` makes the control: every projection's operands are rounded
through float8 e4m3 with a per-tensor scale, the nearest precision below
the bfloat16 the served and trained models compute in (gradients stay
fp32, as an fp8 training recipe keeps them).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-6  # flow-attention eps
NORM_EPS = 1e-6
ROW_BLOCK = 512


def _q8(x):
    """Round ``x`` through float8 e4m3 with a per-tensor amax scale; the
    gradient passes straight through in fp32 (only products see fp8)."""
    amax = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30))
    s = 448.0 / amax
    q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _q8(x), _q8(w)
    return jnp.einsum("...i,io->...o", x, w)


def _norm(p, x, kind):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + NORM_EPS) * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + NORM_EPS) * p["scale"] + p["bias"]


def _rope(x, theta):
    """x: (B, H, N, D); rotate the two halves of D by position."""
    n, d = x.shape[2], x.shape[3]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def flow_attention(q, k, v):
    """Strict-causal grouped flow attention.

    q: (B, Hkv, G, N, D) raw; k: (B, Hkv, N, D); v: (B, Hkv, N, Dv).
    Returns (B, Hkv, G, N, Dv)."""
    g, n = q.shape[2], q.shape[3]
    pq, pk = jax.nn.sigmoid(q), jax.nn.sigmoid(k)
    pos = jnp.arange(1, n + 1, dtype=jnp.float32)
    n_k, n_q = pos, pos * g
    k_cs = jnp.cumsum(pk, axis=2)
    q_cs = jnp.cumsum(pq.sum(axis=2), axis=2)
    sink_in = n_k / jnp.sum((pq + EPS) * (k_cs[:, :, None] + EPS), -1)
    src_out = n_q / jnp.sum((pk + EPS) * (q_cs + EPS), -1)
    ko_cs = jnp.cumsum(pk * src_out[..., None], axis=2)
    cons_sink = jnp.sum((pq + EPS) * (ko_cs[:, :, None] + EPS), -1) / n_q
    qi_cs = jnp.cumsum((pq * sink_in[..., None]).sum(axis=2), axis=2)
    cons_src = jnp.clip(jnp.sum((pk + EPS) * (qi_cs + EPS), -1) / n_k,
                        -1.0, 1.0)
    e = jnp.exp(cons_src)
    z = jnp.cumsum(e, axis=-1)
    q_in = pq * sink_in[..., None]
    v_w = v * e[..., None]
    scale = (n_k / z)[:, :, None] * jax.nn.sigmoid(cons_sink)  # (B,H,G,N)
    outs = []
    for a in range(0, n, ROW_BLOCK):
        b = min(n, a + ROW_BLOCK)
        s = jnp.einsum("bhgid,bhjd->bhgij", q_in[:, :, :, a:b], pk)
        mask = jnp.arange(a, b)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(mask, s, 0.0)
        outs.append(jnp.einsum("bhgij,bhje->bhgie", s, v_w))
    return jnp.concatenate(outs, axis=3) * scale[..., None]


def block(bp, x, model: dict, quant=None):
    """One decoder layer on the residual stream x: (B, N, d_model)."""
    b, n, _ = x.shape
    hq = model["n_heads"]
    hkv = model.get("n_kv_heads") or hq
    hd = model.get("head_dim") or model["d_model"] // hq
    theta = model.get("rope_theta", 10000.0)
    a = bp["attn"]
    h = _norm(bp["norm1"], x, model["norm"])

    def heads(w, nh):
        return _mm(h, w["w"], quant).reshape(b, n, nh, hd).transpose(0, 2, 1, 3)

    q = _rope(heads(a["wq"], hq), theta)
    k = _rope(heads(a["wk"], hkv), theta)
    v = heads(a["wv"], hkv)
    o = flow_attention(q.reshape(b, hkv, hq // hkv, n, hd), k, v)
    o = o.reshape(b, hq, n, hd).transpose(0, 2, 1, 3).reshape(b, n, hq * hd)
    x = x + _mm(o, a["wo"]["w"], quant)
    f = bp["ffn"]
    h = _norm(bp["norm2"], x, model["norm"])
    u = _mm(h, f["w_in"]["w"], quant)
    if model["act"] == "swiglu":
        u = jax.nn.silu(_mm(h, f["w_gate"]["w"], quant)) * u
    elif model["act"] == "gelu":  # the tanh form
        u = 0.5 * u * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                      * (u + 0.044715 * u ** 3)))
    else:
        raise ValueError(f"reference has no activation {model['act']!r}")
    return x + _mm(u, f["w_out"]["w"], quant)


def layer_params(params):
    """The per-layer weight dicts in order, unstacking scanned groups one
    layer at a time (a full-width layer is most of a GB)."""
    if "blocks" in params:
        yield from params["blocks"]
        return
    stacks = params["scan"]
    n_rep = jax.tree.leaves(stacks[0])[0].shape[0]
    for r in range(n_rep):
        for st in stacks:
            yield jax.tree.map(lambda t, r=r: t[r], st)
    yield from params.get("tail", [])


def _logits(params, x, model, quant):
    x = _norm(params["final_norm"], x, model["norm"])
    head = params["embed"] if model.get("tie_embeddings") else params["head"]
    return _mm(x, head["table"].T, quant)


@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _block_jit(bp, x, model_key, quant):
    with jax.default_matmul_precision("highest"):
        return block(bp, x, dict(model_key), quant)


@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _head_jit(params, x, model_key, quant):
    with jax.default_matmul_precision("highest"):
        return _logits(params, x, dict(model_key), quant)


def model_key(model: dict) -> tuple:
    """A hashable form of the model dict's top-level scalars."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if not isinstance(v, (dict, list))))


def forward(params, tokens, model: dict, quant=None):
    """Logits (B, N, vocab) fp32 of ``tokens`` (B, N), one layer per call so
    a full-width model's activations stay small."""
    mk = model_key(model)
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    for bp in layer_params(params):
        x = _block_jit(bp, x, mk, quant)
    return _head_jit({k: params[k] for k in ("final_norm", "embed", "head")
                      if k in params}, x, mk, quant)


def loss_fn(params, inputs, targets, model: dict, quant=None):
    """Mean next-token cross-entropy of one batch, every layer rematerialised
    so the backward holds one layer's attention at a time."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][inputs].astype(jnp.float32)
        step = jax.checkpoint(functools.partial(block, model=model,
                                                quant=quant))
        for bp in layer_params(params):
            x = step(bp, x)
        logits = _logits(params, x, model, quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()


@functools.partial(jax.jit, static_argnames=("model_key", "quant"))
def _loss_grad_jit(params, inputs, targets, model_key, quant):
    return jax.value_and_grad(loss_fn)(params, inputs, targets,
                                       dict(model_key), quant)


def loss_and_grad(params, inputs, targets, model: dict, quant=None):
    """Loss and gradient of the batch, one row at a time (rows have equal
    length, so the batch mean is the mean of the row means).  One program
    serves every row and step: a program traced anew for each step would
    cost more than the reference's arithmetic."""
    mk = model_key(model)
    rows = inputs.shape[0]
    loss, grad = 0.0, None
    for r in range(rows):
        l_r, g_r = _loss_grad_jit(params, inputs[r:r + 1], targets[r:r + 1],
                                  mk, quant)
        loss = loss + l_r / rows
        grad = g_r if grad is None else jax.tree.map(jnp.add, grad, g_r)
    return loss, jax.tree.map(lambda g: g / rows, grad)


def warmup_cosine(step: int, opt: dict) -> float:
    """Learning rate at optimizer step ``step`` (0-based): linear warm-up
    to ``peak_lr``, then cosine decay to ``floor * peak_lr``."""
    peak, warm, total = opt["peak_lr"], opt["warmup"], opt["total_steps"]
    floor = opt.get("lr_floor", 0.1)
    if step < warm:
        return peak * step / max(warm, 1)
    frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * frac)))


def adamw_steps(params, batches, model: dict, opt: dict, quant=None):
    """Run ``len(batches)`` AdamW steps from ``params``: global-norm clip,
    bias-corrected moments, decoupled weight decay on every leaf of two or
    more dimensions.  Returns (losses, first clipped gradient, params)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, batch in enumerate(batches):
        loss, g = loss_and_grad(params, batch["inputs"], batch["targets"],
                                model, quant)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        if first is None:
            first = g
        lr = warmup_cosine(t, opt)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)

        def upd(p, a, s):
            u = (a / c1) / (jnp.sqrt(s / c2) + eps)
            if p.ndim >= 2:
                u = u + opt["weight_decay"] * p
            return p - lr * u

        params = jax.tree.map(upd, params, m, v)
        losses.append(float(loss))
    return losses, first, params
