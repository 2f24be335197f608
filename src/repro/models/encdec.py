"""Whisper-style encoder-decoder backbone (conv frontend is a stub).

``launch.specs.train_inputs`` / ``prefill_inputs`` for this family provide
*precomputed frame embeddings* (B, N_enc, d_model) — the strided-conv audio
stem is out of scope per the assignment.  Encoder self-attention is
non-causal; decoder self-attention is causal; cross-attention is non-causal
flow attention with n != m (queries = decoder, keys/values = encoder),
exercising the rectangular case of Eq. 4.

Serving: cross-attention decode treats each new token as the single sink of
a fresh non-causal flow attention against the cached encoder keys/values
(n = 1 in Eq. 4 — faithful and incremental; see DESIGN.md §5).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.layers.attention import attention, attn_init
from repro.layers.embeddings import embed, embedding_init, unembed
from repro.layers.mixer import resolve_mixer
from repro.layers.ffn import ffn, ffn_init
from repro.layers.norms import apply_norm, norm_init
from repro.layers.rope import default_positions
from repro.utils import KeySeq

Array = jax.Array


def init(key, cfg: ModelConfig) -> dict:
    ks = KeySeq(key)
    d = cfg.d_model

    def enc_layer(k):
        ks2 = KeySeq(k)
        return {
            "norm1": norm_init(d, cfg.norm),
            "attn": attn_init(ks2(), cfg),
            "norm2": norm_init(d, cfg.norm),
            "ffn": ffn_init(ks2(), d, cfg.d_ff, cfg.act),
        }

    def dec_layer(k):
        ks2 = KeySeq(k)
        return {
            "norm1": norm_init(d, cfg.norm),
            "self_attn": attn_init(ks2(), cfg),
            "norm_x": norm_init(d, cfg.norm),
            "cross_attn": attn_init(ks2(), cfg),
            "norm2": norm_init(d, cfg.norm),
            "ffn": ffn_init(ks2(), d, cfg.d_ff, cfg.act),
        }

    n_enc = cfg.n_encoder_layers or cfg.n_layers
    return {
        "embed": embedding_init(ks(), cfg.vocab_size, cfg.d_model),
        "enc_pos": embedding_init(ks(), cfg.max_seq_len, cfg.d_model),
        "encoder": [enc_layer(ks()) for _ in range(n_enc)],
        "enc_norm": norm_init(d, cfg.norm),
        "decoder": [dec_layer(ks()) for _ in range(cfg.n_layers)],
        "final_norm": norm_init(d, cfg.norm),
        "head": embedding_init(ks(), cfg.vocab_size, cfg.d_model),
    }


def encode(params, frames: Array, cfg: ModelConfig, *, dtype=jnp.bfloat16):
    """frames: (B, N_enc, d_model) stub embeddings -> (B, N_enc, d_model)."""
    b, n, _ = frames.shape
    pos_emb = params["enc_pos"]["table"][:n].astype(dtype)
    x = frames.astype(dtype) + pos_emb[None]
    for bp in params["encoder"]:
        h = apply_norm(bp["norm1"], x, cfg.norm)
        x = x + attention(bp["attn"], h, cfg, causal=cfg.encoder_causal)
        x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
    return apply_norm(params["enc_norm"], x, cfg.norm)


def decode_train(params, tokens: Array, memory: Array, cfg: ModelConfig,
                 *, dtype=jnp.bfloat16):
    """Teacher-forced decoder pass.  tokens: (B, N_dec) -> logits."""
    b, n = tokens.shape
    x = embed(params["embed"], tokens, dtype)
    positions = default_positions(b, n)
    for bp in params["decoder"]:
        h = apply_norm(bp["norm1"], x, cfg.norm)
        x = x + attention(bp["self_attn"], h, cfg, causal=True,
                          positions=positions)
        h = apply_norm(bp["norm_x"], x, cfg.norm)
        x = x + attention(bp["cross_attn"], h, cfg, causal=False,
                          kv_input=memory)
        x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return unembed(params["head"], x, softcap=cfg.logit_softcap)


def forward(params, batch_inputs, cfg: ModelConfig, *, dtype=jnp.bfloat16):
    """batch_inputs: (frames, dec_tokens) -> (logits, aux=0)."""
    frames, dec_tokens = batch_inputs
    memory = encode(params, frames, cfg, dtype=dtype)
    logits = decode_train(params, dec_tokens, memory, cfg, dtype=dtype)
    return logits, jnp.zeros((), jnp.float32)


def loss_fn(params, batch: dict, cfg: ModelConfig, *, dtype=jnp.bfloat16):
    logits, aux = forward(params, (batch["frames"], batch["inputs"]), cfg,
                          dtype=dtype)
    targets = batch["targets"]
    mask = batch.get("mask", jnp.ones_like(targets, jnp.float32))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    metrics = {"loss": ce, "ce": ce, "aux": aux,
               "ppl": jnp.exp(jnp.minimum(ce, 20.0)), "tokens": mask.sum()}
    return ce, metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def init_dec_caches(cfg: ModelConfig, batch: int, max_len: int):
    mx = resolve_mixer("attn", cfg)  # decoder self-attention lifecycle
    return [
        {"self": mx.state_init(batch, max_len)} for _ in range(cfg.n_layers)
    ]


def decode_step(params, token: Array, memory: Array, caches, cfg: ModelConfig,
                pos: Array, *, dtype=jnp.bfloat16):
    """One autoregressive decoder step.  token: (B, 1) int."""
    b = token.shape[0]
    x = embed(params["embed"], token, dtype)
    positions = default_positions(b, 1, pos)
    mx = resolve_mixer("attn", cfg)
    new_caches = []
    for i, bp in enumerate(params["decoder"]):
        h = apply_norm(bp["norm1"], x, cfg.norm)
        y, self_cache = mx.decode_step(bp["self_attn"], h, caches[i]["self"],
                                       positions=positions)
        x = x + y
        h = apply_norm(bp["norm_x"], x, cfg.norm)
        # cross-attention: this token is the single sink (n=1 flow attention)
        x = x + attention(bp["cross_attn"], h, cfg, causal=False,
                          kv_input=memory)
        x = x + ffn(bp["ffn"], apply_norm(bp["norm2"], x, cfg.norm), cfg.act)
        new_caches.append({"self": self_cache})
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return unembed(params["head"], x, softcap=cfg.logit_softcap), new_caches
