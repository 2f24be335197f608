"""The registered Flow-Attention backends.

Registration order IS the ``backend="auto"`` preference order:

    pallas_nc > pallas_fused > pallas_chunk > fused_causal > xla_chunked
    > xla_cumsum > pallas_decode > recurrent > cp_nc > cp_causal

(the ``cp_*`` context-parallel glue backends are ``shard_only``: they are
candidates only when resolution carries a ``ShardSpec`` — where every
single-device backend is rejected with a "no collective glue" reason — so
their position in the order never affects unsharded plans).

Pallas backends only self-report applicable on TPU (interpret mode must be
asked for explicitly, and their ops then take ``interpret=True`` from
``registry.run_kwargs``); ``fused_causal`` computes the flow normalizers, the
competition normalizer and the (D, Dv) aggregation state for all chunks at
once (a chunk-local prefix, then an exclusive prefix over the chunk totals,
then batched contractions) and is preferred over the multi-pass XLA paths
wherever its contract (strict causal competition, chunkable length) holds; ``xla_cumsum`` accepts everything and is the
correctness anchor; ``pallas_decode`` runs the serving hot loop (one grid
launch over the whole slot pool) ahead of ``recurrent``, which stays the
decode fallback and a token-by-token oracle.  The pipeline-based causal
strategies additionally provide ``prefill_packed`` — prefill over a
right-padded batch of prompts with the ``FlowState`` gathered at each row's
own boundary (the serving Worker's batched admission path) — and ``verify``,
the speculative-decoding op: continue a ``FlowState`` over a drafted window
in one carry-in pass, returning every position's boundary state so
accept-prefix rollback is a gather (``pipeline.causal_verify``).

Every built-in backend declares gradient capability (``differentiable``):
the XLA/scan strategies are natively differentiable, and the Pallas kernels
carry ``jax.custom_vjp`` rules (``attention/vjp.py``) whose backward passes
are Pallas kernels themselves — so ``resolve(..., needs_grad=True)`` can
pick any of them and training never needs a registry-side special case.
"""
from __future__ import annotations

import functools

import jax

from repro.core.flow_attention import FlowConfig
from repro.attention import fused, pipeline, recurrent
from repro.attention.chunked import chunked_causal_dot_grouped
from repro.attention.dots import causal_dot_grouped
from repro.attention.registry import Backend, ShapeInfo, register_backend

Array = jax.Array


def _cumsum_dot(qg, k, v):
    return causal_dot_grouped(qg, k, v, chunk_size=0)


def _check_causal_self(cfg: FlowConfig, shapes: ShapeInfo):
    if not cfg.causal:
        return "causal-only backend"
    if shapes.n != shapes.m:
        return f"causal requires N == M, got N={shapes.n} M={shapes.m}"
    return None


def _check_state_ops(cfg: FlowConfig, op: str):
    if op in ("prefill", "prefill_packed", "decode", "verify") and not (
        cfg.strict_causal and cfg.use_competition
    ):
        return "recurrent state requires strict_causal competition"
    return None


def _verify_quant(platform: str, dtype: str):
    """Shared ``quant_capable(op="verify")`` verdict for the chunked-verify
    strategies: ``pipeline.causal_verify`` dequantizes the pooled carry-in
    once at entry and the whole drafted window runs fp32, so any platform
    that can store the pool can verify from it."""
    from repro.serving.quant import platform_support

    ok, why = platform_support(dtype, platform)
    if not ok:
        return False, why
    return True, f"boundary dequantize into the fp32 carry-in verify ({why})"


class _ChunkedVerifyQuant:
    """Mixin: the chunked-verify backends serve quantized pools for the
    ``verify`` op (dequantize-at-entry, see ``_verify_quant``)."""

    def quant_capable(self, platform, dtype, op="decode"):
        if op == "verify":
            return _verify_quant(platform, dtype)
        return super().quant_capable(platform, dtype, op)


class XlaCumsum(_ChunkedVerifyQuant, Backend):
    """Pure-XLA reference strategy: plain sums (non-causal) or full-length
    cumsums (causal).  Always applicable — the resolution floor."""

    provides = frozenset({"forward", "prefill", "prefill_packed", "verify"})
    differentiable = frozenset({"forward", "prefill", "prefill_packed"})

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        if cfg.causal:
            why = _check_causal_self(cfg, shapes)
            if why:
                return False, why
        why = _check_state_ops(cfg, op)
        if why:
            return False, why
        return True, "universal fallback"

    def verify_step(self, state, q, k, v, cfg):
        return pipeline.causal_verify(state, q, k, v, cfg)

    def causal_dot_fn(self, cfg):
        """Grouped causal aggregation dot — also the shard-local inner
        strategy the context-parallel glue (``attention/cp.py``) wraps."""
        return _cumsum_dot

    def forward(self, q, k, v, cfg):
        if cfg.causal:
            return pipeline.causal_forward(q, k, v, cfg, _cumsum_dot)
        return pipeline.nc_forward(q, k, v, cfg)

    def prefill(self, q, k, v, cfg, *, lengths=None):
        return pipeline.causal_forward(q, k, v, cfg, _cumsum_dot,
                                       return_state=True, lengths=lengths)


class XlaChunked(_ChunkedVerifyQuant, Backend):
    """Causal aggregation as a lax.scan over MXU-friendly chunks (absorbed
    from the former ``core/chunked.py``)."""

    provides = frozenset({"forward", "prefill", "prefill_packed", "verify"})
    differentiable = frozenset({"forward", "prefill", "prefill_packed"})

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_causal_self(cfg, shapes)
        if why:
            return False, why
        why = _check_state_ops(cfg, op)
        if why:
            return False, why
        c = cfg.chunk_size
        if not c or c <= 0:
            return False, "chunk_size <= 0"
        if op != "verify" and (shapes.n % c or shapes.n <= c):
            # a drafted verify window is a handful of tokens by design and
            # never goes through the blocked dot — exempt from chunkability
            return False, f"N={shapes.n} not chunkable by chunk_size={c}"
        return True, "chunked scan"

    def verify_step(self, state, q, k, v, cfg):
        return pipeline.causal_verify(state, q, k, v, cfg)

    def _dot(self, cfg):
        return functools.partial(chunked_causal_dot_grouped,
                                 chunk_size=cfg.chunk_size)

    # chunked scan doubles as the cp shard-local inner strategy
    causal_dot_fn = _dot

    def forward(self, q, k, v, cfg):
        return pipeline.causal_forward(q, k, v, cfg, self._dot(cfg))

    def prefill(self, q, k, v, cfg, *, lengths=None):
        return pipeline.causal_forward(q, k, v, cfg, self._dot(cfg),
                                       return_state=True, lengths=lengths)


class PallasChunk(_ChunkedVerifyQuant, Backend):
    """Causal aggregation via the ``kernels/flow_chunk`` Pallas TPU kernel
    (carried (D,Dv) state in VMEM scratch).  Differentiable through the
    ``attention/vjp.py`` custom VJP (Pallas backward kernels)."""

    provides = frozenset({"forward", "prefill", "prefill_packed", "verify"})
    differentiable = frozenset({"forward", "prefill", "prefill_packed"})
    pallas = True

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_causal_self(cfg, shapes)
        if why:
            return False, why
        why = _check_state_ops(cfg, op)
        if why:
            return False, why
        if not cfg.chunk_size or cfg.chunk_size <= 0:
            return False, "chunk_size <= 0"
        if platform != "tpu" and not explicit:
            return False, "Pallas compiles on TPU only (interpret mode must be selected explicitly)"
        return True, "pallas kernel"

    def verify_step(self, state, q, k, v, cfg):
        # the drafted window is a handful of tokens: the carry-in cumsum
        # pass is the right realization at any scale a draft produces, so
        # no grid launch is spent on it
        return pipeline.causal_verify(state, q, k, v, cfg)

    def _dot(self, cfg, *, interpret=False):
        # the jit'd wrapper shrinks the chunk to divide N, so any shape that
        # passes supports() really runs the kernel (never a cumsum fallthrough)
        from repro.attention._pallas import chunked_causal_dot_pallas

        return functools.partial(chunked_causal_dot_pallas,
                                 chunk=cfg.chunk_size, interpret=interpret)

    # the Pallas kernel doubles as the cp shard-local inner strategy
    causal_dot_fn = _dot

    def forward(self, q, k, v, cfg, *, interpret=False):
        return pipeline.causal_forward(q, k, v, cfg,
                                       self._dot(cfg, interpret=interpret))

    def prefill(self, q, k, v, cfg, *, lengths=None, interpret=False):
        return pipeline.causal_forward(q, k, v, cfg,
                                       self._dot(cfg, interpret=interpret),
                                       return_state=True, lengths=lengths)


class PallasNC(Backend):
    """Fused non-causal sink side via the ``kernels/flow_nc`` Pallas kernel.
    The kernel hard-codes sigmoid phi and sigmoid allocation — applicability
    reflects that."""

    provides = frozenset({"forward"})
    differentiable = frozenset({"forward"})
    pallas = True

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        if cfg.causal:
            return False, "non-causal-only backend"
        if cfg.phi != "sigmoid":
            return False, f"kernel hard-codes sigmoid phi, cfg has {cfg.phi!r}"
        if not cfg.use_allocation:
            return False, "kernel hard-codes the allocation sigmoid"
        if cfg.gqa_mode != "shared" and shapes.hq != shapes.hkv:
            return False, "kernel implements shared-GQA semantics only"
        if platform != "tpu" and not explicit:
            return False, "Pallas compiles on TPU only (interpret mode must be selected explicitly)"
        return True, "fused nc kernel"

    def forward(self, q, k, v, cfg, *, interpret=False):
        from repro.kernels.flow_nc import flow_attention_nc_pallas

        return flow_attention_nc_pallas(q, k, v, cfg, interpret=interpret)


class PallasFused(_ChunkedVerifyQuant, Backend):
    """The whole strict-causal pipeline in one Pallas kernel
    (``kernels/flow_fused``): flows, conservation, cumulative competition
    and aggregation per grid step, FlowState carried in VMEM scratch.  One
    read of q/k/v, one write of out — and the reverse-scan backward kernel
    saves no (B,H,N)-sized residuals.  Packed prefill masks each row past
    its length so the final carry IS the boundary FlowState (no gathers)."""

    provides = frozenset({"forward", "prefill", "prefill_packed", "verify"})
    differentiable = frozenset({"forward", "prefill"})
    pallas = True

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_causal_self(cfg, shapes)
        if why:
            return False, why
        if not cfg.strict_causal:
            return False, "implements the strict-causal cumulative competition only"
        if not cfg.use_competition:
            return False, "fused carry includes the competition normalizer"
        if not cfg.chunk_size or cfg.chunk_size <= 0:
            return False, "chunk_size <= 0"
        if platform != "tpu" and not explicit:
            return False, "Pallas compiles on TPU only (interpret mode must be selected explicitly)"
        return True, "fused strict-causal pallas kernel"

    def forward(self, q, k, v, cfg, *, interpret=False):
        from repro.kernels.flow_fused import flow_fused_forward

        k, v = pipeline.expand_kv(q, k, v, cfg)
        out, _ = flow_fused_forward(q, k, v, cfg, interpret=interpret)
        return out

    def prefill(self, q, k, v, cfg, *, lengths=None, interpret=False):
        from repro.kernels.flow_fused import flow_fused_forward

        k, v = pipeline.expand_kv(q, k, v, cfg)
        return flow_fused_forward(q, k, v, cfg, return_state=True,
                                  lengths=lengths, interpret=interpret)

    def verify_step(self, state, q, k, v, cfg):
        # verify windows are tiny; the carry-in cumsum pass beats a kernel
        # launch, and the trajectory it returns is what rollback gathers
        return pipeline.causal_verify(state, q, k, v, cfg)


class FusedCausal(Backend):
    """Strict-causal flows + cumulative softmax + aggregation, chunk-parallel:
    every running sum is a chunk-local prefix plus an exclusive prefix over
    the chunk totals, and the aggregation is batched over all chunks, so no
    loop runs chunk after chunk.  The totals are the O(d^2) FlowState, so
    prefill hands decode its state for free."""

    provides = frozenset({"forward", "prefill", "prefill_packed"})
    differentiable = frozenset({"forward", "prefill", "prefill_packed"})

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_causal_self(cfg, shapes)
        if why:
            return False, why
        if not cfg.strict_causal:
            return False, "implements the strict-causal cumulative competition only"
        if not cfg.use_competition:
            return False, "fused carry includes the competition normalizer"
        if not cfg.chunk_size or cfg.chunk_size <= 0:
            return False, "chunk_size <= 0"
        return True, "chunk-parallel strict-causal flow"

    def forward(self, q, k, v, cfg):
        k, v = pipeline.expand_kv(q, k, v, cfg)
        return fused.fused_causal_forward(q, k, v, cfg)

    def prefill(self, q, k, v, cfg, *, lengths=None):
        k, v = pipeline.expand_kv(q, k, v, cfg)
        return fused.fused_causal_forward(q, k, v, cfg, return_state=True,
                                          lengths=lengths)


class Recurrent(Backend):
    """Token-by-token O(d^2) recurrence (absorbed from ``core/decode.py``).
    The canonical ``decode_step`` provider; forward/prefill run the same
    update under lax.scan as an independent oracle."""

    provides = frozenset({"forward", "prefill", "decode"})
    differentiable = frozenset({"forward", "prefill", "decode"})

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_causal_self(cfg, shapes)
        if why:
            return False, why
        if not (cfg.strict_causal and cfg.use_competition):
            return False, "recurrence exists only for strict_causal competition"
        return True, "O(d^2) recurrence"

    def forward(self, q, k, v, cfg):
        k, v = pipeline.expand_kv(q, k, v, cfg)
        return recurrent.forward_by_scan(q, k, v, cfg)

    def prefill(self, q, k, v, cfg, *, lengths=None):
        assert lengths is None, "token scan returns the final state only"
        k, v = pipeline.expand_kv(q, k, v, cfg)
        return recurrent.forward_by_scan(q, k, v, cfg, return_state=True)

    def quant_capable(self, platform, dtype, op="decode"):
        if op != "decode":
            return super().quant_capable(platform, dtype, op)
        from repro.serving.quant import platform_support

        ok, why = platform_support(dtype, platform)
        if not ok:
            return False, why
        return True, f"dequantize -> fp32 recurrence -> requantize ({why})"

    def decode_step(self, state, q, k, v, cfg):
        from repro.serving.quant import QuantizedPool, dequantize_state, \
            quantize_like

        k, v = pipeline.expand_kv(q, k, v, cfg)
        if isinstance(state, QuantizedPool):
            # the XLA oracle for the quantized hot path: same per-(slot,
            # head) scale granularity as the fused kernel, update in fp32
            new, out = recurrent.decode_step(dequantize_state(state),
                                             q, k, v, cfg)
            return quantize_like(state, new), out
        return recurrent.decode_step(state, q, k, v, cfg)


class PallasDecode(Backend):
    """Batched decode step via the ``kernels/flow_decode`` Pallas kernel:
    one grid launch advances the whole (slots, Hkv, D, Dv) state pool —
    the serving engine's hot loop.  Inference-only by design (no VJP
    needed: decode never trains), parity-tested against ``recurrent``."""

    provides = frozenset({"decode"})
    differentiable = frozenset()
    pallas = True

    def supports(self, cfg, shapes, platform, *, op="forward", explicit=False):
        why = _check_state_ops(cfg, op)
        if why:
            return False, why
        if shapes.n != 1:
            return False, f"decode consumes exactly one position, got N={shapes.n}"
        if platform != "tpu" and not explicit:
            return False, "Pallas compiles on TPU only (interpret mode must be selected explicitly)"
        return True, "batched pallas decode kernel"

    def quant_capable(self, platform, dtype, op="decode"):
        if op != "decode":
            return super().quant_capable(platform, dtype, op)
        from repro.serving.quant import platform_support

        ok, why = platform_support(dtype, platform)
        if not ok:
            return False, why
        return True, ("in-kernel dequantize/fp32-accumulate/requantize "
                      f"({why})")

    def decode_step(self, state, q, k, v, cfg, *, interpret=False):
        from repro.serving.quant import QuantizedPool

        k, v = pipeline.expand_kv(q, k, v, cfg)
        if isinstance(state, QuantizedPool):
            from repro.kernels.flow_decode import flow_decode_q_step

            return flow_decode_q_step(state, q, k, v, cfg,
                                      interpret=interpret)
        from repro.kernels.flow_decode import flow_decode_step

        return flow_decode_step(state, q, k, v, cfg, interpret=interpret)


register_backend("pallas_nc", PallasNC())
register_backend("pallas_chunk", PallasChunk())
register_backend("pallas_fused", PallasFused(), before="pallas_chunk")
register_backend("fused_causal", FusedCausal())
register_backend("xla_chunked", XlaChunked())
register_backend("xla_cumsum", XlaCumsum())
register_backend("recurrent", Recurrent())
register_backend("pallas_decode", PallasDecode(), before="recurrent")

# context-parallel collective glue (attention/cp.py): only candidates for
# sharded ExecutionPlans, rejected everywhere else (shard_only)
from repro.attention.cp import ContextParallelCausal, ContextParallelNC  # noqa: E402

register_backend("cp_nc", ContextParallelNC())
register_backend("cp_causal", ContextParallelCausal())
