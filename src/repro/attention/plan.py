"""ExecutionPlan: the execution context bound once, not threaded per call.

Before this existed, every call site threaded execution context as ad-hoc
kwargs per call — backend pins in ``FlowConfig.backend``, ``lengths=`` for
packed admission, ``paged=``/``page_table=`` for the softmax baseline
caches, mesh axis names for sequence parallelism — through layers → models
→ launch → serving.  An ``ExecutionPlan`` folds the *static* decisions
together:

* ``flow``   — the Flow-Attention math + strategy selector (``FlowConfig``)
* ``shapes`` — optional static call shapes (filled from q/k/v when absent)
* ``shard``  — optional ``ShardSpec``: mesh + sequence axis for
  context-parallel execution; makes resolution mesh-aware
* ``packed`` — the plan intends right-padded multi-prompt prefill
  (``prefill_packed``); the per-call ``lengths`` array stays a runtime arg
* ``paged``  — serving option (a ``serving.paged.PagedSpec``) carried for
  the softmax-baseline cache layers; ignored by flow execution
* ``needs_grad`` / ``platform`` — resolution filters

``resolve(plan)`` returns a ``BoundExecutor`` whose canonical ops
(``forward`` / ``prefill`` / ``decode_step`` / ``verify_step``) resolve
through the registry
with the plan applied — a sharded plan lands on the context-parallel
backends (``cp_nc``/``cp_causal``), an unsharded one behaves exactly like
the legacy per-call API.  ``explain(plan)`` renders the same triage as a
human-readable report including each backend's ``shard_support`` verdict.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.flow_attention import FlowConfig
from repro.attention import registry
from repro.attention.registry import Backend, ShapeInfo, ShardSpec
from repro.distribution.sharding import dp_axes

Array = jax.Array


def _run(be: Backend, platform: str, op, *args, cfg, **arrays):
    """Call ``op(*args, cfg, **arrays)``, one of ``be``'s ops, on ``platform``
    and on the traced mesh.

    The compiler cannot partition a Pallas (Mosaic) kernel.  Under a mesh
    context with more than one device on its ``Auto`` axes (the step
    builders in ``launch/steps.py`` trace under
    ``jax.sharding.use_abstract_mesh``) a Pallas op therefore runs in
    ``jax.shard_map``: every array operand and result splits its leading
    (batch) dim over the data-parallel axes, and its head dim over
    ``model`` when every head count divides.  Flow ops never mix batch
    rows or heads, so each device computes its own rows exactly.  XLA ops,
    and every op with no such mesh, run as they are.
    """
    def fn(args, arrays):
        return op(*args, cfg, **arrays, **registry.run_kwargs(be, platform))

    mesh = jax.sharding.get_abstract_mesh()
    auto = {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Auto and mesh.shape[a] > 1}
    if not (be.pallas and auto):
        return fn(args, arrays)
    batch = tuple(a for a in dp_axes(mesh) if a in auto) or None
    out = jax.eval_shape(fn, args, arrays)
    leaves = jax.tree.leaves((args, arrays, out))
    heads = ("model" if "model" in auto and all(
        len(x.shape) < 2 or x.shape[1] % mesh.shape["model"] == 0
        for x in leaves) else None)

    def specs(tree):
        return jax.tree.map(lambda x: P(*(batch, heads)[:len(x.shape)]),
                            tree)

    # the kernels' outputs carry no varying-axes type, so shard_map's vma
    # check cannot type them
    return jax.shard_map(fn, in_specs=specs((args, arrays)),
                         out_specs=specs(out), check_vma=False)(args, arrays)


_QUANT_DTYPES = ("int8", "fp8")


def _quant_of(plan, op: str) -> str | None:
    """The quantized state dtype ``op`` must serve, or None.

    Only the state-consuming ops (decode/verify) see the pool dtype —
    forward/prefill run on activations and produce full-precision
    boundary states that are quantized at install.  bf16/fp32 state
    dtypes are storage overrides, not quantization, and never reach the
    registry.
    """
    sd = plan.state_dtype
    return sd if (sd in _QUANT_DTYPES and op in ("decode", "verify")) else None


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Static execution context for Flow-Attention, hashable (jit-static).

    ``flow`` may be ``None`` in model-level plans (layers fill it from
    ``ModelConfig.attention`` per block); attention-level users set it.
    """

    flow: FlowConfig | None = None
    shapes: ShapeInfo | None = None
    shard: ShardSpec | None = None
    packed: bool = False
    paged: Any = None  # serving.paged.PagedSpec for softmax baseline caches
    needs_grad: bool = False
    platform: str | None = None
    #: speculative decoding: number of drafted tokens scored per verify
    #: window (0 = plain decode).  Carried on the plan so layer resolution
    #: can demand the ``verify_capable`` mixer capability and the registry
    #: can triage the ``verify`` op at build time.
    speculate_k: int = 0
    #: serving state-pool dtype, distinct from the activation dtype:
    #: ``None``/"bf16"/"fp32" keep full-precision states (bf16/fp32
    #: override the positional-cache storage dtype); "int8"/"fp8" wrap
    #: every pool in a ``serving.quant.QuantizedPool`` and make decode/
    #: verify resolution demand ``quant_capable`` from backends and
    #: mixers (named rejections instead of silent dequantization).
    state_dtype: str | None = None

    def with_shapes(self, shapes: ShapeInfo) -> "ExecutionPlan":
        """Copy of this plan with static call shapes attached."""
        return dataclasses.replace(self, shapes=shapes)

    def with_flow(self, flow: FlowConfig) -> "ExecutionPlan":
        """Copy of this plan with ``flow`` (the ``FlowConfig``) replaced."""
        return dataclasses.replace(self, flow=flow)

    def describe(self) -> str:
        """One-line summary of the plan's non-default fields."""
        bits = [f"backend={self.flow.backend!r}" if self.flow else "flow=?"]
        if self.shard is not None:
            bits.append(f"shard[{self.shard.describe()}]")
        if self.packed:
            bits.append("packed")
        if self.paged is not None:
            bits.append(f"paged[{getattr(self.paged, 'page_size', '?')}]")
        if self.needs_grad:
            bits.append("needs_grad")
        if self.speculate_k:
            bits.append(f"speculate_k={self.speculate_k}")
        if self.state_dtype:
            bits.append(f"state_dtype={self.state_dtype}")
        return "ExecutionPlan(" + ", ".join(bits) + ")"


class BoundExecutor:
    """The canonical ops bound to one ``ExecutionPlan``.

    Resolution happens per op at trace time (pure python, deterministic);
    the plan's shard/grad/platform context is applied uniformly so call
    sites never re-thread it.  ``decode_step`` and ``verify_step`` drop the
    shard: they consume one position / a drafted handful — there is no
    sequence axis left to shard, and the O(d^2) state is batch-led.
    """

    def __init__(self, plan: ExecutionPlan):
        """Bind ``plan`` (its ``flow`` must be set) for per-op resolution."""
        if plan.flow is None:
            raise ValueError(
                "ExecutionPlan.flow is unset — attention-level execution "
                "needs the FlowConfig (model layers fill it from "
                "ModelConfig.attention)"
            )
        self.plan = plan

    @property
    def flow(self) -> FlowConfig:
        """The plan's ``FlowConfig`` (set by construction)."""
        return self.plan.flow

    @property
    def platform(self) -> str:
        """The platform ops resolve and run for: the plan's pin, else JAX's
        default backend, read when an op is traced (never at import)."""
        return self.plan.platform or jax.default_backend()

    def _shapes(self, q, k, v) -> ShapeInfo:
        return ShapeInfo.from_qkv(q, k, v)

    def backend(self, op: str = "forward",
                shapes: ShapeInfo | None = None) -> Backend:
        """Resolve and return the backend the plan binds for ``op``."""
        p = self.plan
        shapes = shapes or p.shapes
        if shapes is None:
            raise ValueError(
                f"cannot resolve op={op!r} without shapes: give the plan "
                "ShapeInfo (plan.with_shapes) or call the op with arrays"
            )
        cfg = p.flow
        if op in ("prefill", "prefill_packed", "decode", "verify"):
            cfg = dataclasses.replace(cfg, causal=True, strict_causal=True)
        # decode consumes one position and verify a drafted handful: there
        # is no sequence axis left to shard, and the O(d^2) state is
        # batch-led — both ops drop the plan's ShardSpec
        shard = None if op in ("decode", "verify") else p.shard
        return registry.resolve(cfg, shapes, self.platform, op=op,
                                needs_grad=p.needs_grad, shard=shard,
                                quant=_quant_of(p, op))

    # canonical ops ---------------------------------------------------------
    def forward(self, q: Array, k: Array, v: Array) -> Array:
        """Full-sequence Flow-Attention (``plan.flow.causal`` picks the variant).

        q: (B,Hq,N,D); k: (B,Hkv,M,D); v: (B,Hkv,M,Dv) -> (B,Hq,N,Dv).
        """
        be = self.backend("forward", self._shapes(q, k, v))
        if self.plan.shard is not None:
            return be.forward(q, k, v, self.plan.flow, shard=self.plan.shard)
        return _run(be, self.platform, be.forward, q, k, v, cfg=self.plan.flow)

    def prefill(self, q: Array, k: Array, v: Array,
                *, lengths: Array | None = None):
        """Consume a prompt; return (per-position outputs, decode FlowState).

        ``lengths`` (B,) serves a right-padded batch of prompts in one call
        (the ``prefill_packed`` op); the plan's ``packed`` flag documents
        the intent but the array itself is a runtime argument.
        """
        cfg = dataclasses.replace(self.plan.flow, causal=True,
                                  strict_causal=True)
        op = "prefill" if lengths is None else "prefill_packed"
        be = self.backend(op, self._shapes(q, k, v))
        if self.plan.shard is not None:
            return be.prefill(q, k, v, cfg, lengths=lengths,
                              shard=self.plan.shard)
        return _run(be, self.platform, be.prefill, q, k, v, cfg=cfg,
                    lengths=lengths)

    def decode_step(self, state, q: Array, k: Array, v: Array):
        """Advance one token on the O(d^2) recurrent state."""
        cfg = dataclasses.replace(self.plan.flow, causal=True,
                                  strict_causal=True)
        be = self.backend("decode", self._shapes(q, k, v))
        return _run(be, self.platform, be.decode_step, state, q, k, v,
                    cfg=cfg)

    def verify_step(self, state, q: Array, k: Array, v: Array):
        """Score a drafted window of n tokens from ``state`` in one pass.

        The speculative-decoding verifier: q/k/v carry ``n = k_draft + 1``
        positions continuing each row's context at ``state.t``.  Returns
        ``(out, traj)`` where ``out`` (B,Hq,n,Dv) matches what n sequential
        ``decode_step`` calls would emit and ``traj`` is a trajectory
        ``FlowState`` (position axis at index 1) — gather the accepted
        boundary with ``attention.select_state(traj, accepted)``.
        """
        cfg = dataclasses.replace(self.plan.flow, causal=True,
                                  strict_causal=True)
        be = self.backend("verify", self._shapes(q, k, v))
        # every verify_step is the XLA carry-in pass: no kernel to interpret
        return be.verify_step(state, q, k, v, cfg)


def resolve_plan(plan: ExecutionPlan) -> BoundExecutor:
    """Bind an ``ExecutionPlan`` to an executor (the plan-first ``resolve``).

    Resolution itself is lazy-per-op (ops may bind different backends —
    e.g. a pinned forward strategy never blocks decode); when the plan
    carries shapes, the forward binding is validated eagerly so a plan
    that can never execute fails here, with every backend's rejection
    reason, instead of at first call.
    """
    ex = BoundExecutor(plan)
    if plan.shapes is not None:
        ex.backend("prefill_packed" if plan.packed else "forward")
    return ex


@dataclasses.dataclass(frozen=True)
class PlanExplanation:
    """Human-readable resolution triage for one plan, per op.

    ``sections`` is ``((op, rows), ...)`` with one entry per explained op
    (a single entry when a specific op was requested); each ``rows`` is
    ``((name, applicable, reason), ...)`` for every registered backend.
    ``op`` / ``rows`` expose the first section for single-op callers.
    """

    plan: ExecutionPlan
    platform: str
    sections: tuple  # ((op, ((name, applicable, reason), ...)), ...)

    @property
    def op(self) -> str:
        """The first explained op (the requested one for single-op calls)."""
        return self.sections[0][0]

    @property
    def rows(self) -> tuple:
        """The first section's ``(name, applicable, reason)`` rows."""
        return self.sections[0][1]

    def __str__(self) -> str:
        """Render the triage: plan header, then per-op OK/no rows."""
        p = self.plan
        head = [f"{p.describe()} platform={self.platform!r}"]
        if p.shard is not None:
            head.append(f"  sharded over {p.shard.describe()}")
        elif p.flow is not None:
            head.append("  unsharded (no ShardSpec)")
        body = []
        for op, rows in self.sections:
            body.append(f" op={op!r}:")
            body.extend(
                f"  {'OK ' if ok else 'no '} {name}: {reason}"
                for name, ok, reason in rows
            )
        return "\n".join(head + body)


def explain_plan(plan: ExecutionPlan, *,
                 op: str | None = None) -> PlanExplanation:
    """Per-backend, per-op verdicts for a plan.

    With ``op=None`` (the default) every op the plan implies is triaged —
    ``forward`` / ``prefill`` / ``decode``, plus ``prefill_packed`` for
    packed plans and ``verify`` for speculative ones — so a backend that
    provides forward but not ``decode_step`` (or ``verify_step``) shows its
    per-op rejection instead of silently vanishing from the report.  Pass a
    specific ``op`` to restrict the report.  ``str()`` the result to print
    it; sharded plans include each backend's ``shard_support`` reason.
    """
    if plan.flow is None:
        raise ValueError("ExecutionPlan.flow is unset — nothing to explain")
    platform = plan.platform or jax.default_backend()
    shapes = plan.shapes
    if shapes is None:
        raise ValueError(
            "explain(plan) needs static shapes: plan.with_shapes(ShapeInfo(...))"
        )
    if op is None:
        ops = ["forward", "prefill"]
        if plan.packed:
            ops.append("prefill_packed")
        ops.append("decode")
        if plan.speculate_k:
            ops.append("verify")
    else:
        ops = [op]
    sections = []
    for one in ops:
        cfg = plan.flow
        if one in ("prefill", "prefill_packed", "decode", "verify"):
            cfg = dataclasses.replace(cfg, causal=True, strict_causal=True)
        shard = None if one in ("decode", "verify") else plan.shard
        rows = registry.explain(cfg, shapes, platform, op=one,
                                needs_grad=plan.needs_grad, shard=shard,
                                quant=_quant_of(plan, one))
        sections.append((one, tuple(rows)))
    return PlanExplanation(plan=plan, platform=platform,
                           sections=tuple(sections))
