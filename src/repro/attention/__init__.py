"""Pluggable Flow-Attention execution subsystem.

This package is the ONLY place in the repo that selects how Flow-Attention
(paper Eq. 4/7/8, Alg. 2) actually executes.  Call sites build ONE
``ExecutionPlan`` (FlowConfig + static shapes + mesh/axis ``ShardSpec`` +
serving options) at module-construction time and use the canonical
op API through the bound executor — never naming an execution path:

    from repro import attention

    plan = attention.ExecutionPlan(flow=cfg)       # + shard=, packed=, ...
    ex = attention.resolve(plan)                   # -> BoundExecutor
    out = ex.forward(q, k, v)                      # cfg.causal picks variant
    out, state = ex.prefill(q, k, v)               # strict-causal + FlowState
    state, out = ex.decode_step(state, q, k, v)
    out, traj = ex.verify_step(state, q, k, v)     # speculative verifier

The per-call module functions ``attention.forward/prefill/decode_step(...,
FlowConfig)`` remain as deprecation shims (warn once, behave identically);
passing the ``ExecutionPlan`` in the config position is the supported
spelling.

Mesh-aware resolution
=====================
``ExecutionPlan.shard`` (a ``ShardSpec``: mesh + sequence axis name, and
optionally a batch axis and a pinned shard-local ``inner`` strategy) makes
``resolve`` mesh-aware: backends self-report shard capability in
``Backend.shardable`` / ``shard_support`` exactly as they report gradient
capability, and a sharded plan binds the context-parallel collective-glue
backends:

* ``cp_nc``     — non-causal: the six global flow sums become ``psum``s of
  O(d^2) bytes (sequence-length-independent collectives).
* ``cp_causal`` — strict-causal: local cumsums + an ``all_gather`` of
  per-device partials and a local exclusive prefix; wraps a shard-local
  inner aggregation strategy resolved over the registry (``pallas_chunk``
  on TPU, ``xla_chunked``/``xla_cumsum`` elsewhere), and provides
  ``prefill``/``prefill_packed`` so seq-parallel serving admission
  resolves through the same door.

Single-device backends reject sharded plans with "no collective glue"
reasons (visible in ``ResolutionError.rejections`` and ``explain(plan)``);
the ``cp_*`` backends reject *unsharded* plans symmetrically.

Strategy selection
==================
``FlowConfig.backend`` controls resolution:

* ``"auto"`` (default) — first applicable backend in preference order::

      pallas_nc > pallas_fused > pallas_chunk > fused_causal > xla_chunked
      > xla_cumsum > pallas_decode > recurrent

  Each backend *self-reports* applicability from (config, static shapes,
  platform): Pallas kernels only volunteer on TPU; ``pallas_fused`` and
  ``fused_causal`` need strict-causal competition (any length — awkward N
  is padded to a chunk multiple and masked, never shrunk to tiny chunks);
  ``xla_chunked`` needs ``N % chunk_size == 0``; ``xla_cumsum`` always
  applies.  Resolution is a pure function — same inputs, same backend.
* ``"xla"`` / ``"pallas"`` — legacy families: auto restricted to non-Pallas /
  Pallas backends (the latter allowed to interpret off-TPU).
* any registered name (e.g. ``"fused_causal"``) — exactly that backend;
  resolution raises with the backend's own reason string if it does not
  apply.  Ops the named backend does not provide at all (``decode`` for the
  forward-only strategies) fall back to auto order so pinning a forward
  path never breaks serving.

Gradients: every built-in backend is differentiable end-to-end — the XLA
strategies natively, the Pallas kernels through the ``jax.custom_vjp``
rules in ``attention/vjp.py`` (backward passes are Pallas kernels with the
same chunked-scan structure).  Backends declare the ops ``jax.grad`` flows
through in ``Backend.differentiable``; ``resolve(..., needs_grad=True)``
(or ``resolve_for_training``) filters on that declaration and, like all
resolution failures, raises ``ResolutionError`` whose ``.rejections``
carries every candidate's own reason.

Registered strategies
=====================
* ``pallas_nc``     — non-causal sink side fused in a Pallas TPU kernel
  (``kernels/flow_nc``); sigmoid phi + allocation, shared-GQA.
* ``pallas_chunk``  — causal aggregation in a Pallas TPU kernel with the
  (D, Dv) carry in VMEM scratch (``kernels/flow_chunk``).
* ``fused_causal``  — strict-causal flows + cumulative softmax +
  aggregation, chunk-parallel: chunk-local prefixes, an exclusive prefix
  over the chunk totals, batched contractions, no loop; the totals are the
  decode ``FlowState``, so prefill returns the serving hand-off for free
  (see ``attention/fused.py``).
* ``xla_chunked``   — unfused normalizers + chunked-scan aggregation
  (absorbed from the former ``core/chunked.py``).
* ``xla_cumsum``    — unfused normalizers + full-length cumsum aggregation;
  the always-applicable correctness anchor.
* ``recurrent``     — token-by-token O(d^2) recurrence (absorbed from
  ``core/decode.py``); decode fallback and an independent parity oracle
  for the others.
* ``pallas_decode`` — batched serving decode step (``kernels/flow_decode``):
  one Pallas grid launch advances the whole (slots, Hkv, D, Dv) state pool
  in place; resolves ahead of ``recurrent`` for ``decode`` on TPU.
* ``cp_nc`` / ``cp_causal`` — context-parallel collective glue
  (``attention/cp.py``); candidates only for sharded ExecutionPlans (see
  "Mesh-aware resolution" above).

Serving admission additionally uses the ``prefill_packed`` op (provided by
the cumulative-sum strategies): ``prefill(q, k, v, cfg, lengths=...)``
consumes a right-padded batch of prompts in one call and gathers each
row's FlowState at its own boundary — exact because causality keeps
padding out of every prefix.  Speculative decoding uses the ``verify`` op
(``ex.verify_step``): one carry-in pass scores a drafted window and
returns every position's boundary state, so accept-prefix rollback is a
``select_state`` gather; backends self-report the capability in
``Backend.verify_support``.

Registering a new backend
=========================
Subclass ``Backend``, implement ``supports`` plus the ops you provide, and
register it — no call site changes anywhere::

    from repro.attention import Backend, register_backend

    class MyKernel(Backend):
        provides = frozenset({"forward"})
        # declare {"forward"} once the kernel has a custom VJP; an empty
        # set (the default) makes resolve(needs_grad=True) skip it with a
        # "no VJP rule" reason
        differentiable = frozenset()

        def supports(self, cfg, shapes, platform, *, op="forward",
                     explicit=False):
            if platform != "tpu":
                return False, "my kernel is TPU-only"
            return True, "ok"

        def forward(self, q, k, v, cfg):
            ...

    register_backend("my_kernel", MyKernel(), before="fused_causal")

``before=`` positions the backend in the auto order; it can be pinned by
name (``FlowConfig(backend="my_kernel")``) immediately.
"""
from repro.core.flow_attention import FlowConfig

from repro.attention.registry import (
    Backend,
    ResolutionError,
    ShapeInfo,
    ShardSpec,
    get_backend,
    list_backends,
    register_backend,
)
from repro.attention.plan import (
    BoundExecutor,
    ExecutionPlan,
    PlanExplanation,
    explain_plan,
    resolve_plan,
)
from repro.attention.api import (
    decode_step,
    explain,
    forward,
    prefill,
    resolve,
    resolve_for_training,
    verify_step,
)
from repro.attention.dots import causal_dot, causal_dot_grouped
from repro.attention.recurrent import FlowState, init_state, select_state
from repro.attention._pallas import chunked_causal_dot_pallas
from repro.attention import backends as _backends  # registers the builtins

__all__ = [
    "FlowConfig",
    "FlowState",
    "Backend",
    "BoundExecutor",
    "ExecutionPlan",
    "PlanExplanation",
    "ResolutionError",
    "ShapeInfo",
    "ShardSpec",
    "register_backend",
    "get_backend",
    "list_backends",
    "resolve",
    "resolve_plan",
    "resolve_for_training",
    "explain",
    "explain_plan",
    "forward",
    "prefill",
    "decode_step",
    "verify_step",
    "init_state",
    "select_state",
    "causal_dot",
    "causal_dot_grouped",
    "chunked_causal_dot_pallas",
]
