"""Execution-strategy registry for Flow-Attention.

One Flow-Attention, many ways to run it.  A ``Backend`` packages one
execution strategy behind the canonical op API (``forward`` / ``prefill`` /
``decode_step`` / ``verify_step``) and *self-reports* its applicability —
platform, causality, divisibility, GQA mode, competition flags — via
``supports()``.  ``resolve()`` turns ``FlowConfig.backend`` into a concrete
backend deterministically:

* ``backend="auto"``   — first applicable backend in registration order.
* ``backend="xla"``    — auto, restricted to non-Pallas backends (legacy).
* ``backend="pallas"`` — auto, restricted to Pallas backends, allowed to run
  in interpret mode off-TPU (legacy).
* ``backend=<name>``   — that backend exactly; raises with the backend's own
  reason string if it does not apply.

Ops are resolved independently: if an explicitly named backend does not
*provide* a requested op at all (e.g. ``xla_chunked`` never decodes), the op
falls back to full auto order so serving keeps working when a forward
strategy is pinned.  If the named backend provides the op but rejects the
shapes/config, resolution raises — pinning is a contract, not a hint.

Gradient capability is part of the same self-reporting: each backend
declares the ops ``jax.grad`` flows through in ``Backend.differentiable``
(and may refine the answer in ``grad_support``).  ``resolve(...,
needs_grad=True)`` filters on that declaration — there is no registry-side
list of "training backends"; a backend that gains a custom VJP becomes
trainable by declaring it.  Failed resolution raises ``ResolutionError``
carrying every candidate's rejection reason both in the message and as
structured ``.rejections`` — CI and benchmark sweeps report *why* each
backend was skipped instead of only the last reason.

Shard capability works the same way: resolution is mesh-aware.  A
``ShardSpec`` (mesh + sequence axis name) in the resolution request asks
for context-parallel execution — the sequence axis sharded over devices —
and backends self-report whether they carry the collective glue for it in
``Backend.shardable`` / ``shard_support``.  Single-device strategies leave
``shardable`` empty and are rejected for sharded plans with a "no
collective glue" reason; the context-parallel backends (``cp_nc``,
``cp_causal`` in ``attention/cp.py``) declare it and are in turn rejected
for *unsharded* plans (``shard_only``).  ``ExecutionPlan`` /
``resolve(plan)`` in ``attention/plan.py`` is the high-level door through
which call sites hand all of this over at once.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core.flow_attention import FlowConfig

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ShapeInfo:
    """Static call-site shapes a backend inspects in ``supports()``."""

    b: int
    hq: int
    hkv: int
    n: int  # query length
    m: int  # key/value length
    d: int
    dv: int

    @classmethod
    def from_qkv(cls, q: Array, k: Array, v: Array) -> "ShapeInfo":
        """Build the static shape record from concrete q/k/v arrays."""
        return cls(b=q.shape[0], hq=q.shape[1], n=q.shape[2], d=q.shape[3],
                   hkv=k.shape[1], m=k.shape[2], dv=v.shape[3])


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How the sequence axis is sharded over a device mesh.

    ``axis`` names the mesh axis the (B, H, N, D) sequence dimension is
    split over; ``batch_axis`` optionally names the axis (or axis tuple)
    the batch dimension is split over (replicated when ``None``).
    ``inner`` selects the *shard-local* execution strategy a
    context-parallel backend wraps in collective glue — ``"auto"`` resolves
    it over the registry exactly like an unsharded plan would, so the
    shard-local math can itself be a Pallas kernel on TPU.
    """

    axis: str = "model"
    mesh: object | None = None  # jax.sharding.Mesh (hashable; jit-static)
    batch_axis: object = None  # mesh axis name or tuple of names
    inner: str = "auto"

    @property
    def axis_size(self) -> int | None:
        """Device count along the sharded axis (None without a mesh)."""
        if self.mesh is None:
            return None
        return int(self.mesh.shape[self.axis])

    def describe(self) -> str:
        """One-line summary: axis name, way-ness, batch axis, inner pick."""
        size = self.axis_size
        return (f"axis {self.axis!r}" + (f" ({size}-way)" if size else "")
                + (f", batch over {self.batch_axis!r}" if self.batch_axis else "")
                + (f", inner={self.inner!r}" if self.inner != "auto" else ""))


class Backend:
    """One Flow-Attention execution strategy.

    Subclasses set ``name``, ``provides`` and ``differentiable`` and
    override ``supports`` plus the ops they implement.  ``supports`` must
    be a *pure* function of (cfg, shapes, platform, op, explicit) so
    resolution is deterministic.
    """

    name: str = "?"
    #: subset of {"forward", "prefill", "prefill_packed", "decode",
    #: "verify"} this backend implements (``prefill_packed``: right-padded
    #: prompt batch with the FlowState gathered at per-row boundaries;
    #: ``verify``: speculative-decoding verifier — score a drafted window
    #: in one chunked pass continuing from a FlowState)
    provides: frozenset = frozenset({"forward"})
    #: subset of ``provides`` that ``jax.grad`` flows through — natively
    #: differentiable XLA/scan code or a registered ``jax.custom_vjp``.
    #: Forward-only kernels leave this empty and are skipped by
    #: ``resolve(..., needs_grad=True)``.
    differentiable: frozenset = frozenset()
    #: subset of ``provides`` that can run with the sequence axis sharded
    #: over a mesh (``ShardSpec``) — the backend carries the collective
    #: glue.  Single-device strategies leave this empty and are skipped
    #: when resolution is asked for a sharded plan.
    shardable: frozenset = frozenset()
    #: True for backends that ONLY make sense sharded (context-parallel
    #: glue); they are skipped for unsharded resolution requests.
    shard_only: bool = False
    #: True for Pallas kernel strategies.  Their ops take ``interpret``,
    #: which ``run_kwargs`` sets from the platform resolution ran for.
    pallas: bool = False

    def supports(self, cfg: FlowConfig, shapes: ShapeInfo, platform: str,
                 *, op: str = "forward", explicit: bool = False):
        """Return (applicable: bool, reason: str)."""
        raise NotImplementedError

    def grad_support(self, op: str = "forward"):
        """(ok, reason) — whether ``jax.grad`` flows through ``op``.

        The default answer is the declarative ``differentiable`` set;
        override for shape/config-dependent gradient support.
        """
        if op in self.differentiable:
            return True, f"differentiable {op}"
        return False, (
            f"no VJP rule for {op} (forward-only kernel; differentiable "
            f"ops: {sorted(self.differentiable) or 'none'})"
        )

    def shard_support(self, op: str = "forward", shard: "ShardSpec | None" = None,
                      *, cfg=None, shapes: "ShapeInfo | None" = None,
                      platform: str | None = None):
        """(ok, reason) — can ``op`` run with the sequence axis sharded?

        The default answer is the declarative ``shardable`` set; backends
        with collective glue override this to also validate the mesh axis,
        divisibility, and their inner shard-local strategy.  ``cfg`` /
        ``shapes`` / ``platform`` are the same values ``supports`` sees,
        passed so refinements can be shape-aware.
        """
        if op in self.shardable:
            return True, f"collective glue for sharded {op}"
        return False, (
            f"no collective glue for sharded {op} (single-device strategy"
            + (f"; shardable ops: {sorted(self.shardable)}" if self.shardable
               else "") + ")"
        )

    def quant_capable(self, platform: str, dtype: str, op: str = "decode"):
        """(ok, reason) — can ``op`` serve a quantized state pool directly?

        Quantized serving (``ExecutionPlan.state_dtype`` of ``int8``/
        ``fp8``) hands the op a ``serving.quant.QuantizedPool`` — low-bit
        payload plus per-(slot, head) fp32 scales — instead of a raw
        ``FlowState``.  A capable backend dequantizes per head,
        accumulates the update in fp32, and requantizes on the in-place
        write.  The default declines, so resolution rejects with a named
        reason rather than silently dequantizing through an unaware
        backend.
        """
        return False, (
            f"no quantized-state path for {op} (would silently dequantize "
            f"the {dtype} pool; pick a quant-capable strategy)"
        )

    def verify_support(self, op: str = "verify"):
        """(ok, reason) — whether the backend can score a drafted window.

        Speculative decoding needs ``verify_step``: continue a recurrent
        ``FlowState`` over k drafted tokens in one pass and hand back every
        position's boundary state for accept-prefix rollback.  The default
        answer is declarative (``"verify" in provides``); override for
        config-dependent refinements.  Consulted by resolution exactly like
        ``grad_support`` / ``shard_support``, so a failed speculative plan
        raises ``ResolutionError`` with each backend's own reason.
        """
        if "verify" in self.provides:
            return True, "carry-in chunked verify"
        return False, (
            "no verify_step (cannot continue a FlowState over a drafted "
            "window; speculative decoding needs a chunked-scan strategy)"
        )

    # canonical ops ---------------------------------------------------------
    def forward(self, q: Array, k: Array, v: Array, cfg: FlowConfig) -> Array:
        """Full-sequence Flow-Attention -> (B, Hq, N, Dv)."""
        raise NotImplementedError(f"{self.name} does not provide forward")

    def prefill(self, q: Array, k: Array, v: Array, cfg: FlowConfig,
                *, lengths: Array | None = None):
        """Consume a prompt -> (per-position outputs, decode FlowState)."""
        raise NotImplementedError(f"{self.name} does not provide prefill")

    def decode_step(self, state, q: Array, k: Array, v: Array, cfg: FlowConfig):
        """Advance one token -> (new FlowState, out (B, Hq, 1, Dv))."""
        raise NotImplementedError(f"{self.name} does not provide decode_step")

    def verify_step(self, state, q: Array, k: Array, v: Array, cfg: FlowConfig):
        """Score a drafted window in one pass -> (out, trajectory FlowState)."""
        raise NotImplementedError(f"{self.name} does not provide verify_step")


class ResolutionError(ValueError):
    """No backend applied to a resolution request.

    ``rejections`` is ``((name, reason), ...)`` for every candidate so
    callers (CI gates, benchmark sweeps) can report each backend's own
    reason instead of only the last one.
    """

    def __init__(self, message: str, rejections=()):
        """Store the human message plus the per-candidate rejections."""
        super().__init__(message)
        self.rejections = tuple(rejections)


_REGISTRY: dict[str, Backend] = {}
_ORDER: list[str] = []


def register_backend(name: str, impl: Backend, *, before: str | None = None):
    """Register ``impl`` under ``name``.

    ``before`` inserts the backend ahead of an existing name in the auto
    resolution order (new, more specialized backends outrank fallbacks).
    """
    if name in _REGISTRY:
        raise ValueError(f"backend {name!r} already registered")
    impl.name = name
    _REGISTRY[name] = impl
    if before is not None and before in _ORDER:
        _ORDER.insert(_ORDER.index(before), name)
    else:
        _ORDER.append(name)
    return impl


def get_backend(name: str) -> Backend:
    """Look up a registered backend by name (ValueError when unknown)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {tuple(_ORDER)}"
        ) from None


def list_backends() -> tuple:
    """Registered backend names in auto-resolution order."""
    return tuple(_ORDER)


def _candidates(cfg: FlowConfig) -> tuple[list, bool]:
    """(candidate names in order, explicit) for a FlowConfig.backend value."""
    sel = cfg.backend
    if sel == "auto":
        return list(_ORDER), False
    if sel == "xla":  # legacy: any non-Pallas strategy
        return [n for n in _ORDER if not _REGISTRY[n].pallas], False
    if sel == "pallas":  # legacy: force a Pallas kernel (interpret off-TPU)
        return [n for n in _ORDER if _REGISTRY[n].pallas], True
    if sel in _REGISTRY:
        return [sel], True
    raise ValueError(
        f"unknown FlowConfig.backend {sel!r}; expected 'auto', 'xla', "
        f"'pallas' or one of {tuple(_ORDER)}"
    )


def _judge(be: Backend, cfg: FlowConfig, shapes: ShapeInfo, platform: str,
           op: str, explicit: bool, needs_grad: bool,
           shard: ShardSpec | None = None, quant: str | None = None):
    """(applicable, reason) for one backend under the shared triage.

    The single triage sequence (provides -> gradient capability -> shard
    capability -> quantized-state capability -> supports) shared by
    ``resolve`` and ``explain`` so their answers can never drift apart.
    """
    if op not in be.provides:
        if op == "verify":
            # the backend's own verify_support reason (mirrors grad/shard
            # triage) so speculative resolution failures are debuggable
            return be.verify_support(op)
        return False, f"does not provide {op}"
    if op == "verify":
        ok, why = be.verify_support(op)
        if not ok:
            return False, why
    if needs_grad:
        ok, why = be.grad_support(op)
        if not ok:
            return False, why
    shard_why = None
    if shard is not None:
        ok, why = be.shard_support(op, shard, cfg=cfg, shapes=shapes,
                                   platform=platform)
        if not ok:
            return False, why
        shard_why = why
    elif be.shard_only:
        return False, ("context-parallel glue requires a sharded "
                       "ExecutionPlan (no ShardSpec in this resolution)")
    if quant is not None:
        ok, why = be.quant_capable(platform, quant, op=op)
        if not ok:
            return False, why
    ok, why = be.supports(cfg, shapes, platform, op=op, explicit=explicit)
    if ok and shard_why:
        why = f"{why}; {shard_why}"
    return ok, why


def run_kwargs(be: Backend, platform: str) -> dict:
    """Keyword arguments ``be``'s ops run with on ``platform``.

    A Pallas backend applies off-TPU only when selected explicitly
    (``supports(..., explicit=True)``), and then runs in the Pallas
    interpreter; on TPU its kernels compile.  Other backends take none.
    """
    return {"interpret": platform != "tpu"} if be.pallas else {}


def resolve(cfg: FlowConfig, shapes: ShapeInfo, platform: str | None = None,
            *, op: str = "forward", needs_grad: bool = False,
            shard: ShardSpec | None = None,
            quant: str | None = None) -> Backend:
    """Deterministically pick the backend that will run ``op``.

    ``needs_grad=True`` additionally requires the backend to self-report
    gradient capability for ``op`` (``grad_support``) — training call sites
    use it to fail fast at build time instead of inside ``jax.grad``.

    ``shard`` (a ``ShardSpec``) makes resolution mesh-aware: only backends
    whose ``shard_support`` accepts the spec are candidates, so a sharded
    plan lands on context-parallel collective glue (``cp_*``) and every
    single-device strategy's rejection says "no collective glue".

    ``quant`` (a quantized state dtype name, ``"int8"``/``"fp8"``) asks
    for an op that serves a ``serving.quant.QuantizedPool`` in place —
    only backends whose ``quant_capable`` accepts it are candidates.

    Raises ``ResolutionError`` with every candidate's rejection reason when
    nothing applies — the error is the documentation of why.
    """
    platform = platform or jax.default_backend()
    names, explicit = _candidates(cfg)
    if not any(op in _REGISTRY[n].provides for n in names):
        # a pinned forward strategy never blocks prefill/decode: those ops
        # fall back to full auto order (see module docstring)
        names, explicit = list(_ORDER), False
    rejections = []
    for name in names:
        be = _REGISTRY[name]
        ok, why = _judge(be, cfg, shapes, platform, op, explicit, needs_grad,
                         shard, quant)
        if ok:
            return be
        rejections.append((name, why))
    raise ResolutionError(
        f"no applicable Flow-Attention backend for op={op!r}"
        + (" with gradients" if needs_grad else "")
        + (f" sharded over {shard.describe()}" if shard is not None else "")
        + (f" with {quant} state pools" if quant is not None else "")
        + f" on platform={platform!r} with {shapes}:\n  "
        + "\n  ".join(f"{n}: {w}" for n, w in rejections),
        rejections,
    )


def explain(cfg: FlowConfig, shapes: ShapeInfo, platform: str | None = None,
            *, op: str = "forward", needs_grad: bool = False,
            shard: ShardSpec | None = None, quant: str | None = None) -> list:
    """Triage ``op`` for every registered backend.

    Returns ``[(name, applicable, reason)]`` rows — debugging aid and the
    data source for benchmark sweeps.  With ``shard`` the reasons include
    each backend's ``shard_support`` verdict; with ``quant`` each
    backend's ``quant_capable`` verdict.
    """
    platform = platform or jax.default_backend()
    _, explicit = _candidates(cfg)
    return [
        (name, *_judge(_REGISTRY[name], cfg, shapes, platform, op, explicit,
                       needs_grad, shard, quant))
        for name in _ORDER
    ]
