"""SequenceMixer protocol: ONE layer-level state API for every mixer kind.

The layer-level analogue of the ``repro/attention`` backend registry.  A
sequence mixer is whatever sits between ``norm1`` and the residual add in a
decoder block — Flow/softmax/MLA/local/linear attention, the RG-LRU
recurrence, the Mamba-2 SSD scan.  Every one of them already exposes the
same implicit lifecycle (*Transformers are RNNs*: linear attention and
SSM-style scans share one recurrent-state decode form); this module spells
it once as canonical ops on a ``Mixer`` record:

    init_params(key, cfg)                         parameter pytree
    forward(params, x, cfg, positions, plan)      full-sequence (train)
    state_init(cfg, batch, max_len, plan)         decode-state pytree
    prefill(params, x, cfg, max_len, ...)         prompt -> (out, state)
    prefill_packed(..., lengths)                  right-padded prompt batch,
                                                  per-row boundary states
    decode_step(params, x, state, cfg, ...)       one token on the state
    verify_step(params, x, state, cfg, ...)       n drafted tokens -> per-
                                                  position outputs + pending
    select_verified(pending, accepted, n, cfg)    accept-prefix rollback

plus capability flags each kind self-reports against a concrete
``ModelConfig``:

    packable       — per-row boundary states from ONE padded prefill call
                     (continuous-batching packed admission)
    paged_capable  — the decode cache can live in the paged KV pool
                     (``serving/paged.py``); constant-size states decline
    differentiable — ``jax.grad`` flows through ``forward`` on the given
                     platform
    verify_capable — the decode state can score a drafted window and roll
                     back to the accepted prefix (speculative decoding);
                     overwriting ring buffers decline

``resolve_mixer(kind, cfg, plan)`` binds a kind to its record with the
same rejection-reporting contract as ``attention.resolve``: a plan that
demands a capability the kind lacks raises ``MixerResolutionError`` whose
message and structured ``.rejections`` name the missing capability in the
mixer's own words (e.g. paged + a non-attention kind).  Model-level
callers use ``resolve_mixers(cfg, plan)`` — one bound mixer per layer,
with the plan *narrowed* per layer (the paged pool binds only pageable
layers; everything else keeps its constant-size state).

Registering a new mixer kind makes it a ``cfg.pattern`` citizen everywhere
at once — ``models/lm.py`` stacking, serving admission (the Worker consults
``packable`` instead of special-casing kinds), trainability fail-fasts —
with zero call-site edits::

    from repro.layers.mixer import Mixer, register_mixer

    class MyMixer(Mixer):
        params_field = "mymix"
        def packable(self, cfg):
            return False, "scan returns final-position state only"
        ...

    register_mixer("mymix", MyMixer())

The built-in kinds register themselves on import of their layer modules
(``layers/attention.py`` for attn+local, ``layers/rglru.py``,
``layers/ssd.py``); resolution imports them lazily.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from repro import scopes
from repro.config import ModelConfig

Array = jax.Array


def select_from_trajectory(pending, accepted: Array):
    """Gather one boundary per batch row from a trajectory state pytree.

    Every leaf of ``pending`` carries a window-position axis at index 1
    (shape ``(B, n, ...)``); ``accepted`` (B,) int selects, per row, the
    state after consuming ``accepted+1`` window tokens.  This is the
    generic accept-prefix rollback for constant-size states — a gather,
    never a recompute.
    """
    def gat(leaf: Array) -> Array:
        ii = accepted.reshape(
            (-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.int32)
        return jnp.take_along_axis(leaf, ii, axis=1)[:, 0]

    return jax.tree_util.tree_map(gat, pending)


# ---------------------------------------------------------------------------
# Deprecation plumbing (shared by the layer modules' legacy-name shims)
# ---------------------------------------------------------------------------
_WARNED: set[str] = set()


def warn_once_deprecated(key: str, msg: str):
    if key in _WARNED:
        return
    _WARNED.add(key)
    warnings.warn(msg, DeprecationWarning, stacklevel=3)


def _reset_deprecation_warnings():
    """Test hook: make the next legacy call warn again."""
    _WARNED.clear()


def make_legacy_shim(module: str, name: str, impl, kind: str, proto: str):
    """A warn-once wrapper for a pre-protocol per-kind function name.

    The layer modules keep their old public names (``rglru_prefill``,
    ``attn_cache_init``, ...) alive through these shims; behavior is
    identical, the warning points at the protocol spelling.
    """

    def wrapper(*args, **kwargs):
        warn_once_deprecated(
            f"{module}.{name}",
            f"repro.layers.{module}.{name} is deprecated: resolve the "
            f"mixer registry instead — resolve_mixer({kind!r}, cfg)."
            f"{proto}(...) (repro/layers/mixer.py); behavior is identical",
        )
        return impl(*args, **kwargs)

    wrapper.__name__ = name
    wrapper.__doc__ = (
        f"Deprecated alias of the ``{kind}`` mixer's ``{proto}``."
    )
    return wrapper


# ---------------------------------------------------------------------------
# The protocol
# ---------------------------------------------------------------------------
class Mixer:
    """One sequence-mixer kind behind the canonical layer-level ops.

    Subclasses set ``params_field`` (the key their parameters live under in
    a block's param dict) and implement the ops; capability methods return
    ``(ok, reason)`` so resolution rejections carry the mixer's own words.
    ``block_ffn=False`` marks kinds that ARE the whole block (Mamba-2: no
    separate FFN/norm2 sublayer).
    """

    kind: str = "?"
    params_field: str = "?"
    block_ffn: bool = True

    # capabilities ----------------------------------------------------------
    def packable(self, cfg: ModelConfig):
        """(ok, reason) — can ONE right-padded prefill call return per-row
        boundary states for a batch of different-length prompts?"""
        return True, "per-row boundary states from one padded call"

    def paged_capable(self, cfg: ModelConfig):
        """(ok, reason) — can the decode cache live in the paged KV pool?"""
        return False, "constant-size decode state (nothing to page)"

    def differentiable(self, cfg: ModelConfig, platform: str):
        """(ok, reason) — does ``jax.grad`` flow through ``forward``?"""
        return True, "natively differentiable"

    def verify_capable(self, cfg: ModelConfig):
        """(ok, reason) — can the decode state score a drafted window and
        roll back to the accepted prefix (speculative decoding)?  True by
        default: any kind with ``decode_step`` gets the scanned-decode
        verify with trajectory rollback; kinds whose caches destroy
        history (overwriting ring buffers) decline."""
        return True, "trajectory rollback over scanned decode"

    def quant_capable(self, cfg: ModelConfig, platform: str, dtype: str):
        """(ok, reason) — can the decode state live in a quantized pool
        (``serving.quant.QuantizedPool``: low-bit payload + per-(slot,
        head) fp32 scales, ``ExecutionPlan.state_dtype``)?  The default
        declines so resolution rejects with a named reason instead of a
        kind silently dequantizing a pool it does not understand."""
        return False, (f"no quantized-state decode path (would silently "
                       f"dequantize the {dtype} pool)")

    # canonical ops ---------------------------------------------------------
    def init_params(self, key, cfg: ModelConfig) -> dict:
        raise NotImplementedError(f"{self.kind} does not provide init_params")

    def forward(self, params, x: Array, cfg: ModelConfig, *,
                positions: Array | None = None, plan=None) -> Array:
        raise NotImplementedError(f"{self.kind} does not provide forward")

    def state_init(self, cfg: ModelConfig, batch: int, max_len: int, *,
                   dtype=None, plan=None):
        """``dtype`` is the *serving activation* dtype; kinds whose caches
        follow it (dense KV) honor it, constant-dtype states ignore it."""
        raise NotImplementedError(f"{self.kind} does not provide state_init")

    def prefill(self, params, x: Array, cfg: ModelConfig, max_len: int, *,
                positions: Array | None = None, plan=None):
        raise NotImplementedError(f"{self.kind} does not provide prefill")

    def prefill_packed(self, params, x: Array, cfg: ModelConfig,
                       max_len: int, lengths: Array, *,
                       positions: Array | None = None, plan=None):
        raise NotImplementedError(
            f"{self.kind} does not provide prefill_packed"
        )

    def decode_step(self, params, x: Array, state, cfg: ModelConfig, *,
                    positions: Array | None = None,
                    page_table: Array | None = None, plan=None):
        raise NotImplementedError(f"{self.kind} does not provide decode_step")

    def verify_step(self, params, x: Array, state, cfg: ModelConfig, *,
                    positions: Array | None = None,
                    page_table: Array | None = None, plan=None):
        """Score a drafted window of n tokens; return (out, pending).

        ``x`` is (B, n, width): the last committed token plus the drafted
        candidates.  ``out`` (B, n, width) must match what n sequential
        ``decode_step`` calls would produce; ``pending`` is whatever
        ``select_verified`` needs to roll the state to any accepted prefix.

        The default realization IS n sequential ``decode_step`` calls
        (unrolled: n is a handful by construction) with every intermediate
        state stacked into a trajectory along axis 1 — correct for any
        constant-size recurrent state (flow/linear/rglru/ssd).  Kinds with
        large positional caches override to avoid materializing n cache
        copies.
        """
        n = x.shape[1]
        outs, traj = [], []
        st = state
        for j in range(n):
            pos_j = None if positions is None else positions[..., j:j + 1]
            y, st = self.decode_step(params, x[:, j:j + 1], st, cfg,
                                     positions=pos_j, page_table=page_table,
                                     plan=plan)
            outs.append(y)
            traj.append(st)
        pending = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves, axis=1), *traj)
        return jnp.concatenate(outs, axis=1), pending

    def select_verified(self, pending, accepted: Array, n: int,
                        cfg: ModelConfig, *, plan=None):
        """Roll the pending verify state to the accepted prefix.

        ``accepted`` (B,) int in [0, n-1]: the per-row index of the last
        consumed window token (``accepted+1`` tokens advance).  The default
        pairs with the default ``verify_step``: a trajectory gather.
        """
        del n, cfg, plan
        return select_from_trajectory(pending, accepted)


class MixerResolutionError(ValueError):
    """A mixer kind cannot satisfy the plan; ``rejections`` is
    ``((kind, capability, reason), ...)`` so callers report WHICH
    capability was missing, in the mixer's own words."""

    def __init__(self, message: str, rejections=()):
        super().__init__(message)
        self.rejections = tuple(rejections)


_REGISTRY: dict[str, Mixer] = {}
_BUILTINS_LOADED = False


def register_mixer(kind: str, impl: Mixer) -> Mixer:
    if kind in _REGISTRY:
        raise ValueError(f"mixer kind {kind!r} already registered")
    impl.kind = kind
    _REGISTRY[kind] = impl
    return impl


def _ensure_builtins():
    """Import the layer modules that register the built-in kinds."""
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    _BUILTINS_LOADED = True
    import repro.layers.attention  # noqa: F401  registers attn, local
    import repro.layers.rglru  # noqa: F401  registers rglru
    import repro.layers.ssd  # noqa: F401  registers ssd


def get_mixer(kind: str) -> Mixer:
    _ensure_builtins()
    try:
        return _REGISTRY[kind]
    except KeyError:
        raise MixerResolutionError(
            f"unknown mixer kind {kind!r}; registered: {list_mixers()}"
        ) from None


def list_mixers() -> tuple:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------
class BoundMixer:
    """One mixer kind bound to (ModelConfig, ExecutionPlan): the canonical
    ops without cfg/plan re-threading, plus the resolved capability bools
    serving admission consults (``Worker`` packs when every layer's
    ``packable`` is True instead of crashing on a kind list)."""

    def __init__(self, mixer: Mixer, cfg: ModelConfig, plan, platform: str):
        self.mixer = mixer
        self.cfg = cfg
        self.plan = plan
        self.kind = mixer.kind
        self.params_field = mixer.params_field
        self.block_ffn = mixer.block_ffn
        self.packable = mixer.packable(cfg)[0]
        self.paged_capable = mixer.paged_capable(cfg)[0]
        self.differentiable = mixer.differentiable(cfg, platform)[0]
        self.verify_capable = mixer.verify_capable(cfg)[0]
        self.quant_capable = mixer.quant_capable(
            cfg, platform, _quant_dtype_of(plan) or "int8")[0]

    def init_params(self, key) -> dict:
        return self.mixer.init_params(key, self.cfg)

    def forward(self, params, x: Array, *,
                positions: Array | None = None) -> Array:
        with jax.named_scope(scopes.MIXER):
            return self.mixer.forward(params, x, self.cfg,
                                      positions=positions, plan=self.plan)

    def state_init(self, batch: int, max_len: int, dtype=None):
        return self.mixer.state_init(self.cfg, batch, max_len, dtype=dtype,
                                     plan=self.plan)

    def prefill(self, params, x: Array, max_len: int, *,
                positions: Array | None = None,
                lengths: Array | None = None):
        """``lengths`` (B,) routes to the ``prefill_packed`` op; a kind
        without the capability raises the same rejection ``resolve_mixer``
        would (there is no NotImplementedError path)."""
        if lengths is None:
            with jax.named_scope(scopes.MIXER):
                return self.mixer.prefill(params, x, self.cfg, max_len,
                                          positions=positions, plan=self.plan)
        ok, why = self.mixer.packable(self.cfg)
        if not ok:
            raise MixerResolutionError(
                f"mixer {self.kind!r} cannot satisfy packed prefill — "
                f"missing capability packable: {why}",
                ((self.kind, "packable", why),),
            )
        with jax.named_scope(scopes.MIXER):
            return self.mixer.prefill_packed(params, x, self.cfg, max_len,
                                             lengths, positions=positions,
                                             plan=self.plan)

    def decode_step(self, params, x: Array, state, *,
                    positions: Array | None = None,
                    page_table: Array | None = None):
        with jax.named_scope(scopes.MIXER):
            return self.mixer.decode_step(params, x, state, self.cfg,
                                          positions=positions,
                                          page_table=page_table,
                                          plan=self.plan)

    def verify_step(self, params, x: Array, state, *,
                    positions: Array | None = None,
                    page_table: Array | None = None):
        """Score a drafted window; raises the same rejection
        ``resolve_mixer`` would for a kind without the capability."""
        ok, why = self.mixer.verify_capable(self.cfg)
        if not ok:
            raise MixerResolutionError(
                f"mixer {self.kind!r} cannot satisfy speculative verify — "
                f"missing capability verify_capable: {why}",
                ((self.kind, "verify_capable", why),),
            )
        return self.mixer.verify_step(params, x, state, self.cfg,
                                      positions=positions,
                                      page_table=page_table, plan=self.plan)

    def select_verified(self, pending, accepted: Array, n: int):
        return self.mixer.select_verified(pending, accepted, n, self.cfg,
                                          plan=self.plan)


def _quant_dtype_of(plan) -> str | None:
    """The plan's quantized state dtype, or None for full-precision pools
    (bf16/fp32 state dtypes are storage overrides, not quantization)."""
    sd = getattr(plan, "state_dtype", None) if plan is not None else None
    return sd if sd in ("int8", "fp8") else None


def _plan_demands(plan) -> tuple:
    """((capability, demand-description), ...) a plan places on a mixer."""
    if plan is None:
        return ()
    demands = []
    if getattr(plan, "packed", False):
        demands.append(("packable", "packed multi-prompt prefill"))
    if getattr(plan, "paged", None) is not None:
        demands.append(("paged_capable", "paged decode caches"))
    if getattr(plan, "needs_grad", False):
        demands.append(("differentiable", "gradients through forward"))
    if getattr(plan, "speculate_k", 0):
        demands.append(("verify_capable", "speculative verify windows"))
    qd = _quant_dtype_of(plan)
    if qd is not None:
        demands.append(("quant_capable", f"{qd} quantized state pools"))
    return tuple(demands)


def _capability(mixer: Mixer, cap: str, cfg: ModelConfig, platform: str,
                quant_dtype: str = "int8"):
    if cap == "differentiable":
        return mixer.differentiable(cfg, platform)
    if cap == "quant_capable":
        return mixer.quant_capable(cfg, platform, quant_dtype)
    return getattr(mixer, cap)(cfg)


def plan_platform(plan) -> str:
    """The platform a plan's ops run on: the plan's pin, else JAX's default
    backend, read when called (at trace time, never at import)."""
    return getattr(plan, "platform", None) or jax.default_backend()


def resolve_mixer(kind: str, cfg: ModelConfig, plan=None) -> BoundMixer:
    """Bind one mixer kind to (cfg, plan), enforcing the plan's demands.

    The rejection contract mirrors ``attention.resolve``: every demanded
    capability the kind cannot satisfy is collected, and the raised
    ``MixerResolutionError`` names each missing capability with the
    mixer's own reason (``.rejections`` carries them structured) —
    e.g. a paged plan bound to a non-attention kind reports
    ``paged_capable: constant-size decode state (nothing to page)``.
    """
    mixer = get_mixer(kind)
    platform = plan_platform(plan)
    rejections = []
    for cap, demand in _plan_demands(plan):
        ok, why = _capability(mixer, cap, cfg, platform,
                              _quant_dtype_of(plan) or "int8")
        if not ok:
            rejections.append((kind, cap, why))
    if rejections:
        raise MixerResolutionError(
            f"mixer {kind!r} cannot satisfy {plan.describe()}:\n  "
            + "\n  ".join(f"missing {cap}: {why}" for _, cap, why in
                          rejections),
            rejections,
        )
    return BoundMixer(mixer, cfg, plan, platform)


def _narrow_layer_plan(mixer: Mixer, cfg: ModelConfig, plan):
    """The model-level plan, narrowed to ONE layer: the paged-pool spec is
    a *model* option that binds only pageable layers (constant-size
    flow/linear/rglru/ssd states and bounded local rings keep their dense
    form), so it is stripped — not rejected — for kinds without the
    capability.  ``packed``/``needs_grad`` are whole-stack demands and
    stay."""
    if plan is None:
        return None
    if plan.paged is not None and not mixer.paged_capable(cfg)[0]:
        return dataclasses.replace(plan, paged=None)
    return plan


def resolve_layer_mixer(kind: str, cfg: ModelConfig, plan=None) -> BoundMixer:
    """``resolve_mixer`` with the model-level plan narrowed to one layer."""
    return resolve_mixer(kind, cfg, _narrow_layer_plan(get_mixer(kind), cfg,
                                                       plan))


def resolve_mixers(cfg: ModelConfig, plan=None) -> tuple:
    """One ``BoundMixer`` per layer of ``cfg`` (indexable by layer id).

    Each layer's kind comes from ``cfg.block_kind`` — the single source of
    truth — and is resolved against the plan narrowed to that layer.  A
    whole-stack demand (packed admission, gradients) that some layer's
    kind cannot satisfy raises with that kind's own rejection."""
    by_kind: dict[str, BoundMixer] = {}
    out = []
    for i in range(cfg.n_layers):
        kind = cfg.block_kind(i)
        if kind not in by_kind:
            by_kind[kind] = resolve_layer_mixer(kind, cfg, plan)
        out.append(by_kind[kind])
    return tuple(out)


def stack_capabilities(cfg: ModelConfig, platform: str | None = None) -> dict:
    """Aggregate capability verdict for a whole stack.

    ``packable`` — every layer packs (serving admission's question);
    ``paged_capable`` — at least one layer can page (is a pool worth
    allocating at all); ``differentiable`` — every layer trains;
    ``verify_capable`` — every layer can verify-and-rollback (speculative
    decoding is all-or-nothing across a stack); ``quant_capable`` — every
    layer's state can live in a quantized pool (judged at int8, the
    everywhere-supported format).  Each verdict pairs with the first
    offending/supporting (kind, reason)."""
    platform = platform or jax.default_backend()
    kinds = {cfg.block_kind(i) for i in range(cfg.n_layers)}
    verdicts = {}
    for cap, agg in (("packable", all), ("paged_capable", any),
                     ("differentiable", all), ("verify_capable", all),
                     ("quant_capable", all)):
        rows = [(k, *_capability(get_mixer(k), cap, cfg, platform))
                for k in sorted(kinds)]
        ok = agg(r[1] for r in rows)
        pick = next((r for r in rows if r[1] != (agg is all)), rows[0])
        verdicts[cap] = (ok, pick[0], pick[2])
    return verdicts


def capability_matrix(cfg: ModelConfig, platform: str | None = None) -> list:
    """[(kind, {capability: (ok, reason)})] for every registered kind,
    judged against ``cfg`` — the README table, live."""
    platform = platform or jax.default_backend()
    rows = []
    for kind in list_mixers():
        m = get_mixer(kind)
        rows.append((kind, {
            "packable": m.packable(cfg),
            "paged_capable": m.paged_capable(cfg),
            "differentiable": m.differentiable(cfg, platform),
            "verify_capable": m.verify_capable(cfg),
            "quant_capable": m.quant_capable(cfg, platform, "int8"),
        }))
    return rows
