"""Step builders: distributed train_step / serve_step per architecture.

These produce the exact jitted computations that the launchers (train.py /
serve.py) and the chip benchmark execute.  Each builder constructs ONE
attention ``ExecutionPlan`` at build time (gradient needs for the train
step, the mesh/axis ``ShardSpec`` for sequence-parallel prefill) and the
``repro/attention`` registry resolves it — step builders decide
distribution (sharding, microbatching, sequence parallelism), never which
kernel runs the attention math.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, ShapeSpec
from repro.distribution.sharding import (
    batch_spec,
    dp_axes,
    to_shardings,
    tree_param_specs,
    tree_zero1_specs,
)
from repro.training.train_state import TrainConfig, TrainState, make_train_step
from repro.training import optimizer as opt_lib


def _dp_spec_axis(dp):
    """PartitionSpec entry for the data-parallel axes of a mesh: an axis
    tuple, a single axis name, or None (replicated) when the mesh has no
    dp axes at all."""
    return tuple(dp) if len(dp) > 1 else (dp[0] if dp else None)


def _on_mesh(fn, mesh):
    """``fn`` traced under ``mesh``'s abstract mesh: Pallas attention ops
    inside then run once per device (``attention/plan.py``), since the
    compiler cannot partition a Pallas kernel itself."""
    @functools.wraps(fn)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args)

    return traced


def model_loss_fn(cfg: ModelConfig, xplan=None):
    from repro.models import encdec, lm

    if cfg.family == "encdec":
        return functools.partial(encdec.loss_fn, cfg=cfg)
    return functools.partial(lm.loss_fn, cfg=cfg, plan=xplan)


def training_shapes(cfg: ModelConfig, shape: ShapeSpec):
    """Static attention shapes of one training step (for plan resolution)."""
    from repro import attention

    if cfg.mla is not None:
        d = cfg.mla.nope_head_dim + cfg.mla.rope_head_dim
        dv, hq, hkv = cfg.mla.v_head_dim, cfg.n_heads, cfg.n_heads
    else:
        d = dv = cfg.dim_head
        hq, hkv = cfg.n_heads, cfg.kv_heads
    return attention.ShapeInfo(b=max(1, shape.global_batch), hq=hq,
                               hkv=hkv, n=shape.seq_len, m=shape.seq_len,
                               d=d, dv=dv)


def check_flow_trainable(cfg: ModelConfig, shape: ShapeSpec, xplan=None):
    """Fail fast if any configured execution path cannot provide gradients.

    Two layers of build-time triage, both raising with self-reported
    reasons instead of failing deep inside ``jax.grad`` tracing:

    * every layer *kind* must be a differentiable mixer on this platform
      (``resolve_mixers`` with a ``needs_grad`` plan — every stock mixer
      now trains on TPU since the ssd_chunk backward landed, but custom
      mixers still reject by name here);
    * a pinned forward-only flow *backend* raises with every attention
      backend's own rejection reason.
    """
    from repro import attention
    from repro.layers.attention import flow_cfg_of, plan_of
    from repro.layers.mixer import resolve_mixers

    xplan = xplan if xplan is not None else plan_of(cfg, needs_grad=True)
    resolve_mixers(cfg, xplan)
    if cfg.attention.kind != "flow":
        return None
    shapes = training_shapes(cfg, shape)
    be = attention.resolve_for_training(
        xplan.with_shapes(shapes).with_flow(flow_cfg_of(cfg, causal=True)))
    if cfg.family == "encdec":  # encoder side trains non-causally too
        attention.resolve_for_training(
            xplan.with_shapes(shapes).with_flow(flow_cfg_of(cfg, causal=False)))
    return be


@dataclasses.dataclass(frozen=True)
class RunPlan:
    """Distribution plan for one (arch x shape x mesh) cell."""

    param_mode: str = "replicated"  # replicated (dp) | fsdp (zero-sharded)
    microbatch: int = 0
    optimizer: str = "adamw"
    # opt-in perf bundle (baseline False)
    fused_vg: bool = False    # one value_and_grad pass instead of two fwd
    act_shard: bool = False   # pin residual activations to (dp, None, None)

    @staticmethod
    def choose(cfg: ModelConfig, shape: ShapeSpec, mesh) -> "RunPlan":
        n_params = cfg.param_count()
        model_par = mesh.shape.get("model", 1)
        bf16_per_chip = 2 * n_params / model_par
        # keep bf16 compute params under ~4 GiB/chip, else FSDP-gather
        param_mode = "fsdp" if bf16_per_chip > 4e9 else "replicated"
        # keep per-chip microbatch tokens <= 64k for train shapes
        microbatch = 0
        if shape.kind == "train":
            dp = 1
            for a in dp_axes(mesh):
                dp *= mesh.shape[a]
            per_dp_batch = max(1, shape.global_batch // dp)
            tokens = per_dp_batch * shape.seq_len
            budget = 32768 if n_params > 5e10 else 131072
            while tokens > budget and per_dp_batch > 1:
                per_dp_batch //= 2
                tokens = per_dp_batch * shape.seq_len
            microbatch = per_dp_batch * dp
            if microbatch >= shape.global_batch:
                microbatch = 0
        return RunPlan(param_mode=param_mode, microbatch=microbatch)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def build_train_step(cfg: ModelConfig, shape: ShapeSpec, mesh,
                     plan: RunPlan | None = None,
                     train_overrides: dict | None = None):
    """Returns (jit_step, state_shapes, batch_specs_tree, plan)."""
    import dataclasses as _dc

    from repro.launch.specs import params_shape, train_inputs

    from repro.layers.attention import plan_of

    plan = plan or RunPlan.choose(cfg, shape, mesh)
    # ONE attention ExecutionPlan for the whole training step, built here
    # at construction time; forward-only backend pins fail fast below
    xplan = plan_of(cfg, needs_grad=True)
    check_flow_trainable(cfg, shape, xplan)
    tcfg = TrainConfig(microbatch=plan.microbatch, optimizer=plan.optimizer,
                       fused_value_grad=plan.fused_vg)
    if train_overrides:
        tcfg = _dc.replace(tcfg, **train_overrides)
    pshape = params_shape(cfg)
    pspecs = tree_param_specs(pshape, mesh)
    zspecs = tree_zero1_specs(pshape, mesh)
    compute_specs = zspecs if plan.param_mode == "fsdp" else pspecs

    loss = model_loss_fn(cfg, xplan)

    def constrained_loss(params, batch):
        params = jax.lax.with_sharding_constraint(
            params, to_shardings(compute_specs, mesh)
        )
        return loss(params, batch)

    step_fn = make_train_step(constrained_loss, tcfg)
    if plan.act_shard:
        from repro.distribution.act_sharding import activation_sharding

        dp = dp_axes(mesh)
        raw_step = step_fn

        def step_fn(state, batch):  # context active at trace time
            with activation_sharding(P(_dp_spec_axis(dp), None, None), mesh):
                return raw_step(state, batch)

    # state shapes/specs
    state_shape = jax.eval_shape(
        lambda p: TrainState(
            master=p,
            opt=opt_lib.adamw_init(p) if plan.optimizer == "adamw"
            else opt_lib.adafactor_init(p),
            step=jnp.zeros((), jnp.int32),
        ),
        pshape,
    )
    from repro.training.train_state import _opt_leaf_specs

    opt_specs = type(state_shape.opt)(*[
        _opt_leaf_specs(getattr(state_shape.opt, f), pshape, mesh)
        for f in state_shape.opt._fields
    ])
    state_specs = TrainState(master=zspecs, opt=opt_specs, step=P())
    # the state as the step holds it: a first state placed with these
    # shardings has the type of every later one, so the step compiles once
    state_shape = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        state_shape, to_shardings(state_specs, mesh))

    binputs = train_inputs(cfg, shape)
    bspec = batch_spec(mesh, shape.global_batch)
    batch_specs = jax.tree.map(
        lambda x: P(*(list(bspec)[:1] + [None] * (x.ndim - 1))), binputs
    )

    jit_step = jax.jit(
        _on_mesh(step_fn, mesh),
        in_shardings=(to_shardings(state_specs, mesh),
                      to_shardings(batch_specs, mesh)),
        out_shardings=(to_shardings(state_specs, mesh), None),
        donate_argnums=(0,),
    )
    return jit_step, state_shape, batch_specs, plan


# ---------------------------------------------------------------------------
# Serve steps (prefill / decode)
# ---------------------------------------------------------------------------
def build_prefill_step(cfg: ModelConfig, shape: ShapeSpec, mesh,
                       plan: RunPlan | None = None, *, seq_shard: bool = False):
    """Prefill serve step.  ``seq_shard`` enables sequence-parallel prefill
    (flow attention's O(d^2)-collective context parallelism)."""
    from repro.launch.specs import params_shape, prefill_inputs
    from repro.models import encdec, lm

    from repro import attention
    from repro.layers.attention import plan_of

    plan = plan or RunPlan.choose(cfg, shape, mesh)
    pshape = params_shape(cfg)
    pspecs = tree_param_specs(pshape, mesh)
    if plan.param_mode == "fsdp":
        pspecs = tree_zero1_specs(pshape, mesh)

    # seq-parallel flow prefill resolves through the registry like every
    # other strategy: ONE sharded ExecutionPlan built here binds the
    # context-parallel backends (cp_causal + collective glue) inside the
    # jitted step.  Shapes the glue cannot shard (indivisible N) fall back
    # to the unsharded plan — GSPMD still seq-shards the XLA cumsums.
    xplan = None
    if seq_shard and cfg.attention.kind == "flow":
        dp = dp_axes(mesh)
        shard = attention.ShardSpec(axis="model", mesh=mesh,
                                    batch_axis=_dp_spec_axis(dp))
        cand = plan_of(cfg, shard=shard)
        try:
            # validate the op this step actually runs (prefill forces the
            # strict-causal serving competition, so paper-faithful
            # strict_causal=False configs still bind the glue)
            attention.BoundExecutor(
                cand.with_shapes(training_shapes(cfg, shape))
            ).backend("prefill")
            xplan = cand
        except attention.ResolutionError as err:
            print(f"[steps] seq-shard plan fell back to GSPMD: "
                  f"{err.rejections[-1] if err.rejections else err}")

    if cfg.family == "encdec":
        def base_prefill(params, batch):
            return encdec.encode(params, batch["frames"], cfg)
    else:
        def base_prefill(params, batch):
            return lm.prefill(params, batch["inputs"], cfg, shape.seq_len,
                              plan=xplan)

    if plan.act_shard or seq_shard:
        from repro.distribution.act_sharding import activation_sharding

        dp = dp_axes(mesh)
        saxis = "model" if seq_shard else None

        def prefill_fn(params, batch):
            with activation_sharding(
                P(_dp_spec_axis(dp), saxis, None), mesh
            ):
                return base_prefill(params, batch)
    else:
        prefill_fn = base_prefill

    binputs = prefill_inputs(cfg, shape)
    bspec = batch_spec(mesh, shape.global_batch, seq_sharded=seq_shard)
    batch_specs = jax.tree.map(
        lambda x: P(*(list(bspec) + [None] * (x.ndim - 2))[: x.ndim]), binputs
    )
    jit_step = jax.jit(
        _on_mesh(prefill_fn, mesh),
        in_shardings=(to_shardings(pspecs, mesh),
                      to_shardings(batch_specs, mesh)),
    )
    return jit_step, pshape, batch_specs, plan


def build_decode_step(cfg: ModelConfig, shape: ShapeSpec, mesh,
                      plan: RunPlan | None = None, *,
                      fused_sampling: bool = False):
    """Decode serve step.  ``fused_sampling`` fuses the serving Worker's
    batched sampler into the same jit (one ``jax.random.categorical`` over
    the slot batch under per-slot temperatures + live mask), so the
    distributed step returns sampled tokens instead of logits — the same
    zero-per-slot-sync contract as ``repro/serving/worker.py``."""
    from repro.launch.specs import decode_inputs, params_shape
    from repro.layers.attention import plan_of
    from repro.models import encdec, lm

    plan = plan or RunPlan.choose(cfg, shape, mesh)
    xplan = plan_of(cfg)  # the decode step's attention plan (no shard:
    # a decode step has no sequence axis; the state pool is batch-led)
    pshape = params_shape(cfg)
    pspecs = tree_param_specs(pshape, mesh)
    if plan.param_mode == "fsdp":
        pspecs = tree_zero1_specs(pshape, mesh)

    if cfg.family == "encdec":
        if fused_sampling:
            raise ValueError("fused sampling serves lm decoders only")

        def decode_fn(params, batch):
            return encdec.decode_step(
                params, batch["token"], batch["memory"], batch["caches"],
                cfg, batch["pos"],
            )
    elif fused_sampling:
        from repro.serving.worker import sample_tokens

        def decode_fn(params, batch):
            logits, caches = lm.decode(params, batch["token"],
                                       batch["caches"], cfg, batch["pos"],
                                       plan=xplan)
            tok = sample_tokens(batch["key"], logits, batch["temps"],
                                batch["live"])
            return tok, caches
    else:
        def decode_fn(params, batch):
            return lm.decode(params, batch["token"], batch["caches"], cfg,
                             batch["pos"], plan=xplan)

    binputs = dict(decode_inputs(cfg, shape))
    if fused_sampling:
        b = shape.global_batch
        sds = jax.ShapeDtypeStruct
        binputs.update(
            temps=sds((b,), jnp.float32),
            live=sds((b,), jnp.bool_),
            key=jax.eval_shape(lambda: jax.random.PRNGKey(0)),
        )
    bspec = batch_spec(mesh, shape.global_batch)
    baxis = list(bspec)[0] if len(list(bspec)) else None

    def spec_of(x):
        if x.ndim == 0:
            return P()
        # batch-led tensors (token, caches, memory) shard dim0 over dp
        if x.shape[0] == shape.global_batch:
            return P(*([baxis] + [None] * (x.ndim - 1)))
        return P(*([None] * x.ndim))

    batch_specs = jax.tree.map(spec_of, binputs)
    if fused_sampling:
        batch_specs["key"] = P(None)  # the PRNG key is replicated, never
        # batch-sharded (its leading dim can coincide with tiny batches)
    jit_step = jax.jit(
        _on_mesh(decode_fn, mesh),
        in_shardings=(to_shardings(pspecs, mesh),
                      to_shardings(batch_specs, mesh)),
    )
    return jit_step, pshape, batch_specs, plan


def abstract_batch(specs_tree):
    """ShapeDtypeStructs for a batch-spec tree (identity: already SDS)."""
    return specs_tree
