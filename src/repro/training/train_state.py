"""TrainState + distributed train-step factory.

Memory/parallelism strategy (DESIGN.md §4):
  * fp32 master params + optimizer moments: ZeRO-1 sharded over (pod, data)
    on top of the TP spec — pjit materializes reduce-scatter(grads) ->
    local optimizer -> all-gather(params) automatically from the shardings.
  * compute params: bf16, TP-sharded, DP-replicated — cast once per step.
  * gradient accumulation: ``lax.scan`` over microbatches (fp32 accumulators,
    param-spec sharded) so arbitrarily large global batches fit.
  * activations: per-block remat (cfg.remat) + scan-over-layers in the model.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import scopes
from repro.distribution.sharding import (
    batch_spec,
    to_shardings,
    tree_zero1_specs,
)
from repro.training import optimizer as opt_lib
from repro.training import schedule as sched_lib
from repro.utils import tree_cast

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"  # adamw | adafactor
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"  # cosine | invsqrt | constant
    microbatch: int = 0  # 0 = no accumulation (single microbatch)
    grad_clip: float = 1.0
    weight_decay: float = 0.1
    compute_dtype: Any = jnp.bfloat16
    # §Perf iteration 1: fuse loss+grad into one value_and_grad pass
    # (baseline False reproduces the paper-faithful first implementation,
    # which lowered an extra metrics forward)
    fused_value_grad: bool = False


class TrainState(NamedTuple):
    master: PyTree  # fp32 params, ZeRO-1 sharded
    opt: Any  # optimizer state, ZeRO-1 sharded
    step: jax.Array


def init_train_state(params_fp32: PyTree, tcfg: TrainConfig) -> TrainState:
    if tcfg.optimizer == "adamw":
        opt = opt_lib.adamw_init(params_fp32)
    elif tcfg.optimizer == "adafactor":
        opt = opt_lib.adafactor_init(params_fp32)
    else:
        raise ValueError(tcfg.optimizer)
    return TrainState(master=params_fp32, opt=opt, step=jnp.zeros((), jnp.int32))


def _lr(step, tcfg: TrainConfig):
    if tcfg.schedule == "cosine":
        return sched_lib.warmup_cosine(step, peak_lr=tcfg.peak_lr,
                                       warmup=tcfg.warmup, total=tcfg.total_steps)
    if tcfg.schedule == "invsqrt":
        return sched_lib.warmup_invsqrt(step, peak_lr=tcfg.peak_lr,
                                        warmup=tcfg.warmup)
    return sched_lib.constant(step, peak_lr=tcfg.peak_lr, warmup=tcfg.warmup)


def make_train_step(
    loss_fn: Callable[[PyTree, dict], tuple[jax.Array, dict]],
    tcfg: TrainConfig,
):
    """Build the (un-jitted) train step: state, batch -> state, metrics.

    ``loss_fn(params_bf16, microbatch) -> (loss, metrics)``.
    """
    adamw_cfg = opt_lib.AdamWConfig(grad_clip=tcfg.grad_clip,
                                    weight_decay=tcfg.weight_decay)

    def split_microbatches(batch: dict, n_micro: int) -> dict:
        def f(x):
            b = x.shape[0]
            return x.reshape(n_micro, b // n_micro, *x.shape[1:])

        return jax.tree.map(f, batch)

    def train_step(state: TrainState, batch: dict):
        params = tree_cast(state.master, tcfg.compute_dtype)
        vg_fn = jax.value_and_grad(lambda p, mb: loss_fn(p, mb), argnums=0,
                                   has_aux=True)
        grad_fn = jax.grad(lambda p, mb: loss_fn(p, mb)[0], argnums=0)
        def value_fn(p, mb):
            return loss_fn(p, mb)

        first = jax.tree.leaves(batch)[0]
        n_micro = tcfg.microbatch and max(1, first.shape[0] // tcfg.microbatch)
        if n_micro and n_micro > 1:
            mbs = split_microbatches(batch, n_micro)
            g0 = jax.tree.map(
                lambda x: jnp.zeros(x.shape, jnp.float32), params
            )

            if tcfg.fused_value_grad:
                def accum(carry, mb):
                    g_acc, loss_acc = carry
                    (_, metrics), g = vg_fn(params, mb)
                    g_acc = jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), g_acc, g
                    )
                    return (g_acc, loss_acc + metrics["loss"]), metrics

                (grads, _), mstack = jax.lax.scan(accum, (g0, 0.0), mbs)
                metrics = jax.tree.map(lambda x: x.mean(), mstack)
            else:
                def accum(carry, mb):
                    g = grad_fn(params, mb)
                    return jax.tree.map(
                        lambda a, b: a + b.astype(jnp.float32), carry, g
                    ), None

                grads, _ = jax.lax.scan(accum, g0, mbs)
                _, metrics = value_fn(params, jax.tree.map(lambda x: x[0], mbs))
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        elif tcfg.fused_value_grad:
            (_, metrics), grads = vg_fn(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            loss, metrics = value_fn(params, batch)
            grads = grad_fn(params, batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        with jax.named_scope(scopes.OPTIMIZER):
            lr = _lr(state.step, tcfg)
            if tcfg.optimizer == "adamw":
                new_master, new_opt, stats = opt_lib.adamw_update(
                    grads, state.opt, state.master, lr, adamw_cfg
                )
            else:
                new_master, new_opt, stats = opt_lib.adafactor_update(
                    grads, state.opt, state.master, lr,
                    opt_lib.AdafactorConfig(weight_decay=tcfg.weight_decay),
                )
        metrics = {**metrics, **stats, "lr": lr}
        return TrainState(new_master, new_opt, state.step + 1), metrics

    return train_step


def shard_train_step(
    train_step, mesh, params_shape: PyTree, opt_shape, batch_shape: dict,
):
    """jit the train step with explicit ZeRO-1 in/out shardings."""
    zspecs = tree_zero1_specs(params_shape, mesh)
    if hasattr(opt_shape, "_fields"):  # NamedTuple optimizer state
        opt_specs = type(opt_shape)(*[
            _opt_leaf_specs(getattr(opt_shape, f), params_shape, mesh)
            for f in opt_shape._fields
        ])
    else:
        opt_specs = jax.tree.map(lambda _: P(), opt_shape)
    state_specs = TrainState(master=zspecs, opt=opt_specs, step=P())
    first = jax.tree.leaves(batch_shape)[0]
    bspec = batch_spec(mesh, first.shape[0])
    batch_specs = jax.tree.map(
        lambda x: P(*(list(bspec)[:1] + [None] * (x.ndim - 1))), batch_shape
    )
    return jax.jit(
        train_step,
        in_shardings=(to_shardings(state_specs, mesh),
                      to_shardings(batch_specs, mesh)),
        out_shardings=(to_shardings(state_specs, mesh), None),
        donate_argnums=(0,),
    )


def _opt_leaf_specs(opt_tree, params_shape, mesh):
    """Specs for one optimizer-state field: mirror params where shapes match."""
    from repro.distribution.sharding import param_spec, zero1_spec

    leaves_o = jax.tree.leaves(opt_tree)
    if not leaves_o or (len(leaves_o) == 1 and leaves_o[0] is opt_tree):
        return P()  # scalar leaf field (e.g. step counter)
    flat_p = jax.tree_util.tree_flatten_with_path(params_shape)[0]
    flat_o, tdef = jax.tree_util.tree_flatten_with_path(opt_tree)
    specs = []
    for (kp, oleaf), (kpp, pleaf) in zip(flat_o, flat_p):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kpp)
        if oleaf.shape == pleaf.shape:
            base = param_spec(path, pleaf.shape, mesh)
            specs.append(zero1_spec(base, pleaf.shape, mesh))
        else:  # factored adafactor rows/cols or scalars
            specs.append(P(*([None] * len(oleaf.shape))))
    return jax.tree_util.tree_unflatten(tdef, specs)
