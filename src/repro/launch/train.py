"""Production train driver: sharded steps + checkpoint/restart + elastic.

End-to-end path (also exercised by examples/train_lm.py at small scale):

    python -m repro.launch.train --arch flowformer-lm --steps 200 \
        --batch 16 --seq 512 --ckpt-dir /tmp/run1

Crash-restart: rerunning the same command resumes from the last committed
checkpoint (params, optimizer, data-iterator position).  On simulated
device failure (--fail-at N, used by integration tests) the driver
re-plans the mesh via runtime/elastic.py and continues.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.config import ModelConfig, ShapeSpec
from repro.configs import get_config, get_smoke_config
from repro.data.loader import lm_loader
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import RunPlan, build_train_step, training_shapes
from repro.models import lm
from repro.runtime.elastic import StepMonitor
from repro.training.train_state import TrainState
from repro.training import optimizer as opt_lib
from repro.utils import device_summary, pretty_count, tree_size


def train(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          mesh=None, seed: int = 0, log_every: int = 10,
          peak_lr: float = 3e-4) -> dict:
    mesh = mesh or make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("custom", seq, batch, "train")
    plan = RunPlan.choose(cfg, shape, mesh)
    jit_step, state_shape, _, plan = build_train_step(
        cfg, shape, mesh, plan,
        train_overrides={"total_steps": steps,
                         "warmup": max(5, steps // 10),
                         "peak_lr": peak_lr,
                         "fused_value_grad": True},
    )

    params = lm.init(jax.random.PRNGKey(seed), cfg)
    state = jax.device_put(TrainState(
        master=params,
        opt=opt_lib.adamw_init(params),
        step=jnp.zeros((), jnp.int32),
    ), jax.tree.map(lambda s: s.sharding, state_shape))
    print(f"[train] {cfg.name}: {pretty_count(tree_size(params))} params, "
          f"plan={plan}, {device_summary()}")
    attention_backend = None
    if cfg.attention.kind == "flow":
        from repro import attention
        from repro.layers.attention import plan_of

        xplan = plan_of(cfg, needs_grad=True).with_shapes(
            training_shapes(cfg, shape))
        attention_backend = attention.resolve_for_training(xplan).name
        print(f"[train] attention {xplan.describe()} -> {attention_backend}")

    start_step = 0
    mgr = None
    if ckpt_dir:
        mgr = CheckpointManager(ckpt_dir, keep=3)
        restored = mgr.restore_latest(state)
        if restored is not None:
            start_step, state, extra = restored
            print(f"[train] resumed from step {start_step}")

    loader = lm_loader(seed, batch=batch, seq=seq, vocab=cfg.vocab_size,
                       start_step=start_step)
    monitor = StepMonitor()
    history, grad_norms = [], []
    t_start = time.time()
    for step in range(start_step, steps):
        batch_np = next(loader)
        monitor.start()
        state, metrics = jit_step(state, jax.tree.map(jnp.asarray, batch_np))
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        dt = monitor.stop(step)
        history.append(loss)
        grad_norms.append(gnorm)
        if step % log_every == 0 or step == steps - 1:
            print(f"  step {step:5d} loss={loss:.4f} "
                  f"ppl={float(metrics['ppl']):.2f} "
                  f"gnorm={gnorm:.3f} {dt*1000:.0f}ms")
        if mgr and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, state, extra=loader.state(), async_=True)
    if mgr:
        mgr.save(steps, state, extra=loader.state())
        mgr.wait()
    return {"history": history, "grad_norms": grad_norms,
            "final_loss": history[-1] if history else None,
            "wall_s": time.time() - t_start, "state": state,
            "attention_backend": attention_backend}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="flowformer-lm")
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--attn", default=None, help="override attention kind")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.attn:
        cfg = dataclasses.replace(
            cfg, attention=dataclasses.replace(cfg.attention, kind=args.attn)
        )
    out = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    print(f"[train] done: final_loss={out['final_loss']:.4f} "
          f"({out['wall_s']:.1f}s)")


if __name__ == "__main__":
    main()
