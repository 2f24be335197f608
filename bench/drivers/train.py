"""Training cells: the program's jitted train step, back to back.

Set-up builds the step as ``launch/train.py`` builds it
(``launch.steps.build_train_step``, fused value-and-grad, AdamW on fp32
master weights, bf16 compute) with the job's schedule, places the state
made from the seed, and drives that same step object through its first
three steps on three different batches: those are the steps the reference
follows.  The window then keeps calling the same step, with up to
``AHEAD`` steps queued on the device, and ends with ``block_until_ready``.
A traced run hands the compiled step's text to the scope split
(``bench/scopes.py``) as ``step_hlo`` in its context.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import harness, traffic

CHECK_STEPS = 3
# steps the host may run ahead of the device: about six seconds of work
# at the cell's size, so that a pause of the host (a one-chip machine
# shares its cores) shorter than that leaves the device busy, as an
# asynchronous training loop keeps it
AHEAD = 6


def build(run: harness.Run):
    """(cfg, jit_step, state, device batches) for the cell, from the seed."""
    import jax
    import jax.numpy as jnp

    from repro.config import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import RunPlan, build_train_step
    from repro.models import lm
    from repro.training import optimizer as opt_lib
    from repro.training.train_state import TrainState

    from bench.weights import make_weights

    cfg = harness.model_config(run.config["model"])
    mix, opt = run.mix, run.mix["optimizer"]
    shape = ShapeSpec("bench", mix["seq"], mix["batch"], "train")
    mesh = make_mesh((1, 1), ("data", "model"), devices=run.devices[:1])
    jit_step, state_shape, _, _ = build_train_step(
        cfg, shape, mesh, RunPlan.choose(cfg, shape, mesh),
        train_overrides={"total_steps": opt["total_steps"],
                         "warmup": opt["warmup"], "peak_lr": opt["peak_lr"],
                         "grad_clip": opt["grad_clip"],
                         "weight_decay": opt["weight_decay"],
                         "fused_value_grad": True})
    shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    params = make_weights(shapes, run.seed, device=run.devices[0])
    state = jax.device_put(
        TrainState(master=params, opt=opt_lib.adamw_init(params),
                   step=jnp.zeros((), jnp.int32)),
        jax.tree.map(lambda s: s.sharding, state_shape))
    batches = [jax.device_put(b, run.devices[0])
               for b in traffic.train_batches(mix, run.seed, cfg.vocab_size)]
    return cfg, jit_step, state, state_shape, batches


def compile_step(jit_step, state_shape, batch, traced: bool) -> str | None:
    """Compile the step before its first call; return its optimized HLO
    text where the run is traced, for the scope split
    (``bench/scopes.py``), else None."""
    compiled = jit_step.lower(state_shape, batch).compile()
    return compiled.as_text() if traced else None


def first_steps(jit_step, state, batches):
    """Drive the step through its first ``CHECK_STEPS`` steps; return the
    state and the program's readings (host copies)."""
    import jax

    p0 = jax.device_get(state.master)
    losses = []
    m1 = None
    for t in range(CHECK_STEPS):
        state, met = jit_step(state, batches[t])
        losses.append(float(met["loss"]))
        if t == 0:
            m1 = jax.device_get(state.opt.m)
    p3 = jax.device_get(state.master)
    return state, {"p0": p0, "m1": m1, "p3": p3, "losses": losses}


def window(jit_step, state, batches, seconds: float, start: int,
           counter: harness.CompileCounter | None = None,
           tracer: harness.TailTrace | None = None):
    """Steps back to back for ``seconds``; returns (state, steps, window
    length, last loss)."""
    import jax

    queued = collections.deque()
    k = start
    # the longest time between two calls: a step's time while the queue is
    # full, more where the host paused
    gap, gap_at = 0.0, start
    if counter is not None:
        counter.counting = True
    with harness.no_gc():
        t0 = last = time.perf_counter()
        with harness.span("bench.window"):
            while True:
                if tracer is not None:
                    tracer.poll(time.perf_counter() - t0)
                with harness.span("bench.train_step"):
                    state, met = jit_step(state, batches[k % len(batches)])
                k += 1
                queued.append(met["loss"])
                if len(queued) > AHEAD:
                    queued.popleft().block_until_ready()
                now = time.perf_counter()
                if now - last > gap:
                    gap, gap_at = now - last, k
                last = now
                if now - t0 >= seconds:
                    break
            jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    if counter is not None:
        counter.counting = False
    if tracer is not None:
        tracer.stop()
    harness.log(f"longest time between two calls {gap:.3f}s, before step "
                f"{gap_at}")
    return state, k - start, window_s, float(queued[-1])


def leaf_norms(tree) -> list[float]:
    """The L2 norm of each leaf, in tree order."""
    import jax

    return [float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for x in jax.tree.leaves(tree)]


def readings(prog: dict, ref: dict, b1: float) -> dict:
    """The three compared numbers of a training cell.

    * ``loss_gap``: the largest |loss - reference loss| over the first
      three steps.
    * ``grad_gap``: the first step's clipped gradient as the optimizer got
      it (its first moment after one step over 1 - b1), by the worst leaf:
      |program norm - reference norm| over the larger of the reference
      leaf's norm and the median leaf's.
    * ``update_gap``: the change of the weights over the three steps, by
      the worst leaf in the same measure; leaves whose reference gradient
      is under a thousandth of the median leaf's (moved by round-off
      alone) are left out.
    """
    import jax

    g_prog = leaf_norms(jax.tree.map(lambda m: m / (1.0 - b1), prog["m1"]))
    g_ref = leaf_norms(ref["g1"])
    g_med = float(np.median(g_ref))
    d_prog = leaf_norms(jax.tree.map(lambda a, b: np.asarray(a) - b,
                                     prog["p3"], prog["p0"]))
    d_ref = leaf_norms(jax.tree.map(lambda a, b: np.asarray(a) - b,
                                    ref["p3"], prog["p0"]))
    d_med = float(np.median(d_ref))
    grad_gap = max(abs(a - b) / max(b, g_med) for a, b in zip(g_prog, g_ref))
    moved = [i for i, g in enumerate(g_ref) if g >= 1e-3 * g_med]
    update_gap = max(abs(d_prog[i] - d_ref[i]) / max(d_ref[i], d_med)
                     for i in moved)
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap}


def reference(run: harness.Run, p0, batches, quant=None) -> dict:
    """The first three steps of the plain reference the configuration
    names, from the same weights."""
    import jax
    import jax.numpy as jnp

    params = jax.device_put(jax.tree.map(jnp.asarray, p0), run.devices[0])
    losses, g1, p3 = harness.reference(run.config).adamw_steps(
        params, batches[:CHECK_STEPS], run.config["model"],
        run.mix["optimizer"], quant=quant)
    return {"losses": losses, "g1": jax.device_get(g1),
            "p3": jax.device_get(p3)}


def run(r: harness.Run) -> harness.Outcome:
    """One run of a training cell."""
    mix = r.mix
    counter = harness.CompileCounter()
    cfg, jit_step, state, state_shape, batches = build(r)
    step_hlo = compile_step(jit_step, state_shape, batches[0],
                            r.trace_dir is not None)
    state, prog = first_steps(jit_step, state, batches)
    harness.log(f"first steps: losses {prog['losses']}")
    setup_s = time.perf_counter() - r.t0
    state, n, window_s, last = window(
        jit_step, state, batches, r.seconds, CHECK_STEPS, counter,
        harness.TailTrace(r.trace_dir, r.seconds))
    peak = harness.memory_peak(r.devices[0])
    tokens = n * mix["batch"] * mix["seq"]
    e2e = {"train_tok_s": tokens / window_s}
    harness.log(f"window {window_s:.3f}s, {n} steps, last loss {last}: {e2e}")
    del state
    ref = reference(r, prog["p0"], batches)
    got = readings(prog, ref, mix["optimizer"]["b1"])
    harness.log(f"readings {got}")
    # a reading with no limit file entry has no upper reading to set one
    checks = {k: (got[k], lim["limit"]) for k, lim in r.limits.items()}
    return harness.Outcome(
        e2e=e2e, checks=checks, attempted=n, failed=0, setup_s=setup_s,
        memory_peak_bytes=peak,
        ctx={"kind": "train", "steps": n, "window_s": window_s,
             "tokens": tokens, "compiles": counter.count,
             "model": r.config["model"], "mix": mix, "step_hlo": step_hlo})
