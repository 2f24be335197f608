"""Serving cells: the program's ``Engine`` under an open-loop schedule.

Set-up builds the engine as ``launch/serve.py`` builds it (fp32 weights as
initialised, bf16 activations, one packed-admission plan), warms every
program the cell's traffic can reach — the decode step and each
``(rows, bucket)`` packed prefill, because the worker compiles one per
admitted row count — and then submits each request when it is due and
calls ``Engine.step`` in a loop for the window.  After the window it stops
submitting, drains what was due, and checks a seeded sample of finished
requests against the plain reference.
"""
from __future__ import annotations

import concurrent.futures
import os
import time

import numpy as np

from bench import harness, stats, traffic

EXPECTED_BACKENDS = {"prefill_packed": "pallas_fused",
                     "decode": "pallas_decode"}


def build(run: harness.Run, params=None):
    """The engine the window drives, on the weights from the seed."""
    import jax
    import jax.numpy as jnp

    from repro.layers.attention import plan_of
    from repro.models import lm
    from repro.serving.engine import Engine

    from bench.weights import make_weights

    cfg = harness.model_config(run.config["model"])
    if params is None:
        shapes = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
        params = make_weights(shapes, run.seed, device=run.devices[0])
    engine = Engine(params, cfg, slots=run.mix["slots"],
                    max_len=run.mix["max_len"],
                    plan=plan_of(cfg, packed=True), dtype=jnp.bfloat16)
    if run.devices[0].platform == "tpu":  # the kernels the cell measures
        got = resolved_backends(engine.worker, cfg, run.mix)
        if got != EXPECTED_BACKENDS:
            raise RuntimeError(f"serving resolved {got}, the cell measures "
                               f"{EXPECTED_BACKENDS}")
    return cfg, params, engine


def resolved_backends(worker, cfg, mix) -> dict:
    """The attention backend each serving op binds under the worker's plan."""
    from repro import attention

    ex = attention.resolve(worker.plan)
    d = cfg.dim_head

    def shapes(n):
        return attention.ShapeInfo(b=worker.slots, hq=cfg.n_heads,
                                   hkv=cfg.kv_heads, n=n, m=n, d=d, dv=d)

    return {"prefill_packed": ex.backend(
                "prefill_packed", shapes(mix["prompt"]["max"])).name,
            "decode": ex.backend("decode", shapes(1)).name}


def prefill_grid(mix: dict) -> list[tuple[int, int]]:
    """Every ``(rows, bucket)`` packed-prefill shape the mix can reach."""
    from repro.serving.worker import _bucket_len

    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    buckets = sorted({_bucket_len(n, mix["max_len"])
                      for n in range(lo, hi + 1)})
    return [(r, b) for b in buckets for r in range(1, mix["slots"] + 1)]


def warm(engine, mix: dict):
    """Compile every program of the cell (from several threads), then run
    each once, so the window traces and compiles nothing."""
    import jax
    import jax.numpy as jnp

    w = engine.worker
    sds = jax.ShapeDtypeStruct
    s = w.slots
    grid = prefill_grid(mix)

    def lower_prefill(r, lb):
        return w._prefill.lower(
            w.params, sds((r, lb), jnp.int32), sds((r,), jnp.int32),
            sds((r,), jnp.int32), w.caches, None, None,
            sds((r,), jnp.float32), w._key, 1).compile()

    def lower_step():
        return w._step.lower(
            w.params, sds((s, 1), jnp.int32), w.caches, sds((s,), jnp.int32),
            None, sds((s,), jnp.float32), sds((s,), jnp.bool_), w._key,
            1).compile()

    jobs = [lower_step] + [lambda r=r, b=b: lower_prefill(r, b)
                           for r, b in grid]
    with concurrent.futures.ThreadPoolExecutor(
            min(16, os.cpu_count() or 1)) as ex:
        for f in [ex.submit(j) for j in jobs]:
            f.result()
    for r, lb in grid:
        w.prefill([np.zeros(lb, np.int32)] * r, list(range(r)),
                  np.zeros(r, np.float32))
    live = np.zeros(s, bool)
    for _ in range(2):
        w.step(np.zeros(s, np.int32), np.zeros(s, np.int32),
               np.zeros(s, np.float32), live)
    return len(grid) + 1


def window(engine, schedule, seconds: float, drain_s: float,
           counter: harness.CompileCounter | None = None,
           tracer: harness.TailTrace | None = None):
    """Drive the engine on the schedule for ``seconds``, then drain.

    Returns (request logs, step records, window length, drain end), times
    relative to the window's start.  Each step record is
    ``(start, end, admitted, decoded, prompt_tokens_admitted)``.
    """
    from repro.serving.engine import Request

    reqs = [Request(uid=a.uid, prompt=a.prompt,
                    max_new_tokens=a.max_new_tokens, temperature=0.0)
            for a in schedule]
    logs = [stats.RequestLog(due=a.due) for a in schedule]
    seen = [0] * len(reqs)
    inflight: list[int] = []
    steps = []
    nxt = 0
    clock = time.perf_counter

    def submit_due(now):
        nonlocal nxt
        while nxt < len(schedule) and schedule[nxt].due <= now:
            engine.submit(reqs[nxt])
            inflight.append(nxt)
            nxt += 1

    def one_step(t_a):
        with harness.span("bench.engine_step"):
            decoded = engine.step()
        t_b = clock() - t0
        admitted = ptoks = 0
        keep = []
        for i in inflight:
            r = reqs[i]
            got = len(r.generated) - seen[i]
            if got:
                if seen[i] == 0:
                    admitted += 1
                    ptoks += len(r.prompt)
                    logs[i].admitted_at = t_a
                logs[i].token_times.extend([t_b] * got)
                seen[i] = len(r.generated)
            if not r.done:
                keep.append(i)
        inflight[:] = keep
        steps.append((t_a, t_b, admitted, decoded, ptoks))

    if counter is not None:
        counter.counting = True
    t0 = clock()
    with harness.span("bench.window"):
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            if tracer is not None:
                tracer.poll(now)
            submit_due(now)
            if not inflight:
                wake = min(schedule[nxt].due if nxt < len(schedule)
                           else seconds, seconds)
                if tracer is not None and tracer.started_at is None:
                    wake = min(wake, tracer.start_at)
                with harness.span("bench.wait_for_arrival"):
                    time.sleep(max(0.0, wake - now))
                continue
            one_step(now)
    window_s = max(clock() - t0, seconds)
    if counter is not None:
        counter.counting = False
    if tracer is not None:
        tracer.stop()
    submit_due(seconds * (1 - 1e-12))
    with harness.span("bench.drain"):
        while inflight and clock() - t0 < window_s + drain_s:
            one_step(clock() - t0)
    drain_end = clock() - t0
    return reqs, logs, steps, window_s, drain_end


def step_summary(steps, logs, window_s: float) -> str:
    """One line on the window's steps, for the run's log: how many
    admitted and how long they and the decode-only steps took (median and
    worst, ms), and the longest wait from due to admission."""
    def ms(xs):
        xs = sorted(xs)
        return (f"{len(xs)} x {1e3 * xs[len(xs) // 2]:.1f}/"
                f"{1e3 * xs[-1]:.1f} ms" if xs else "none")

    inw = [s for s in steps if s[0] < window_s]
    waits = [r.admitted_at - r.due for r in logs
             if r.admitted_at is not None and r.due < window_s]
    return (f"admitting steps {ms([b - a for a, b, n, _, _ in inw if n])}, "
            f"decode-only steps {ms([b - a for a, b, n, d, _ in inw if d and not n])}, "
            f"longest queue wait {1e3 * max(waits, default=0.0):.1f} ms")


def sample_finished(reqs, seed: int, n: int) -> list:
    """A seeded sample of ``n`` finished requests, the longest among them."""
    done = [r for r in reqs if r.done and r.generated]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.generated))
    rest = [r for r in done if r is not longest]
    rng = traffic.rng_of(seed, 2)
    pick = rng.permutation(len(rest))[: max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def token_gaps(params, config: dict, sample, quant=None, pad: int = 1024):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best at that position, as a
    share of the largest |logit| there.  The reference is the one the
    configuration file ``config`` names.

    With ``quant`` set the reference also runs in that lower precision
    (the control), and the gap read is that of the token the lower
    precision puts first, at the same positions: those whose served token
    the program's gap reads."""
    import jax.numpy as jnp

    ref_lm, model = harness.reference(config), config["model"]
    out = []
    for r in sample:
        gen = np.asarray(r.generated, np.int32)
        seq = np.concatenate([r.prompt, gen[:-1]]).astype(np.int32)
        n = -(-len(seq) // pad) * pad
        toks = np.zeros((1, n), np.int32)
        toks[0, : len(seq)] = seq
        start = len(r.prompt) - 1
        rows = slice(start, start + len(gen))
        ref = ref_lm.forward(params, jnp.asarray(toks), model)[0, rows]
        if quant is None:
            picked = jnp.asarray(gen)
        else:
            low = ref_lm.forward(params, jnp.asarray(toks), model,
                                 quant=quant)[0, rows]
            picked = jnp.argmax(low, axis=-1)
        best = ref.max(axis=-1)
        got = jnp.take_along_axis(ref, picked[:, None], axis=-1)[:, 0]
        out.append(np.asarray((best - got) / jnp.abs(ref).max(axis=-1)))
    return out


def run(r: harness.Run) -> harness.Outcome:
    """One run of a serving cell."""
    mix = r.mix
    counter = harness.CompileCounter()
    cfg, params, engine = build(r)
    schedule = traffic.serving_schedule(mix, r.seed, r.seconds,
                                        cfg.vocab_size)
    n_programs = warm(engine, mix)
    harness.log(f"warmed {n_programs} programs; {len(schedule)} requests "
                f"due in {r.seconds}s")
    setup_s = time.perf_counter() - r.t0
    tracer = harness.TailTrace(r.trace_dir, r.seconds)
    with harness.no_gc():
        reqs, logs, steps, window_s, drain_end = window(
            engine, schedule, r.seconds, mix.get("drain_s", 60.0), counter,
            tracer)
    peak = harness.memory_peak(r.devices[0])
    e2e = stats.serving_metrics(logs, window_s, drain_end)
    harness.log(f"window {window_s:.3f}s, {len(steps)} steps, drained at "
                f"{drain_end:.3f}s: {e2e}")
    harness.log(step_summary(steps, logs, window_s))
    sample = sample_finished(reqs, r.seed, mix["check"]["requests"])
    del engine
    gaps = token_gaps(params, r.config, sample)
    worst = float(max((g.max() for g in gaps), default=float("nan")))
    served = sum(len(g) for g in gaps)
    harness.log(f"checked {len(sample)} requests, {served} served tokens")
    due = [i for i, a in enumerate(schedule) if a.due < window_s]
    failed = sum(1 for i in due if not logs[i].token_times)
    return harness.Outcome(
        e2e=e2e,
        checks={"served_token_gap": (worst,
                                     r.limits["served_token_gap"]["limit"])},
        attempted=len(due), failed=failed, setup_s=setup_s,
        memory_peak_bytes=peak,
        complete=served >= mix["check"].get("min_tokens", 1),
        ctx={"kind": "serve", "steps": steps, "logs": logs,
             "window_s": window_s, "compiles": counter.count,
             "traced_from": tracer.started_at,
             "model": r.config["model"], "mix": mix,
             "checked_tokens": served})
