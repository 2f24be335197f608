"""Arithmetic the per-layer metric readers share.

Each reader (``bench/metrics/<metric>.py``) gets the run's context: the
harness's records of the window and, in a traced run, the reduced trace
(``ctx["trace"]``, a ``bench.trace.Trace``).  A reader that finds nothing
to read returns None and the metric is left out of the result line.
"""
from __future__ import annotations

import statistics

from bench import counts, trace

# the jitted programs of the serving worker and of the train step
DECODE, PREFILL, TRAIN = "jit_step_fn", "jit_prefill_fn", "jit_train_step"
ACT_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def window_steps(ctx) -> list:
    """The engine steps that started inside the window."""
    return [s for s in ctx["steps"] if s[0] < ctx["window_s"]]


def traced_steps(ctx) -> list:
    """The engine steps that started inside the traced stretch of the
    window: those whose device time the trace holds."""
    t = ctx.get("traced_from")
    return [s for s in window_steps(ctx) if t is not None and s[0] >= t]


def median_or_none(values):
    """The median, or None for no values."""
    values = list(values)
    return statistics.median(values) if values else None


def mfu(ctx, module: str, flops: float):
    """``flops`` over the summed device time of ``module`` times the peak,
    in %; None without a trace or an execution."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    n, ns = trace.module_time_ns(tr, module)
    if not n or not ns or not flops:
        return None
    return {"value": 100.0 * flops / (ns * 1e-9 * ctx["peaks"]["bf16_flops"])}


def call_counts(op, chunk: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one Flow-Attention kernel execution, from the
    operand shapes in its HLO text."""
    shapes = trace.operand_shapes(op.text)
    (qdt, q), (_, _k), (_, v) = shapes[1], shapes[2], shapes[3]
    act = ACT_BYTES.get(qdt, 2)
    if len(q) == 3:  # decode: q (BH, G, D)
        return counts.flow_decode(q[0], q[1], q[2], v[-1], act)
    bh, g, n, d = q
    # the backward kernel reaches the HLO under the forward wrapper's name;
    # it is the call that also takes the state totals and cotangents
    if len(shapes) == 4:  # lens, q, k, v
        return counts.flow_fused_fwd(bh, g, n, d, v[-1], chunk, act)
    return counts.flow_fused_bwd(bh, g, n, d, v[-1], chunk, act)


def roofline(ctx, module: str):
    """Least time over measured time of the module's kernel executions,
    in %, with the bound that sets the least time of most of them."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    ops = trace.kernel_ops(tr, module)
    if not ops:
        return None
    chunk = ctx["model"].get("attention", {}).get("chunk_size", 128)
    least, took, bounds = 0.0, 0, {}
    for op in ops:
        t, bound = counts.least_time(*call_counts(op, chunk), ctx["peaks"])
        least += t
        took += op.dur
        bounds[bound] = bounds.get(bound, 0) + 1
    return {"value": 100.0 * least / (took * 1e-9),
            "bound": max(bounds, key=bounds.get), "calls": len(ops)}


def idle(ctx):
    """Share of the traced window in which no op ran on the device, in %."""
    tr = ctx.get("trace")
    if tr is None or trace.window_ns(tr) <= 0:
        return None
    return {"value": 100.0 * (1.0 - trace.busy_ns(tr) / trace.window_ns(tr))}
