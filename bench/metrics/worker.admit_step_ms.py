"""Median host time of the ``Engine.step`` calls that admitted at least one
request: packed prefill, slot install and the decode of the same step,
each ending in the worker's token transfer (harness clock)."""
from bench.readers import median_or_none, window_steps


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    v = median_or_none(b - a for a, b, adm, _, _ in window_steps(ctx) if adm)
    return None if v is None else {"value": 1e3 * v}
