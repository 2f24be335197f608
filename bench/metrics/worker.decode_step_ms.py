"""Median host time of the ``Engine.step`` calls that only decoded
(harness clock)."""
from bench.readers import median_or_none, window_steps


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    v = median_or_none(b - a for a, b, adm, dec, _ in window_steps(ctx)
                       if dec and not adm)
    return None if v is None else {"value": 1e3 * v}
