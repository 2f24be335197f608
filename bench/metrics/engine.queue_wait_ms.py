"""Median wait of a request in the engine's queue: from when it was due to
the start of the ``Engine.step`` that admitted it (harness clock)."""
from bench.readers import median_or_none


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    w = ctx["window_s"]
    v = median_or_none(r.admitted_at - r.due for r in ctx["logs"]
                       if r.admitted_at is not None and r.admitted_at < w)
    return None if v is None else {"value": 1e3 * v}
