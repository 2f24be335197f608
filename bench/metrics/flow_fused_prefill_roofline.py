"""Roofline share of the fused Flow-Attention forward kernel inside the
packed-prefill programs, in %."""
from bench.readers import PREFILL, roofline


def read(ctx):
    return roofline(ctx, PREFILL) if ctx["kind"] == "serve" else None
