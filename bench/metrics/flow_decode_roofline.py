"""Roofline share of the batched Flow-Attention decode kernel: the least
time its executions in the decode program could take (from their shapes)
over the time they took, in %."""
from bench.readers import DECODE, roofline


def read(ctx):
    return roofline(ctx, DECODE) if ctx["kind"] == "serve" else None
