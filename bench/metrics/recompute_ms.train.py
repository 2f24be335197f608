"""Device ms per train step in ops that rematerialise forward values for
the backward pass (``rematted_computation``), whatever their scope, with
the part of each scope class as a field; it overlaps the other five
scope metrics."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "recompute")
