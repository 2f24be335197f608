"""Device ms per train step in ops under the program's
``step.head.fused`` scope, a part of ``step.head``: the vocab head's
fused projection and cross-entropy kernels and the backward's matmuls
they feed, forward and backward; nothing where the head runs unfused."""
from bench import scopes


def read(ctx):
    return scopes.scope_reading(ctx, "step.head.fused")
