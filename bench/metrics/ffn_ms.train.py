"""Device ms per train step in ops under the program's ``step.ffn``
scope, forward and backward."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "ffn")
