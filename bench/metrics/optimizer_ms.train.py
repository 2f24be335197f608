"""Device ms per train step in ops under the program's ``step.optimizer``
scope: the learning rate, gradient clipping and the AdamW update."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "optimizer")
