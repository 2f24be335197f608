"""Device ms per train step in ops under the program's ``step.head``
scope: the vocabulary projection and its fp32 log-softmax and NLL,
forward and backward."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "head")
