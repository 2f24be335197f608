"""Device ms per train step in ops under the program's ``step.mixer``
scope (attention, whatever its backend), forward and backward."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "mixer")
