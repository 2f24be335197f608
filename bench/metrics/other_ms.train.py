"""Device ms per train step outside the mixer, FFN, head and optimizer
scopes: ``step.embed`` and ``step.norm`` ops, residual adds, the layer
scan's slices and copies and casts (``unscoped``), ops XLA inserted with
no metadata (``no_metadata``), ops not joined to the compiled step
(``unjoined``), and time inside the step with no op running (``gaps``)."""
from bench import scopes


def read(ctx):
    return scopes.reading(ctx, "other")
