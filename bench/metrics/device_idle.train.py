"""Share of the traced training window in which no op ran on the device."""
from bench.readers import idle


def read(ctx):
    return idle(ctx) if ctx["kind"] == "train" else None
