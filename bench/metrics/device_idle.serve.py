"""Share of the traced serving window in which no op ran on the device."""
from bench.readers import idle


def read(ctx):
    return idle(ctx) if ctx["kind"] == "serve" else None
