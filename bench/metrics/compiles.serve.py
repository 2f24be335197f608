"""Programs JAX lowered for compilation inside a serving window (its own
monitoring events; a persistent-cache load counts too).  0 when every
shape was warmed."""


def read(ctx):
    return {"value": ctx["compiles"]} if ctx["kind"] == "serve" else None
