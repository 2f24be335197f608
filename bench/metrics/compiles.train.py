"""Programs JAX lowered for compilation inside a training window."""


def read(ctx):
    return {"value": ctx["compiles"]} if ctx["kind"] == "train" else None
