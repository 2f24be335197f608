"""Tokens trained per second in the window times the model FLOPs of a token
(forward and backward, no recompute) over the bf16 peak, in %."""
from bench import counts


def read(ctx):
    if ctx["kind"] != "train":
        return None
    rate = ctx["tokens"] / ctx["window_s"]
    return {"value": 100.0 * rate * counts.train_token_flops(ctx["model"])
            / ctx["peaks"]["bf16_flops"]}
