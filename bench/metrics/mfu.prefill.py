"""Model FLOPs of the prompts prefilled in the traced stretch of the window
(real tokens, padding excluded) over the prefill programs' summed device
time there times the bf16 peak, in %."""
from bench import counts
from bench.readers import PREFILL, mfu, traced_steps


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    steps = traced_steps(ctx)
    tokens = sum(s[4] for s in steps)
    prompts = sum(s[2] for s in steps)
    return mfu(ctx, PREFILL, counts.prefill_flops(ctx["model"], tokens,
                                                  prompts))
