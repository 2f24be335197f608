"""Model FLOPs of the tokens decoded in the traced stretch of the window
(live slots only) over the decode program's summed device time there times
the bf16 peak, in %."""
from bench import counts
from bench.readers import DECODE, mfu, traced_steps


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    tokens = sum(dec for _, _, _, dec, _ in traced_steps(ctx))
    return mfu(ctx, DECODE, tokens * counts.decode_token_flops(ctx["model"]))
