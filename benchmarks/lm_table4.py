"""Table 4 — causal language modeling perplexity (WikiText-103 stand-in:
Zipfian text with copy structure).  Exercises the CAUSAL Flow-Attention,
including the competition/allocation ablations of the paper."""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import print_table, save_table, with_kind
from repro.configs import get_config
from repro.launch.train import train


def run(*, quick: bool = True) -> dict:
    steps, batch, seq = (60, 6, 96) if quick else (2000, 16, 512)
    base = get_config("flowformer_lm")
    base = dataclasses.replace(base, n_layers=2, d_model=128, n_heads=4,
                               n_kv_heads=4, d_ff=512, vocab_size=2048)
    variants = {
        "flowformer": with_kind(base, "flow"),
        "flowformer (paper-faithful causal)": with_kind(
            base, "flow", strict_causal=False),
        "flowformer w/o competition": with_kind(base, "flow",
                                                use_competition=False),
        "flowformer w/o allocation": with_kind(base, "flow",
                                               use_allocation=False),
        "transformer (softmax)": with_kind(base, "softmax"),
        "linear transformer": with_kind(base, "linear"),
    }
    rows = {}
    for name, cfg in variants.items():
        out = train(cfg, steps=steps, batch=batch, seq=seq, log_every=10**9)
        tail = out["history"][-max(3, steps // 20):]
        ce = float(np.mean(tail))
        rows[name] = {"loss": ce, "ppl": float(np.exp(min(ce, 20.0)))}
    print_table("Table 4 (LM stand-in): perplexity (lower=better)", rows,
                ["loss", "ppl"])
    save_table("lm_table4", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
