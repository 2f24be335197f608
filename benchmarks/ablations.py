"""Tables 10 & 11 — phi activation ablation and competition/allocation
activation-function choices, on the ListOps stand-in."""
from __future__ import annotations

import dataclasses

from benchmarks.common import print_table, save_table, train_eval_classifier, with_kind
from repro.configs import get_config
from repro.data.synthetic import LISTOPS_VOCAB, PAD, listops
from repro.models import classifier


def run(*, quick: bool = True) -> dict:
    n_train, n_eval, steps, seq = (
        (400, 120, 70, 96) if quick else (20000, 2000, 3000, 512)
    )
    base = get_config("flowformer_lra")
    base = dataclasses.replace(base, n_layers=2, d_model=96, n_heads=4,
                               n_kv_heads=4, d_ff=192,
                               vocab_size=LISTOPS_VOCAB)
    xs, ys = listops(42, n_train + n_eval, seq=seq, depth=3, max_args=4)
    import numpy as np

    mask = (xs != PAD).astype(np.float32)
    tr = {"inputs": xs[:n_train], "labels": ys[:n_train], "mask": mask[:n_train]}
    ev = {"inputs": xs[n_train:], "labels": ys[n_train:], "mask": mask[n_train:]}

    rows = {}
    # Table 10: phi in {sigmoid, elu1, relu}
    for phi in ("sigmoid", "elu1", "relu"):
        cfg = with_kind(base, "flow", phi=phi)
        res = train_eval_classifier(
            cfg,
            lambda k, cfg=cfg: classifier.init(k, cfg, n_classes=10),
            lambda p, b, cfg=cfg: classifier.loss_fn(p, b, cfg),
            tr, ev, steps=steps, batch=32,
        )
        rows[f"phi={phi}"] = {"listops_acc": res["acc"]}
    print_table("Table 10 (phi ablation)", rows, ["listops_acc"])
    save_table("ablations", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
