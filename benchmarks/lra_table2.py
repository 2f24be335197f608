"""Table 2 — Long-Range Arena stand-in: ListOps + byte-text-style pixel
sequences, flow vs softmax vs linear vs the two paper ablations
(w/o competition, w/o allocation).  Real LRA data is unavailable offline;
synthetic tasks preserve the comparisons (DESIGN.md §8)."""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import print_table, save_table, train_eval_classifier, with_kind
from repro.configs import get_config
from repro.data.synthetic import LISTOPS_VOCAB, PAD, listops, pixel_images
from repro.models import classifier


def run(*, quick: bool = True) -> dict:
    n_train, n_eval, steps, seq = (
        (500, 150, 70, 96) if quick else (20000, 2000, 3000, 512)
    )
    base = get_config("flowformer_lra")
    base = dataclasses.replace(base, n_layers=2, d_model=96, n_heads=4,
                               n_kv_heads=4, d_ff=192,
                               vocab_size=LISTOPS_VOCAB)

    variants = {
        "flowformer": with_kind(base, "flow"),
        "flowformer w/o competition": with_kind(base, "flow",
                                                use_competition=False),
        "flowformer w/o allocation": with_kind(base, "flow",
                                               use_allocation=False),
        "transformer (softmax)": with_kind(base, "softmax"),
        "linear transformer": with_kind(base, "linear"),
    }

    rows = {}
    # --- ListOps ---
    xs, ys = listops(0, n_train + n_eval, seq=seq, depth=3, max_args=4)
    mask = (xs != PAD).astype(np.float32)
    tr = {"inputs": xs[:n_train], "labels": ys[:n_train],
          "mask": mask[:n_train]}
    ev = {"inputs": xs[n_train:], "labels": ys[n_train:],
          "mask": mask[n_train:]}
    for name, cfg in variants.items():
        res = train_eval_classifier(
            cfg,
            lambda k, cfg=cfg: classifier.init(k, cfg, n_classes=10),
            lambda p, b, cfg=cfg: classifier.loss_fn(p, b, cfg),
            tr, ev, steps=steps, batch=32,
        )
        rows.setdefault(name, {})["listops"] = res["acc"]

    # --- Image (pixel sequences) ---
    size = 16 if quick else 32
    xs2, ys2 = pixel_images(1, n_train + n_eval, size=size, n_classes=10)
    seqs = xs2.reshape(len(xs2), size * size, 1)
    tr = {"inputs": seqs[:n_train], "labels": ys2[:n_train]}
    ev = {"inputs": seqs[n_train:], "labels": ys2[n_train:]}
    for name, cfg in variants.items():
        res = train_eval_classifier(
            cfg,
            lambda k, cfg=cfg: classifier.init(k, cfg, n_classes=10, in_dim=1),
            lambda p, b, cfg=cfg: classifier.loss_fn(p, b, cfg),
            tr, ev, steps=steps, batch=32,
        )
        rows[name]["image"] = res["acc"]

    for name in rows:
        rows[name]["avg"] = float(np.mean(list(rows[name].values())))
    print_table("Table 2 (LRA stand-in): accuracy", rows,
                ["listops", "image", "avg"])
    save_table("lra_table2", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
