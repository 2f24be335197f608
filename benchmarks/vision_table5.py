"""Table 5 — image recognition with the paper's hierarchical Flowformer
(synthetic textures stand in for ImageNet-1K)."""
from __future__ import annotations

import dataclasses

from benchmarks.common import print_table, save_table, train_eval_classifier, with_kind
from repro.configs import get_config
from repro.data.synthetic import pixel_images
from repro.models import vision


def run(*, quick: bool = True) -> dict:
    n_train, n_eval, steps, size = (
        (400, 120, 60, 32) if quick else (20000, 2000, 2000, 64)
    )
    base = get_config("flowformer_vision")
    base = dataclasses.replace(
        base, stage_layers=(1, 1, 2, 1), stage_channels=(32, 64, 96, 128),
        n_heads=4, n_classes=10,
    )
    xs, ys = pixel_images(0, n_train + n_eval, size=size, n_classes=10,
                          channels=3)
    tr = {"images": xs[:n_train], "labels": ys[:n_train]}
    ev = {"images": xs[n_train:], "labels": ys[n_train:]}
    rows = {}
    for kind in ("flow", "softmax", "linear"):
        cfg = with_kind(base, kind, strict_causal=False)
        res = train_eval_classifier(
            cfg,
            lambda k, cfg=cfg: vision.init(k, cfg),
            lambda p, b, cfg=cfg: vision.loss_fn(p, b, cfg),
            tr, ev, steps=steps, batch=32,
        )
        rows[f"hierarchical-{kind}"] = {"top1": res["acc"],
                                        "steps_per_s": res["steps_per_s"]}
    print_table("Table 5 (vision stand-in): top-1", rows,
                ["top1", "steps_per_s"])
    save_table("vision_table5", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
