"""Table 6 — UEA-style multivariate time-series classification."""
from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import print_table, save_table, train_eval_classifier, with_kind
from repro.configs import get_config
from repro.data.synthetic import timeseries
from repro.models import classifier


def run(*, quick: bool = True) -> dict:
    n_train, n_eval, steps, length = (
        (400, 120, 70, 96) if quick else (8000, 1000, 1500, 512)
    )
    base = get_config("flowformer_timeseries")
    base = dataclasses.replace(base, d_model=96, n_heads=4, n_kv_heads=4,
                               d_ff=192)
    rows = {}
    datasets = {"freqmix6": dict(dims=8, n_classes=6),
                "freqmix3-hd": dict(dims=24, n_classes=3)}
    for ds_name, kw in datasets.items():
        xs, ys = timeseries(hash(ds_name) % 2**31, n_train + n_eval,
                            length=length, **kw)
        tr = {"inputs": xs[:n_train], "labels": ys[:n_train]}
        ev = {"inputs": xs[n_train:], "labels": ys[n_train:]}
        for kind in ("flow", "softmax", "linear"):
            cfg = with_kind(base, kind, strict_causal=False)
            res = train_eval_classifier(
                cfg,
                lambda k, cfg=cfg, kw=kw: classifier.init(
                    k, cfg, n_classes=kw["n_classes"], in_dim=kw["dims"]),
                lambda p, b, cfg=cfg: classifier.loss_fn(p, b, cfg),
                tr, ev, steps=steps, batch=32,
            )
            rows.setdefault(kind, {})[ds_name] = res["acc"]
    for kind in rows:
        rows[kind]["avg"] = float(np.mean(list(rows[kind].values())))
    print_table("Table 6 (time series stand-in): accuracy", rows,
                list(datasets) + ["avg"])
    save_table("timeseries_table6", rows)
    return rows


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    run(quick="--full" not in sys.argv)
