"""The control, kept at a size a test run holds: the reference computed in
float8 in the program's place reads past the cell's limit where the
program reads within it."""
import time

import jax

from bench import harness
from bench.tests.conftest import TINY
from bench.tests.test_faults import (SERVE_CELL, SERVE_MIX, TRAIN_CELL,
                                     TRAIN_MIX, TRAIN_MODEL)

control = harness.load_module(harness.BENCH / "control.py", "bench_control")


def _run(mix, model, cell):
    return harness.Run(cell={"name": cell},
                       config={"model": model, "reference": "flow_lm"},
                       mix=mix, limits={}, seed=3, seconds=1.5, trace_dir=None,
                       t0=time.perf_counter(), devices=jax.devices())


def _limits(cell):
    return {k: v["limit"] for k, v in harness.load_json(
        harness.BENCH / "limits" / f"{cell}.json").items()}


def test_serving_control_fails_where_the_program_passes():
    lim = _limits(SERVE_CELL)["served_token_gap"]
    row = next(control.serve_readings(_run(SERVE_MIX, TINY, SERVE_CELL),
                                      [2**33 + 1], 1.5))
    assert row["program"] <= lim < row["control"], row


def test_training_control_and_half_batch_fail():
    lim = _limits(TRAIN_CELL)
    row = next(control.train_readings(_run(TRAIN_MIX, TRAIN_MODEL,
                                           TRAIN_CELL), [5]))
    assert all(row["program"][k] <= lim[k] for k in lim), row
    for reading in ("control", "half_batch"):
        assert any(row[reading][k] > lim[k] for k in lim), row
