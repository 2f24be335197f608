"""Readings that set a cell's limits: the program's, the control's and the
faults', on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 20

Serving cells: each seed runs the cell's traffic at its own load for a
short window, drains, and over the same seeded sample of finished requests
reads ``served_token_gap`` twice: for the served tokens (the program), and
for the tokens the reference computed in float8 puts first at every
position of the same prompts and served tokens (the control).

Training cells: each seed reads the program's first three steps, the
float8 reference put in the program's place (the control), and the
program with half of each batch left out (a fault), all against the fp32
reference, and judges each with the cell's limits as a run would
(``<reading>_correct``).  A step that returns its state unchanged reads
``update_gap`` = 1 by construction and needs no run.

One JSON line per seed on standard output.  The benchmark's own runs never
run this.  Exits non-zero without a TPU, as ``run.py`` does.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402

QUANT = "fp8"


def serve_readings(run: harness.Run, seeds, seconds: float):
    """Program and control ``served_token_gap`` for each seed."""
    import numpy as np

    from bench.drivers import serve
    from bench.weights import make_weights

    import jax

    cfg, params, engine = serve.build(run)
    serve.warm(engine, run.mix)
    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          params)
    for seed in seeds:
        engine.worker.params = params = None  # one set of weights at a time
        params = make_weights(shapes, seed, device=run.devices[0])
        engine.worker.params = params
        schedule = traffic.serving_schedule(run.mix, seed, seconds,
                                            cfg.vocab_size)
        reqs, logs, steps, window_s, drain_end = serve.window(
            engine, schedule, seconds, run.mix.get("drain_s", 60.0))
        sample = serve.sample_finished(reqs, seed,
                                       run.mix["check"]["requests"])
        prog = serve.token_gaps(params, run.config, sample)
        ctrl = serve.token_gaps(params, run.config, sample, quant=QUANT)
        yield {"seed": seed, "requests": len(sample),
               "tokens": int(sum(len(g) for g in prog)),
               "program": float(max(g.max() for g in prog)),
               "control": float(max(g.max() for g in ctrl)),
               "control_median_token": float(np.median(np.concatenate(ctrl))),
               "program_median_token": float(np.median(np.concatenate(prog)))}


def judged(run: harness.Run, got: dict) -> bool:
    """``correct`` as a run of the cell would give it for these readings:
    ``harness.judge`` over the numbers that the cell's limits name."""
    return harness.judge({k: (got[k], lim["limit"])
                          for k, lim in run.limits.items()})


def train_readings(run: harness.Run, seeds):
    """Program, control and half-batch readings for each seed."""
    import jax

    from bench.drivers import train

    b1 = run.mix["optimizer"]["b1"]
    for seed in seeds:
        run.seed = seed
        cfg, jit_step, state, state_shape, batches = train.build(run)
        p0 = jax.device_get(state.master)
        half = [jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
                for b in batches]
        state2 = jax.tree.map(lambda x: x.copy(), state)
        _, prog = train.first_steps(jit_step, state, batches)
        _, halfp = train.first_steps(jit_step, state2, half)
        del state, state2
        ref = train.reference(run, p0, batches)
        ctrl_ref = train.reference(run, p0, batches, quant=QUANT)
        ctrl = {"p0": p0, "p3": ctrl_ref["p3"], "losses": ctrl_ref["losses"],
                "m1": jax.tree.map(lambda g: g * (1.0 - b1), ctrl_ref["g1"])}
        row = {"seed": seed, "losses": prog["losses"],
               "ref_losses": ref["losses"]}
        for name, got in (("program", prog), ("control", ctrl),
                          ("half_batch", halfp)):
            row[name] = train.readings(got, ref, b1)
            row[f"{name}_correct"] = judged(run, row[name])
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    config = harness.load_json(ROOT / conf["file"])
    harness.reference(config)  # a missing reference fails before the chip
    devices = harness.require_chips(cell["chips"])
    harness.configure_cache()
    run = harness.Run(cell=cell, config=config,
                      mix=mix, limits=harness.load_json(
                          harness.BENCH / "limits" / f"{cell['name']}.json"),
                      seed=args.seeds[0],
                      seconds=args.seconds, trace_dir=None, t0=T0,
                      devices=devices)
    rows = (serve_readings(run, args.seeds, args.seconds)
            if mix["driver"] == "serve" else train_readings(run, args.seeds))
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
