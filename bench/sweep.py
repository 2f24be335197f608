"""Find a serving cell's knee: its traffic at several offered rates, in one
process, one window each.

    python3 bench/sweep.py --workload <cell> --rates 2 3 4 5 --seconds 30

For each rate it prints one JSON line: the end-to-end metrics, how many
requests were due, how many were still queued when the window closed, and
the median queue wait.  The knee is the highest rate at which the queue
does not grow through the window; a cell offers a fixed share of it (its
traffic file's ``rate_per_s``).  The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, stats, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    mix = traffic.load_mix(cell["traffic"])
    devices = harness.require_chips(cell["chips"])
    harness.configure_cache()

    from bench.drivers import serve

    run = harness.Run(cell=cell, config=harness.load_json(ROOT / conf["file"]),
                      mix=mix, limits={}, seed=args.seed,
                      seconds=args.seconds, trace_dir=None, t0=T0,
                      devices=devices)
    cfg, _, engine = serve.build(run)
    serve.warm(engine, mix)
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate, drain_s=0.0)
        schedule = traffic.serving_schedule(m, args.seed, args.seconds,
                                            cfg.vocab_size)
        reqs, logs, steps, window_s, drain_end = serve.window(
            engine, schedule, args.seconds, 0.0)
        waits = [r.admitted_at - r.due for r in logs
                 if r.admitted_at is not None]
        queued = sum(1 for r in logs if r.admitted_at is None)
        print(json.dumps({
            "rate_per_s": rate, "due": len(schedule),
            "queued_at_close": queued,
            "queue_wait_median_ms": 1e3 * statistics.median(waits)
            if waits else None,
            **stats.serving_metrics(logs, window_s, drain_end)}), flush=True)
        # let the engine finish what is left before the next rate
        while engine.step() or engine.queue:
            pass
        engine.take_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
