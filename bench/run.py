"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the entry of ``workloads`` in ``BENCHMARK.json`` named
``--workload``.  Its configuration file, its traffic file
(``bench/traffic/<traffic>.json``), the run loop of the traffic's kind
(``bench/drivers/<driver>.py``), its limits (``bench/limits/<cell>.json``),
the plain reference its configuration names (``bench/reference/<name>.py``)
and a reader per per-layer metric (``bench/metrics/<metric>.py``) are all
found by name; adding a cell, a mix or a metric adds files and entries.

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  Otherwise it makes its weights and inputs
from ``--seed``, warms every program the cell uses (set-up, ``setup_s``),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints the numbers compared, each beside its
limit, as its last lines on standard error.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, traffic  # noqa: E402


def find(entries: list, name: str, what: str) -> dict:
    """The entry of ``entries`` called ``name``."""
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: dict, key: str, cell: str) -> list:
    """The metrics of ``spec[key]`` that the cell reports."""
    return [m for m in spec[key] if cell in m.get("workloads", [cell])]


def per_layer(spec: dict, cell: str, ctx: dict) -> dict:
    """Each per-layer metric's reading, by its reader; those that find
    nothing are left out."""
    out = {}
    for m in cell_metrics(spec, "per_layer", cell):
        reader = harness.load_module(harness.BENCH / "metrics"
                                     / f"{m['name']}.py")
        got = reader.read(ctx)
        if got is not None:
            out[m["name"]] = {"value": got.pop("value"), "unit": m["unit"],
                              **got}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = find(spec["workloads"], args.workload, "workload")
    conf = find(spec["configs"], cell["config"], "config")
    mix = traffic.load_mix(cell["traffic"])
    limits = harness.load_json(harness.BENCH / "limits"
                               / f"{cell['name']}.json")
    config = harness.load_json(ROOT / conf["file"])
    harness.reference(config)  # a missing reference fails before the chip
    devices = harness.require_chips(cell["chips"])
    harness.configure_cache()

    from bench.peaks import peaks_of

    peaks = peaks_of(devices[0].device_kind)
    trace_dir = None
    if args.trace:
        trace_dir = harness.BENCH / ".runs" / f"trace-{cell['name']}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{mix['driver']}.py")
    out = driver.run(harness.Run(
        cell=cell, config=config, mix=mix,
        limits=limits, seed=args.seed, seconds=args.seconds,
        trace_dir=trace_dir, t0=T0, devices=devices))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": harness.judge(out.checks, out.complete),
              "attempted": out.attempted, "failed": out.failed}
    if args.trace:
        from bench import trace

        tr = trace.load(trace_dir)
        ctx = dict(out.ctx, trace=tr, peaks=peaks)
        result["metrics"] = per_layer(spec, cell["name"], ctx)
        device["busy_s"] = trace.busy_ns(tr) / 1e9
        device["window_s"] = trace.window_ns(tr) / 1e9
        result["device"] = device
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    else:
        vals = dict(out.e2e, setup_s=out.setup_s)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell_metrics(spec, "end_to_end",
                                                   cell["name"])}
        result["device"] = device
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in out.checks.items()}
    if not out.complete:  # an answer the check needed never came
        result["checks"]["answers_missing"] = {"value": 1, "limit": 0}
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
