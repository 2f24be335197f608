"""Benchmark orchestrator — one harness per paper table (deliverable d).

    PYTHONPATH=src python -m benchmarks.run [--full] [--only t2,t3,...]

Tables: t2 LRA, t3 efficiency, t4 LM, t5 vision, t6 time series, t7 RL,
ablations (Tab. 10/11), roofline (from dry-run artifacts, if present).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

RESULTS = pathlib.Path(__file__).resolve().parent.parent / "results"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-protocol sizes (hours); default quick sizes")
    ap.add_argument("--only", default="",
                    help="comma list: t2,t3,t4,t5,t6,t7,ablations,roofline")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    quick = not args.full
    only = set(filter(None, args.only.split(",")))

    def want(tag: str) -> bool:
        return not only or tag in only

    t_start = time.time()
    summary = {}

    if want("t2"):
        from benchmarks import lra_table2
        summary["t2"] = lra_table2.run(quick=quick)
    if want("t3"):
        from benchmarks import efficiency_table3
        summary["t3"] = efficiency_table3.run(quick=quick)
    if want("t4"):
        from benchmarks import lm_table4
        summary["t4"] = lm_table4.run(quick=quick)
    if want("t5"):
        from benchmarks import vision_table5
        summary["t5"] = vision_table5.run(quick=quick)
    if want("t6"):
        from benchmarks import timeseries_table6
        summary["t6"] = timeseries_table6.run(quick=quick)
    if want("t7"):
        from benchmarks import rl_table7
        summary["t7"] = rl_table7.run(quick=quick)
    if want("ablations"):
        from benchmarks import ablations
        summary["ablations"] = ablations.run(quick=quick)
    if want("roofline"):
        dry = RESULTS / "dryrun.json"
        if dry.exists():
            import subprocess
            subprocess.run([sys.executable, "-m", "benchmarks.roofline"],
                           check=False)
        else:
            print("[roofline] skipped: run repro.launch.dryrun first")

    (RESULTS / "bench_summary.json").write_text(json.dumps(summary, indent=1))
    print(f"\n[benchmarks] done in {time.time() - t_start:.0f}s "
          f"-> {RESULTS}/bench_*.json")


if __name__ == "__main__":
    main()
