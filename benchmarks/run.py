"""Benchmark orchestrator — one section per paper table/figure plus the
framework-level benches.

  PYTHONPATH=src python -m benchmarks.run            # full
  PYTHONPATH=src python -m benchmarks.run --quick    # reduced sizes
  PYTHONPATH=src python -m benchmarks.run --only table1,roofline

Every suite that returns a payload gets it persisted as BENCH_<name>.json
in the repo root (refine_bench also writes its own file directly so the
CI bench-smoke job tracks it standalone), so the perf trajectory is
machine-readable across PRs.
"""
from __future__ import annotations

import argparse
import time
import traceback

from . import (baselines_compare, batch_study, distributed_bench,
               dynamics_bench, fig7_8_simtime, fig9_10_load_traces,
               kernel_bench, planner_bench, refine_bench, robustness_bench,
               roofline, sparse_bench, sweep_bench, table1_cost_frameworks,
               train_bench)
from .common import write_bench_json

SUITES = {
    "table1": table1_cost_frameworks.run,
    "batch": batch_study.run,
    "fig7_8": fig7_8_simtime.run,
    "fig9_10": fig9_10_load_traces.run,
    "baselines": baselines_compare.run,
    "planner": planner_bench.run,
    "kernel": kernel_bench.run,
    "train": train_bench.run,
    "roofline": roofline.run,
    "distributed": distributed_bench.run,
    "refine": refine_bench.run,
    "dynamics": dynamics_bench.run,
    "sweeps": sweep_bench.run,
    "sparse": sparse_bench.run,
    "robustness": robustness_bench.run,
}

# these write their BENCH_<name>.json themselves (they must also do so
# when invoked standalone by the CI smoke jobs)
_SELF_WRITING = {"refine", "dynamics", "sweeps", "sparse", "robustness"}

# these accept a telemetry dir and emit JSONL run logs (DESIGN.md §14)
_TELEMETRY = {"refine", "sweeps", "sparse", "distributed", "robustness"}


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="write per-suite telemetry JSONL run logs to DIR "
                         "(suites: " + ", ".join(sorted(_TELEMETRY)) + ")")
    args = ap.parse_args()
    names = list(SUITES) if not args.only else args.only.split(",")
    t0 = time.time()
    failures = []
    for name in names:
        t = time.time()
        try:
            kwargs = {"quick": args.quick}
            if args.telemetry and name in _TELEMETRY:
                kwargs["telemetry"] = args.telemetry
            payload = SUITES[name](**kwargs)
            if payload is not None and name not in _SELF_WRITING:
                write_bench_json(name, payload)
        except Exception:
            failures.append(name)
            print(f"[FAIL] suite {name}:")
            traceback.print_exc()
        print(f"[{name}: {time.time() - t:.1f}s]")
    print(f"\ntotal: {time.time() - t0:.1f}s; "
          f"{len(names) - len(failures)}/{len(names)} suites OK"
          + (f"; FAILED: {failures}" if failures else ""))
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
