"""Read the numbers that decide ``correct`` over many seeds in one process.

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 10 [--control]

Each seed runs the cell as ``bench.run`` does (set-up, a window of
``--seconds``, the float64 check), sharing one compilation across seeds.
``--control`` runs the configuration's ``control`` instead: the same
entry on the problem in the precision below the one the configuration
states, which the check has to find not correct.  One JSON line per
seed: the seed, ``correct``, ``attempted`` and each number with its
limit.  The limits in the configuration files were set from these
readings (``PERF.md``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

from bench import run


def control_cell(cell: run.Cell) -> run.Cell:
    over = {k: v for k, v in cell.config["control"].items() if k != "why"}
    return dataclasses.replace(cell, config=dict(cell.config, **over))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    opts = ap.parse_args(argv)
    cell = run.load_cell(opts.workload)
    if opts.control:
        cell = control_cell(cell)
    run.use_program()
    run.enable_compile_cache()
    for seed in (int(s) for s in opts.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            line = run.run_cell(cell, seed, opts.seconds, False, t0=t0)
        except run.NoChip as e:
            print(f"bench.calibrate: {e}", file=sys.stderr)
            return run.NO_CHIP
        print(json.dumps({"seed": seed, "control": opts.control,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": {k: v["value"] for k, v
                                      in line["metrics"].items()},
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
