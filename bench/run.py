"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): the deployment's instance drawn from the
seed, the program's problem object built on the device, and one
rebalance of the cell's own shapes as warm-up, compiled through JAX's
persistent cache in ``bench/.jax_cache``; for a mix that starts from
the last placement, that warm-up is the cold solve the first request
starts from.  The window then sends rebalance requests one at a time
through the configuration's public entry point, each waited for before
the next (a closed loop with one client), until ``--seconds`` have
passed.  Results stay on the device until the window closes.  After it,
every rebalance is held to the float64 reference (``bench.reference``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared with its
limit, also printed as the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it prints no result and
exits with 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".jax_cache"
NO_CHIP = 2


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


def use_program(root: Path = ROOT) -> None:
    """Import the program from this checkout's ``src`` and nowhere else."""
    src = (root / "src").resolve()
    sys.path.insert(0, str(src))
    import repro.core

    where = Path(repro.core.__file__).resolve().parents[2]
    if where != src:
        raise ImportError(f"repro comes from {where}, not {src}")


def enable_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: str
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=w["traffic"],
                end_to_end=[m for m in manifest["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in manifest["per_layer"]
                           if _applies(m, name)])


@dataclasses.dataclass
class RunView:
    """What a per-layer reader may read (``bench/metrics``)."""
    config: dict
    chips: int
    device_kind: str
    num_nodes: int
    num_edges: int
    num_machines: int
    turns: list[int]
    moves: list[int]
    trace: object | None


@dataclasses.dataclass
class Record:
    request: object
    outcome: object


def build_problem(config: dict, seed: int):
    """The deployment's instance (host) and problem object (device)."""
    from bench.instances import device_problem, make_instance

    inst = make_instance(config, seed)
    problem = device_problem(inst, config["representation"],
                             dtype=config["dtype"],
                             edge_capacity=config.get("edge_capacity"),
                             degree_capacity=config.get("degree_capacity"))
    return inst, problem


def _window(entry, problem, stream, args, previous, seconds: float):
    """Requests one at a time until ``seconds`` have passed; returns the
    records and the wall time up to the end of the last rebalance."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench.instances import with_speeds

    records = []
    speeds, current = None, problem
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with TraceAnnotation("bench.request"):
                request = stream.next(previous)
                if speeds is None or not np.array_equal(speeds,
                                                        request.speeds):
                    speeds = request.speeds
                    current = with_speeds(problem, speeds)
            with TraceAnnotation("bench.dispatch"):
                outcome = entry.rebalance(current, request, args)
            with TraceAnnotation("bench.wait"):
                jax.block_until_ready(outcome)
            records.append(Record(request, outcome))
            previous = outcome.assignment
        wall = time.perf_counter() - t0
    return records, wall


def check_records(config: dict, inst, records):
    """Hold every rebalance to the float64 reference.  Returns the numbers
    compared (max over rebalances), the rebalances that failed one, and
    the potential ratio of the window."""
    import numpy as np

    from bench import reference

    graph = reference.Graph.of(inst)
    limits = config["limits"]
    total_b = float(inst.node_weights.astype(np.float64).sum())
    worst: dict[str, float] = {}
    failed = 0
    c0_start = c0_end = 0.0
    for rec in records:
        req, out = rec.request, rec.outcome
        speeds = req.speeds.astype(np.float64)
        start = np.asarray(req.start)
        final = np.asarray(out.assignment)
        verdict = reference.check(graph, final, speeds,
                                  epsilon=config["epsilon"])
        c0_end += verdict.c0
        c0_start += reference.potential(graph, start, speeds)
        numbers = {
            "equilibrium_ratio": verdict.equilibrium_ratio,
            "load_gap": float(np.abs(np.asarray(out.loads, np.float64)
                                     - verdict.loads).max() / total_b),
            "moves_short": float(max(0, int((start != final).sum())
                                     - int(out.num_moves))),
        }
        if out.potentials is not None:
            c0 = float(np.asarray(out.potentials)[-1])
            numbers["potential_gap"] = abs(c0 - verdict.c0) / abs(verdict.c0)
        bad = False
        for key, value in numbers.items():
            worst[key] = max(worst.get(key, value), value)
            bad |= not value <= limits[key]
        failed += bad
    ratio = c0_end / c0_start if c0_start else float("nan")
    return worst, failed, ratio


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, entry=None,
             t0: float | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``entry`` replaces the configuration's entry module (the tests plant
    faults under the timed path with it)."""
    t0 = time.perf_counter() if t0 is None else t0
    import jax
    import numpy as np

    from bench import trace as trace_mod
    from bench.traffic import Mix, Stream

    config = cell.config
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    if entry is None:
        entry = importlib.import_module(f"bench.entries.{config['entry']}")
    args = dict(config["entry_args"])
    mix = Mix.load(cell.traffic)

    inst, problem = build_problem(config, seed)
    warm = Stream(mix, seed, inst.num_nodes, inst.base_speeds, stream=1)
    first = entry.rebalance(problem, warm.cold(), args)
    jax.block_until_ready(first)
    setup_s = time.perf_counter() - t0

    stream = Stream(mix, seed, inst.num_nodes, inst.base_speeds)
    window_s = min(seconds, config["trace_seconds"]) if trace else seconds
    if trace:
        logdir = tempfile.TemporaryDirectory()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir.name, profiler_options=options)
    try:
        records, wall = _window(entry, problem, stream, args,
                                first.assignment, window_s)
    finally:
        if trace:
            jax.profiler.stop_trace()
    stats = [d.memory_stats() or {} for d in used]
    memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    del first

    numbers, failed, potential_ratio = check_records(config, inst, records)
    limits = config["limits"]
    correct = bool(records) and all(v <= limits[k]
                                    for k, v in numbers.items())

    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    line: dict = {"correct": correct, "attempted": len(records),
                  "failed": int(failed)}
    if not trace:
        found = {"rebalance_s": wall / len(records) if records else None,
                 "potential_ratio": potential_ratio, "setup_s": setup_s}
        metrics = {}
        for m in cell.end_to_end:
            if found.get(m["name"]) is None:
                raise RuntimeError(f"{cell.name}: nothing measured for "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": found[m["name"]],
                                  "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = device
    else:
        summary = trace_mod.reduce(logdir.name, cell.chips)
        logdir.cleanup()
        view = RunView(
            config=config, chips=cell.chips, device_kind=used[0].device_kind,
            num_nodes=inst.num_nodes,
            num_edges=int(getattr(problem, "num_edges", 0)),
            num_machines=inst.num_machines,
            turns=[int(r.outcome.num_turns) for r in records],
            moves=[int(r.outcome.num_moves) for r in records],
            trace=summary)
        metrics = {}
        for m in cell.per_layer:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = dict(device, busy_s=summary.busy_s,
                              window_s=summary.window_s)
        line["breakdown"] = {"device_ops": [list(x) for x in summary.ops[:10]],
                             "idle_gaps": [list(x) for x in summary.gaps[:10]]}
    line["checks"] = {k: {"value": v, "limit": limits[k]}
                      for k, v in numbers.items()}
    return line


def main(argv=None) -> int:
    t0 = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    cell = load_cell(opts.workload)
    use_program()
    enable_compile_cache()
    try:
        line = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace),
                        t0=t0)
    except NoChip as e:
        print(f"bench.run: {e}; nothing was run", file=sys.stderr)
        return NO_CHIP
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
