"""Device time by program scope: one cell run with its profile kept.

    python3 -m bench.scopes --workload <cell> --seed <n> --seconds <s>
        --out <dir> [--trace-seconds <s>]

The program names the parts of its refinement loops with
``jax.named_scope`` (DESIGN.md §14.6): ``refine`` / ``refine_sweeps``
around each entry's whole body, and under it ``init`` (the aggregate
build), ``elect`` (the election), ``apply`` (the move apply) and
``rebuild`` (the sweeps' O(E·K) fallback, inside ``apply``).  Each name
is a path component of the ``op_name`` of the ops traced inside it,
which a TPU profile keeps as the ``tf_op`` stat of each op's metadata
on its ``XLA Ops`` line (read by ``bench.xspace``).  The program also
opens the host spans ``repro.refine`` / ``repro.refine_sweeps``, counts
its rebuild sweeps (``RefineResult.num_rebuilds``) and its
compilations (``repro.obs.compiles``).

This command reads all of them for one cell.  It makes the cell's
set-up as ``bench.run`` does, then two windows of the same requests:
one with the profiler off, one with it on, whose ``.xplane.pb`` it
keeps under ``--out``.  The last line of standard output is one JSON
object: the per-layer numbers (``metrics``, named as the per-layer
metrics that would read them), the scopes' share of the device's busy
time inside the program's executions, the ops left outside them,
``rebalance_s`` with the profiler off and on, and the host time of the
entry call.

The reduction works on plain tuples, so its arithmetic is tested on
synthetic events and on a recorded trace (``bench/tests/test_scopes.py``).
"""
from __future__ import annotations

import argparse
import bisect
import functools
import glob
import json
import os
import re
import sys
import time
from pathlib import Path

from bench import trace as trace_mod
from bench import xspace

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
ENTRY_SCOPES = frozenset({"refine", "refine_sweeps"})
LAYER_SCOPES = frozenset({"init", "elect", "apply"})
SCOPES = ("init", "elect", "apply", "rebuild")
MODULES_LINE = "XLA Modules"
PATH_STAT = "tf_op"
ENTRY_SPAN = "repro."
BENCH_SPAN = "bench."


@functools.lru_cache(maxsize=None)
def components(path: str) -> frozenset[str]:
    """The path's components: ``a/b/c:d`` gives ``{a, b, c, d}``."""
    return frozenset(p for p in re.split(r"[/:]", path) if p)


def read_scoped(path: str, chip: int = 0):
    """From one ``.xplane.pb``: the ops of chip ``chip``'s ``XLA Ops``
    line as ``(name, scope_path, start_ns, end_ns)``, its ``XLA Modules``
    line (one event per program execution) as ``(name, start_ns,
    end_ns)``, and the host spans of the benchmark and of the program
    as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData

    device = xspace.read_lines(
        path, lambda name: trace_mod._chip_id(name) == chip,
        (trace_mod.OPS_LINE, MODULES_LINE), PATH_STAT)
    modules = [(n, s, e) for n, _, s, e in device[MODULES_LINE]]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns))
                    for e in line.events
                    if e.name.startswith((BENCH_SPAN, ENTRY_SPAN)))
    return device[trace_mod.OPS_LINE], modules, spans


def _union_ns(intervals, lo: int, hi: int) -> int:
    return sum(e - s for s, e in trace_mod.merge(
        trace_mod.clip(intervals, lo, hi)))


def scope_ns(ops, scope: str, lo: int, hi: int) -> int:
    """Device nanoseconds in ``[lo, hi]`` of the ops whose path holds
    ``scope`` as a component: the union of their intervals, so nested
    ops and an enclosing op of the same scope count once."""
    return _union_ns(((s, e) for _, p, s, e in ops
                      if scope in components(p)), lo, hi)


def extents(ops, modules, lo: int, hi: int) -> list[tuple[int, int]]:
    """One interval per execution of the program's entry: each program
    execution (``XLA Modules`` event) that runs ops under an entry
    scope, from the first of those ops to the end of the last, clipped
    to ``[lo, hi]``."""
    inside = sorted((s, e) for _, p, s, e in ops
                    if components(p) & ENTRY_SCOPES)
    starts = [s for s, _ in inside]
    out = []
    for _, a, b in sorted(modules, key=lambda m: m[1]):
        mine = inside[bisect.bisect_left(starts, a):
                      bisect.bisect_left(starts, b)]
        if mine:
            first, last = mine[0][0], max(e for _, e in mine)
            if last > lo and first < hi:
                out.append((max(first, lo), min(last, hi)))
    return out


def _uncovered_ns(s: int, e: int, merged, merged_starts) -> int:
    """The part of ``[s, e]`` outside the disjoint sorted ``merged``."""
    k = max(bisect.bisect_right(merged_starts, s) - 1, 0)
    covered = 0
    while k < len(merged) and merged[k][0] < e:
        a, b = merged[k]
        covered += max(0, min(b, e) - max(a, s))
        k += 1
    return (e - s) - covered


def summarize(ops, modules, spans, rebalances: int) -> dict:
    """The per-layer numbers of one traced window.  Device seconds are
    per rebalance; ``in_program_idle_share`` is the idle time inside the
    entry's executions over the window, in %."""
    windows = [sp for sp in spans if sp[0] == trace_mod.WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {trace_mod.WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    _, lo, hi = windows[0]
    per = max(rebalances, 1)
    metrics = {f"{scope}_device_s": scope_ns(ops, scope, lo, hi) / per / 1e9
               for scope in SCOPES}
    ordered = sorted(ops, key=lambda op: op[2])
    starts = [op[2] for op in ordered]
    calls = extents(ops, modules, lo, hi)
    busy_in = idle_in = scoped_in = 0
    unscoped: dict[str, int] = {}
    for a, b in calls:
        mine = [(name, p, max(s, a), min(e, b)) for name, p, s, e in
                ordered[bisect.bisect_left(starts, a):
                        bisect.bisect_left(starts, b)]]
        busy = _union_ns(((s, e) for _, _, s, e in mine), a, b)
        scoped = trace_mod.merge((s, e) for _, p, s, e in mine
                                 if components(p) & LAYER_SCOPES)
        busy_in += busy
        idle_in += (b - a) - busy
        scoped_in += sum(e - s for s, e in scoped)
        scoped_starts = [s for s, _ in scoped]
        for name, p, s, e in mine:
            if not components(p) & LAYER_SCOPES:
                unscoped[name] = unscoped.get(name, 0) + _uncovered_ns(
                    s, e, scoped, scoped_starts)
    metrics["in_program_idle_share"] = 100.0 * idle_in / (hi - lo)
    metrics["entry_host_s"] = sum(
        min(e, hi) - max(s, lo) for name, s, e in spans
        if name.startswith(ENTRY_SPAN) and e > lo and s < hi) / per / 1e9
    busy = trace_mod.merge(trace_mod.clip(((s, e) for _, _, s, e in ops),
                                          lo, hi))
    host = [sp for sp in spans if sp[0] != trace_mod.WINDOW_SPAN]
    gaps = trace_mod.attribute_gaps(busy, lo, hi, host)
    return {
        "metrics": metrics,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(e - s for s, e in busy) / 1e9,
        "calls": len(calls),
        "calls_s": sum(b - a for a, b in calls) / 1e9,
        "busy_in_calls_s": busy_in / 1e9,
        "scoped_share": 100.0 * scoped_in / busy_in if busy_in else None,
        "unscoped_ops": sorted(((k[:64], v / 1e9)
                                for k, v in unscoped.items() if v > 0),
                               key=lambda kv: -kv[1])[:8],
        "idle_gaps": sorted(((k, v / 1e9) for k, v in gaps.items()),
                            key=lambda kv: -kv[1]),
    }


def timeline(ops, modules, spans, lo: int, hi: int, count: int = 2):
    """Where the first ``count`` entry executions of the window lie, in
    ms from its start: the program execution (``XLA Modules``), its
    first and last op under an entry scope, its last op of any kind, and
    the host spans that overlap it."""
    inside = sorted((s, e) for _, p, s, e in ops
                    if components(p) & ENTRY_SCOPES)
    starts = [s for s, _ in inside]
    ends = sorted(e for _, _, _, e in ops)
    ms = lambda t: round((t - lo) / 1e6, 3)   # noqa: E731
    out = []
    for _, a, b in sorted(modules, key=lambda m: m[1]):
        mine = inside[bisect.bisect_left(starts, a):
                      bisect.bisect_left(starts, b)]
        if not mine or b < lo or a > hi:
            continue
        last_any = ends[bisect.bisect_right(ends, b) - 1]
        out.append({
            "module": [ms(a), ms(b)],
            "entry_ops": [ms(mine[0][0]), ms(max(e for _, e in mine))],
            "last_op": ms(last_any),
            "host": [[n, ms(s), ms(e)] for n, s, e in spans
                     if e > a and s < b][:8]})
        if len(out) == count:
            break
    return out


def top_ops(ops, lo: int, hi: int, count: int = 12) -> list:
    """The costliest ops of the window: name, scope path, seconds and
    how many times each ran."""
    per: dict[tuple[str, str], list[int]] = {}
    for name, p, s, e in ops:
        if e > lo and s < hi:
            acc = per.setdefault((name[:64], p), [0, 0])
            acc[0] += min(e, hi) - max(s, lo)
            acc[1] += 1
    return [[n, p, v / 1e9, c] for (n, p), (v, c) in
            sorted(per.items(), key=lambda kv: -kv[1][0])[:count]]


class Program:
    """The configuration's entry, called as ``bench.entries`` calls it,
    returning the whole ``RefineResult`` (``num_rebuilds`` included) and
    keeping the host time of each call."""

    def __init__(self, config: dict):
        import importlib

        module = importlib.import_module("repro.core.refine")
        self.fn = getattr(module, config["entry"])
        self.sweep = config["step"] == "sweep"
        self.host_s: list[float] = []

    def rebalance(self, problem, request, args):
        t0 = time.perf_counter()
        if self.sweep:
            out = self.fn(problem, request.start, key=request.key,
                          **args)[0]
        else:
            out = self.fn(problem, request.start, **args)
        self.host_s.append(time.perf_counter() - t0)
        return out


def _mean(values) -> float | None:
    values = [float(v) for v in values]
    return sum(values) / len(values) if values else None


def measure(workload: str, seed: int, seconds: float, out: Path,
            trace_seconds: float | None = None) -> dict:
    t0 = time.perf_counter()
    from bench import run

    cell = run.load_cell(workload)
    run.use_program()
    run.enable_compile_cache()
    from repro.obs import compiles

    start = compiles()
    import jax
    import jax.monitoring

    hits, names = [], []
    jax.monitoring.register_event_listener(
        lambda event, **_: hits.append(event)
        if event == CACHE_HIT_EVENT else None)
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _, fun_name="?", **__: names.append(fun_name)
        if event == COMPILE_EVENT else None)
    from bench.traffic import Mix, Stream

    config = cell.config
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise run.NoChip(f"no TPU: JAX found {device.platform}")
    program = Program(config)
    args = dict(config["entry_args"])
    mix = Mix.load(cell.traffic)
    inst, problem = run.build_problem(config, seed)
    warm = Stream(mix, seed, inst.num_nodes, inst.base_speeds, stream=1)
    first = program.rebalance(problem, warm.cold(), args)
    jax.block_until_ready(first)
    setup_s = time.perf_counter() - t0
    set_up = compiles()
    setup_hits, setup_names = len(hits), len(names)

    def window(length):
        program.host_s.clear()
        stream = Stream(mix, seed, inst.num_nodes, inst.base_speeds)
        records, wall = run._window(program, problem, stream, args,
                                    first.assignment, length)
        return records, wall, _mean(program.host_s)

    off, wall_off, host_off = window(seconds)
    after_off = compiles()
    off_names = len(names)
    traced = min(seconds, config["trace_seconds"]) \
        if trace_seconds is None else trace_seconds
    out.mkdir(parents=True, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=options)
    try:
        on, wall_on, host_on = window(traced)
    finally:
        jax.profiler.stop_trace()
    after_on = compiles()
    found = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    xplane = max(found, key=os.path.getmtime)
    ops, modules, spans = read_scoped(xplane)
    summary = summarize(ops, modules, spans, len(on))
    _, lo, hi = [sp for sp in spans if sp[0] == trace_mod.WINDOW_SPAN][0]
    metrics = summary.pop("metrics")
    if program.sweep:
        metrics["rebuild_sweeps_per_rebalance"] = _mean(
            r.outcome.num_rebuilds for r in on)
    else:
        metrics.pop("rebuild_device_s")
    metrics["window_compiles"] = after_off.count - set_up.count
    metrics["setup_compile_s"] = set_up.seconds - start.seconds
    return {
        "workload": workload, "seed": seed,
        "device": {"platform": device.platform, "kind": device.device_kind},
        "metrics": metrics,
        "setup_s": setup_s,
        "setup_compiles": {"count": set_up.count - start.count,
                           "seconds": set_up.seconds - start.seconds,
                           "cache_hits": setup_hits},
        "window_compiled": names[setup_names:off_names],
        "traced_window_compiles": after_on.count - after_off.count,
        "rebalance_s": {"trace_off": wall_off / len(off),
                        "trace_on": wall_on / len(on)},
        "rebalances": {"trace_off": len(off), "trace_on": len(on)},
        "entry_call_s": {"trace_off": host_off, "trace_on": host_on},
        "steps_per_rebalance": _mean(r.outcome.num_turns for r in on),
        "moves_per_rebalance": _mean(r.outcome.num_moves for r in on),
        **summary,
        "top_ops": top_ops(ops, lo, hi),
        "timeline": timeline(ops, modules, spans, lo, hi),
        "ops_without_path": sum(1 for _, p, _, _ in ops if not p),
        "ops": len(ops),
        "profile": xplane,
        "profile_bytes": os.path.getsize(xplane),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=None,
                    help="length of the profiled window (default: the "
                         "configuration's trace_seconds)")
    ap.add_argument("--out", type=Path, required=True,
                    help="directory where the profile is kept")
    opts = ap.parse_args(argv)
    from bench import run

    try:
        line = measure(opts.workload, opts.seed, opts.seconds, opts.out,
                       opts.trace_seconds)
    except run.NoChip as e:
        print(f"bench.scopes: {e}; nothing was run", file=sys.stderr)
        return run.NO_CHIP
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
