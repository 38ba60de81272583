"""Tests of the per-scope reduction (``bench.scopes``), on synthetic
events and on a profile recorded on a TPU v5e.

    python3 -m pytest bench/tests/test_scopes.py -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import scopes, trace  # noqa: E402

SWEEP = "jit(_refine_sweeps)/refine_sweeps"
BODY = SWEEP + "/while/body/closed_call"
RECORDED = Path(__file__).resolve().parent / "data" / "paper230-cold.xplane.pb"


def test_components_match_whole_names_only():
    parts = scopes.components(f"{BODY}/apply/cond/branch_0_fun/rebuild/add")
    assert {"refine_sweeps", "apply", "rebuild"} <= parts
    assert "refine" not in parts                  # not a prefix match
    assert "elect" in scopes.components("elect/reduce_max")   # partial path


def _synthetic():
    """Two executions of the sweeps entry inside a 200 ns window.

    Execution 1 (module 8-92, ops 10-90): the while op encloses
    everything; init 10-20; elect 20-40 with a nested fusion 25-30; the
    cond of the apply 45-80 encloses its rebuild branch 50-70 and one
    partial-path op (no jit prefix) 55-60; an idle gap 40-45 inside the
    call; an unscoped op 82-90 under the entry scope alone.
    Execution 2 (module 108-142): init 110-115, elect 115-125, apply
    130-140, idle 125-130.  Another program runs 150-160."""
    ops = [
        ("while.1", f"{SWEEP}/while", 10, 90),
        ("fusion.1", f"{SWEEP}/init/add", 10, 20),
        ("fusion.2", f"{BODY}/elect/max", 20, 40),
        ("fusion.3", f"{BODY}/elect/argmax", 25, 30),
        ("conditional.6", f"{BODY}/apply/cond", 45, 80),
        ("fusion.4", f"{BODY}/apply/cond/branch_0_fun/rebuild/add", 50, 70),
        ("fusion.5", "rebuild/scatter", 55, 60),
        ("fusion.6", f"{SWEEP}/reduce_sum", 82, 90),
        ("fusion.1", f"{SWEEP}/init/add", 110, 115),
        ("fusion.2", f"{BODY}/elect/max", 115, 125),
        ("fusion.7", f"{BODY}/apply/scatter", 130, 140),
        ("fusion.9", "jit(_draw)/random_bits", 150, 160),
    ]
    modules = [("jit__refine_sweeps(1)", 8, 92),
               ("jit__refine_sweeps(1)", 108, 142),
               ("jit__lambda(2)", 149, 161)]
    spans = [("bench.window", 0, 200),
             ("bench.dispatch", 0, 8), ("repro.refine_sweeps", 1, 6),
             ("bench.wait", 8, 95),
             ("bench.dispatch", 100, 108), ("repro.refine_sweeps", 101, 105),
             ("bench.wait", 108, 145), ("bench.request", 145, 200)]
    return ops, modules, spans


def test_scope_seconds_are_unions():
    ops, modules, spans = _synthetic()
    out = scopes.summarize(ops, modules, spans, rebalances=2)
    m = out["metrics"]
    assert m["init_device_s"] == pytest.approx((10 + 5) / 2 * 1e-9)
    assert m["elect_device_s"] == pytest.approx((20 + 10) / 2 * 1e-9)
    # the cond, its rebuild branch and the partial-path op count once
    assert m["apply_device_s"] == pytest.approx((35 + 10) / 2 * 1e-9)
    assert m["rebuild_device_s"] == pytest.approx(20 / 2 * 1e-9)
    assert m["rebuild_device_s"] <= m["apply_device_s"]
    assert m["entry_host_s"] == pytest.approx((5 + 4) / 2 * 1e-9)


def test_extents_and_idle_inside_the_calls():
    ops, modules, spans = _synthetic()
    assert scopes.extents(ops, modules, 0, 200) == [(10, 90), (110, 140)]
    assert scopes.extents(ops, modules, 15, 120) == [(15, 90), (110, 120)]
    out = scopes.summarize(ops, modules, spans, rebalances=2)
    # the while op covers 10-90, so call 1 is busy throughout; call 2
    # idles 125-130
    assert out["calls"] == 2
    assert out["busy_in_calls_s"] == pytest.approx((80 + 25) * 1e-9)
    assert out["metrics"]["in_program_idle_share"] \
        == pytest.approx(100.0 * 5 / 200)
    # scoped: call 1 10-40, 45-80 (65 of 80); call 2 all 25
    assert out["scoped_share"] == pytest.approx(100.0 * 90 / 105)
    unscoped = dict(out["unscoped_ops"])
    assert unscoped["while.1"] == pytest.approx(15e-9)   # 40-45, 80-90
    assert unscoped["fusion.6"] == pytest.approx(8e-9)
    assert "fusion.9" not in unscoped                   # outside the calls


def test_busy_time_matches_the_window_reduction():
    ops, modules, spans = _synthetic()
    out = scopes.summarize(ops, modules, spans, rebalances=2)
    ev = trace.Events(device_ops={0: [(n, s, e) for n, _, s, e in ops]},
                      host_spans=[sp for sp in spans
                                  if sp[0].startswith("bench.")])
    assert out["busy_s"] == pytest.approx(trace.summarize(ev, 1).busy_s)
    assert out["window_s"] == pytest.approx(200e-9)


def test_recorded_tpu_profile():
    """A ``--trace 1`` profile of ``paper230-cold`` recorded on a TPU v5e
    (``bench.scopes --trace-seconds 0.02``, one rebalance): the plane and
    line names the reductions read, and the program's scopes on its ops."""
    from jax.profiler import ProfileData

    planes = {p.name: {line.name for line in p.lines}
              for p in ProfileData.from_file(str(RECORDED)).planes}
    assert {trace.OPS_LINE, scopes.MODULES_LINE} <= planes["/device:TPU:0"]
    events = trace.read_events(str(RECORDED))
    assert events.device_ops[0]
    assert any(sp[0] == trace.WINDOW_SPAN for sp in events.host_spans)

    ops, modules, spans = scopes.read_scoped(str(RECORDED))
    assert sorted((s, e) for _, _, s, e in ops) \
        == sorted((s, e) for _, s, e in events.device_ops[0])
    found = set().union(*(scopes.components(p) for _, p, _, _ in ops))
    assert {"refine", "init", "elect", "apply"} <= found
    assert any(name.startswith("jit__refine") for name, _, _ in modules)
    assert [name for name, _, _ in spans
            if name.startswith(scopes.ENTRY_SPAN)] == ["repro.refine"]
    out = scopes.summarize(ops, modules, spans, rebalances=1)
    assert out["calls"] == 1
    assert out["busy_s"] == pytest.approx(trace.summarize(events, 1).busy_s)
    m = out["metrics"]
    assert m["elect_device_s"] > 0 and m["apply_device_s"] > 0
    assert m["init_device_s"] > 0 and m["rebuild_device_s"] == 0
    assert out["scoped_share"] > 50
