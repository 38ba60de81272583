"""Run telemetry layer contracts (DESIGN.md §14).

Three properties are load-bearing and pinned here:

  1. **Disabled mode is free** — every instrumented entry point called
     with ``recorder=None`` produces bitwise-identical results to the
     telemetry-enabled call, and its jaxpr contains ZERO host callbacks
     (§14.3's overhead contract).
  2. **The log is sufficient** — a run can be replayed from its event
     stream alone: the report module's replay reconstructs the final
     loads, move counts and potential descent that the live run
     produced, and round-trips through the JSONL sink + report CLI.
  3. **Measured wire == ledger** — distributed runs under a recorder
     carry a ``wire`` event whose measured bytes equal the §9.3 analytic
     prediction exactly (the deep per-driver grid lives in
     ``tests/test_distributed.py``; here the event-stream side is
     checked).
"""
from __future__ import annotations

import importlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.problem import make_problem
from repro.core.refine import (refine, refine_simultaneous, refine_sweeps,
                               refine_traced)
from repro.distributed import refine_distributed
from repro.graphs.generators import random_degree_graph, random_weights
from repro.obs import (EVENT_KINDS, JsonlSink, MemorySink, Recorder,
                       chrome_trace, compiles, make_event, read_jsonl,
                       validate_event)
from repro.obs.report import check_run, main as report_main, replay_run, \
    split_runs

N, K = 48, 4


@pytest.fixture(scope="module")
def instance():
    adj = random_degree_graph(N, seed=3)
    b, c = random_weights(adj, seed=4, mean=5.0)
    prob = make_problem(c, b, np.ones(K) / K, mu=8.0)
    r0 = jnp.asarray(np.random.default_rng(5).integers(0, K, N), jnp.int32)
    return prob, r0


def _tree_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# event schema
# ---------------------------------------------------------------------------

def test_event_schema_registry():
    assert {"run_start", "turn", "sweep", "tick", "des_refine", "wire",
            "drift", "phase", "element", "run_end"} <= set(EVENT_KINDS)
    event = make_event("turn", "r0000", t=0, moved=True, c0=1.0, ct0=2.0)
    validate_event(event)
    with pytest.raises(ValueError):
        make_event("turn", "r0000", t=0)          # missing required fields
    with pytest.raises(ValueError):
        validate_event({"kind": "nope", "run": "r0000"})


def test_events_are_json_serializable(instance):
    prob, r0 = instance
    rec = Recorder()
    refine_traced(prob, r0, "c", max_turns=64, recorder=rec)
    for event in rec.events:
        json.loads(json.dumps(event))


# ---------------------------------------------------------------------------
# disabled mode: bitwise identical, zero callbacks
# ---------------------------------------------------------------------------

def test_disabled_mode_results_bitwise(instance):
    prob, r0 = instance
    rec = Recorder()
    for fn, kwargs in ((refine, {"max_turns": 500}),
                       (refine_traced, {"max_turns": 64}),
                       (refine_simultaneous, {"max_sweeps": 16})):
        base = fn(prob, r0, "c", **kwargs)
        inst = fn(prob, r0, "c", **kwargs, recorder=rec)
        assert _tree_equal(base, inst), fn.__name__
    assert any(e["kind"] == "run_end" for e in rec.events)


def test_disabled_entry_points_have_no_callbacks():
    # registry-driven coverage (DESIGN.md §16.3): EVERY registered public
    # entry point — not just refine — stages zero host callbacks on its
    # telemetry-disabled path.  The per-path jaxprs are traced once per
    # process and shared with tests/test_contracts.py.
    from repro.analysis.entrypoints import (registered_entry_points,
                                            trace_entry_point)
    from repro.analysis.jaxpr_rules import callback_primitives

    eps = registered_entry_points()
    assert len(eps) >= 10
    for ep in eps:
        assert callback_primitives(trace_entry_point(ep.name)) == [], ep.name


# ---------------------------------------------------------------------------
# replay: the log alone reproduces the run
# ---------------------------------------------------------------------------

def test_refine_replay_matches_result(instance):
    prob, r0 = instance
    rec = Recorder()
    result = refine(prob, r0, "c", max_turns=500, recorder=rec)
    summary = replay_run(rec.events)
    assert check_run(summary) == []
    assert summary["accepted"] == int(result.num_moves)
    np.testing.assert_allclose(summary["loads"],
                               np.asarray(result.loads, np.float64),
                               rtol=1e-5, atol=1e-3)
    # carried C_0 descends monotonically for the sequential game
    pots = [c0 for _, c0, _ in summary["potentials"]]
    assert pots and pots[-1] <= pots[0]


def test_traced_replay_and_load_cv_trace(instance):
    prob, r0 = instance
    rec = Recorder()
    refine_traced(prob, r0, "c", max_turns=96, recorder=rec)
    summary = replay_run(rec.events)
    assert check_run(summary) == []
    cv = summary["cv_trace"]
    assert cv.size and cv[-1] < cv[0]     # §5: refinement balances loads


def test_distributed_wire_event_reconciles(instance):
    prob, r0 = instance
    rec = Recorder()
    base = refine_distributed(prob, r0, "c", num_shards=K, max_turns=500)
    inst = refine_distributed(prob, r0, "c", num_shards=K, max_turns=500,
                              recorder=rec)
    assert _tree_equal(base, inst)
    wires = [e for e in rec.events if e["kind"] == "wire"]
    assert len(wires) == 1 and wires[0]["ok"]
    assert wires[0]["measured_payload"] == wires[0]["predicted_payload"]
    assert wires[0]["measured_setup"] == wires[0]["predicted_setup"]
    assert check_run(replay_run(rec.events)) == []


def test_des_telemetry_bitwise_and_replay():
    from repro.des.engine import (DESConfig, make_initial_state,
                                  run_simulation)
    from repro.des.workload import flooded_packet_workload
    from repro.graphs.generators import preferential_attachment

    n, k, threads = 20, 3, 8
    adj = preferential_attachment(n, 5, m=2)
    spec = flooded_packet_workload(adj, 9, num_threads=threads,
                                   num_windows=2, scope=2,
                                   window_sim_time=40.0, max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=k, num_threads=threads,
                    event_capacity=48, history_capacity=96,
                    inter_delay=6, intra_delay=1, trace_stride=10,
                    max_ticks=20_000, machine_speeds=(1.0, 0.7, 0.5),
                    refine_freq=80, refine_theta_scale=5.0,
                    migration_freeze=0.25)
    m0 = jnp.asarray(np.arange(n) % k, jnp.int32)
    state0 = make_initial_state(cfg, m0, spec.src, spec.time, spec.count)
    adjj = jnp.asarray(adj, jnp.float32)

    base = run_simulation(cfg, adjj, state0)
    rec = Recorder()
    inst = run_simulation(cfg, adjj, state0, recorder=rec)
    assert _tree_equal(base, inst)

    summary = replay_run(rec.events)
    assert check_run(summary) == []
    assert summary["ticks"] > 0 and summary["des_refines"] > 0
    ticks = [e for e in rec.events if e["kind"] == "tick"]
    assert all(e["t"] % cfg.trace_stride == 0 for e in ticks)
    assert summary["end"]["converged"]


def test_sweep_telemetry_results_identical(instance):
    from repro import sweeps

    prob, r0 = instance
    cases = [sweeps.SweepCase(problem=prob, assignment=r0, framework=fw,
                              label=fw) for fw in ("c", "ct")]
    spec = sweeps.make_spec(cases, mode="traced", max_turns=64)
    base = sweeps.run_sweep(spec)
    rec = Recorder()
    inst = sweeps.run_sweep(spec, recorder=rec)
    for r_base, r_inst in zip(base.results, inst.results):
        assert _tree_equal(r_base, r_inst)

    elements = [e for e in rec.events if e["kind"] == "element"]
    assert [e["batch"] for e in elements] == [0, 1]
    turns = [e for e in rec.events if e["kind"] == "turn"]
    assert turns and {e["batch"] for e in turns} == {0, 1}
    assert check_run(replay_run(rec.events)) == []


# ---------------------------------------------------------------------------
# JSONL round-trip + report CLI
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_through_report_cli(instance, tmp_path, capsys):
    prob, r0 = instance
    log = tmp_path / "run.jsonl"
    rec = Recorder([JsonlSink(log)])
    refine(prob, r0, "c", max_turns=500, recorder=rec)
    refine_distributed(prob, r0, "ct", num_shards=K, max_turns=500,
                       recorder=rec)
    rec.close()
    events = read_jsonl(log)
    assert events == rec.events
    assert len(split_runs(events)) == 2

    assert report_main([str(log), "--check"]) == 0
    out = capsys.readouterr().out
    assert "[refine]" in out and "[distributed]" in out
    assert "wire [OK]" in out

    assert report_main([str(log), "--json"]) == 0
    for line in capsys.readouterr().out.strip().splitlines():
        json.loads(line)


def test_report_cli_namespaces_multiple_logs(instance, tmp_path, capsys):
    """Distinct logs reuse run ids (r0000, ...); reporting several at once
    must not merge unrelated runs."""
    prob, r0 = instance
    paths = []
    for name in ("a", "b"):
        path = tmp_path / f"{name}.jsonl"
        rec = Recorder([JsonlSink(path)])
        refine(prob, r0, "c", max_turns=500, recorder=rec)
        rec.close()
        paths.append(str(path))
    assert report_main([*paths, "--check"]) == 0
    out = capsys.readouterr().out
    assert "run a:r0000" in out and "run b:r0000" in out


def test_report_cli_check_fails_on_bad_log(tmp_path, capsys):
    log = tmp_path / "bad.jsonl"
    events = [
        make_event("run_start", "r0000", runtime="distributed",
                   n=8, k=2, framework="c"),
        make_event("wire", "r0000", rounds=3, measured_payload=100,
                   predicted_payload=96, measured_setup=12,
                   predicted_setup=12, ok=False),
        make_event("run_end", "r0000"),
    ]
    with JsonlSink(log) as sink:
        for event in events:
            sink.write(event)
    assert report_main([str(log), "--check"]) == 1
    assert "wire" in capsys.readouterr().err


def test_chrome_trace_export(instance, tmp_path):
    prob, r0 = instance
    log = tmp_path / "run.jsonl"
    rec = Recorder([JsonlSink(log)])
    refine(prob, r0, "c", max_turns=500, recorder=rec)
    rec.close()
    trace_path = tmp_path / "trace.json"
    assert report_main([str(log), "--trace", str(trace_path)]) == 0
    trace = json.loads(trace_path.read_text())
    slices = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert slices and all(e["dur"] >= 0 for e in slices)
    assert chrome_trace(rec.events)["traceEvents"]


# ---------------------------------------------------------------------------
# sinks + recorder mechanics
# ---------------------------------------------------------------------------

def test_memory_sink_fanout_and_phase():
    rec = Recorder([MemorySink(), MemorySink()])
    run = rec.new_run("refine", n=8, k=2, framework="c")
    with rec.phase("unit.test", run):
        pass
    rec.emit("run_end", run)
    for sink in rec.sinks:
        assert [e["kind"] for e in sink.events] == \
            ["run_start", "phase", "run_end"]
    assert rec.events == rec.sinks[0].events


# ---------------------------------------------------------------------------
# device scopes, the rebuild counter and the compile counter (§14.6)
# ---------------------------------------------------------------------------

def _op_name_components(hlo_text: str) -> set[str]:
    names = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        names.update(path.split("/"))
    return names


def _sparse_instance(n: int = 2048, k: int = 5):
    from repro.core.sparse import make_sparse_problem
    from repro.graphs.generators import (random_degree_graph_edges,
                                         random_weights_edges)

    s, r = random_degree_graph_edges(n, seed=1)
    b, w = random_weights_edges(n, s, seed=2)
    sp = make_sparse_problem(s, r, w, b, [0.1, 0.2, 0.3, 0.3, 0.1][:k])
    r0 = jnp.asarray(np.random.default_rng(3).integers(0, k, n), jnp.int32)
    return sp, r0


UNBOUNDED = dict(max_sweeps=64, moves_per_machine=None, move_prob=0.5,
                 epsilon=1e-3)


def test_sweep_scopes_in_compiled_hlo():
    refine_mod = importlib.import_module("repro.core.refine")
    sp, r0 = _sparse_instance(n=256)
    key = jax.random.PRNGKey(4)
    text = refine_mod._refine_sweeps.lower(
        sp, r0, key, "c", **UNBOUNDED).compile().as_text()
    assert {"refine_sweeps", "init", "elect", "apply", "rebuild"} \
        <= _op_name_components(text)


def test_refine_scopes_in_compiled_hlo(instance):
    refine_mod = importlib.import_module("repro.core.refine")
    prob, r0 = instance
    text = refine_mod._refine.lower(prob, r0, "c").compile().as_text()
    assert {"refine", "init", "elect", "apply"} \
        <= _op_name_components(text)


@pytest.fixture
def overflowing_start():
    """Every LP of the 2,048-LP instance on one machine: about half of
    them move in the first unbounded sweep, whose edges then outgrow the
    largest edge buffer (a quarter of E), so that sweep rebuilds."""
    sp, _ = _sparse_instance()
    return sp, jnp.zeros((sp.num_nodes,), jnp.int32)


def test_num_rebuilds_counts_overflowing_sweeps(overflowing_start):
    refine_mod = importlib.import_module("repro.core.refine")
    from repro.core.aggregate import edge_buffer_sizes

    sp, r0 = overflowing_start
    key = jax.random.PRNGKey(4)
    result, _, movers = refine_mod._refine_sweeps(
        sp, r0, key, "c", telemetry=True, **UNBOUNDED)
    # a sweep rebuilds when its movers' edges exceed the largest buffer:
    # certainly if even its movers' least degrees do, never if their
    # largest do not
    largest = max(edge_buffer_sizes(sp.num_edges))
    ends = np.append(np.asarray(sp.row_start)[1:], sp.num_edges)
    degree = ends - np.asarray(sp.row_start)
    movers = np.asarray(movers)
    surely = movers * degree.min() > largest
    maybe = int((movers * sp.max_degree > largest).sum())
    # the first sweep alone: its movers' edges, read off the placement
    first, _, _ = refine_mod._refine_sweeps(
        sp, r0, key, "c", **dict(UNBOUNDED, max_sweeps=1))
    moved = np.asarray(first.assignment) != np.asarray(r0)
    assert degree[moved].sum() > largest and int(first.num_rebuilds) == 1
    surely[0] = True
    assert 1 <= int(surely.sum()) <= int(result.num_rebuilds) <= maybe
    # the public entry (no telemetry side output) counts the same
    plain, _ = refine_sweeps(sp, r0, key=key, **UNBOUNDED)
    assert int(plain.num_rebuilds) == int(result.num_rebuilds)
    assert _tree_equal(plain, result)


def test_num_rebuilds_zero_when_the_buffer_holds():
    sp, r0 = _sparse_instance(n=256)
    result, _ = refine_sweeps(sp, r0, key=jax.random.PRNGKey(4), **UNBOUNDED)
    assert int(result.num_rebuilds) == 0


def test_num_rebuilds_zero_outside_the_unbounded_mode(instance,
                                                      overflowing_start):
    prob, r0 = instance
    sp, s0 = overflowing_start
    one, _ = refine_sweeps(sp, s0, moves_per_machine=1, max_sweeps=64)
    assert int(one.num_moves) > 0 and int(one.num_rebuilds) == 0
    assert int(one.apply_slots) == 0
    turn = refine(prob, r0, "c", max_turns=500)
    assert int(turn.num_moves) > 0 and int(turn.num_rebuilds) == 0


def test_run_end_carries_num_sweeps(instance):
    prob, r0 = instance
    sp, s0 = _sparse_instance(n=256)
    rec = Recorder()
    result, _ = refine_sweeps(sp, s0, key=jax.random.PRNGKey(4),
                              recorder=rec, **dict(UNBOUNDED, max_sweeps=256))
    end = [e for e in rec.events if e["kind"] == "run_end"][-1]
    assert end["num_sweeps"] == int(result.num_sweeps) \
        == int(result.num_turns) + 1
    refine(prob, r0, "c", max_turns=500, recorder=rec)
    end = [e for e in rec.events if e["kind"] == "run_end"][-1]
    assert "num_sweeps" not in end


def test_compile_counter_counts_fresh_compiles():
    x = jnp.arange(7.0)
    before = compiles()
    fn = jax.jit(lambda v: v * 3.0 + 1.0)
    fn(x).block_until_ready()
    first = compiles()
    fn(x).block_until_ready()
    second = compiles()
    assert first.count - before.count == 1
    assert first.seconds > before.seconds
    assert second == first


def test_host_spans_land_in_a_profile(instance, tmp_path):
    """The entry points' and Recorder.phase's spans are on the profiler's
    host line, beside the device ops of the same profile."""
    from jax.profiler import ProfileData

    prob, r0 = instance
    refine(prob, r0, "c", max_turns=500)           # compile outside
    refine_sweeps(prob, r0, max_sweeps=8)
    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(refine(prob, r0, "c", max_turns=500))
        jax.block_until_ready(refine_sweeps(prob, r0, max_sweeps=8))
        with rec.phase("unit.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events}
    assert {"repro.refine", "repro.refine_sweeps", "unit.span"} <= names
