"""Tests of the benchmark's yardstick, on the CPU at small sizes.

    python3 -m pytest bench/tests -q

They cover the §5.1 generator, the float64 reference against the
program's own, the peaks table, the trace reduction's arithmetic, the
refusal to run without a chip or without the program,
and that ``correct`` comes out false for the control and for each fault
planted under the timed path.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, roofline, run, trace  # noqa: E402
from bench.instances import device_problem, make_instance  # noqa: E402

run.use_program()

from bench.tests import faults  # noqa: E402

BIG_SEED = 2**31 + 12345
PAPER_SPEEDS = [0.1, 0.2, 0.3, 0.3, 0.1]     # arXiv:1111.0875 §5.1
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def sec51_instance(seed, n, speeds=PAPER_SPEEDS, dmin=3, dmax=6):
    config = {"name": "test", "num_nodes": n, "num_machines": len(speeds),
              "speeds": speeds, "mu": 8.0,
              "graph": {"model": "sec51", "degree_min": dmin,
                        "degree_max": dmax, "weight_mean": 5.0}}
    return make_instance(config, seed)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env.pop("PYTHONPATH", None)
    return env


# -- generator ---------------------------------------------------------------

def test_generator_follows_sec51_and_the_seed():
    n = 20_000
    inst = sec51_instance(BIG_SEED, n)
    deg = inst.degrees()
    assert deg.min() >= 3
    assert 8.5 < deg.mean() < 9.5            # 2 x E[U{3..6}] = 9
    assert (inst.a < inst.b).all()
    assert np.unique(inst.a * n + inst.b).size == inst.a.size
    for w in (inst.node_weights, inst.edge_weights):
        assert 0.0 <= w.min() and w.max() < 10.0
        assert abs(w.mean() - 5.0) < 0.1
    again = sec51_instance(BIG_SEED, n)
    other = sec51_instance(BIG_SEED + 1, n)
    assert np.array_equal(again.a, inst.a) and np.array_equal(again.b, inst.b)
    assert np.array_equal(again.edge_weights, inst.edge_weights)
    assert not np.array_equal(other.node_weights, inst.node_weights)


def test_generator_is_connected():
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    n = 5_000
    inst = sec51_instance(7, n, dmin=1, dmax=1)   # sparse enough to split
    g = coo_matrix((np.ones(inst.a.size), (inst.a, inst.b)), shape=(n, n))
    assert connected_components(g, directed=False)[0] == 1


def test_sparse_layout_equals_the_programs_constructor():
    from repro.core.sparse import make_sparse_problem

    inst = sec51_instance(3, 3_000)
    cap = 9 * 3_000 + 1024
    ours = device_problem(inst, "sparse", edge_capacity=cap,
                          degree_capacity=32)
    theirs = make_sparse_problem(inst.a, inst.b, inst.edge_weights,
                                 inst.node_weights, inst.base_speeds, mu=8.0,
                                 pad_edges_multiple=cap,
                                 pad_degree_multiple=32)
    for f in ("senders", "receivers", "edge_weights", "row_start",
              "node_weights", "speeds", "mu"):
        assert np.array_equal(np.asarray(getattr(ours, f)),
                              np.asarray(getattr(theirs, f))), f
    assert ours.max_degree == theirs.max_degree
    ours.validate()


# -- reference ---------------------------------------------------------------

@pytest.mark.parametrize("representation", ["dense", "sparse"])
def test_reference_agrees_with_the_programs(representation):
    from repro.core import reference as prog

    n, k = 512, 5
    inst = sec51_instance(11, n)
    problem = device_problem(inst, representation, edge_capacity=9 * n + 512,
                             degree_capacity=32)
    speeds = np.asarray(problem.speeds, np.float64)
    g = reference.Graph.of(inst)
    r = np.random.default_rng(0).integers(0, k, n)
    assert np.allclose(reference.aggregate(g, r),
                       prog.host_aggregate(problem, r), rtol=1e-12)
    assert np.allclose(reference.costs(g, r, speeds),
                       prog.host_costs(problem, r, "c"), rtol=1e-12)
    assert np.isclose(reference.potential(g, r, speeds),
                      prog.host_potentials(problem, r)[0], rtol=1e-12)


def _one_move_short(problem):
    """An equilibrium from ``refine``, and the placement before its last
    move."""
    import jax

    from repro.core.refine import refine, refine_traced

    n = problem.num_nodes
    r0 = jax.random.randint(jax.random.PRNGKey(4), (n,), 0,
                            problem.num_machines)
    done = refine(problem, r0)
    _, tr = refine_traced(problem, r0, max_turns=int(done.num_turns))
    last = int(np.flatnonzero(np.asarray(tr.moved))[-1])
    short = refine(problem, r0, max_turns=last)
    assert int(short.num_moves) == int(done.num_moves) - 1
    return np.asarray(done.assignment), np.asarray(short.assignment)


@pytest.mark.parametrize("representation", ["dense", "sparse"])
def test_reference_accepts_equilibrium_and_rejects_one_move_short(
        representation):
    from repro.core import reference as prog

    n = 256
    inst = sec51_instance(12, n)
    problem = device_problem(inst, representation, edge_capacity=9 * n + 512,
                             degree_capacity=32)
    speeds = np.asarray(problem.speeds, np.float64)
    g = reference.Graph.of(inst)
    done, short = _one_move_short(problem)
    ok = reference.check(g, done, speeds)
    bad = reference.check(g, short, speeds)
    assert ok.equilibrium_ratio <= 1.0 and ok.dissatisfied == 0
    assert bad.equilibrium_ratio > 1.0 and bad.dissatisfied >= 1
    assert prog.check_equilibrium(problem, done, "c").ok
    assert not prog.check_equilibrium(problem, short, "c").ok


def test_reference_epsilon_allowance_matches_the_programs():
    import jax

    from repro.core import reference as prog
    from repro.core.refine import refine_sweeps

    n = 65_536
    inst = sec51_instance(3, n)
    problem = device_problem(inst, "sparse", edge_capacity=9 * n + 1024,
                             degree_capacity=32)
    res, _ = refine_sweeps(problem, jax.random.randint(
        jax.random.PRNGKey(0), (n,), 0, 5), moves_per_machine=None,
        move_prob=0.5, epsilon=1e-3, key=jax.random.PRNGKey(1))
    r = np.asarray(res.assignment)
    ours = reference.check(reference.Graph.of(inst), r, inst.base_speeds,
                           epsilon=1e-3)
    theirs = prog.check_equilibrium(problem, r, "c", epsilon=1e-3)
    assert bool(res.converged)
    assert (ours.dissatisfied == 0) == theirs.ok
    assert ours.dissatisfied == theirs.violations


# -- peaks and bytes ---------------------------------------------------------

def test_unknown_device_kind_raises():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peak("cpu")


def test_bytes_per_step():
    assert roofline.sweep_bytes(10, 100, 4) == 100 * 16 + 2 * 10 * 4 * 4 + 80
    assert roofline.turn_bytes(10, 4, False) == 160 + 80
    assert roofline.turn_bytes(10, 4, True) == 160 + 80 + 40 + 80


# -- trace reduction ---------------------------------------------------------

def test_trace_arithmetic_on_synthetic_events():
    ev = trace.Events(
        device_ops={0: [("fusion.1", 10, 20), ("fusion.2", 15, 30),
                        ("all-gather.3", 50, 60), ("fusion.1", 95, 120)]},
        host_spans=[("bench.window", 0, 100), ("bench.dispatch", 0, 12),
                    ("bench.wait", 12, 60), ("bench.request", 60, 100)])
    s = trace.summarize(ev, chips=1)
    assert s.window_s == 100e-9
    assert s.busy_s == pytest.approx((20 + 10 + 5) * 1e-9)
    assert s.collective_s == pytest.approx(10e-9)
    assert dict(s.ops)["fusion.1"] == pytest.approx(15e-9)
    gaps = dict(s.gaps)
    assert gaps["bench.dispatch"] == pytest.approx(10e-9)      # [0, 10)
    assert gaps["bench.wait"] == pytest.approx(20e-9)          # [30, 50)
    assert gaps["bench.request"] == pytest.approx(35e-9)       # [60, 95)
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)


# -- refusals ----------------------------------------------------------------

def test_run_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == run.NO_CHIP
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout


# -- correct: sound runs, the control and planted faults ----------------------

SMALL_NODES = 65_536     # the sparse cells at a size a test run can hold


def small_cell(name: str, control: bool = False) -> run.Cell:
    from bench.calibrate import control_cell

    cell = run.load_cell(name)
    n = min(cell.config["num_nodes"], SMALL_NODES)
    config = dict(cell.config, num_nodes=n)
    if "edge_capacity" in config:
        config["edge_capacity"] = 9 * n + 1024
    cell = dataclasses.replace(cell, config=config)
    return control_cell(cell) if control else cell


def test_listed_cells_load():
    for w in MANIFEST["workloads"]:
        cell = run.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer


def _run(cell, entry=None, seconds=0.05):
    return run.run_cell(cell, BIG_SEED, seconds, False, require_tpu=False,
                        entry=entry)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(name):
    line = _run(small_cell(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    ctl = _run(small_cell(name, control=True))
    assert not ctl["correct"], ctl["checks"]


def _entry(cell):
    import importlib

    return importlib.import_module(f"bench.entries.{cell.config['entry']}")


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(name, fault):
    cell = small_cell(name)
    line = _run(cell, entry=faults.FAULTS[fault](_entry(cell)))
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1


# -- traffic -----------------------------------------------------------------

def test_stream_is_fixed_by_the_seed():
    from bench.traffic import Mix, Stream

    base = np.array(PAPER_SPEEDS)
    cold = [Stream(Mix("uniform", None), BIG_SEED, 100, base).next()
            for _ in range(2)]
    assert np.array_equal(np.asarray(cold[0].start), np.asarray(cold[1].start))
    other = Stream(Mix("uniform", None), BIG_SEED + 1, 100, base).next()
    assert not np.array_equal(np.asarray(other.start),
                              np.asarray(cold[0].start))


def test_stream_slows_one_machine_at_a_time():
    from bench.traffic import Mix, Stream

    base = np.array(PAPER_SPEEDS)
    stream = Stream(Mix("previous", 0.25), BIG_SEED, 100, base)
    previous, slowed = "placement", None
    for _ in range(6):
        req = stream.next(previous)
        assert req.start == previous
        ratio = req.speeds / (base / base.sum()).astype(np.float32)
        low = np.flatnonzero(ratio < ratio.max() * 0.5)
        assert low.size == 1 and low[0] != slowed
        assert ratio[low[0]] / ratio.max() == pytest.approx(0.25)
        slowed = low[0]
