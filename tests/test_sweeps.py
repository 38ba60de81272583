"""Batched sweep runtime (DESIGN.md §12): vmap-vs-loop bitwise contracts.

The load-bearing promise: every element of a batched run reproduces its
own looped run — move sequences, assignments, loads and gains bitwise
for all three refinement entry points; complete final states (traces
included) bitwise for the DES engine — with the carried potentials
inside the §10.3 ≤1e-3 relative budget.  Exercised across mixed graph
generators, both frameworks, theta on/off, and (for DES) churn schedules
with refinement, hysteresis and migration freezes enabled.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import sweeps
from repro.core import costs
from repro.core.batch import (batch_size, refine_batched,
                              refine_simultaneous_batched,
                              refine_sweeps_batched, refine_traced_batched,
                              stack_problems, stack_pytrees, unstack_pytree)
from repro.core.problem import make_problem
from repro.core.refine import (refine, refine_simultaneous, refine_sweeps,
                               refine_traced)
from repro.des import scenarios
from repro.des.engine import (DESConfig, make_initial_state, run_simulation,
                              run_simulation_batch)
from repro.des.workload import flooded_packet_workload
from repro.graphs.generators import (preferential_attachment,
                                     random_degree_graph, random_weights,
                                     specialized_geometric)

POTENTIAL_TOL = 1e-3
GENERATORS = (random_degree_graph,
              lambda n, s: preferential_attachment(n, s, m=2),
              specialized_geometric)


def _mixed_problems(num: int, n: int = 40, k: int = 4, seed0: int = 0):
    problems, r0s = [], []
    for s in range(num):
        adj = GENERATORS[s % len(GENERATORS)](n, seed0 + s)
        b, c = random_weights(adj, seed=seed0 + s + 77, mean=5.0)
        rng = np.random.default_rng(seed0 + s)
        speeds = rng.uniform(0.5, 2.0, k)
        problems.append(make_problem(c, b, speeds / speeds.sum(), mu=8.0))
        r0s.append(jnp.asarray(rng.integers(0, k, n), jnp.int32))
    return problems, r0s


def _tree_equal_at(tree_loop, tree_batch, index: int, context: str):
    flat_l = jax.tree_util.tree_leaves_with_path(tree_loop)
    flat_b = jax.tree.leaves(tree_batch)
    assert len(flat_l) == len(flat_b)
    for (path, a), b in zip(flat_l, flat_b):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)[index],
            err_msg=f"{context}[{index}] diverged at "
                    f"{jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# stacking primitives
# ---------------------------------------------------------------------------

def test_stack_problems_is_a_problem_with_leading_axis():
    problems, _ = _mixed_problems(3)
    stacked = stack_problems(problems)
    assert stacked.adjacency.shape == (3, 40, 40)
    assert stacked.node_weights.shape == (3, 40)
    assert stacked.speeds.shape == (3, 4)
    assert stacked.mu.shape == (3,)
    assert batch_size(stacked) == 3
    elem = unstack_pytree(stacked, 1)
    np.testing.assert_array_equal(np.asarray(elem.adjacency),
                                  np.asarray(problems[1].adjacency))


def test_stack_problems_rejects_mixed_shapes():
    a, _ = _mixed_problems(1, n=16)
    b, _ = _mixed_problems(1, n=24)
    with pytest.raises(ValueError, match="one shape signature"):
        stack_problems(a + b)


def test_stack_pytrees_empty_raises():
    with pytest.raises(ValueError):
        stack_pytrees([])


# ---------------------------------------------------------------------------
# vmap-vs-loop bitwise: all three refinement entry points
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("framework", ["c", "ct"])
@pytest.mark.parametrize("theta_on", [False, True])
def test_refine_traced_batched_bitwise(framework, theta_on):
    problems, r0s = _mixed_problems(4)
    stacked = stack_problems(problems)
    r0 = jnp.stack(r0s)
    theta = None
    thetas = [None] * 4
    if theta_on:
        thetas = [np.random.default_rng(9 + i).uniform(0, 3, 40)
                  for i in range(4)]
        theta = jnp.stack([jnp.asarray(t, jnp.float32) for t in thetas])
    res_b, tr_b = refine_traced_batched(stacked, r0, framework,
                                        max_turns=96, theta=theta)
    for i in range(4):
        res_l, tr_l = refine_traced(problems[i], r0s[i], framework,
                                    max_turns=96, theta=thetas[i])
        for field in ("moved", "node", "source", "dest", "gain", "active"):
            np.testing.assert_array_equal(
                np.asarray(getattr(tr_l, field)),
                np.asarray(getattr(tr_b, field))[i],
                err_msg=f"trace.{field} diverged for element {i}")
        np.testing.assert_array_equal(np.asarray(res_l.assignment),
                                      np.asarray(res_b.assignment)[i])
        np.testing.assert_array_equal(np.asarray(res_l.loads),
                                      np.asarray(res_b.loads)[i])
        for pot in ("c0", "ct0"):
            a = np.asarray(getattr(tr_l, pot), np.float64)
            b = np.asarray(getattr(tr_b, pot), np.float64)[i]
            rel = np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-9))
            assert rel <= POTENTIAL_TOL, (pot, i, rel)


@pytest.mark.parametrize("framework", ["c", "ct"])
def test_refine_batched_bitwise(framework):
    problems, r0s = _mixed_problems(4, seed0=20)
    stacked = stack_problems(problems)
    res_b = refine_batched(stacked, jnp.stack(r0s), framework,
                           max_turns=2000)
    for i in range(4):
        res_l = refine(problems[i], r0s[i], framework, max_turns=2000)
        _tree_equal_at(res_l, res_b, i, f"refine[{framework}]")
    assert np.asarray(res_b.converged).all()


def test_refine_batched_scalar_theta_broadcasts():
    problems, r0s = _mixed_problems(3, seed0=31)
    stacked = stack_problems(problems)
    res_b = refine_batched(stacked, jnp.stack(r0s), "c", max_turns=2000,
                           theta=2.5)
    for i in range(3):
        res_l = refine(problems[i], r0s[i], "c", max_turns=2000, theta=2.5)
        _tree_equal_at(res_l, res_b, i, "refine[theta-scalar]")


@pytest.mark.parametrize("framework", ["c", "ct"])
def test_refine_simultaneous_batched_bitwise(framework):
    problems, r0s = _mixed_problems(4, seed0=40)
    stacked = stack_problems(problems)
    res_b, (c0_b, ct0_b, act_b) = refine_simultaneous_batched(
        stacked, jnp.stack(r0s), framework, max_sweeps=48)
    for i in range(4):
        res_l, (c0_l, ct0_l, act_l) = refine_simultaneous(
            problems[i], r0s[i], framework, max_sweeps=48)
        _tree_equal_at(res_l, res_b, i, f"simultaneous[{framework}]")
        np.testing.assert_array_equal(np.asarray(act_l),
                                      np.asarray(act_b)[i])
        for name, a, b in (("c0", c0_l, c0_b), ("ct0", ct0_l, ct0_b)):
            aa = np.asarray(a, np.float64)
            bb = np.asarray(b, np.float64)[i]
            rel = np.max(np.abs(aa - bb) / np.maximum(np.abs(aa), 1e-9))
            assert rel <= POTENTIAL_TOL, (name, i, rel)


# ---------------------------------------------------------------------------
# multi-move probabilistic sweeps (DESIGN.md §17): conformance suite
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("framework", ["c", "ct"])
@pytest.mark.parametrize("theta", [None, 0.5])
def test_refine_sweeps_degenerate_bitwise(framework, theta,
                                          simultaneous_ref):
    """moves_per_machine=1, move_prob=1, epsilon=0 is the §4.5 mode: the
    whole result — assignment, loads, move counts, the per-sweep potential
    traces — must equal the mode's recorded move sequence bitwise, through
    refine_sweeps and through refine_simultaneous alike."""
    problems, r0s = _mixed_problems(3, seed0=60)
    for i, (prob, r0) in enumerate(zip(problems, r0s)):
        prefix = f"dense/{framework}/{theta}/{i}"
        simultaneous_ref(prefix, *refine_sweeps(
            prob, r0, framework, max_sweeps=48, theta=theta))
        simultaneous_ref(prefix, *refine_simultaneous(
            prob, r0, framework, max_sweeps=48, theta=theta))


@pytest.mark.parametrize("framework", ["c", "ct"])
def test_refine_sweeps_multimove_descends(framework):
    """Elected multi-move sweeps (M=2, flat coin) under a fixed seed reach
    an equilibrium below the starting potential — the §17.1 expected-drop
    argument, checked empirically per DESIGN.md §17."""
    problems, r0s = _mixed_problems(3, seed0=70)
    for i, (prob, r0) in enumerate(zip(problems, r0s)):
        res, (c0s, ct0s, active) = refine_sweeps(
            prob, r0, framework, max_sweeps=256, moves_per_machine=2,
            move_prob=0.5, epsilon=1e-3, key=jax.random.PRNGKey(100 + i))
        assert bool(res.converged), f"element {i} did not converge"
        pots = np.asarray(c0s if framework == "c" else ct0s, np.float64)
        start = float(costs.global_cost(prob, r0, framework))
        n_active = int(np.asarray(active).sum())
        assert n_active >= 1
        assert pots[n_active - 1] < start
        # descent in expectation: the mean per-sweep drop over the active
        # prefix is strictly negative (individual sweeps may ascend)
        if n_active >= 2:
            assert (pots[n_active - 1] - pots[0]) / (n_active - 1) < 0.0


@pytest.mark.parametrize("framework", ["c", "ct"])
def test_refine_sweeps_batched_bitwise(framework):
    """Probabilistic multi-move fleets == looped per-element, coins
    included: each element folds its own key, so the batched coin
    sequences are the looped ones."""
    problems, r0s = _mixed_problems(4, seed0=80)
    stacked = stack_problems(problems)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    res_b, (c0_b, ct0_b, act_b) = refine_sweeps_batched(
        stacked, jnp.stack(r0s), framework, max_sweeps=96,
        moves_per_machine=2, move_prob=0.5, epsilon=1e-3, keys=keys)
    for i in range(4):
        res_l, (c0_l, ct0_l, act_l) = refine_sweeps(
            problems[i], r0s[i], framework, max_sweeps=96,
            moves_per_machine=2, move_prob=0.5, epsilon=1e-3, key=keys[i])
        _tree_equal_at(res_l, res_b, i, f"sweeps[{framework}]")
        np.testing.assert_array_equal(np.asarray(act_l),
                                      np.asarray(act_b)[i])
        for name, a, b in (("c0", c0_l, c0_b), ("ct0", ct0_l, ct0_b)):
            aa = np.asarray(a, np.float64)
            bb = np.asarray(b, np.float64)[i]
            rel = np.max(np.abs(aa - bb) / np.maximum(np.abs(aa), 1e-9))
            assert rel <= POTENTIAL_TOL, (name, i, rel)


def test_refine_sweeps_batched_requires_keys():
    problems, r0s = _mixed_problems(2, seed0=80)
    stacked = stack_problems(problems)
    with pytest.raises(ValueError, match="keys"):
        refine_sweeps_batched(stacked, jnp.stack(r0s), "c", move_prob=0.5)


@pytest.mark.parametrize("moves_per_machine", [None, 1, 4],
                         ids=["unbounded", "m1", "m4"])
def test_refine_sweeps_stops_at_convergence(moves_per_machine):
    """The sweep loop stops after the first sweep with no candidate.  Its
    per-sweep outputs keep length max_sweeps, padded with what the sweeps
    not run would write (the final potentials, inactive), so a run capped
    at exactly the sweeps it needs gives the same result, and each batch
    element stops at its own sweep."""
    problems, r0s = _mixed_problems(3, seed0=110)
    keys = jax.random.split(jax.random.PRNGKey(110), 3)
    kw = dict(moves_per_machine=moves_per_machine, move_prob=0.5,
              epsilon=1e-3)
    res_b, _ = refine_sweeps_batched(stack_problems(problems),
                                     jnp.stack(r0s), "c", max_sweeps=256,
                                     keys=keys, **kw)
    for i, (prob, r0, key) in enumerate(zip(problems, r0s, keys)):
        res, outs = refine_sweeps(prob, r0, "c", max_sweeps=256, key=key,
                                  **kw)
        turns, ran = int(res.num_turns), int(res.num_sweeps)
        assert bool(res.converged)
        assert ran == turns + 1 < 256
        assert int(np.asarray(res_b.num_sweeps)[i]) == ran
        c0s, ct0s, active = (np.asarray(o) for o in outs)
        assert c0s.shape == ct0s.shape == active.shape == (256,)
        assert active[:turns].all() and not active[turns:].any()

        exact, exact_outs = refine_sweeps(prob, r0, "c", max_sweeps=ran,
                                          key=key, **kw)
        assert int(exact.num_sweeps) == ran and bool(exact.converged)
        for name in ("assignment", "loads", "num_moves", "num_turns"):
            np.testing.assert_array_equal(np.asarray(getattr(res, name)),
                                          np.asarray(getattr(exact, name)),
                                          err_msg=name)
        for full, short in zip((c0s, ct0s, active), exact_outs):
            np.testing.assert_array_equal(full[:ran], np.asarray(short))
        for pots in (c0s, ct0s):      # the final carried value, repeated
            np.testing.assert_array_equal(pots[ran - 1:], pots[ran - 1])

        capped, capped_outs = refine_sweeps(prob, r0, "c", max_sweeps=3,
                                            key=key, **kw)
        assert int(capped.num_sweeps) == 3 and not bool(capped.converged)
        np.testing.assert_array_equal(np.asarray(capped_outs[0]), c0s[:3])


# ---------------------------------------------------------------------------
# the SweepSpec -> SweepResult runtime
# ---------------------------------------------------------------------------

def _mixed_cases(num: int = 6):
    problems, r0s = _mixed_problems(num, seed0=50)
    return [sweeps.SweepCase(
        problem=p, assignment=r,
        framework="c" if i % 2 == 0 else "ct",
        theta=None if i % 3 == 0 else float(i),
        label=f"case{i}") for i, (p, r) in enumerate(zip(problems, r0s))]


def test_run_sweep_groups_and_preserves_case_order():
    cases = _mixed_cases()
    res = sweeps.run_sweep(sweeps.make_spec(cases, mode="traced",
                                            max_turns=64))
    assert len(res) == len(cases)
    # every case's result must equal ITS OWN looped run (ordering survived
    # the group-by-static round trip)
    for i, case in enumerate(cases):
        res_l, tr_l = refine_traced(case.problem,
                                    jnp.asarray(case.assignment, jnp.int32),
                                    case.framework, max_turns=64,
                                    theta=case.theta)
        np.testing.assert_array_equal(np.asarray(res_l.assignment),
                                      np.asarray(res.results[i].assignment),
                                      err_msg=case.label)
        np.testing.assert_array_equal(np.asarray(tr_l.node),
                                      np.asarray(res.traces[i].node),
                                      err_msg=case.label)
    labels = [s["label"] for s in res.summary()]
    assert labels == [c.label for c in cases]


def test_run_sweep_refine_mode_kernel_matches_jnp():
    cases = [c for c in _mixed_cases() if c.theta is None]
    jnp_res = sweeps.run_sweep(sweeps.make_spec(cases, mode="refine",
                                                max_turns=2000))
    ker_res = sweeps.run_sweep(sweeps.make_spec(cases, mode="refine",
                                                max_turns=2000,
                                                use_kernel=True))
    np.testing.assert_array_equal(jnp_res.assignments, ker_res.assignments)
    np.testing.assert_array_equal(jnp_res.moves, ker_res.moves)


def test_run_sweep_simultaneous_mode_and_potentials():
    cases = _mixed_cases(4)
    res = sweeps.run_sweep(sweeps.make_spec(cases, mode="simultaneous",
                                            max_turns=32))
    c0, ct0 = res.final_potentials()
    assert c0.shape == (4,) and np.isfinite(c0).all()
    assert ct0.shape == (4,) and np.isfinite(ct0).all()


def test_run_sweep_multimove_mode_matches_looped():
    """Fleet multimove results == looped refine_sweeps with the per-case
    fold_in key, regardless of how the runtime groups the cases."""
    cases = _mixed_cases(4)
    spec = sweeps.make_spec(cases, mode="multimove", max_turns=96,
                            moves_per_machine=2, move_prob=0.5,
                            epsilon=1e-3, seed=11)
    res = sweeps.run_sweep(spec)
    for i, case in enumerate(cases):
        key = jax.random.fold_in(jax.random.PRNGKey(11), i)
        res_l, _ = refine_sweeps(
            case.problem, jnp.asarray(case.assignment, jnp.int32),
            case.framework, max_sweeps=96, theta=case.theta,
            moves_per_machine=2, move_prob=0.5, epsilon=1e-3, key=key)
        np.testing.assert_array_equal(np.asarray(res_l.assignment),
                                      np.asarray(res.results[i].assignment),
                                      err_msg=case.label)
        assert int(res_l.num_moves) == int(res.results[i].num_moves), \
            case.label
    c0, ct0 = res.final_potentials()
    assert np.isfinite(c0).all() and np.isfinite(ct0).all()


def test_sweep_spec_validation():
    cases = _mixed_cases(2)
    with pytest.raises(ValueError, match="unknown sweep mode"):
        sweeps.make_spec(cases, mode="bogus")
    with pytest.raises(ValueError, match="use_kernel"):
        sweeps.make_spec(cases, mode="traced", use_kernel=True)
    with pytest.raises(ValueError, match="multimove"):
        sweeps.make_spec(cases, mode="traced", move_prob=0.5)
    with pytest.raises(ValueError, match="multimove"):
        sweeps.make_spec(cases, mode="simultaneous", moves_per_machine=None)


def test_sweep_metrics_cv_and_trace():
    cases = _mixed_cases(3)
    res = sweeps.run_sweep(sweeps.make_spec(cases, mode="traced",
                                            max_turns=96))
    cv = res.load_cv()
    assert cv.shape == (3,) and (cv >= 0).all()
    traces = res.load_cv_traces()
    for i, tr in enumerate(traces):
        assert tr.shape == (96,)
        # replayed final CV agrees with the device loads' CV (f64 replay
        # vs f32 carry: close, not bitwise)
        np.testing.assert_allclose(tr[-1], cv[i], rtol=1e-4, atol=1e-6)
    # refinement descends load imbalance in these instances
    assert np.all([t[-1] <= t[0] + 1e-9 for t in traces])


def test_metrics_load_cv_balanced_is_zero():
    assert sweeps.load_cv(np.array([2.0, 1.0]), np.array([2.0, 1.0])) == 0.0
    out = sweeps.load_cv(np.array([[1.0, 1.0], [3.0, 1.0]]),
                         np.array([1.0, 1.0]))
    assert out[0] == 0.0 and out[1] > 0.0


# ---------------------------------------------------------------------------
# batched DES engine
# ---------------------------------------------------------------------------

def _des_fixture(n=16, k=3, threads=6, refine_freq=60, theta_scale=5.0,
                 freeze=0.25):
    adj = preferential_attachment(n, 3, m=2)
    deg = int((adj > 0).sum(1).max())
    spec = flooded_packet_workload(adj, 7, num_threads=threads,
                                   num_windows=2, scope=2,
                                   window_sim_time=30.0, max_per_lp=3)
    cfg = DESConfig(
        num_lps=n, num_machines=k, num_threads=threads,
        event_capacity=max(32, 2 * deg + 8),
        history_capacity=max(64, 4 * deg + 16),
        inter_delay=5, intra_delay=1, trace_stride=10, max_ticks=8_000,
        machine_speeds=(1.0, 0.7, 0.5)[:k],
        refine_freq=refine_freq, refine_theta_scale=theta_scale,
        migration_freeze=freeze)
    m0 = jnp.asarray(np.arange(n) % k, jnp.int32)
    state0 = make_initial_state(cfg, m0, spec.src, spec.time, spec.count)
    return jnp.asarray(adj, jnp.float32), cfg, state0


def _des_scheds(k=3):
    base = (1.0, 0.7, 0.5)[:k]
    return [scenarios.constant(k, base),
            scenarios.slowdown(k, machine=0, at_tick=90, factor=0.3,
                               recover_tick=300, base=base),
            scenarios.random_churn(k, num_segments=3, segment_ticks=120,
                                   seed=3, low=0.3, high=1.0)]


def test_des_batch_bitwise_with_refine_theta_freeze():
    adjj, cfg, state0 = _des_fixture()
    scheds = _des_scheds()
    stacked = scenarios.stack_schedules(scheds)
    padded = [scenarios.pad_segments(s, int(stacked.times.shape[1]))
              for s in scheds]
    states = stack_pytrees([state0] * len(scheds))
    adjs = jnp.stack([adjj] * len(scheds))
    outb = run_simulation_batch(cfg, adjs, states, stacked)
    for i, sched in enumerate(padded):
        out_l = run_simulation(cfg, adjj, state0, sched)
        assert bool(out_l.done)
        _tree_equal_at(out_l, outb, i, "des")


def test_des_batch_no_schedules_no_refine():
    adjj, cfg0, state0 = _des_fixture(refine_freq=0, theta_scale=0.0,
                                      freeze=0.0)
    states = stack_pytrees([state0] * 2)
    adjs = jnp.stack([adjj] * 2)
    outb = run_simulation_batch(cfg0, adjs, states, None, chunk=64)
    out_l = run_simulation(cfg0, adjj, state0, None)
    assert bool(out_l.done)
    for i in range(2):
        _tree_equal_at(out_l, outb, i, "des-noref")


def test_pad_segments_preserves_speeds_at():
    sched = scenarios.slowdown(3, machine=1, at_tick=50, factor=0.5,
                               recover_tick=120)
    padded = scenarios.pad_segments(sched, 6)
    assert padded.times.shape == (6,)
    for tick in (0, 49, 50, 119, 120, 5000):
        np.testing.assert_array_equal(
            np.asarray(scenarios.speeds_at(sched, jnp.int32(tick))),
            np.asarray(scenarios.speeds_at(padded, jnp.int32(tick))))
    with pytest.raises(ValueError):
        scenarios.pad_segments(padded, 2)


def test_stack_schedules_shapes_and_mismatch():
    scheds = _des_scheds()
    stacked = scenarios.stack_schedules(scheds)
    assert stacked.times.shape[0] == 3
    assert stacked.speeds.shape[0] == 3
    assert stacked.times.shape[1] == stacked.speeds.shape[1]
    with pytest.raises(ValueError, match="machine count"):
        scenarios.stack_schedules([scenarios.constant(2),
                                   scenarios.constant(3)])
    with pytest.raises(ValueError):
        scenarios.stack_schedules([])


def test_sweep_time_averaged_cv():
    flat = np.ones((5, 4))
    assert sweeps.time_averaged_cv(flat) == 0.0
    skew = np.array([[4.0, 0.0, 0.0, 0.0]] * 5)
    assert sweeps.time_averaged_cv(skew) > 1.0
    assert sweeps.time_averaged_cv(np.zeros((3, 4))) == 0.0
