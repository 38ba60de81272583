"""Incremental aggregate-state refinement vs the recompute oracle (§10).

Acceptance contract (ISSUE 2): the incremental path reproduces the
recompute path's move sequence EXACTLY and both potentials to <= 1e-3
relative over a 512-turn trace, for both cost frameworks; the
``verify_every`` cross-check observes only f32-drift-sized deviations.

Plus targeted coverage for ``count_discrepancies`` (ascent counting under
both frameworks, rel_tol edge cases) that ISSUE 2 calls out as missing.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aggregate as agg_mod
from repro.core import costs
from repro.core.problem import machine_loads
from repro.core.refine import (Trace, count_discrepancies, refine,
                               refine_simultaneous, refine_traced)

from conftest import small_problem

AGREE_TOL = 1e-3


def _rand_assignment(prob, seed):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, prob.num_machines, prob.num_nodes), jnp.int32)


# ---------------------------------------------------------------------------
# acceptance: incremental == recompute (moves exact, potentials <= 1e-3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("framework", costs.FRAMEWORKS)
def test_traced_incremental_matches_recompute(framework, paper_problem):
    """512-turn trace: identical move sequence, potentials <= 1e-3 rel."""
    adj, prob = paper_problem
    r0 = _rand_assignment(prob, 42)
    res_i, tr_i = refine_traced(prob, r0, framework, max_turns=512)
    res_r, tr_r = refine_traced(prob, r0, framework, max_turns=512,
                                incremental=False)
    for field in ("moved", "node", "source", "dest", "active"):
        np.testing.assert_array_equal(
            np.asarray(getattr(tr_i, field)),
            np.asarray(getattr(tr_r, field)), err_msg=field)
    np.testing.assert_array_equal(np.asarray(res_i.assignment),
                                  np.asarray(res_r.assignment))
    assert int(res_i.num_moves) == int(res_r.num_moves)
    assert int(res_i.num_turns) == int(res_r.num_turns)
    for pot in ("c0", "ct0"):
        a = np.asarray(getattr(tr_i, pot), np.float64)
        b = np.asarray(getattr(tr_r, pot), np.float64)
        rel = np.max(np.abs(a - b) / np.abs(b))
        assert rel <= AGREE_TOL, f"{pot} drifted {rel:.2e}"


@pytest.mark.parametrize("framework", costs.FRAMEWORKS)
def test_refine_incremental_matches_recompute(framework, paper_problem):
    adj, prob = paper_problem
    r0 = _rand_assignment(prob, 7)
    res_i = refine(prob, r0, framework)
    res_r = refine(prob, r0, framework, incremental=False)
    np.testing.assert_array_equal(np.asarray(res_i.assignment),
                                  np.asarray(res_r.assignment))
    assert int(res_i.num_moves) == int(res_r.num_moves)
    assert int(res_i.num_turns) == int(res_r.num_turns)
    np.testing.assert_allclose(np.asarray(res_i.loads),
                               np.asarray(res_r.loads), rtol=1e-5)


def test_traced_incremental_potentials_vs_true_costs(paper_problem):
    """Carried potentials track the TRUE global costs of the evolving
    assignment (replayed from the move sequence) to <= 1e-3 relative —
    a stronger check than recompute-trace agreement because the oracle
    here is evaluated per-prefix from the original problem."""
    adj, prob = paper_problem
    r0 = _rand_assignment(prob, 3)
    res, tr = refine_traced(prob, r0, "c", max_turns=256)
    r = np.asarray(r0).copy()
    moved = np.asarray(tr.moved)
    nodes = np.asarray(tr.node)
    dests = np.asarray(tr.dest)
    check_at = [0, 1, 5, 25, 100, 255]
    for t in range(256):
        if moved[t]:
            r[nodes[t]] = dests[t]
        if t in check_at:
            np.testing.assert_allclose(
                float(tr.c0[t]),
                float(costs.global_cost_c0(prob, jnp.asarray(r))),
                rtol=AGREE_TOL, err_msg=f"c0 at turn {t}")
            np.testing.assert_allclose(
                float(tr.ct0[t]),
                float(costs.global_cost_ct0(prob, jnp.asarray(r))),
                rtol=AGREE_TOL, err_msg=f"ct0 at turn {t}")


# ---------------------------------------------------------------------------
# AggregateState invariants + verify_every cross-check
# ---------------------------------------------------------------------------

def test_apply_move_invariants():
    """After a chain of unilateral moves: aggregate == rebuilt, loads exact,
    potentials match the global definitions (I1-I3 of DESIGN.md §10)."""
    adj, prob = small_problem(n=30, k=4, seed=11)
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.integers(0, 4, 30), jnp.int32)
    total_b = jnp.sum(prob.node_weights)
    agg = agg_mod.init_aggregate_state(prob, r)
    for step in range(40):
        node = jnp.asarray(int(rng.integers(0, 30)), jnp.int32)
        dest = jnp.asarray(int(rng.integers(0, 4)), jnp.int32)
        source = agg.assignment[node]
        do_move = source != dest
        agg = agg_mod.apply_move(prob, agg, node, source, dest, do_move,
                                 total_b)
    fresh = agg_mod.init_aggregate_state(prob, agg.assignment)
    np.testing.assert_allclose(np.asarray(agg.aggregate),
                               np.asarray(fresh.aggregate),
                               rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(agg.loads), np.asarray(fresh.loads),
                               rtol=1e-5)
    np.testing.assert_allclose(float(agg.c0), float(fresh.c0), rtol=AGREE_TOL)
    np.testing.assert_allclose(float(agg.ct0), float(fresh.ct0),
                               rtol=AGREE_TOL)
    # drift/resync report the same deviation the asserts above bound
    assert float(agg_mod.drift(prob, agg)) < 1.0


def test_exact_potential_identity_deltas():
    """potential_deltas == the brute-force global-cost differences
    (Thm. 3.1 / 5.1 — the identities the incremental trace relies on)."""
    adj, prob = small_problem(n=24, k=3, seed=2)
    rng = np.random.default_rng(9)
    r = jnp.asarray(rng.integers(0, 3, 24), jnp.int32)
    total_b = jnp.sum(prob.node_weights)
    agg = agg_mod.init_aggregate_state(prob, r)
    for node, dest in [(0, 1), (5, 2), (17, 0), (23, 2)]:
        node = jnp.asarray(node, jnp.int32)
        dest = jnp.asarray(dest, jnp.int32)
        source = r[node]
        dc0, dct0 = agg_mod.potential_deltas(
            agg.aggregate[node], prob.node_weights[node], source, dest,
            agg.loads, prob.speeds, prob.mu, total_b)
        r_new = r.at[node].set(dest)
        np.testing.assert_allclose(
            float(dc0),
            float(costs.global_cost_c0(prob, r_new)
                  - costs.global_cost_c0(prob, r)), rtol=1e-3, atol=5e-2)
        np.testing.assert_allclose(
            float(dct0),
            float(costs.global_cost_ct0(prob, r_new)
                  - costs.global_cost_ct0(prob, r)), rtol=1e-3, atol=5e-2)


def test_verify_every_bounds_drift(paper_problem):
    """The verify_every cross-check: observed drift is f32-noise-sized and
    the resynced run still reproduces the recompute oracle exactly."""
    adj, prob = paper_problem
    r0 = _rand_assignment(prob, 42)
    res_v, tr_v = refine_traced(prob, r0, "c", max_turns=512,
                                verify_every=64)
    # drift at the checkpoints is tiny relative to the O(1e6) potentials /
    # O(1e3) aggregate entries involved
    assert float(res_v.aggregate_drift) < 1.0
    res_r, tr_r = refine_traced(prob, r0, "c", max_turns=512,
                                incremental=False)
    np.testing.assert_array_equal(np.asarray(tr_v.node), np.asarray(tr_r.node))
    np.testing.assert_array_equal(np.asarray(res_v.assignment),
                                  np.asarray(res_r.assignment))
    # while_loop driver exposes the same knob
    res_w = refine(prob, r0, "c", verify_every=64)
    assert float(res_w.aggregate_drift) < 1.0
    np.testing.assert_array_equal(np.asarray(res_w.assignment),
                                  np.asarray(res_r.assignment))


def test_cut_from_aggregate_identity():
    """Invariant I4: the O(N) cut identity equals the O(N^2) definition."""
    adj, prob = small_problem(n=28, k=3, seed=4)
    r = jnp.asarray(np.random.default_rng(1).integers(0, 3, 28), jnp.int32)
    agg = costs.adjacency_aggregate(prob.adjacency, r, 3)
    np.testing.assert_allclose(
        float(agg_mod.cut_from_aggregate(agg, r)),
        float(costs.total_cut(prob.adjacency, r)), rtol=1e-5)


def test_machine_loads_float32_error_stays_small_at_a_million_nodes():
    """A million float32 weights summed into five loads: a serial
    scatter-add drifts by tens of load units here, enough to move a
    node's float32 gain past the ε-equilibrium allowance; the loads must
    stay within one load unit of float64."""
    rng = np.random.default_rng(0)
    n, k = 1 << 20, 5
    b = rng.uniform(0.0, 10.0, n).astype(np.float32)
    r = rng.integers(0, k, n).astype(np.int32)
    exact = np.bincount(r, b.astype(np.float64), minlength=k)
    loads = np.asarray(jax.jit(machine_loads, static_argnums=2)(b, r, k),
                       np.float64)
    assert np.abs(loads - exact).max() < 1.0


def test_potentials_closed_form_matches_global():
    adj, prob = small_problem(n=26, k=4, seed=8)
    r = jnp.asarray(np.random.default_rng(2).integers(0, 4, 26), jnp.int32)
    b = prob.node_weights
    loads = machine_loads(b, r, 4)
    sq_loads = machine_loads(b * b, r, 4)
    cut = costs.total_cut(prob.adjacency, r)
    c0, ct0 = agg_mod.potentials_closed_form(loads, sq_loads, cut,
                                             prob.speeds, prob.mu,
                                             jnp.sum(b))
    np.testing.assert_allclose(float(c0),
                               float(costs.global_cost_c0(prob, r)),
                               rtol=1e-4)
    np.testing.assert_allclose(float(ct0),
                               float(costs.global_cost_ct0(prob, r)),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# §4.5 simultaneous mode: honest move accounting + carried potentials
# ---------------------------------------------------------------------------

def test_simultaneous_counts_actual_moves(paper_problem):
    """num_moves is sum(will_move) per sweep, not the K*sweeps bound."""
    adj, prob = paper_problem
    k = prob.num_machines
    # perturb an equilibrium by one node: the fixup needs FAR fewer than
    # K moves per sweep, which the old upper-bound accounting reported
    eq = refine(prob, _rand_assignment(prob, 1), "c").assignment
    r_pert = eq.at[0].set((eq[0] + 1) % k)
    res, (c0s, ct0s, active) = refine_simultaneous(prob, r_pert, "c")
    assert int(res.num_turns) >= 1
    assert int(res.num_moves) >= 1
    assert int(res.num_moves) < k * int(res.num_turns), \
        "num_moves still reports the K*sweeps upper bound"


def test_simultaneous_potentials_match_assignment(paper_problem):
    """The per-sweep closed-form potentials equal the true global costs of
    the final assignment."""
    adj, prob = paper_problem
    r0 = _rand_assignment(prob, 5)
    res, (c0s, ct0s, active) = refine_simultaneous(prob, r0, "c")
    np.testing.assert_allclose(
        float(c0s[-1]), float(costs.global_cost_c0(prob, res.assignment)),
        rtol=1e-4)
    np.testing.assert_allclose(
        float(ct0s[-1]), float(costs.global_cost_ct0(prob, res.assignment)),
        rtol=1e-4)


# ---------------------------------------------------------------------------
# count_discrepancies coverage (both frameworks, rel_tol edges)
# ---------------------------------------------------------------------------

def _mk_trace(moved, c0, ct0):
    n = len(moved)
    return Trace(moved=jnp.asarray(moved),
                 node=jnp.zeros(n, jnp.int32),
                 source=jnp.zeros(n, jnp.int32),
                 dest=jnp.zeros(n, jnp.int32),
                 gain=jnp.zeros(n),
                 c0=jnp.asarray(c0, jnp.float32),
                 ct0=jnp.asarray(ct0, jnp.float32),
                 active=jnp.ones(n, bool))


def test_count_discrepancies_c_framework_counts_ct0_ascents():
    """Criterion C_i -> ascents of the OTHER potential (Ct_0) count."""
    tr = _mk_trace([True, True, True, False],
                   c0=[10.0, 9.0, 8.0, 8.0],
                   ct0=[5.0, 6.0, 7.0, 7.0])       # two Ct_0 ascents
    n = count_discrepancies(tr, costs.C_FRAMEWORK,
                            initial_other=jnp.asarray(5.5))
    assert int(n) == 2


def test_count_discrepancies_ct_framework_counts_c0_ascents():
    tr = _mk_trace([True, True, False, True],
                   c0=[10.0, 12.0, 12.0, 11.0],    # ascent at turn 1
                   ct0=[5.0, 4.0, 4.0, 3.0])
    n = count_discrepancies(tr, costs.CT_FRAMEWORK,
                            initial_other=jnp.asarray(11.0))
    assert int(n) == 1


def test_count_discrepancies_ignores_unmoved_turns():
    """An ascent on a forsaken turn is bookkeeping noise, never counted."""
    tr = _mk_trace([False, False],
                   c0=[10.0, 20.0], ct0=[1.0, 2.0])
    for fw in costs.FRAMEWORKS:
        assert int(count_discrepancies(tr, fw,
                                       initial_other=jnp.asarray(1.0))) == 0


def test_count_discrepancies_rel_tol_edges():
    """Ascents right at the threshold: counted iff delta > rel_tol*|prev|."""
    base = 1000.0
    just_below = base * (1 + 0.5e-4)       # 0.005% — below default 1e-4
    just_above = base * (1 + 5e-4)         # 0.05%  — above default 1e-4
    tr = _mk_trace([True, True],
                   c0=[just_below, just_above],
                   ct0=[1.0, 1.0])
    n_default = count_discrepancies(tr, costs.CT_FRAMEWORK,
                                    initial_other=jnp.asarray(base))
    assert int(n_default) == 1             # only the 0.05% ascent
    n_loose = count_discrepancies(tr, costs.CT_FRAMEWORK,
                                  initial_other=jnp.asarray(base),
                                  rel_tol=1e-5)
    assert int(n_loose) == 2               # both exceed 0.001%
    n_strict = count_discrepancies(tr, costs.CT_FRAMEWORK,
                                   initial_other=jnp.asarray(base),
                                   rel_tol=1e-2)
    assert int(n_strict) == 0              # neither exceeds 1%


def test_count_discrepancies_negative_potentials():
    """rel_tol scales by |prev| — correct sign handling for negative Ct_0
    values (the Ct load term can be negative at small mu)."""
    tr = _mk_trace([True], c0=[1.0], ct0=[-99.0])
    # prev = -100 -> threshold |prev|*1e-4 = 0.01; delta = +1.0 counts
    n = count_discrepancies(tr, costs.C_FRAMEWORK,
                            initial_other=jnp.asarray(-100.0))
    assert int(n) == 1
