"""Sparse edge-list runtime (DESIGN.md §13): round trips, aggregate
invariants, refinement agreement with the dense path, batched sweeps and
the fused edge-block kernel."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import costs
from repro.core.aggregate import apply_move, init_aggregate_state
from repro.core.batch import problem_shape_key, stack_problems
from repro.core.problem import make_problem, make_state
from repro.core.refine import (refine, refine_simultaneous, refine_sweeps,
                               refine_traced)
from repro.core.sparse import (SparseProblem, dense_from_sparse,
                               make_sparse_problem, node_incident_edges,
                               sparse_from_dense)
from repro.graphs.generators import (random_degree_graph,
                                     random_degree_graph_edges,
                                     random_weights, random_weights_edges)
from repro import sweeps


def _instance(n=60, k=4, seed=0):
    adj = random_degree_graph(n, seed=seed, dmin=2, dmax=4)
    b, c = random_weights(adj, seed=seed + 1, mean=5.0)
    prob = make_problem(c, b, np.linspace(0.5, 2.0, k), mu=8.0)
    r0 = jnp.asarray(np.random.default_rng(seed + 2).integers(0, k, n),
                     jnp.int32)
    return prob, sparse_from_dense(prob), r0


# ---------------------------------------------------------------------------
# representation: round trips, layout invariants
# ---------------------------------------------------------------------------

def test_dense_sparse_dense_round_trip_exact():
    prob, sp, _ = _instance()
    back = dense_from_sparse(sp)
    np.testing.assert_array_equal(np.asarray(back.adjacency),
                                  np.asarray(prob.adjacency))
    np.testing.assert_array_equal(np.asarray(back.node_weights),
                                  np.asarray(prob.node_weights))
    np.testing.assert_array_equal(np.asarray(back.speeds),
                                  np.asarray(prob.speeds))


@given(n=st.integers(6, 40), seed=st.integers(0, 5_000))
@settings(max_examples=10)
def test_round_trip_property(n, seed):
    adj = random_degree_graph(n, seed=seed, dmin=1, dmax=3)
    b, c = random_weights(adj, seed=seed + 7, mean=5.0)
    prob = make_problem(c, b, np.ones(3) / 3, mu=4.0)
    back = dense_from_sparse(sparse_from_dense(prob))
    np.testing.assert_array_equal(np.asarray(back.adjacency),
                                  np.asarray(prob.adjacency))


def test_sparse_layout_invariants():
    _, sp, _ = _instance()
    sp.validate()
    s = np.asarray(sp.senders)
    r = np.asarray(sp.receivers)
    w = np.asarray(sp.edge_weights)
    rs = np.asarray(sp.row_start)
    assert np.all(np.diff(s) >= 0), "senders must be sorted"
    # directed edge count (before padding) is even: both orientations
    assert (w > 0).sum() % 2 == 0
    # row_start really is the CSR offset of each node's slab
    for node in range(sp.num_nodes):
        real = np.flatnonzero((s == node) & (w > 0))
        if real.size:
            assert real[0] == rs[node]
            assert np.all(np.diff(real) == 1)
            assert real.size <= sp.max_degree
    # padded slots are weight-0 and keep sortedness
    pad = np.flatnonzero(w == 0)
    assert np.all(s[pad] == sp.num_nodes - 1) or pad.size == 0


def test_make_sparse_problem_dedupes_and_drops_loops():
    sp = make_sparse_problem([0, 1, 0, 2], [1, 0, 0, 1],
                             [2.0, 3.0, 9.0, 1.0],
                             np.ones(3), np.ones(2), mu=1.0)
    dense = np.asarray(dense_from_sparse(sp).adjacency)
    assert dense[0, 1] == 5.0          # duplicate {0,1} weights summed
    assert dense[0, 0] == 0.0          # self loop dropped
    assert dense[1, 2] == 1.0


def test_node_incident_edges_window():
    prob, sp, _ = _instance()
    adj = np.asarray(prob.adjacency)
    for node in [0, 7, sp.num_nodes - 1]:
        nbrs, w = node_incident_edges(sp, jnp.asarray(node))
        got = np.zeros(sp.num_nodes, np.float32)
        np.add.at(got, np.asarray(nbrs), np.asarray(w))
        np.testing.assert_array_equal(got, adj[node])


# ---------------------------------------------------------------------------
# costs: aggregates, cut, potentials
# ---------------------------------------------------------------------------

def test_sparse_aggregate_matches_dense():
    prob, sp, r0 = _instance()
    a_dense = costs.adjacency_aggregate(prob.adjacency, r0,
                                        prob.num_machines)
    a_sparse = costs.adjacency_aggregate_sparse(sp, r0)
    np.testing.assert_allclose(np.asarray(a_sparse), np.asarray(a_dense),
                               rtol=1e-6, atol=1e-4)


def test_sparse_cut_and_potentials_match_dense():
    prob, sp, r0 = _instance()
    np.testing.assert_allclose(float(costs.total_cut_sparse(sp, r0)),
                               float(costs.total_cut(prob.adjacency, r0)),
                               rtol=1e-6)
    for fn in (costs.global_cost_c0, costs.global_cost_ct0):
        d, s = float(fn(prob, r0)), float(fn(sp, r0))
        assert abs(d - s) <= 1e-3 * abs(d), (fn.__name__, d, s)


def test_sparse_cost_matrix_matches_dense():
    prob, sp, r0 = _instance()
    st_ = make_state(prob, r0)
    for fw in costs.FRAMEWORKS:
        cd = np.asarray(costs.cost_matrix(prob, st_, fw), np.float64)
        cs = np.asarray(costs.cost_matrix(sp, st_, fw), np.float64)
        assert np.max(np.abs(cd - cs) / (np.abs(cd) + 1.0)) < 1e-5


# ---------------------------------------------------------------------------
# aggregate carry: I1-I4 over edge aggregates, O(deg) moves
# ---------------------------------------------------------------------------

def test_sparse_init_aggregate_invariants():
    prob, sp, r0 = _instance()
    agg = init_aggregate_state(sp, r0)
    # I1 vs the dense oracle
    np.testing.assert_allclose(
        np.asarray(agg.aggregate),
        np.asarray(costs.adjacency_aggregate(prob.adjacency, r0,
                                             prob.num_machines)),
        rtol=1e-6, atol=1e-4)
    # I2
    np.testing.assert_allclose(
        np.asarray(agg.loads),
        np.asarray(jnp.zeros(4).at[r0].add(prob.node_weights)), rtol=1e-6)
    # I3
    assert abs(float(agg.c0) - float(costs.global_cost_c0(sp, r0))) == 0.0
    assert abs(float(agg.ct0) - float(costs.global_cost_ct0(sp, r0))) == 0.0


def test_sparse_apply_move_matches_rebuild():
    _, sp, r0 = _instance()
    agg = init_aggregate_state(sp, r0)
    total_b = jnp.sum(sp.node_weights)
    node, source, dest = jnp.asarray(5), r0[5], jnp.asarray(
        (int(r0[5]) + 1) % 4)
    moved = apply_move(sp, agg, node, source, dest, jnp.asarray(True),
                       total_b)
    fresh = init_aggregate_state(sp, moved.assignment)
    np.testing.assert_allclose(np.asarray(moved.aggregate),
                               np.asarray(fresh.aggregate),
                               rtol=1e-6, atol=1e-4)
    assert abs(float(moved.c0) - float(fresh.c0)) \
        <= 1e-3 * abs(float(fresh.c0))
    # gated-off move is the identity
    frozen = apply_move(sp, agg, node, source, dest, jnp.asarray(False),
                        total_b)
    np.testing.assert_array_equal(np.asarray(frozen.assignment),
                                  np.asarray(agg.assignment))
    np.testing.assert_array_equal(np.asarray(frozen.aggregate),
                                  np.asarray(agg.aggregate))


def test_sparse_drift_small_after_refinement():
    # f32-noise-sized vs the O(1e5) carried potentials — the same bound
    # test_incremental.py pins for the dense carry
    _, sp, r0 = _instance(n=80, k=4, seed=3)
    res = refine(sp, r0, "c", verify_every=16)
    assert float(res.aggregate_drift) < 1.0


# ---------------------------------------------------------------------------
# refinement: sparse reproduces dense accepted-move sequences
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
@pytest.mark.parametrize("theta", [None, 0.5])
def test_sparse_traced_matches_dense(fw, theta):
    prob, sp, r0 = _instance(n=90, k=5, seed=2)
    res_d, tr_d = refine_traced(prob, r0, fw, max_turns=192, theta=theta)
    res_s, tr_s = refine_traced(sp, r0, fw, max_turns=192, theta=theta)
    for field in ("moved", "node", "source", "dest", "active"):
        np.testing.assert_array_equal(np.asarray(getattr(tr_s, field)),
                                      np.asarray(getattr(tr_d, field)),
                                      err_msg=field)
    np.testing.assert_array_equal(np.asarray(res_s.assignment),
                                  np.asarray(res_d.assignment))
    for pot in ("c0", "ct0"):
        a = np.asarray(getattr(tr_s, pot), np.float64)
        b = np.asarray(getattr(tr_d, pot), np.float64)
        assert np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)) <= 1e-3


def test_sparse_refine_and_simultaneous_match_dense():
    prob, sp, r0 = _instance(n=72, k=4, seed=5)
    rd = refine(prob, r0, "ct")
    rs = refine(sp, r0, "ct")
    assert int(rd.num_moves) == int(rs.num_moves)
    np.testing.assert_array_equal(np.asarray(rs.assignment),
                                  np.asarray(rd.assignment))
    rd, (c0d, _, actd) = refine_simultaneous(prob, r0, "c", max_sweeps=48)
    rs, (c0s, _, acts) = refine_simultaneous(sp, r0, "c", max_sweeps=48)
    np.testing.assert_array_equal(np.asarray(rs.assignment),
                                  np.asarray(rd.assignment))
    np.testing.assert_array_equal(np.asarray(acts), np.asarray(actd))


def test_sparse_theta_zero_matches_none_bitwise():
    _, sp, r0 = _instance(n=64, k=4, seed=9)
    res0, tr0 = refine_traced(sp, r0, "c", max_turns=128, theta=None)
    resz, trz = refine_traced(sp, r0, "c", max_turns=128, theta=0.0)
    for field in ("moved", "node", "source", "dest", "gain"):
        np.testing.assert_array_equal(np.asarray(getattr(tr0, field)),
                                      np.asarray(getattr(trz, field)))


def test_pure_edge_list_pipeline_never_densifies():
    """End to end from generators: edges -> SparseProblem -> refinement,
    no (N, N) array anywhere; sanity-checked against the densified twin."""
    n, k = 120, 4
    s, r = random_degree_graph_edges(n, seed=11)
    b, w = random_weights_edges(n, s, seed=12, mean=5.0)
    sp = make_sparse_problem(s, r, w, b, np.ones(k) / k, mu=8.0)
    r0 = jnp.asarray(np.arange(n) % k, jnp.int32)
    res_s = refine(sp, r0, "c")
    res_d = refine(dense_from_sparse(sp), r0, "c")
    assert int(res_s.num_moves) == int(res_d.num_moves)
    np.testing.assert_array_equal(np.asarray(res_s.assignment),
                                  np.asarray(res_d.assignment))
    assert bool(res_s.converged)


# ---------------------------------------------------------------------------
# batching: stacking rules + vmapped sparse fleets
# ---------------------------------------------------------------------------

def test_problem_shape_key_and_stacking():
    prob, sp, _ = _instance(seed=0)
    _, sp2, _ = _instance(seed=1)
    assert problem_shape_key(sp) == problem_shape_key(sp2)
    assert problem_shape_key(sp) != problem_shape_key(prob)
    stacked = stack_problems([sp, sp2])
    assert isinstance(stacked, SparseProblem)
    assert stacked.senders.shape == (2, sp.num_edges)
    assert stacked.max_degree == sp.max_degree
    with pytest.raises(ValueError):
        stack_problems([prob, sp])


def test_sparse_sweep_matches_looped_bitwise():
    cases, looped = [], []
    for seed in range(3):
        _, sp, r0 = _instance(n=48, k=3, seed=seed)
        cases.append(sweeps.SweepCase(problem=sp, assignment=r0,
                                      framework="c", label=f"s{seed}"))
        looped.append(refine_traced(sp, r0, "c", max_turns=96))
    res = sweeps.run_sweep(sweeps.make_spec(cases, mode="traced",
                                            max_turns=96))
    for i, (lr, lt) in enumerate(looped):
        for field in ("moved", "node", "source", "dest", "gain"):
            np.testing.assert_array_equal(
                np.asarray(getattr(res.traces[i], field)),
                np.asarray(getattr(lt, field)), err_msg=f"case {i} {field}")
        np.testing.assert_array_equal(np.asarray(res.results[i].assignment),
                                      np.asarray(lr.assignment))


def test_sparse_and_dense_cases_group_separately():
    prob, sp, r0 = _instance(seed=4)
    res = sweeps.run_sweep(sweeps.make_spec(
        [sweeps.SweepCase(problem=prob, assignment=r0, framework="c"),
         sweeps.SweepCase(problem=sp, assignment=r0, framework="c")],
        mode="refine", max_turns=512))
    np.testing.assert_array_equal(np.asarray(res.results[0].assignment),
                                  np.asarray(res.results[1].assignment))


# ---------------------------------------------------------------------------
# edge-block kernel through the dissat_fn seam
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
@pytest.mark.parametrize("theta", [None, 0.3])
def test_edge_kernel_matches_jnp_reduction(fw, theta):
    from repro.kernels.edge_block import (build_edge_tile_layout,
                                          dissatisfaction_from_edges_pallas)
    _, sp, r0 = _instance(n=150, k=5, seed=6)
    agg = init_aggregate_state(sp, r0)
    total_b = jnp.sum(sp.node_weights)
    cost = costs.cost_matrix_from_aggregate(
        agg.aggregate, r0, sp.node_weights, agg.loads, sp.speeds, sp.mu,
        fw, total_weight=total_b)
    th = None if theta is None else jnp.full((sp.num_nodes,), theta)
    d_ref, b_ref = costs.dissatisfaction_from_cost(cost, r0, th)
    layout = build_edge_tile_layout(sp)
    d_k, b_k = dissatisfaction_from_edges_pallas(
        layout, r0, sp.node_weights, agg.loads, sp.speeds, sp.mu, fw,
        theta=theta, total_weight=total_b)
    np.testing.assert_array_equal(np.asarray(b_k), np.asarray(b_ref))
    # the Ct cost entries are O(1e5) in f32, so a reassociated assembly
    # differs by up to ~1e-3 relative on the dissat differences — the
    # pinned DESIGN.md §13.3 budget
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_ref),
                               rtol=1e-3, atol=1e-2)


def test_refine_via_edge_kernel_matches_jnp_path():
    from repro.kernels.ops import make_edge_dissat_fn
    _, sp, r0 = _instance(n=100, k=4, seed=8)
    res_j = refine(sp, r0, "c")
    res_k = refine(sp, r0, "c", dissat_fn=make_edge_dissat_fn(sp))
    assert int(res_j.num_moves) == int(res_k.num_moves)
    np.testing.assert_array_equal(np.asarray(res_k.assignment),
                                  np.asarray(res_j.assignment))


def test_edge_kernel_interpret_modes_agree():
    from repro.kernels.edge_block import (build_edge_tile_layout,
                                          dissatisfaction_from_edges_pallas)
    _, sp, r0 = _instance(n=70, k=3, seed=10)
    agg = init_aggregate_state(sp, r0)
    layout = build_edge_tile_layout(sp)
    args = (layout, r0, sp.node_weights, agg.loads, sp.speeds, sp.mu, "c")
    d_i, b_i = dissatisfaction_from_edges_pallas(*args, interpret=True)
    assert np.asarray(d_i).shape == (70,)
    assert np.asarray(b_i).dtype == np.int32
    assert int(np.asarray(b_i).max()) < 3


# ---------------------------------------------------------------------------
# multi-move probabilistic sweeps on SparseProblem (DESIGN.md §17)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
@pytest.mark.parametrize("theta", [None, 0.5])
def test_sparse_sweeps_degenerate_bitwise(fw, theta, simultaneous_ref):
    """moves_per_machine=1, move_prob=1, epsilon=0 is the §4.5 mode on the
    sparse path too — the whole result must equal the recorded one
    bitwise, through refine_sweeps and refine_simultaneous alike."""
    _, sp, r0 = _instance(seed=4)
    prefix = f"sparse/{fw}/{theta}"
    simultaneous_ref(prefix, *refine_sweeps(sp, r0, fw, max_sweeps=64,
                                            theta=theta))
    simultaneous_ref(prefix, *refine_simultaneous(sp, r0, fw, max_sweeps=64,
                                                  theta=theta))


@pytest.mark.parametrize("seed", range(8))
def test_sparse_sweeps_degenerate_bitwise_property(seed, simultaneous_ref):
    """Sparse instances × frameworks × theta (eight fixed seeds covering
    every framework/theta pair): the degenerate config stays bitwise."""
    _, sp, r0 = _instance(seed=seed % 11)
    fw = "ct" if seed % 2 else "c"
    theta = None if seed % 3 == 0 else 0.5
    prefix = f"sparse_seed/{seed}"
    simultaneous_ref(prefix, *refine_sweeps(sp, r0, fw, max_sweeps=48,
                                            theta=theta))
    simultaneous_ref(prefix, *refine_simultaneous(sp, r0, fw, max_sweeps=48,
                                                  theta=theta))


@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
@pytest.mark.parametrize("theta", [None, 0.5])
def test_sparse_dense_multimove_match(fw, theta):
    """Sparse == dense multi-move sweep sequences under a shared key:
    same accepted sweeps, same assignment, same mover count; potentials
    within the §13.3 reassociation budget."""
    prob, sp, r0 = _instance(seed=5)
    key = jax.random.PRNGKey(21)
    kwargs = dict(max_sweeps=128, theta=theta, moves_per_machine=2,
                  move_prob=0.5, epsilon=1e-3, key=key)
    res_d, (c0_d, ct0_d, act_d) = refine_sweeps(prob, r0, fw, **kwargs)
    res_s, (c0_s, ct0_s, act_s) = refine_sweeps(sp, r0, fw, **kwargs)
    np.testing.assert_array_equal(np.asarray(res_s.assignment),
                                  np.asarray(res_d.assignment))
    assert int(res_s.num_moves) == int(res_d.num_moves)
    np.testing.assert_array_equal(np.asarray(act_s), np.asarray(act_d))
    for name, a, b in (("c0", c0_d, c0_s), ("ct0", ct0_d, ct0_s)):
        aa = np.asarray(a, np.float64)
        bb = np.asarray(b, np.float64)
        rel = np.max(np.abs(aa - bb) / np.maximum(np.abs(aa), 1e-9))
        assert rel <= 1e-3, (name, rel)


@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
def test_sparse_unbounded_sweeps_descend_and_converge(fw):
    """The unbounded mode with cs/0506098 adaptive acceptance descends to
    an equilibrium below the start (fixed-seed empirical check)."""
    _, sp, r0 = _instance(n=120, k=4, seed=9)
    res, (c0s, ct0s, active) = refine_sweeps(
        sp, r0, fw, max_sweeps=512, moves_per_machine=None,
        move_prob=0.5, epsilon=1e-3, key=jax.random.PRNGKey(3))
    assert bool(res.converged)
    pots = np.asarray(c0s if fw == "c" else ct0s, np.float64)
    n_active = int(np.asarray(active).sum())
    assert n_active >= 1
    assert pots[n_active - 1] < float(costs.global_cost(sp, r0, fw))


def test_refine_sweeps_validation():
    from repro.kernels.ops import make_edge_sweep_fn
    _, sp, r0 = _instance()
    with pytest.raises(ValueError, match="key"):
        refine_sweeps(sp, r0, "c", move_prob=0.5)
    fn = make_edge_sweep_fn(sp, interpret=True)
    with pytest.raises(ValueError, match="moves_per_machine"):
        refine_sweeps(sp, r0, "c", sweep_fn=fn, moves_per_machine=2)
    with pytest.raises(ValueError, match="not both"):
        refine_sweeps(sp, r0, "c", sweep_fn=fn, dissat_fn=fn)


# ---------------------------------------------------------------------------
# fused sweep-election kernel (DESIGN.md §17.4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fw", costs.FRAMEWORKS)
@pytest.mark.parametrize("theta", [None, 0.5])
def test_sweep_kernel_election_matches_jnp(fw, theta):
    """The kernel's per-machine election — gains, picks, destinations —
    against the jnp reference: picks/dests EXACTLY (same lowest-index
    tie-break as jnp.argmax), gains within the §13.3 budget."""
    from repro.kernels.edge_block import (build_edge_tile_layout,
                                          sweep_candidates_from_edges_pallas)
    _, sp, r0 = _instance(n=150, k=5, seed=6)
    agg = init_aggregate_state(sp, r0)
    total_b = jnp.sum(sp.node_weights)
    th = None if theta is None else jnp.full((sp.num_nodes,), theta)
    cost = costs.cost_matrix_from_aggregate(
        agg.aggregate, r0, sp.node_weights, agg.loads, sp.speeds, sp.mu,
        fw, total_weight=total_b)
    dissat, best = costs.dissatisfaction_from_cost(cost, r0, th)
    owned = jax.nn.one_hot(r0, sp.num_machines, dtype=dissat.dtype)
    masked = jnp.where(owned.T > 0, dissat[None, :], -jnp.inf)
    pick_ref = jnp.argmax(masked, axis=1).astype(jnp.int32)
    gain_ref = jnp.max(masked, axis=1)
    dest_ref = best[pick_ref]

    layout = build_edge_tile_layout(sp)
    gain_k, pick_k, dest_k = sweep_candidates_from_edges_pallas(
        layout, r0, sp.node_weights, agg.loads, sp.speeds, sp.mu, fw,
        theta=theta, total_weight=total_b)
    np.testing.assert_array_equal(np.asarray(pick_k), np.asarray(pick_ref))
    np.testing.assert_array_equal(np.asarray(dest_k), np.asarray(dest_ref))
    np.testing.assert_allclose(np.asarray(gain_k), np.asarray(gain_ref),
                               rtol=1e-3, atol=5e-2)


def test_refine_sweeps_via_sweep_fn_matches_jnp_path():
    """Full refinement through the fused election == the jnp path: same
    coins (shared key, same (K,) shape), so identical elections must give
    identical accepted sweeps, assignment and mover counts."""
    from repro.kernels.ops import make_edge_sweep_fn
    _, sp, r0 = _instance(n=100, k=4, seed=8)
    fn = make_edge_sweep_fn(sp)
    for fw in costs.FRAMEWORKS:
        kwargs = dict(max_sweeps=256, move_prob=0.5, epsilon=1e-3,
                      key=jax.random.PRNGKey(5))
        res_j, (_, _, act_j) = refine_sweeps(sp, r0, fw, **kwargs)
        res_k, (_, _, act_k) = refine_sweeps(sp, r0, fw, sweep_fn=fn,
                                             **kwargs)
        assert bool(res_j.converged) and bool(res_k.converged)
        assert int(res_j.num_moves) == int(res_k.num_moves)
        np.testing.assert_array_equal(np.asarray(res_k.assignment),
                                      np.asarray(res_j.assignment))
        np.testing.assert_array_equal(np.asarray(act_k), np.asarray(act_j))
