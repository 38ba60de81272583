"""The paper's two node-level cost frameworks and their global potentials.

Framework 1 (Eq. 1):
    C_i(r) = (b_i / w_{r_i}) * sum_{j != i, r_j = r_i} b_j
             + (mu/2) * sum_{j: r_j != r_i} c_ij
  Global potential (Thm. 3.1):  C_0(r) = sum_i C_i(r),
  with the exact-potential identity  Delta C_0 = 2 * Delta C_l  for a
  unilateral move of node l.

Framework 2 (Eq. 6):
    Ct_i(r) = b_i^2/w_{r_i}^2 + (2 b_i / w_{r_i}^2) * sum_{j != i, r_j=r_i} b_j
              - (2 b_i / w_{r_i}) * B + (mu/2) * sum_{j: r_j != r_i} c_ij
  Global objective (Eq. 8, centralized load-variance + cut):
    Ct_0(r) = sum_k (L_k / w_k - B)^2 + (mu/2) * cut(r)
  with the exact-potential identity  Delta Ct_0 = Delta Ct_l  (Thm. 5.1).

Convention note (DESIGN.md §8): Eq. 8 as printed sums ordered pairs, which
double-counts each cut edge and breaks the Thm. 5.1 identity by a factor of
two.  We use the (mu/2) * unordered-cut convention, under which the identity
is *exact*; tests/test_game_theory.py asserts both identities numerically.

Everything here is O(N*K) given the aggregate matrix A[i,k] = sum_j c_ij
1[r_j = k], itself an (N,N)x(N,K) matmul — the refinement hot spot that
``repro/kernels/dissatisfaction.py`` implements as a fused Pallas kernel.
The refinement engines avoid even that matmul after the first turn:
``repro.core.aggregate`` carries A through the loop and applies a rank-1
column update per move (DESIGN.md §10); :func:`cost_matrix_from_aggregate`
is the shared O(N*K) assembly both paths delegate to.

Sparse problems (DESIGN.md §13): every public entry point taking a
``problem`` also accepts a :class:`~repro.core.sparse.SparseProblem` —
the aggregate becomes an O(E*K) ``segment_sum`` over the edge list
(:func:`adjacency_aggregate_sparse`), the cut an O(E) edge sum
(:func:`total_cut_sparse`), and both global potentials the O(K) closed
forms of :func:`potentials_closed_form`, so nothing on the sparse path
ever touches an O(N^2) array.  Dispatch happens at trace time via
``isinstance`` — the dense op sequence is untouched.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .problem import PartitionProblem, PartitionState, machine_loads
from .sparse import SparseProblem

Array = jax.Array

AnyProblem = PartitionProblem | SparseProblem

C_FRAMEWORK = "c"     # Eq. 1
CT_FRAMEWORK = "ct"   # Eq. 6
FRAMEWORKS = (C_FRAMEWORK, CT_FRAMEWORK)

# Declared asymptotic budgets for the dense representation, consumed by
# the complexity analyzers (DESIGN.md §18).  Exponent caps per problem
# dimension: the (N, N) adjacency is the representation floor, so dense
# paths may stage O(N^2) intermediates and O(N^2 * K) work — anything
# steeper is a finding.
DENSE_COMPLEXITY = {
    "mem": {"n": 2.0, "k": 1.0},
    "ops": {"n": 2.0, "k": 1.0},
}


def adjacency_aggregate(adjacency: Array, assignment: Array, num_machines: int) -> Array:
    """A[i, k] = sum_j c_ij * 1[r_j = k]; computed as C @ one_hot(r).

    ``HIGHEST`` precision: a TPU evaluates an f32 matmul at its default
    precision in bf16, which rounds the edge weights to 8 bits."""
    onehot = jax.nn.one_hot(assignment, num_machines, dtype=adjacency.dtype)
    return jnp.matmul(adjacency, onehot, precision=jax.lax.Precision.HIGHEST)


def adjacency_aggregate_sparse(sp: SparseProblem, assignment: Array) -> Array:
    """The same (N, K) aggregate from the edge list: one O(E)
    ``segment_sum`` keyed on the flattened ``sender * K + r[receiver]``
    slot id (DESIGN.md §13.2).  Each (row, machine) slot accumulates its
    slab's edges receiver-ascending — the same per-slot order as the
    per-edge one-hot formulation this replaces (whose skipped entries
    were exact ``+0.0``\\ s), so values are bitwise unchanged while the
    (E, K) intermediate and its K-fold memory traffic disappear.  Padded
    edges carry weight 0 and land on a real slot of the last row, an
    exact ``+0.0``.
    """
    slot = sp.senders * sp.num_machines + assignment[sp.receivers]
    flat = jax.ops.segment_sum(
        sp.edge_weights, slot,
        num_segments=sp.num_nodes * sp.num_machines,
        indices_are_sorted=False)
    return flat.reshape(sp.num_nodes, sp.num_machines)


def problem_aggregate(problem: AnyProblem, assignment: Array,
                      num_machines: int) -> Array:
    """Build the (N, K) aggregate for either problem representation."""
    if isinstance(problem, SparseProblem):
        return adjacency_aggregate_sparse(problem, assignment)
    return adjacency_aggregate(problem.adjacency, assignment, num_machines)


def cut_matrix(adjacency: Array, assignment: Array, num_machines: int,
               aggregate: Array | None = None) -> Array:
    """cut[i, k] = (1) * sum_{j: r_j != k} c_ij  (the mu/2 factor applied later)."""
    if aggregate is None:
        aggregate = adjacency_aggregate(adjacency, assignment, num_machines)
    degree = jnp.sum(aggregate, axis=-1, keepdims=True)       # = sum_j c_ij
    return degree - aggregate


def cost_matrix_from_aggregate(aggregate: Array, row_assignment: Array,
                               node_weights: Array, loads: Array,
                               speeds: Array, mu: Array, framework: str,
                               total_weight: Array | None = None) -> Array:
    """O(rows*K) cost assembly from an already-built adjacency aggregate.

    This is THE shared cost formula (DESIGN.md §10): the recompute path
    (:func:`cost_matrix`), the shard-local path
    (:func:`repro.distributed.protocol.shard_cost_matrix`) and the
    incremental path (:mod:`repro.core.aggregate`) all delegate here, so
    any two paths fed the same aggregate produce bitwise-identical costs.

    ``aggregate`` is the (rows, K) block A[i, k] = sum_j c_ij 1[r_j = k]
    (rows may be a shard's row block of a larger graph);
    ``row_assignment`` gives the rows' OWN machines; ``total_weight`` is
    the global weight sum B, required by the Ct framework (defaults to
    ``sum(node_weights)``, correct only when the rows are the full graph).
    """
    b = node_weights
    k = loads.shape[0]
    degree = jnp.sum(aggregate, axis=-1, keepdims=True)       # = sum_j c_ij
    cut_term = 0.5 * mu * (degree - aggregate)
    own = jax.nn.one_hot(row_assignment, k, dtype=b.dtype)    # (rows, K)
    # others[i, k] = sum_{j != i, r_j = k} b_j if i were moved to k: node
    # i's weight is subtracted only on its CURRENT machine — every other
    # machine's load already excludes i.
    others = loads[None, :] - b[:, None] * own
    if framework == C_FRAMEWORK:
        load_term = (b[:, None] / speeds[None, :]) * others
        return load_term + cut_term
    elif framework == CT_FRAMEWORK:
        if total_weight is None:
            total_weight = jnp.sum(b)
        inv_w = 1.0 / speeds[None, :]
        load_term = (b[:, None] ** 2) * inv_w**2 \
            + 2.0 * b[:, None] * inv_w**2 * others \
            - 2.0 * b[:, None] * inv_w * total_weight
        return load_term + cut_term
    raise ValueError(f"unknown framework {framework!r}")


def cost_matrix(problem: AnyProblem, state: PartitionState,
                framework: str = C_FRAMEWORK,
                aggregate: Array | None = None) -> Array:
    """(N, K) matrix of node costs: entry [i, k] = cost of node i if on machine k.

    Column r_i of row i is the node's *current* cost; other columns are the
    hypothetical post-move costs (all other assignments held fixed), exactly
    the quantities a machine needs to compute dissatisfaction (Eq. 4).
    """
    K = problem.num_machines
    if aggregate is None:
        aggregate = problem_aggregate(problem, state.assignment, K)
    return cost_matrix_from_aggregate(
        aggregate, state.assignment, problem.node_weights, state.loads,
        problem.speeds, problem.mu, framework,
        total_weight=jnp.sum(problem.node_weights))


def node_costs(problem: AnyProblem, state: PartitionState,
               framework: str = C_FRAMEWORK) -> Array:
    """(N,) current cost of every node under its current assignment."""
    cm = cost_matrix(problem, state, framework)
    return jnp.take_along_axis(cm, state.assignment[:, None], axis=1)[:, 0]


def _min_argmin_sum(a, b):
    """Reducer of :func:`dissatisfaction_from_cost`: (min, lowest-index
    argmin, sum) — ``jnp.argmin``'s semantics, NaN counting as smallest."""
    (va, ia, sa), (vb, ib, sb) = a, b
    a_nan, b_nan = jnp.isnan(va), jnp.isnan(vb)
    tie = (va == vb) | (a_nan & b_nan)
    take_a = (va < vb) | (a_nan & ~b_nan) | (tie & (ia < ib))
    return (jnp.where(take_a, va, vb), jnp.where(take_a, ia, ib), sa + sb)


def dissatisfaction_from_cost(cost: Array, row_assignment: Array,
                              theta: Array | None = None):
    """Eq. 4 from an already-assembled cost block: I(i) and the arg-best
    machine.  Ties break toward the lowest machine index (DESIGN.md §7).

    ``theta`` is the per-node migration-price (hysteresis) threshold of
    DESIGN.md §11: the returned dissatisfaction is NET of it
    (``I(i) - theta_i``), so a node is movable only when its raw Eq.-4
    dissatisfaction exceeds its migration price.  This is THE one place
    theta is subtracted — core, distributed and kernel paths all route
    through it (or mirror its exact op order), preserving the bitwise
    core↔distributed contract.  ``theta=None`` skips the subtraction
    entirely and is bit-for-bit today's behavior.
    """
    # One variadic reduction yields the current cost, the best cost and
    # its machine together, so all three read the SAME evaluation of each
    # cost entry.  Separate reductions let the compiler re-evaluate the
    # fused cost expression per consumer with different rounding (e.g. an
    # FMA in one loop and not the other), and a node already on its best
    # machine would then show a spurious non-zero gain.
    kidx = jax.lax.broadcasted_iota(jnp.int32, cost.shape, 1)
    own_cost = jnp.where(kidx == row_assignment[:, None], cost,
                         jnp.zeros((), cost.dtype))
    best, best_machine, current = jax.lax.reduce(
        (cost, kidx, own_cost),
        (jnp.asarray(jnp.inf, cost.dtype), jnp.int32(cost.shape[1]),
         jnp.zeros((), cost.dtype)),
        _min_argmin_sum, (1,))
    dissat = current - best
    if theta is not None:
        dissat = dissat - theta
    return dissat, best_machine


def dissatisfaction(problem: AnyProblem, state: PartitionState,
                    framework: str = C_FRAMEWORK,
                    cost: Array | None = None,
                    theta: Array | None = None):
    """Eq. 4:  I(i) = C_i(r_i) - min_k C_i(k), with the arg-best machine.

    Returns (dissat (N,), best_machine (N,)).  Ties break toward the lowest
    machine index (deterministic, DESIGN.md §7).  ``theta`` as in
    :func:`dissatisfaction_from_cost` (net-of-migration-price Eq. 4).
    """
    if cost is None:
        cost = cost_matrix(problem, state, framework)
    return dissatisfaction_from_cost(cost, state.assignment, theta)


# ---------------------------------------------------------------------------
# Global potentials
# ---------------------------------------------------------------------------

def total_cut(adjacency: Array, assignment: Array) -> Array:
    """Unordered cut weight: (1/2) sum_{i,j} c_ij 1[r_i != r_j]."""
    diff = assignment[:, None] != assignment[None, :]
    return 0.5 * jnp.sum(adjacency * diff)


def total_cut_sparse(sp: SparseProblem, assignment: Array) -> Array:
    """Unordered cut from the edge list — O(E), no O(N^2) mask matrix.

    Each undirected edge appears in both directions, so summing the
    directed crossings and halving reproduces the unordered convention;
    padded edges (weight 0) contribute exactly 0.
    """
    crossing = assignment[sp.senders] != assignment[sp.receivers]
    return 0.5 * jnp.sum(jnp.where(crossing, sp.edge_weights,
                                   jnp.zeros((), sp.edge_weights.dtype)))


def problem_cut(problem: AnyProblem, assignment: Array) -> Array:
    """Unordered cut for either problem representation."""
    if isinstance(problem, SparseProblem):
        return total_cut_sparse(problem, assignment)
    return total_cut(problem.adjacency, assignment)


def potentials_closed_form(loads: Array, sq_loads: Array, cut: Array,
                           speeds: Array, mu: Array,
                           total_weight: Array) -> tuple[Array, Array]:
    """(C_0, Ct_0) as O(K) closed forms of machine-level sums.

    C_0 = sum_k (L_k^2 - S_k)/w_k + mu * cut, with S_k = sum_{i on k}
    b_i^2 (from summing Eq. 1 over i); Ct_0 = sum_k (L_k/w_k - B)^2 +
    mu/2 * cut (Eq. 8).  Used by the §4.5 sweep mode (simultaneous moves
    are not unilateral, so the exact-potential identities do not apply —
    DESIGN.md §10) and by the sparse path's global potentials, where the
    per-node Eq.-1 sum would need the O(N, K) cost matrix for a scalar.
    """
    c0 = jnp.sum((loads * loads - sq_loads) / speeds) + mu * cut
    ct0 = jnp.sum((loads / speeds - total_weight) ** 2) + 0.5 * mu * cut
    return c0, ct0


def global_cost_c0(problem: AnyProblem, assignment: Array) -> Array:
    """C_0(r) = sum_i C_i(r)  (Thm. 3.1 potential, social welfare).

    Sparse problems evaluate the O(K) closed form over (loads, sq_loads,
    cut) instead of summing N node costs — same value up to f32
    reassociation (within the ≤1e-3 budget of DESIGN.md §13.3).
    """
    b = problem.node_weights
    if isinstance(problem, SparseProblem):
        k = problem.num_machines
        loads = machine_loads(b, assignment, k)
        sq_loads = machine_loads(b * b, assignment, k)
        cut = total_cut_sparse(problem, assignment)
        return potentials_closed_form(loads, sq_loads, cut, problem.speeds,
                                      problem.mu, jnp.sum(b))[0]
    state = PartitionState(assignment,
                           machine_loads(b, assignment,
                                         problem.num_machines))
    return jnp.sum(node_costs(problem, state, C_FRAMEWORK))


def global_cost_ct0(problem: AnyProblem, assignment: Array) -> Array:
    """Ct_0(r) = sum_k (L_k / w_k - B)^2 + (mu/2) cut(r)  (Eq. 8, see note)."""
    b = problem.node_weights
    loads = machine_loads(b, assignment, problem.num_machines)
    total = jnp.sum(b)
    variance = jnp.sum((loads / problem.speeds - total) ** 2)
    return variance + 0.5 * problem.mu * problem_cut(problem, assignment)


def global_cost(problem: AnyProblem, assignment: Array, framework: str) -> Array:
    if framework == C_FRAMEWORK:
        return global_cost_c0(problem, assignment)
    if framework == CT_FRAMEWORK:
        return global_cost_ct0(problem, assignment)
    raise ValueError(f"unknown framework {framework!r}")


def load_imbalance(problem: AnyProblem, assignment: Array) -> Array:
    """max_k L_k/w_k divided by B — 1.0 means perfectly balanced."""
    loads = machine_loads(problem.node_weights, assignment, problem.num_machines)
    total = jnp.sum(problem.node_weights)
    return jnp.max(loads / problem.speeds) / total
