"""Persistent aggregate state for incremental refinement (DESIGN.md §10).

The paper's point is that a turn's decision needs only *aggregate* state:
the (N, K) adjacency aggregate A[i, k] = sum_j c_ij 1[r_j = k], the O(K)
load vector, and the global potentials.  The recompute path rebuilds A
from scratch every turn — an (N,N) @ (N,K) matmul, O(N^2 K) — and pays two
more O(N^2) passes per turn for the traced potentials.  This module keeps
all of it in the ``lax.while_loop`` / ``lax.scan`` carry instead:

  * a move of node l from machine s to d is a **rank-1 column update**
        A[:, s] -= c[:, l]        A[:, d] += c[:, l]
    (column l of the symmetric adjacency), O(N);
  * the loads update is the O(1) two-entry delta the paper's protocol
    already exchanges;
  * both global potentials update via the **exact-potential identities**
    (Thm. 3.1:  ΔC_0 = 2 ΔC_l;  Thm. 5.1:  ΔCt_0 = ΔCt_l), where ΔC_l /
    ΔCt_l are read off the moved node's O(K) cost rows — no O(N^2) pass.

Invariants carried by :class:`AggregateState` (asserted by
``tests/test_incremental.py`` and the ``verify_every`` cross-check),
stated over either graph representation — for a dense problem
``c[i, l]`` is an adjacency entry, for a sparse one
(:class:`~repro.core.sparse.SparseProblem`, DESIGN.md §13) it is the
weight of edge (i, l) in the edge list (0 when absent):

  I1.  aggregate[i, k] == sum over incident edges (i, j) of
       w_ij * 1[r_j = k]  — dense: ``adjacency @ one_hot(assignment)``;
       sparse: ``segment_sum`` of edge one-hots over sender slabs
       (up to f32 drift either way)
  I2.  loads[k]  == sum_{i: r_i = k} b_i
  I3.  c0  == C_0(assignment)   and   ct0 == Ct_0(assignment)
  I4.  cut(assignment) == 0.5 * (sum_i degree_i - sum_i A[i, r_i]) — the
       O(N) identity the §4.5 sweep mode uses to re-derive the cut after a
       rank-K update (simultaneous moves are not unilateral, so the
       exact-potential identities do not apply; instead both potentials
       are O(K) closed forms of (loads, sq_loads, cut), see
       :func:`repro.core.costs.potentials_closed_form`).

The carried (N, K) aggregate is the same object for both — only how
moves update it differs: a dense move applies column l of the adjacency
(O(N)); a sparse move scatters the moved node's ``max_degree`` incident
edge window (O(deg), :func:`repro.core.sparse.node_incident_edges`).

Drift: every quantity is updated by exact +/- of input values, so f32
error grows only with the number of moves that touch an entry.  The
``verify_every=M`` option of the refinement engines rebuilds the state
from scratch every M turns, records the observed drift, and resyncs —
bounding the error for arbitrarily long runs.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import costs
from .problem import PartitionProblem, machine_loads
from .sparse import SparseProblem, node_incident_edges

Array = jax.Array

AnyProblem = costs.AnyProblem


class AggregateState(NamedTuple):
    """Everything a refinement turn needs, carried through the loop."""
    assignment: Array   # (N,) int32
    loads: Array        # (K,) float — L_k = sum of owned b
    aggregate: Array    # (N, K) float — A[i, k] = sum_j c_ij 1[r_j = k]
    c0: Array           # ()  float — C_0(assignment)   (Thm. 3.1 potential)
    ct0: Array          # ()  float — Ct_0(assignment)  (Eq. 8 potential)


def init_aggregate_state(problem: AnyProblem,
                         assignment: Array) -> AggregateState:
    """Build the carry from scratch: one O(N^2 K) aggregate matmul and one
    O(N^2) pass per potential — paid once, then never again.  Sparse
    problems pay O(E K) + O(E) instead (segment sums over the edge list,
    closed-form potentials — DESIGN.md §13.2)."""
    assignment = jnp.asarray(assignment, jnp.int32)
    k = problem.num_machines
    aggregate = costs.problem_aggregate(problem, assignment, k)
    loads = machine_loads(problem.node_weights, assignment, k)
    c0 = costs.global_cost_c0(problem, assignment)
    ct0 = costs.global_cost_ct0(problem, assignment)
    return AggregateState(assignment=assignment, loads=loads,
                          aggregate=aggregate, c0=c0, ct0=ct0)


def node_cost_rows(agg_row: Array, b_node: Array, source: Array,
                   loads: Array, speeds: Array, mu: Array,
                   total_weight: Array) -> tuple[Array, Array]:
    """Both frameworks' O(K) cost rows of one node from its aggregate row.

    ``agg_row`` is A[l, :] (pre-move), ``source`` the node's current
    machine.  Delegates to :func:`costs.cost_matrix_from_aggregate` with a
    single-row block so the numbers are bitwise identical to the full
    cost-matrix rows either path would compute.
    """
    row = agg_row[None, :]
    r_row = source[None]
    b_row = b_node[None]
    c_row = costs.cost_matrix_from_aggregate(
        row, r_row, b_row, loads, speeds, mu, costs.C_FRAMEWORK,
        total_weight=total_weight)[0]
    ct_row = costs.cost_matrix_from_aggregate(
        row, r_row, b_row, loads, speeds, mu, costs.CT_FRAMEWORK,
        total_weight=total_weight)[0]
    return c_row, ct_row


def potential_deltas(agg_row: Array, b_node: Array, source: Array,
                     dest: Array, loads: Array, speeds: Array, mu: Array,
                     total_weight: Array) -> tuple[Array, Array]:
    """(ΔC_0, ΔCt_0) of moving one node from ``source`` to ``dest`` via the
    exact-potential identities — O(K), no global pass.

    Thm. 3.1:  ΔC_0  = 2 (C_l(dest)  - C_l(source))
    Thm. 5.1:  ΔCt_0 =    Ct_l(dest) - Ct_l(source)
    """
    c_row, ct_row = node_cost_rows(agg_row, b_node, source, loads, speeds,
                                   mu, total_weight)
    dc0 = 2.0 * (c_row[dest] - c_row[source])
    dct0 = ct_row[dest] - ct_row[source]
    return dc0, dct0


def apply_move(problem: AnyProblem, agg: AggregateState, node: Array,
               source: Array, dest: Array, do_move: Array,
               total_weight: Array) -> AggregateState:
    """Apply one (gated) unilateral move: rank-1 aggregate update,
    O(1) load delta, O(K) potential deltas via the exact identities.

    Dense path — the rank-1 update is expressed as a dense outer product
    against the ``±1`` one-hot column delta rather than a two-column
    scatter: the values are bitwise identical (the untouched columns add
    an exact ``+0.0``, and an accepted move always has ``source != dest``
    — an own-column argmin yields non-positive net dissatisfaction, and
    rejected turns are discarded by the ``do_move`` select), while the
    dense form vectorizes under ``jax.vmap`` where a batched two-column
    scatter serializes (DESIGN.md §12.2).

    Sparse path (DESIGN.md §13.2) — only the moved node's ``max_degree``
    incident-edge window is scattered into the two affected columns:
    O(deg) work and the O(N^2) adjacency never exists.  Masked window
    slots carry weight 0 and add an exact ``±0.0``.
    """
    b_node = problem.node_weights[node]
    dc0, dct0 = potential_deltas(agg.aggregate[node], b_node, source, dest,
                                 agg.loads, problem.speeds, problem.mu,
                                 total_weight)
    kidx = jnp.arange(agg.loads.shape[0])
    dt = agg.aggregate.dtype
    col_delta = (kidx == dest).astype(dt) - (kidx == source).astype(dt)
    if isinstance(problem, SparseProblem):
        nbrs, w = node_incident_edges(problem, node)
        new_aggregate = agg.aggregate.at[nbrs].add(
            w[:, None] * col_delta[None, :])
    else:
        col = problem.adjacency[node]       # symmetric: row l == column l
        new_aggregate = agg.aggregate + col[:, None] * col_delta[None, :]
    new_assignment = agg.assignment.at[node].set(dest)
    new_loads = agg.loads.at[source].add(-b_node).at[dest].add(b_node)
    return AggregateState(
        assignment=jnp.where(do_move, new_assignment, agg.assignment),
        loads=jnp.where(do_move, new_loads, agg.loads),
        aggregate=jnp.where(do_move, new_aggregate, agg.aggregate),
        c0=jnp.where(do_move, agg.c0 + dc0, agg.c0),
        ct0=jnp.where(do_move, agg.ct0 + dct0, agg.ct0),
    )


# ---------------------------------------------------------------------------
# §4.5 simultaneous sweeps: rank-K update + O(K) closed-form potentials
# ---------------------------------------------------------------------------

def cut_from_aggregate(aggregate: Array, assignment: Array) -> Array:
    """Invariant I4: unordered cut = 0.5 (sum_i degree_i - sum_i A[i, r_i]).

    O(N K) (the row sums) given the carried aggregate — re-derived fresh
    each sweep rather than accumulated, so it never drifts beyond the
    aggregate's own drift.
    """
    degree = jnp.sum(aggregate, axis=-1)
    internal = jnp.take_along_axis(aggregate, assignment[:, None],
                                   axis=1)[:, 0]
    return 0.5 * (jnp.sum(degree) - jnp.sum(internal))


# canonical home moved to costs.py so the sparse global potentials can
# share it without an import cycle; re-exported here for the §10 API
potentials_closed_form = costs.potentials_closed_form


def apply_sweep(problem: AnyProblem, agg: AggregateState, picks: Array,
                dests: Array, will_move: Array,
                total_weight: Array) -> AggregateState:
    """Apply a §4.5 sweep: machine m moves node picks[m] (owned by m) to
    dests[m] wherever will_move[m] — a rank-K aggregate update, then both
    potentials via (loads, sq_loads, cut) closed forms.

    ``picks`` entries of idle machines may be garbage (argmax fallback);
    their columns are zeroed by the mask so they contribute exactly 0.
    Sparse problems scatter the K moved nodes' incident-edge windows
    (O(K·max_degree)) instead of the K dense adjacency columns.
    """
    k = problem.num_machines
    b = problem.node_weights
    mask = will_move.astype(agg.aggregate.dtype)              # (K,)
    # sources are exactly 0..K-1 (machine m moves an m-owned node)
    if isinstance(problem, SparseProblem):
        nbrs, ws = jax.vmap(lambda nd: node_incident_edges(problem, nd)
                            )(picks)                          # (K, Dmax)
        ws = ws * mask[:, None]
        kidx = jnp.arange(k)
        col_delta = (dests[:, None] == kidx[None, :]).astype(ws.dtype) \
            - (kidx[None, :] == kidx[:, None]).astype(ws.dtype)   # (K, K)
        new_aggregate = agg.aggregate.at[nbrs].add(
            ws[:, :, None] * col_delta[:, None, :])           # dups summed
    else:
        cols = problem.adjacency[:, picks] * mask[None, :]    # (N, K)
        new_aggregate = agg.aggregate - cols
        new_aggregate = new_aggregate.at[:, dests].add(cols)  # dups summed
    safe_picks = jnp.where(will_move, picks, jnp.int32(problem.num_nodes))
    new_assignment = agg.assignment.at[safe_picks].set(dests, mode="drop")
    new_loads = machine_loads(b, new_assignment, k)
    sq_loads = machine_loads(b * b, new_assignment, k)
    cut = cut_from_aggregate(new_aggregate, new_assignment)
    c0, ct0 = potentials_closed_form(new_loads, sq_loads, cut,
                                     problem.speeds, problem.mu,
                                     total_weight)
    return AggregateState(assignment=new_assignment, loads=new_loads,
                          aggregate=new_aggregate, c0=c0, ct0=ct0)


def apply_moves(problem: AnyProblem, agg: AggregateState, nodes: Array,
                dests: Array, will_move: Array,
                total_weight: Array) -> AggregateState:
    """Apply up to R simultaneous moves (DESIGN.md §17): node ``nodes[r]``
    migrates to ``dests[r]`` wherever ``will_move[r]`` — a rank-R
    aggregate update, then both potentials via the (loads, sq_loads,
    cut) closed forms, exactly like :func:`apply_sweep`.

    The generalization over :func:`apply_sweep` is that sources are read
    from the carried assignment instead of being the machine ids 0..K-1,
    so R is free: the multi-move sweep mode elects up to
    ``moves_per_machine`` nodes per machine (R = K·M, via ``top_k`` over
    disjoint ownership rows, so real picks never collide).  Masked slots
    (``will_move[r]`` False — idle elections, coin rejections) have
    their edge/column contributions zeroed and their assignment writes
    dropped, contributing an exact ``±0.0``.

    Sparse problems scatter the R moved nodes' incident-edge windows
    (O(R·max_degree·K)); dense ones apply one (N, R) @ (R, K) matmul of
    gathered adjacency columns against the ``±1`` one-hot column deltas.
    """
    k = problem.num_machines
    b = problem.node_weights
    dt = agg.aggregate.dtype
    mask = will_move.astype(dt)                               # (R,)
    sources = agg.assignment[nodes]                           # (R,)
    kidx = jnp.arange(k)
    col_delta = (dests[:, None] == kidx[None, :]).astype(dt) \
        - (sources[:, None] == kidx[None, :]).astype(dt)      # (R, K)
    if isinstance(problem, SparseProblem):
        nbrs, ws = jax.vmap(lambda nd: node_incident_edges(problem, nd)
                            )(nodes)                          # (R, Dmax)
        ws = ws * mask[:, None]
        new_aggregate = agg.aggregate.at[nbrs].add(
            ws[:, :, None] * col_delta[:, None, :])           # dups summed
    else:
        cols = problem.adjacency[:, nodes] * mask[None, :]    # (N, R)
        new_aggregate = agg.aggregate + jnp.matmul(
            cols, col_delta, precision=jax.lax.Precision.HIGHEST)
    safe_nodes = jnp.where(will_move, nodes, jnp.int32(problem.num_nodes))
    new_assignment = agg.assignment.at[safe_nodes].set(dests, mode="drop")
    new_loads = machine_loads(b, new_assignment, k)
    sq_loads = machine_loads(b * b, new_assignment, k)
    cut = cut_from_aggregate(new_aggregate, new_assignment)
    c0, ct0 = potentials_closed_form(new_loads, sq_loads, cut,
                                     problem.speeds, problem.mu,
                                     total_weight)
    return AggregateState(assignment=new_assignment, loads=new_loads,
                          aggregate=new_aggregate, c0=c0, ct0=ct0)


# A slot of the unbounded sweep apply's edge buffer (DESIGN.md §17.5)
# costs a few gathers, a K-wide row update and a few passes of the buffer:
# about three times what the O(E) rebuild pays per edge, one scatter update
# and one gather (about 19 ns an edge on a TPU v5e, PERF.md).  So past
# E / REBUILD_CROSSOVER movers' edges the rebuild is the cheaper apply.
REBUILD_CROSSOVER = 4
EDGE_BUFFER_COUNT = 5       # edge buffers below the crossover, a factor
EDGE_BUFFER_MIN = 1024      # of four apart, none smaller than this
_LANES = 128


def edge_buffer_sizes(num_edges: int) -> tuple[int, ...]:
    """The static edge-slot buffers of :func:`apply_mover_edges`,
    ascending: the largest is the power of two at or below
    ``num_edges / REBUILD_CROSSOVER``, each smaller one a quarter of the
    next, none under ``EDGE_BUFFER_MIN``."""
    top = max(num_edges // REBUILD_CROSSOVER, EDGE_BUFFER_MIN)
    top = 1 << (top.bit_length() - 1)
    return tuple(sorted({max(top >> 2 * i, EDGE_BUFFER_MIN)
                         for i in range(EDGE_BUFFER_COUNT)}))


def _scan(x: Array, op, identity) -> Array:
    """Inclusive scan of ``x`` along axis 0 by log-step shifts in rows of
    128, then of the row totals the same way.  Only elementwise ops: the
    TPU compiler takes seconds for it where ``jnp.cumsum`` or
    ``lax.cummax`` over ~10^6 elements (a ``reduce_window``) takes half
    a minute.  Exact for integer ``op``."""
    n = x.shape[0]
    pad = -n % _LANES
    fill = jnp.full((pad,) + x.shape[1:], identity, x.dtype)
    rows = jnp.concatenate([x, fill]).reshape((-1, _LANES) + x.shape[1:])
    shift = 1
    while shift < _LANES:
        head = jnp.full((rows.shape[0], shift) + x.shape[1:], identity,
                        x.dtype)
        rows = op(rows, jnp.concatenate([head, rows[:, :-shift]], axis=1))
        shift *= 2
    if rows.shape[0] > 1:
        before = _scan(rows[:, -1], op, identity)[:-1]
        rows = rows.at[1:].set(op(rows[1:], before[:, None]))
    return rows.reshape((-1,) + x.shape[1:])[:n]


def _compact(keep: Array, size: int) -> Array:
    """The first ``size`` indices where ``keep``, ascending, ``n`` past
    the last (``jnp.nonzero(keep, size=size, fill_value=n)``).  Each row
    of 128 is sorted on its own, then written at its offset by one
    128-wide window per row, the windows' overlaps resolved by a minimum:
    no sort or scatter as long as ``keep``, which the TPU runs
    element by element."""
    n = keep.shape[0]
    i32 = jnp.int32
    ids = jnp.where(keep, jnp.arange(n, dtype=i32), n)
    ids = jnp.concatenate([ids, jnp.full((-n % _LANES,), n, i32)])
    rows = jnp.sort(ids.reshape(-1, _LANES), axis=1)
    counts = jnp.sum((rows < n).astype(i32), axis=1)
    starts = _scan(counts, jnp.add, 0) - counts
    windows = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,))
    out = jax.lax.scatter_min(
        jnp.full((size + _LANES,), n, i32), starts[:, None], rows, windows,
        indices_are_sorted=True, mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return out[:size]


def apply_mover_edges(problem: SparseProblem, agg: AggregateState,
                      accept: Array, dests: Array, total_weight: Array
                      ) -> tuple[AggregateState, Array]:
    """Apply every accepted move of an unbounded sweep (DESIGN.md §17.5):
    node ``i`` migrates to ``dests[i]`` wherever ``accept[i]``.

    The movers' real incident edges are compacted into one buffer, and
    each edge (mover j, neighbour v, weight w) adds ``-w`` at
    ``A[v, r_j]`` and ``+w`` at ``A[v, dests[j]]``: one K-wide row update
    per edge.  The buffer is the smallest of :func:`edge_buffer_sizes`
    that holds the movers' D edges, picked by a ``lax.switch``; when D
    exceeds the largest, the aggregate is rebuilt from scratch
    (:func:`rebuild_state`) instead.

    The movers are compacted in ascending id order; each marks its first
    edge slot with its slab offset and machines, and running maxima
    carry those to the rest of its slots, so the slots run
    mover-ascending, then in CSR order.  Every aggregate entry therefore
    receives the same non-zero addends, in the same order, as
    :func:`apply_moves` given the same movers ascending; the addends that
    differ are exact ``±0.0``.  Slots past D carry weight 0.

    Returns ``(state, slots)``: ``slots`` is the size of the edge buffer
    used, 0 when the sweep rebuilt.
    """
    n, k, e = problem.num_nodes, problem.num_machines, problem.num_edges
    b = problem.node_weights
    dt = agg.aggregate.dtype
    i32 = jnp.int32
    ends = jnp.concatenate([problem.row_start[1:], jnp.full((1,), e, i32)])
    degree = jnp.minimum(ends - problem.row_start, problem.max_degree)
    moving = accept & (degree > 0)
    total = jnp.sum(jnp.where(moving, degree, 0))
    new_assignment = jnp.where(accept, dests, agg.assignment).astype(i32)
    sizes = edge_buffer_sizes(e)
    # a mover takes at least one slot, so a buffer of B slots has at most
    # B movers
    movers = _compact(moving, min(sizes[-1], n))
    per_node = jnp.stack([degree, problem.row_start, agg.assignment, dests],
                         axis=1)
    per_edge = jnp.stack([problem.receivers, jax.lax.bitcast_convert_type(
        problem.edge_weights, i32)], axis=1)
    kidx = jnp.arange(k, dtype=i32)

    def incremental(slots):
        r = min(slots, n)
        mover = movers[:r]
        real = mover < n
        deg, start, src, dst = per_node.at[jnp.minimum(mover, n - 1)].get(
            indices_are_sorted=True).T
        deg = jnp.where(real, deg, 0)
        first = _scan(deg, jnp.add, 0) - deg          # mover's first slot
        rank = jnp.arange(r, dtype=i32) * k
        # slab offset (edge id = offset + slot) and machines, each
        # non-decreasing over the movers, so a running max fills them in
        at = jnp.where(real, jnp.minimum(first, slots), slots)  # sorted
        marks = jnp.zeros((slots, 3), i32).at[at].max(
            jnp.stack([start - first, rank + src, rank + dst], axis=1),
            mode="drop", indices_are_sorted=True)
        offset, src, dst = _scan(marks, jnp.maximum, 0).T
        t = jnp.arange(slots, dtype=i32)
        nbr, w = per_edge.at[jnp.minimum(offset + t, e - 1)].get(
            indices_are_sorted=True).T
        w = jnp.where(t < total, jax.lax.bitcast_convert_type(w, dt),
                      jnp.zeros((), dt))
        delta = (kidx == dst[:, None] % k).astype(dt) \
            - (kidx == src[:, None] % k).astype(dt)
        return agg.aggregate.at[nbr].add(w[:, None] * delta)

    def rebuild():
        with jax.named_scope("rebuild"):
            return rebuild_state(problem, new_assignment,
                                 total_weight).aggregate

    branch = jnp.sum((total > jnp.asarray(sizes)).astype(i32))
    new_aggregate = jax.lax.switch(
        branch, [partial(incremental, s) for s in sizes] + [rebuild])
    new_loads = machine_loads(b, new_assignment, k)
    sq_loads = machine_loads(b * b, new_assignment, k)
    cut = cut_from_aggregate(new_aggregate, new_assignment)
    c0, ct0 = potentials_closed_form(new_loads, sq_loads, cut,
                                     problem.speeds, problem.mu,
                                     total_weight)
    state = AggregateState(assignment=new_assignment, loads=new_loads,
                           aggregate=new_aggregate, c0=c0, ct0=ct0)
    return state, jnp.asarray((*sizes, 0), i32)[branch]


def apply_cluster_move(problem: AnyProblem, agg: AggregateState, mask: Array,
                       source: Array, dest: Array, do_move: Array,
                       total_weight: Array) -> AggregateState:
    """Apply a §7 cluster move: every node in the boolean ``mask`` (all
    owned by ``source``) migrates jointly to ``dest`` when ``do_move``.

    The aggregate update is a two-column group update: for every node i,
    ``delta_i = sum_{j in cluster} c_ij`` moves from column ``source``
    to column ``dest`` — one O(E) masked ``segment_sum`` on sparse
    problems (the cluster members' combined incident weight per node),
    one O(N^2) masked matvec on dense ones.  Potentials are re-derived
    via the closed forms (a cluster move is not unilateral, so the
    exact-potential identities do not apply — same reasoning as
    :func:`apply_sweep`).
    """
    k = problem.num_machines
    b = problem.node_weights
    dt = agg.aggregate.dtype
    if isinstance(problem, SparseProblem):
        hit = jnp.where(mask[problem.receivers], problem.edge_weights,
                        jnp.zeros((), dt))
        delta = jax.ops.segment_sum(hit, problem.senders,
                                    num_segments=problem.num_nodes,
                                    indices_are_sorted=True)  # (N,)
    else:
        delta = jnp.matmul(problem.adjacency, mask.astype(dt),
                           precision=jax.lax.Precision.HIGHEST)   # (N,)
    kidx = jnp.arange(k)
    col_delta = (kidx == dest).astype(dt) - (kidx == source).astype(dt)
    new_aggregate = agg.aggregate + delta[:, None] * col_delta[None, :]
    new_assignment = jnp.where(mask, dest, agg.assignment).astype(jnp.int32)
    new_loads = machine_loads(b, new_assignment, k)
    sq_loads = machine_loads(b * b, new_assignment, k)
    cut = cut_from_aggregate(new_aggregate, new_assignment)
    c0, ct0 = potentials_closed_form(new_loads, sq_loads, cut,
                                     problem.speeds, problem.mu,
                                     total_weight)
    new = AggregateState(assignment=new_assignment, loads=new_loads,
                         aggregate=new_aggregate, c0=c0, ct0=ct0)
    return jax.tree.map(lambda n_, o: jnp.where(do_move, n_, o), new, agg)


def rebuild_state(problem: AnyProblem, assignment: Array,
                  total_weight: Array) -> AggregateState:
    """Build a fresh :class:`AggregateState` with closed-form potentials.

    Same carried quantities as :func:`init_aggregate_state`, but C_0 and
    Ct_0 come from :func:`repro.core.costs.potentials_closed_form` over
    (loads, sq_loads, cut) — O(E·K) + O(K) total — instead of the
    representation-dispatched global passes.  This is the overflow path
    of the unbounded multi-move mode (DESIGN.md §17.5): when a sweep's
    movers' edges outgrow the largest edge buffer of
    :func:`apply_mover_edges`, a from-scratch rebuild is both cheaper
    and drift-free by construction.
    """
    assignment = jnp.asarray(assignment, jnp.int32)
    k = problem.num_machines
    b = problem.node_weights
    aggregate = costs.problem_aggregate(problem, assignment, k)
    loads = machine_loads(b, assignment, k)
    sq_loads = machine_loads(b * b, assignment, k)
    cut = cut_from_aggregate(aggregate, assignment)
    c0, ct0 = potentials_closed_form(loads, sq_loads, cut, problem.speeds,
                                     problem.mu, total_weight)
    return AggregateState(assignment=assignment, loads=loads,
                          aggregate=aggregate, c0=c0, ct0=ct0)


# ---------------------------------------------------------------------------
# verify_every cross-check
# ---------------------------------------------------------------------------

def resync(problem: AnyProblem, agg: AggregateState
           ) -> tuple[AggregateState, Array]:
    """Rebuild the carry from scratch, returning (fresh state, observed
    drift) — drift being the max absolute deviation of any carried
    quantity from its from-scratch value (the ``verify_every`` bound)."""
    fresh = init_aggregate_state(problem, agg.assignment)
    observed = jnp.maximum(
        jnp.max(jnp.abs(agg.aggregate - fresh.aggregate)),
        jnp.maximum(
            jnp.max(jnp.abs(agg.loads - fresh.loads)),
            jnp.maximum(jnp.abs(agg.c0 - fresh.c0),
                        jnp.abs(agg.ct0 - fresh.ct0))))
    return fresh, observed


def drift(problem: AnyProblem, agg: AggregateState) -> Array:
    """Max absolute deviation of the carried state from a rebuild."""
    return resync(problem, agg)[1]


def repair_columns(problem: AnyProblem, agg: AggregateState, tol: float
                   ) -> tuple[AggregateState, Array, Array]:
    """Active repair (DESIGN.md §15.3): rebuild from scratch like
    :func:`resync`, but patch ONLY the quantities that actually deviate
    beyond ``tol`` — per machine-column for the (N, K) aggregate, per
    entry for the loads, and per scalar (relative) for the potentials.
    Clean state passes through bitwise untouched, so a repair boundary
    on an undrifted carry is a no-op rather than a wholesale rewrite.

    Detection predicates are NaN-safe (``~(dev <= tol)`` flags NaN and
    inf as corrupt), so bit-corrupted columns are always caught.

    Returns ``(repaired, observed, cols)``: the patched state, the max
    pre-repair deviation (NaN mapped to inf — same convention as the
    ``verify_every`` drift record), and the number of aggregate columns
    patched.
    """
    fresh = init_aggregate_state(problem, agg.assignment)
    inf_dev = lambda x: jnp.nan_to_num(x, nan=jnp.inf, posinf=jnp.inf)

    col_dev = jnp.max(jnp.abs(agg.aggregate - fresh.aggregate), axis=0)  # (K,)
    col_bad = ~(col_dev <= tol)
    aggregate = jnp.where(col_bad[None, :], fresh.aggregate, agg.aggregate)

    load_dev = jnp.abs(agg.loads - fresh.loads)
    load_bad = ~(load_dev <= tol)
    loads = jnp.where(load_bad, fresh.loads, agg.loads)

    # Potentials are O(N^2)-sized f32 sums — compare relatively.
    def patch_scalar(live, ref):
        dev = jnp.abs(live - ref)
        bad = ~(dev <= tol * jnp.maximum(1.0, jnp.abs(ref)))
        return jnp.where(bad, ref, live), inf_dev(dev)

    c0, c0_dev = patch_scalar(agg.c0, fresh.c0)
    ct0, ct0_dev = patch_scalar(agg.ct0, fresh.ct0)

    observed = jnp.maximum(
        jnp.max(inf_dev(col_dev)),
        jnp.maximum(jnp.max(inf_dev(load_dev)),
                    jnp.maximum(c0_dev, ct0_dev)))
    repaired = AggregateState(assignment=agg.assignment, loads=loads,
                              aggregate=aggregate, c0=c0, ct0=ct0)
    return repaired, observed, jnp.sum(col_bad.astype(jnp.int32))
