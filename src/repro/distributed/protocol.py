"""The O(K) aggregate-exchange protocol (DESIGN.md §9.2).

Per sequential turn (acting machine m), each shard ships exactly one
:class:`Candidate` — 16 bytes: its most dissatisfied m-owned node, that
node's best-response machine, the dissatisfaction gain, and the node's
weight.  The all-gather of these S candidates *is* the entire inter-machine
exchange of the turn; every machine then runs the same deterministic
:func:`elect` on the gathered array and applies the same
:func:`apply_move` delta to its replicated assignment mirror and O(K) load
vector.  No O(N) state ever crosses the wire after the one-time
O(boundary) ghost sync (see :mod:`~repro.distributed.views`).

Shard-local compute is **incremental** (DESIGN.md §10): each shard carries
its (Ns, K) row-block aggregate in the loop and applies the elected move
as a rank-1 column update (:func:`update_block_aggregate`) — the candidate
costs come from :func:`shard_cost_from_aggregate` in O(Ns*K) per turn, and
the one-time block-aggregate matmul is the only O(Ns*N) work of a run.

Traced runs additionally exchange, per candidate, the two
exact-potential-identity deltas (ΔC_0, ΔCt_0 — Thm. 3.1/5.1, computed by
the proposing shard from its aggregate row in O(K)); the winner's deltas
update every machine's replicated potentials.  8 extra bytes per
candidate, still independent of N; the initial potentials are reduced
once from per-shard partials.

Hysteresis (DESIGN.md §11): the per-node migration-price threshold
``theta`` is a *shard-local* input — each shard subtracts its own slice
before picking its candidate, so candidates carry gains net of the
migration price and the wire payload is unchanged (still 16 B/candidate,
O(K) per turn, independent of N).

Numerical contract: :func:`shard_cost_matrix` (recompute) and
:func:`shard_cost_from_aggregate` (incremental) reproduce the rows of the
controller's cost matrix *bitwise* — both delegate to
:func:`repro.core.costs.cost_matrix_from_aggregate`, and the row-block
aggregate matmul / rank-1 updates mirror the controller's operations
exactly.  :func:`elect` reproduces the global ``argmax`` tie-breaking
(first/lowest node index wins among equal gains).  Together these make
the distributed runtime's move sequence identical to the single
controller's — asserted by tests/test_distributed.py.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import aggregate as agg_mod
from ..core import costs
from ..core.refine import Acceptance, DissatFn, acceptance_threshold

Array = jax.Array

I32_MAX = jnp.int32(2**31 - 1)

# Wire sizes (bytes) of the protocol messages, for the accounting ledgers.
CANDIDATE_BYTES = 16          # gain f32 + node i32 + dest i32 + weight f32
TRACE_PARTIAL_BYTES = 8       # ΔC_0 f32 + ΔCt_0 f32 per traced candidate


def load_partial_bytes(num_machines: int) -> int:
    """Fresh O(K) load partial exchanged per shard per §4.5 sweep."""
    return 4 * num_machines


class Candidate(NamedTuple):
    """One shard's proposal for the acting machine's move (16 bytes)."""
    gain: Array     # f32 — dissatisfaction of the proposed node (-inf if
                    #       the shard holds no movable node for machine m)
    node: Array     # i32 — global node id
    dest: Array     # i32 — the node's best-response machine
    weight: Array   # f32 — b_node (lets every peer update loads locally)


class Winner(NamedTuple):
    """Deterministic election result, identical on every machine."""
    moved: Array    # bool — gain clears the acceptance threshold
    node: Array     # i32
    dest: Array     # i32
    gain: Array     # f32
    weight: Array   # f32
    shard: Array    # i32 — index of the winning candidate's shard (lets
                    #       traced callers pick that shard's potential
                    #       deltas out of the gathered arrays)


# ---------------------------------------------------------------------------
# Shard-local compute (no communication)
# ---------------------------------------------------------------------------

def block_aggregate(row_block: Array, assignment: Array,
                    num_machines: int) -> Array:
    """One-time (Ns, K) row-block aggregate: A_s = rows @ one_hot(r).

    The contraction dimension stays exactly N, so the rows are bitwise
    equal to the controller's full-aggregate rows (DESIGN.md §9.1).
    """
    onehot = jax.nn.one_hot(assignment, num_machines, dtype=row_block.dtype)
    return jnp.matmul(row_block, onehot,
                      precision=jax.lax.Precision.HIGHEST)   # as the controller


def update_block_aggregate(aggregate: Array, row_block: Array, node: Array,
                           source: Array, dest: Array,
                           moved: Array) -> Array:
    """Apply the elected move's rank-1 column update to the shard's block:
    the same ``A[:, s] -= c[:, l]; A[:, d] += c[:, l]`` the controller
    applies, restricted to the shard's rows — O(Ns), no communication
    (every shard holds column l of its own row block)."""
    col = row_block[:, node]
    new = aggregate.at[:, source].add(-col).at[:, dest].add(col)
    return jnp.where(moved, new, aggregate)


def update_block_aggregate_sweep(aggregate: Array, row_block: Array,
                                 picks: Array, dests: Array,
                                 moved: Array) -> Array:
    """§4.5 rank-K block update: machine m's move of node picks[m] (owned
    by m, so source column = m) to dests[m], for all moving machines at
    once — mirrors :func:`repro.core.aggregate.apply_sweep` restricted to
    the shard's rows.  Idle machines' columns are masked to exact zero."""
    mask = moved.astype(row_block.dtype)                     # (K,)
    cols = row_block[:, picks] * mask[None, :]               # (Ns, K)
    new = aggregate - cols
    return new.at[:, dests].add(cols)                        # dups summed


def shard_cost_from_aggregate(aggregate: Array, r_local: Array,
                              b_local: Array, loads: Array, speeds: Array,
                              mu: Array, total_b: Array,
                              framework: str) -> Array:
    """(Ns, K) cost rows from the shard's carried block aggregate — O(Ns*K)
    per turn, bitwise equal to the controller's incremental-path rows
    (shared assembly: :func:`repro.core.costs.cost_matrix_from_aggregate`)."""
    return costs.cost_matrix_from_aggregate(
        aggregate, r_local, b_local, loads, speeds, mu, framework,
        total_weight=total_b)


def shard_cost_matrix(row_block: Array, r_local: Array, b_local: Array,
                      assignment: Array, loads: Array, speeds: Array,
                      mu: Array, total_b: Array, framework: str) -> Array:
    """(Ns, K) cost rows rebuilt from scratch (the recompute path) —
    bitwise equal to the same rows of :func:`repro.core.costs.cost_matrix`.

    ``assignment`` is the shard's O(N) *mirror* (maintained by move
    broadcasts, never re-shipped); ``loads`` the replicated O(K) vector;
    ``total_b`` the global weight total B (a one-time O(1) allreduce —
    node weights are constants of the game).
    """
    k = speeds.shape[0]
    aggregate = block_aggregate(row_block, assignment, k)   # (Ns, K)
    return shard_cost_from_aggregate(aggregate, r_local, b_local, loads,
                                     speeds, mu, total_b, framework)


def _shard_dissatisfaction(row_block, b_local, ids, valid, assignment,
                           loads, speeds, mu, total_b, framework,
                           cost_matrix_fn=None, theta_local=None):
    """Per-node dissatisfaction + best machine for the shard's rows.

    ``theta_local`` is the shard's slice of the per-node hysteresis
    threshold (DESIGN.md §11) — evaluated locally, so the wire payload
    stays the same O(K) candidates; the subtraction delegates to
    :func:`repro.core.costs.dissatisfaction_from_cost` so the net values
    are bitwise identical to the controller's.
    """
    if cost_matrix_fn is None:
        cost_matrix_fn = shard_cost_matrix
    r_local = assignment[ids]
    cost = cost_matrix_fn(row_block, r_local, b_local, assignment,
                          loads, speeds, mu, total_b, framework)
    dissat, best_machine = costs.dissatisfaction_from_cost(cost, r_local,
                                                           theta_local)
    return r_local, dissat, best_machine


def _movable(acc: Acceptance, framework: str, dissat: Array, b_local: Array,
             r_local: Array, best_machine: Array, loads: Array,
             speeds: Array) -> Array:
    """Rows whose gain clears their own acceptance threshold — the
    sequential turn's filter, mirroring ``repro.core.refine._turn``."""
    return dissat > acceptance_threshold(acc, framework, b_local, r_local,
                                         best_machine, loads, speeds)


def local_candidate(row_block: Array, b_local: Array, ids: Array,
                    valid: Array, assignment: Array, loads: Array,
                    speeds: Array, mu: Array, total_b: Array,
                    machine: Array, framework: str, acc: Acceptance,
                    cost_matrix_fn=None, theta_local=None) -> Candidate:
    """The shard's most dissatisfied node owned by ``machine`` (Eq. 4)
    among those whose gain clears its acceptance threshold."""
    r_local, dissat, best_machine = _shard_dissatisfaction(
        row_block, b_local, ids, valid, assignment, loads, speeds, mu,
        total_b, framework, cost_matrix_fn, theta_local)
    owned = (r_local == machine) & valid & _movable(
        acc, framework, dissat, b_local, r_local, best_machine, loads, speeds)
    masked = jnp.where(owned, dissat, -jnp.inf)
    loc = jnp.argmax(masked).astype(jnp.int32)
    return Candidate(gain=masked[loc], node=ids[loc],
                     dest=best_machine[loc], weight=b_local[loc])


def local_candidate_from_aggregate(aggregate: Array, b_local: Array,
                                   ids: Array, valid: Array,
                                   assignment: Array, loads: Array,
                                   speeds: Array, mu: Array, total_b: Array,
                                   machine: Array, framework: str,
                                   acc: Acceptance,
                                   with_deltas: bool = False,
                                   dissat_fn: DissatFn | None = None,
                                   theta_local=None):
    """Incremental-path candidate: costs from the shard's carried block
    aggregate, O(Ns*K) — no matmul, no read of any off-shard adjacency.

    With ``with_deltas=True`` additionally returns (ΔC_0, ΔCt_0) for the
    PROPOSED move via the exact-potential identities (Thm. 3.1/5.1),
    computed from the node's aggregate row in O(K) — the 8 traced bytes
    each shard attaches to its candidate.  ``dissat_fn`` substitutes a
    fused kernel for the jnp (dissat, best) reduction; it follows the
    canonical 9-argument convention of :mod:`repro.core.refine` ("The
    ``dissat_fn`` convention"), so
    ``repro.kernels.ops.make_aggregate_dissat_fn()`` plugs into both.
    ``theta_local`` is the shard's slice of the per-node
    hysteresis threshold (DESIGN.md §11) — subtracted shard-locally, so
    candidates carry net gains and the wire stays O(K).
    """
    r_local = assignment[ids]
    if dissat_fn is None:
        cost = shard_cost_from_aggregate(aggregate, r_local, b_local, loads,
                                         speeds, mu, total_b, framework)
        dissat, best_machine = costs.dissatisfaction_from_cost(cost, r_local,
                                                               theta_local)
    else:
        dissat, best_machine = dissat_fn(aggregate, r_local, b_local, loads,
                                         speeds, mu, framework, total_b,
                                         theta_local)
    owned = (r_local == machine) & valid & _movable(
        acc, framework, dissat, b_local, r_local, best_machine, loads, speeds)
    masked = jnp.where(owned, dissat, -jnp.inf)
    loc = jnp.argmax(masked).astype(jnp.int32)
    cand = Candidate(gain=masked[loc], node=ids[loc],
                     dest=best_machine[loc], weight=b_local[loc])
    if not with_deltas:
        return cand
    dc0, dct0 = agg_mod.potential_deltas(
        aggregate[loc], b_local[loc], machine, best_machine[loc], loads,
        speeds, mu, total_b)
    return cand, dc0, dct0


def local_candidates_all_machines_from_aggregate(
        aggregate: Array, b_local: Array, ids: Array, valid: Array,
        assignment: Array, loads: Array, speeds: Array, mu: Array,
        total_b: Array, framework: str, dissat_fn=None,
        theta_local=None) -> Candidate:
    """§4.5 sweep candidates (one per machine) from the carried block
    aggregate — Candidate of (K,) arrays, O(Ns*K) per sweep.
    ``dissat_fn`` / ``theta_local`` as in
    :func:`local_candidate_from_aggregate`."""
    k = speeds.shape[0]
    r_local = assignment[ids]
    if dissat_fn is None:
        cost = shard_cost_from_aggregate(aggregate, r_local, b_local, loads,
                                         speeds, mu, total_b, framework)
        dissat, best_machine = costs.dissatisfaction_from_cost(cost, r_local,
                                                               theta_local)
    else:
        dissat, best_machine = dissat_fn(aggregate, r_local, b_local, loads,
                                         speeds, mu, framework, total_b,
                                         theta_local)
    owned = valid[None, :] & (r_local[None, :]
                              == jnp.arange(k, dtype=jnp.int32)[:, None])
    masked = jnp.where(owned, dissat[None, :], -jnp.inf)     # (K, Ns)
    loc = jnp.argmax(masked, axis=1).astype(jnp.int32)       # (K,)
    return Candidate(gain=jnp.take_along_axis(masked, loc[:, None], 1)[:, 0],
                     node=ids[loc], dest=best_machine[loc],
                     weight=b_local[loc])


def local_candidates_all_machines(row_block: Array, b_local: Array,
                                  ids: Array, valid: Array, assignment: Array,
                                  loads: Array, speeds: Array, mu: Array,
                                  total_b: Array, framework: str,
                                  cost_matrix_fn=None,
                                  theta_local=None) -> Candidate:
    """§4.5 sweep mode: one candidate per machine — Candidate of (K,) arrays."""
    k = speeds.shape[0]
    r_local, dissat, best_machine = _shard_dissatisfaction(
        row_block, b_local, ids, valid, assignment, loads, speeds, mu,
        total_b, framework, cost_matrix_fn, theta_local)
    owned = valid[None, :] & (r_local[None, :]
                              == jnp.arange(k, dtype=jnp.int32)[:, None])
    masked = jnp.where(owned, dissat[None, :], -jnp.inf)     # (K, Ns)
    loc = jnp.argmax(masked, axis=1).astype(jnp.int32)       # (K,)
    return Candidate(gain=jnp.take_along_axis(masked, loc[:, None], 1)[:, 0],
                     node=ids[loc], dest=best_machine[loc],
                     weight=b_local[loc])


# ---------------------------------------------------------------------------
# Exchange + replicated apply (the O(K) part)
# ---------------------------------------------------------------------------

def candidate_thresholds(cands: Candidate, acc: Acceptance, machine: Array,
                         loads: Array, speeds: Array,
                         framework: str) -> Array:
    """(S,) acceptance thresholds of the gathered candidates — computed
    by every machine from the candidate's own weight and destination and
    the replicated loads, so nothing extra crosses the wire."""
    return acceptance_threshold(acc, framework, cands.weight, machine,
                                cands.dest, loads, speeds)


def elect(cands: Candidate, thresh: Array) -> Winner:
    """Pick the winning candidate from the gathered (S,) Candidate arrays.

    Max gain wins; exact-gain ties break toward the lowest global node id —
    precisely the semantics of the single controller's ``jnp.argmax`` over
    the full masked dissatisfaction vector, because each shard's local
    argmax already picked its lowest-id maximizer and shard blocks are
    contiguous ascending id ranges.  The winner moves when its gain clears
    its own entry of ``thresh`` (:func:`candidate_thresholds`).
    """
    best_gain = jnp.max(cands.gain)
    tie = cands.gain == best_gain
    shard = jnp.argmin(jnp.where(tie, cands.node, I32_MAX)).astype(jnp.int32)
    return Winner(moved=best_gain > thresh[shard],
                  node=cands.node[shard],
                  dest=cands.dest[shard],
                  gain=best_gain,
                  weight=cands.weight[shard],
                  shard=shard)


def elect_degraded(cands: Candidate, thresh: Array, lag: Array,
                   stale_penalty) -> Winner:
    """Degraded-mode election under bounded staleness (DESIGN.md §15.2).

    A shard whose carried aggregate is ``lag`` winner broadcasts old
    competes with its gain discounted by ``lag * stale_penalty``, and
    moves only if the discounted gain clears its acceptance threshold —
    the S-dependent threshold from the Adolphs–Berenbrink bounded-staleness
    analysis (arXiv:1109.6925): stale gains are optimistic by at most the
    drift a bounded number of missed moves can cause, so demanding a
    proportionally larger improvement keeps the potential descending.
    Callers mask unavailable shards (down / quarantined / undelivered)
    to ``-inf`` gain before electing.

    With ``lag == 0`` everywhere and no masks this is decision-equivalent
    to :func:`elect` (subtracting an exact zero leaves every gain
    unchanged): the winner, tie-break, and every ``moved``-gated field
    match bitwise, which is what keeps a zero-fault plan through the
    faulty drivers identical to the fault-free path.
    """
    eff = cands.gain - stale_penalty * lag.astype(jnp.float32)   # (S,)
    eff = jnp.where(jnp.isnan(eff), -jnp.inf, eff)   # NaN-poisoned columns
    best = jnp.max(eff)
    tie = eff == best
    shard = jnp.argmin(jnp.where(tie, cands.node, I32_MAX)).astype(jnp.int32)
    return Winner(moved=best > thresh[shard],
                  node=cands.node[shard],
                  dest=cands.dest[shard],
                  gain=cands.gain[shard],
                  weight=cands.weight[shard],
                  shard=shard)


def apply_move(assignment: Array, loads: Array, winner: Winner,
               machine: Array) -> tuple[Array, Array]:
    """Apply the elected move to the replicated mirror + O(K) loads.

    Mirrors ``repro.core.refine._turn`` operation-for-operation (same
    incremental ``.at[].add`` update order) so the replicated state stays
    bitwise identical to the single controller's.
    """
    new_assignment = jnp.where(
        winner.moved, assignment.at[winner.node].set(winner.dest), assignment)
    new_loads = jnp.where(
        winner.moved,
        loads.at[machine].add(-winner.weight).at[winner.dest].add(winner.weight),
        loads)
    return new_assignment, new_loads


# ---------------------------------------------------------------------------
# Traced-mode potential partials (pure reductions — O(1)/O(K) per shard)
# ---------------------------------------------------------------------------

def shard_load_partial(b_local: Array, ids: Array, valid: Array,
                       assignment: Array, num_machines: int) -> Array:
    """(K,) fresh load partial: sum of owned b over the shard's nodes."""
    bv = jnp.where(valid, b_local, jnp.zeros_like(b_local))
    return jnp.zeros((num_machines,), b_local.dtype).at[assignment[ids]].add(bv)


def shard_c0_partial(row_block: Array, b_local: Array, ids: Array,
                     valid: Array, assignment: Array, fresh_loads: Array,
                     speeds: Array, mu: Array, total_b: Array) -> Array:
    """Shard's contribution to C_0 = sum_i C_i (Thm. 3.1 potential)."""
    r_local = assignment[ids]
    cost = shard_cost_matrix(row_block, r_local, b_local, assignment,
                             fresh_loads, speeds, mu, total_b,
                             costs.C_FRAMEWORK)
    current = jnp.take_along_axis(cost, r_local[:, None], axis=1)[:, 0]
    return jnp.sum(jnp.where(valid, current, 0.0))


def shard_cut_partial(row_block: Array, ids: Array, valid: Array,
                      assignment: Array) -> Array:
    """Shard's (unhalved) cut contribution: sum_{i local} sum_j c_ij [r_i != r_j]."""
    r_local = assignment[ids]
    diff = r_local[:, None] != assignment[None, :]
    rows = jnp.where(valid[:, None], row_block, jnp.zeros_like(row_block))
    return jnp.sum(rows * diff)


def shard_cut_partial_from_aggregate(aggregate: Array, ids: Array,
                                     valid: Array,
                                     assignment: Array) -> Array:
    """Shard's (unhalved) cut contribution from its carried block aggregate
    — O(Ns*K) instead of the O(Ns*N) row sweep: per owned node,
    degree_i - A[i, r_i] (invariant I4 of DESIGN.md §10)."""
    r_local = assignment[ids]
    degree = jnp.sum(aggregate, axis=-1)
    internal = jnp.take_along_axis(aggregate, r_local[:, None], axis=1)[:, 0]
    return jnp.sum(jnp.where(valid, degree - internal, 0.0))


def global_potentials(c0_partials: Array, cut_partials: Array,
                      fresh_loads: Array, speeds: Array, mu: Array,
                      total_b: Array) -> tuple[Array, Array]:
    """Reduce gathered partials to (C_0, Ct_0) — replicated compute."""
    c0 = jnp.sum(c0_partials)
    cut = 0.5 * jnp.sum(cut_partials)
    variance = jnp.sum((fresh_loads / speeds - total_b) ** 2)
    ct0 = variance + 0.5 * mu * cut
    return c0, ct0
