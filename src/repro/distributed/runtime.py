"""Sharded refinement drivers (DESIGN.md §9).

Three execution modes over the same shard-local kernel + O(K) protocol:

  * :func:`refine_distributed`          — sequential round-robin turns,
    ``lax.while_loop`` to convergence (what ``repro.des.engine`` calls
    when ``refine_backend="distributed"``).
  * :func:`refine_distributed_traced`   — fixed-length scan recording the
    per-turn moves and both global potentials; move-for-move identical
    to :func:`repro.core.refine.refine_traced` (tests/test_distributed.py).
  * :func:`refine_distributed_simultaneous` — the §4.5 sweep mode: every
    machine moves its most dissatisfied node in the same round (descent
    not guaranteed, K× fewer exchange rounds).

Shard-local compute is **incremental by default** (DESIGN.md §10): each
shard carries its (Ns, K) row-block aggregate, built by one O(Ns·N·K)
matmul at round 0, assembles its candidates from it in O(Ns·K) per turn
and applies the elected move as the controller's rank-1 column update —
wire traffic stays the O(K) candidate exchange; the traced mode attaches
the 8-byte exact-potential deltas (Thm. 3.1/5.1) to each candidate.
``incremental=False`` restores the recompute path (block aggregate matmul
every turn); ``cost_fn="pallas"`` routes either path through its fused
Pallas kernel.

Each mode is ONE jitted loop.  The recompute path leaves the carried
aggregate's slot of the loop state ``None`` (an empty pytree), and a
``fault_plan`` (DESIGN.md §15) stages the fault steps on the incremental
path; ``fault_plan=None`` stages none of them.  The **emulated** drivers
map the shard axis with ``vmap`` on one device (the DES engine embeds
them); :func:`refine_distributed_shard_map` puts each row block on its
own device of a ``Mesh`` and exchanges candidates with ``lax.all_gather``.
One harness (:func:`_run`) serves the four public wrappers: telemetry,
the wire reconciliation and the recover-or-raise audit.
"""
from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core import aggregate as agg_mod
from ..core import costs
from ..core.problem import PartitionProblem, make_state
from ..core.refine import (DEFAULT_TOL, Acceptance, DissatFn, RefineResult,
                           Trace, _open_run, acceptance)
from . import accounting, faults, protocol
from .views import ShardViews, boundary_stats, build_views, shard_node_values

Array = jax.Array

# Declared asymptotic budgets for the distributed drivers, consumed by
# the complexity analyzers (DESIGN.md §18).  The drivers shard the dense
# representation, so per-driver memory/work carry the dense budget; the
# paper's feasibility claim (§5 of arXiv 1111.0875) lives in the
# collective schedule instead — see DISTRIBUTED_COLLECTIVES below.
DISTRIBUTED_COMPLEXITY = {
    "mem": {"n": 2.0, "k": 1.0},
    "ops": {"n": 2.0, "k": 1.0},
}

# Per-driver collective budget: total per-shard operand bytes entering
# psum/all_gather-family primitives, split into the per-round
# ("recurring", inside the refinement while-loop) and one-off ("setup")
# phases.  The emulated drivers exchange through staged buffers audited
# by wire_rules (§9.2), so they must stage ZERO collectives; the mesh
# driver gathers exactly one CandidateMsg per round — 4 scalar
# all_gathers whose per-shard operands sum to protocol.CANDIDATE_BYTES
# (§14.5), independent of N.
DISTRIBUTED_COLLECTIVES = {
    "distributed.refine": {"recurring_bytes": 0, "setup_bytes": 0},
    "distributed.refine_traced": {"recurring_bytes": 0, "setup_bytes": 0},
    "distributed.refine_simultaneous": {"recurring_bytes": 0,
                                        "setup_bytes": 0},
    "distributed.shard_map": {"recurring_bytes": protocol.CANDIDATE_BYTES,
                              "setup_bytes": 0},
}


class WireMeasurement(NamedTuple):
    """Measured exchange bytes of one distributed run (DESIGN.md §14.5).

    Produced under ``measure_wire=True``: ``payload_bytes`` is the byte
    size of the buffers that crossed the exchange each round (measured via
    :func:`_nbytes`, not from the analytic formulas) times the rounds run;
    ``setup_bytes`` the one-time replicated state.  ``rounds`` follows
    ``RefineResult.num_turns``, as ``accounting.ledger_for_run`` does.
    """
    rounds: Array          # int32 — active turns/sweeps (== num_turns)
    payload_bytes: Array   # int32 — per-round exchange, whole run
    setup_bytes: Array     # int32 — one-time replicated state


def _nbytes(tree) -> int:
    """Total byte size of a pytree's array leaves — a Python int even
    under jit (shapes are static): the size of the buffers exchanged."""
    return int(sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(tree)))


def _vmap_shards(fn, theta_blocks: Array | None, *axes):
    """Map ``fn(*per_shard_args, theta_local)`` over the shard axis with
    the optional (S, Ns) theta operand.  THE one place the optional-theta
    dispatch lives: ``theta_blocks=None`` passes a literal ``None``
    threshold through (the bitwise no-subtraction path of DESIGN.md §11)
    instead of mapping a zero block."""
    if theta_blocks is None:
        return jax.vmap(lambda *a: fn(*a, None))(*axes)
    return jax.vmap(fn)(*axes, theta_blocks)


def _shard_theta(theta, problem: PartitionProblem,
                 num_shards: int) -> Array | None:
    """(S, Ns) shard blocks of the per-node hysteresis threshold, or None;
    each shard reads only its own block, never the wire (DESIGN.md §11)."""
    if theta is None:
        return None
    theta = jnp.broadcast_to(jnp.asarray(theta, jnp.float32),
                             (problem.num_nodes,))
    return shard_node_values(theta, num_shards)


def shard_problem(problem: PartitionProblem, num_shards: int) -> ShardViews:
    """Build the static per-shard views for ``problem`` (see views.py)."""
    return build_views(problem, num_shards)


def _resolve_shards(problem: PartitionProblem, num_shards: int | None) -> int:
    if num_shards is None:
        num_shards = problem.num_machines
    return max(1, min(num_shards, problem.num_nodes))


def _shard_cost_fn(cost_fn: str):
    """Shard-local (Ns, K) cost-row builder for the RECOMPUTE path: "jnp"
    (exact, default) or "pallas" (fused kernel per row block, §3.2)."""
    if cost_fn == "jnp":
        return protocol.shard_cost_matrix
    if cost_fn == "pallas":
        from ..kernels.dissatisfaction import cost_matrix_pallas

        def pallas_rows(row_block, r_local, b_local, assignment, loads,
                        speeds, mu, total_b, framework):
            return cost_matrix_pallas(
                row_block, assignment, b_local, loads, speeds, mu,
                framework, row_assignment=r_local, total_weight=total_b)

        return pallas_rows
    raise ValueError(f"unknown cost_fn {cost_fn!r}")


def _shard_dissat_fn(cost_fn: str) -> DissatFn | None:
    """Shard-local (dissat, best) from the carried block aggregate, for the
    INCREMENTAL path: "jnp" (shared O(Ns·K) assembly, bitwise equal to the
    controller) or "pallas" (the fused kernel, through the ``dissat_fn``
    convention of :mod:`repro.core.refine`)."""
    if cost_fn == "jnp":
        return None
    if cost_fn == "pallas":
        from ..kernels.ops import make_aggregate_dissat_fn
        return make_aggregate_dissat_fn()
    raise ValueError(f"unknown cost_fn {cost_fn!r}")


def _acceptance(problem: PartitionProblem, assignment: Array,
                tol) -> Acceptance:
    """The controller's acceptance constants, built from the full problem
    before sharding — replicated, so no collective is needed for them."""
    return acceptance(problem, costs.problem_aggregate(
        problem, assignment, problem.num_machines), tol)


def _init_block_aggregates(views: ShardViews, assignment: Array,
                           num_machines: int) -> Array:
    """(S, Ns, K) carried block aggregates — the one-time matmuls."""
    return jax.vmap(
        lambda rb: protocol.block_aggregate(rb, assignment, num_machines)
    )(views.row_block)


def _vmap_candidates(views: ShardViews, aggs: Array | None,
                     assignment: Array, loads: Array, speeds: Array,
                     mu: Array, total_b: Array, machine: Array,
                     framework: str, cost_fn: str, acc: Acceptance,
                     with_deltas: bool = False,
                     theta_blocks: Array | None = None):
    """Emulated turn exchange: all S candidates, stacked — from the carried
    block aggregates ``aggs``, or recomputed from the row blocks when
    ``aggs`` is None (the recompute path, which has no deltas)."""
    if aggs is None:
        shard_cost = _shard_cost_fn(cost_fn)

        def one(rb, b, ids, valid, th):
            with jax.named_scope("shard_candidate"):
                return protocol.local_candidate(
                    rb, b, ids, valid, assignment, loads, speeds, mu,
                    total_b, machine, framework, acc,
                    cost_matrix_fn=shard_cost, theta_local=th)

        return _vmap_shards(one, theta_blocks, views.row_block,
                            views.weights, views.ids, views.valid)
    dissat_fn = _shard_dissat_fn(cost_fn)

    def one(agg, b, ids, valid, th):
        with jax.named_scope("shard_candidate_incremental"):
            return protocol.local_candidate_from_aggregate(
                agg, b, ids, valid, assignment, loads, speeds, mu, total_b,
                machine, framework, acc, with_deltas=with_deltas,
                dissat_fn=dissat_fn, theta_local=th)

    return _vmap_shards(one, theta_blocks, aggs, views.weights,
                        views.ids, views.valid)


def _update_block_aggregates(views: ShardViews, block_aggs: Array,
                             winner: protocol.Winner,
                             machine: Array) -> Array:
    """Every shard applies the elected rank-1 update to its own block."""
    return jax.vmap(
        lambda agg, rb: protocol.update_block_aggregate(
            agg, rb, winner.node, machine, winner.dest, winner.moved)
    )(block_aggs, views.row_block)


def _load_partials(views: ShardViews, weights: Array, assignment: Array,
                   num_machines: int) -> Array:
    """(S, K) per-shard load partials for the given per-shard weights."""
    return jax.vmap(
        lambda b, ids, v: protocol.shard_load_partial(
            b, ids, v, assignment, num_machines)
    )(weights, views.ids, views.valid)


def _vmap_potentials(views: ShardViews, assignment: Array, speeds: Array,
                     mu: Array, total_b: Array, num_machines: int,
                     fresh_loads: Array | None = None):
    """Emulated reduction of the per-shard potential partials from the row
    blocks (the traced init, and every round of the recompute paths);
    ``fresh_loads``, when the caller holds them, skips the load partials.
    Returns ``(loads, c0, ct0, partial_bytes)``, the last a trace-time
    int: the bytes of the partials exchanged (DESIGN.md §14.5)."""
    partial_bytes = 0
    if fresh_loads is None:
        load_partials = _load_partials(views, views.weights, assignment,
                                       num_machines)
        fresh_loads = jnp.sum(load_partials, axis=0)
        partial_bytes += _nbytes(load_partials)
    c0_partials = jax.vmap(
        lambda rb, b, ids, v: protocol.shard_c0_partial(
            rb, b, ids, v, assignment, fresh_loads, speeds, mu, total_b)
    )(views.row_block, views.weights, views.ids, views.valid)
    cut_partials = jax.vmap(
        lambda rb, ids, v: protocol.shard_cut_partial(rb, ids, v, assignment)
    )(views.row_block, views.ids, views.valid)
    partial_bytes += _nbytes((c0_partials, cut_partials))
    c0, ct0 = protocol.global_potentials(c0_partials, cut_partials,
                                         fresh_loads, speeds, mu, total_b)
    return fresh_loads, c0, ct0, partial_bytes


def _closed_potentials(views: ShardViews, sq_weights: Array, aggs: Array,
                       assignment: Array, speeds: Array, mu, total_b,
                       num_machines: int):
    """Loads + closed-form potentials from the load, squared-load and
    aggregate-cut partials (O(K) wire, no pass over the row blocks);
    returns ``(loads, c0, ct0, partial_bytes)``."""
    load_partials = _load_partials(views, views.weights, assignment,
                                   num_machines)
    fresh_loads = jnp.sum(load_partials, axis=0)
    sq_partials = _load_partials(views, sq_weights, assignment, num_machines)
    sq_loads = jnp.sum(sq_partials, axis=0)
    cut_partials = jax.vmap(
        lambda agg, ids, v: protocol.shard_cut_partial_from_aggregate(
            agg, ids, v, assignment)
    )(aggs, views.ids, views.valid)
    cut = 0.5 * jnp.sum(cut_partials)
    c0, ct0 = agg_mod.potentials_closed_form(fresh_loads, sq_loads, cut,
                                             speeds, mu, total_b)
    return fresh_loads, c0, ct0, _nbytes((load_partials, sq_partials,
                                          cut_partials))


def _elect(cands: protocol.Candidate, acc: Acceptance, loads: Array,
           problem: PartitionProblem, framework: str, machine=None,
           row=None, penalty=None) -> protocol.Winner:
    """One turn's election over (S,) candidates for ``machine`` or, with
    ``machine=None``, a sweep's per-machine elections over (S, K)
    candidates.  A fault plan ``row`` masks the blocked shards and prices
    staleness (``protocol.elect_degraded``)."""
    if row is not None:
        cands = _mask_blocked(cands, row)
    sweep = machine is None
    if sweep:
        machine = jnp.arange(problem.num_machines, dtype=jnp.int32)[None, :]
    thresh = protocol.candidate_thresholds(cands, acc, machine, loads,
                                           problem.speeds, framework)
    if row is None:
        elect, args, axes = protocol.elect, (cands, thresh), (1, 1)
    else:
        elect, args, axes = (protocol.elect_degraded,
                             (cands, thresh, row.lag, penalty),
                             (1, 1, None, None))
    return (jax.vmap(elect, in_axes=axes) if sweep else elect)(*args)


def _next_idle(idle: Array, moved: Array, row=None) -> Array:
    """Consecutive no-move turns; under a fault plan only fault-clear
    rounds count (a blocked no-move round is no evidence of equilibrium)."""
    step = idle + 1 if row is None else jnp.where(row.clear, idle + 1, idle)
    return jnp.where(moved, 0, step)


def _trace_row(winner: protocol.Winner, moved: Array, machine: Array,
               c0: Array, ct0: Array, active: Array) -> Trace:
    """One turn of the traced driver's per-turn record."""
    return Trace(
        moved=moved,
        node=jnp.where(winner.moved, winner.node, -1),
        source=jnp.where(winner.moved, machine, -1),
        dest=jnp.where(winner.moved, winner.dest, -1),
        gain=jnp.where(winner.moved, winner.gain, 0.0),
        c0=c0, ct0=ct0, active=active)


def _with_wire(outs: tuple, measure_wire: bool, rounds, per_round: int,
               setup_bytes: int, fault_bytes=None):
    """A driver's return: ``outs`` plus, under ``measure_wire``, the
    :class:`WireMeasurement` of ``rounds`` exchanges of ``per_round``
    bytes (+ a faulty run's fault bytes); a lone result is bare."""
    if measure_wire:
        payload = rounds * per_round
        if fault_bytes is not None:
            payload = payload + fault_bytes
        outs = outs + (WireMeasurement(
            rounds=rounds, payload_bytes=payload,
            setup_bytes=jnp.int32(setup_bytes)),)
    return outs[0] if len(outs) == 1 else outs


# ---------------------------------------------------------------------------
# Fault steps (DESIGN.md §15)
# ---------------------------------------------------------------------------
#
# With a ``fault_plan`` each round of an incremental driver consults its
# FaultPlan row: blocked shards' candidates are masked out, the election
# prices staleness (``protocol.elect_degraded``, the 1109.6925
# bounded-staleness rule), omitted broadcasts leave a carried aggregate
# stale, corruption entries overwrite aggregate columns, and the repair
# schedule column-patches flagged shards inside the loop via ``lax.cond``.
# A zero-fault plan reproduces the fault-free run bitwise: every degraded
# branch is gated by a predicate that is constant-false on a clear plan,
# and ``elect_degraded`` decides as ``elect`` does at lag 0.  The run ends
# with an oracle audit (:func:`_fault_close`) that the harness turns into
# the recover-or-raise contract.

class FaultTrace(NamedTuple):
    """Per-round repair side channel of the faulty scan drivers."""
    repaired: Array       # (T,) bool  — in-loop repair fired this round
    repair_drift: Array   # (T,) f32   — worst pre-repair column deviation
    repaired_cols: Array  # (T,) i32   — aggregate columns replaced


def _inf_dev(x: Array) -> Array:
    """Deviation → finite-or-inf: NaN counts as infinite drift, so a
    ``<= budget`` recovery check can never be satisfied by NaN soup."""
    return jnp.nan_to_num(x, nan=jnp.inf, posinf=jnp.inf)


def _fault_inject(aggs: Array, row, gate, num_machines: int) -> Array:
    """Overwrite column ``corrupt_col`` of flagged shards with
    ``corrupt_val`` (set semantics — a NaN payload lands as NaN)."""
    colmask = (jnp.arange(num_machines, dtype=jnp.int32)[None, :]
               == row.corrupt_col[:, None])                     # (S, K)
    zap = (row.corrupt & gate)[:, None] & colmask
    return jnp.where(zap[:, None, :], row.corrupt_val[:, None, None], aggs)


def _mask_blocked(cands: protocol.Candidate, row) -> protocol.Candidate:
    """Candidates of down / quarantined / undelivered shards cannot win:
    their gains become -inf ((S,) turn or (S, K) sweep candidates)."""
    blocked = row.down | row.quarantined | ~row.delivered
    if cands.gain.ndim == 2:
        blocked = blocked[:, None]
    return cands._replace(gain=jnp.where(blocked, -jnp.inf, cands.gain))


def _keep_missed(aggs: Array, new_aggs: Array, row) -> Array:
    """Shards that are down or missed the winner broadcast keep their
    carried (now stale) block aggregates."""
    return jnp.where((row.omit | row.down)[:, None, None], aggs, new_aggs)


def _fault_repair_cols(views: ShardViews, aggs: Array, assignment: Array,
                       repair_mask: Array, rtol: float, num_machines: int):
    """Rebuild the oracle aggregates and patch — for flagged shards only —
    the columns whose carried values deviate beyond ``rtol``.  Healthy
    columns are left bit-identical (the guard predicate is NaN-safe)."""
    fresh = _init_block_aggregates(views, assignment, num_machines)
    col_dev = jnp.max(jnp.abs(aggs - fresh), axis=1)            # (S, K)
    colbad = ~(col_dev <= rtol)                                 # NaN → bad
    sel = repair_mask[:, None] & colbad
    patched = jnp.where(sel[:, None, :], fresh, aggs)
    drift = jnp.max(jnp.where(repair_mask[:, None], _inf_dev(col_dev), 0.0))
    cols = jnp.sum(sel.astype(jnp.int32))
    return patched, drift, cols


def _fault_close(views: ShardViews, fault_plan, aggs: Array,
                 result: RefineResult, last_round, rtol: float,
                 num_machines: int, counters):
    """Post-run oracle audit + guarded patch of a faulty run; returns the
    audited ``(result, FaultOutcome)``.  ``counters`` are the in-loop
    ``(repairs, repaired_cols, max_repair_drift)`` or the
    :class:`FaultTrace` they are summed from.

    ``final_drift`` is the worst carried-vs-recomputed deviation (columns
    and loads, NaN → inf) before the patch of still-alive shards' bad
    columns and bad loads; ``post_drift`` (the ``aggregate_drift``) after
    it.  A shard down on the last round of a non-converged run is dead:
    it stays unpatched and the harness raises ``DeadShardError``."""
    assignment, loads = result.assignment, result.loads
    horizon = fault_plan.down.shape[0] - 1
    last = jnp.clip(last_round, 0, horizon)
    dead_row = fault_plan.down[last] & ~result.converged        # (S,)
    fresh = _init_block_aggregates(views, assignment, num_machines)
    col_dev = jnp.max(jnp.abs(aggs - fresh), axis=1)            # (S, K)
    fresh_loads = jnp.sum(_load_partials(
        views, views.weights, assignment, num_machines), axis=0)
    load_dev = _inf_dev(jnp.abs(loads - fresh_loads))
    final_drift = jnp.maximum(jnp.max(_inf_dev(col_dev)),
                              jnp.max(load_dev))
    sel = (~dead_row)[:, None] & ~(col_dev <= rtol)
    aggs = jnp.where(sel[:, None, :], fresh, aggs)
    loads = jnp.where(~(load_dev <= rtol), fresh_loads, loads)
    post_col = jnp.max(jnp.abs(aggs - fresh), axis=1)
    post_drift = jnp.maximum(
        jnp.max(_inf_dev(post_col)),
        jnp.max(_inf_dev(jnp.abs(loads - fresh_loads))))
    fcols = jnp.sum(sel.astype(jnp.int32))
    dead = jnp.any(dead_row)
    if isinstance(counters, FaultTrace):
        repairs = jnp.sum(counters.repaired.astype(jnp.int32))
        cols = jnp.sum(counters.repaired_cols) + fcols
        repair_drift = jnp.max(counters.repair_drift)
    else:
        repairs, cols, repair_drift = counters
        cols = cols + fcols
    outcome = faults.FaultOutcome(
        final_drift=final_drift, post_drift=post_drift, dead=dead,
        repairs=repairs, repaired_cols=cols, max_repair_drift=repair_drift)
    return result._replace(loads=loads, aggregate_drift=post_drift), outcome


# ---------------------------------------------------------------------------
# Sequential round-robin turns (paper §4.2 protocol, distributed)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("framework", "num_shards", "max_turns",
                                   "cost_fn", "incremental", "measure_wire",
                                   "degraded"))
def _refine_distributed(problem: PartitionProblem, assignment: Array,
                        framework: str = costs.C_FRAMEWORK,
                        num_shards: int | None = None,
                        max_turns: int = 10_000, tol: float = DEFAULT_TOL,
                        cost_fn: str = "jnp",
                        incremental: bool = True,
                        theta=None, measure_wire: bool = False,
                        fault_plan=None,
                        degraded=faults.DEFAULT_DEGRADED):
    """Distributed round-robin refinement to convergence (K idle turns).

    Protocol per turn: each shard computes one Candidate from local state
    (16 bytes on the wire), the candidates are all-gathered, every machine
    elects the same winner and applies the same O(1) delta to its
    replicated assignment mirror + O(K) load vector — and, on the default
    incremental path, the same rank-1 update to its carried (Ns, K) block
    aggregate, so no shard ever rebuilds its aggregate matmul after turn 0.

    ``theta`` (scalar or (N,)) is the migration-price hysteresis threshold
    (DESIGN.md §11), evaluated shard-locally — the wire stays O(K) and
    ``theta=None``/``0`` reproduces the threshold-free move sequence
    bitwise (the core↔distributed contract).

    ``measure_wire=True`` (static) returns ``(result, wire)`` with a
    :class:`WireMeasurement` of the per-turn candidate exchange
    (DESIGN.md §14.5); the default jaxpr is unchanged.

    ``fault_plan`` (incremental path only) stages the fault steps under
    the static ``degraded`` rules: idles count only on fault-clear
    rounds, the wire includes the fault bytes, and the return is
    ``(result, outcome)`` (+ wire) with a :class:`faults.FaultOutcome`.
    """
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    faulty = fault_plan is not None
    aggs0 = (_init_block_aggregates(views, state0.assignment, k)
             if incremental else None)
    if faulty:
        msg = faults.message_bytes(traced=False, simultaneous=False,
                                   num_machines=k)
        zero_i, zero_f = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)

    def cond(carry):
        idle, turns = carry[4], carry[5]
        return (idle < k) & (turns < max_turns)

    def body(carry):
        r, loads, aggs, machine, idle, turns, moves = carry[:7]
        row = None
        if faulty:
            row = faults.plan_row(fault_plan, turns)
            aggs = _fault_inject(aggs, row, True, k)
        cands = _vmap_candidates(
            views, aggs, r, loads, problem.speeds, problem.mu, total_b,
            machine, framework, cost_fn, acc, theta_blocks=theta_blocks)
        measured["turn"] = _nbytes(cands)
        winner = _elect(cands, acc, loads, problem, framework, machine,
                        row, degraded.stale_penalty)
        if incremental:
            new_aggs = _update_block_aggregates(views, aggs, winner, machine)
            aggs = new_aggs if row is None else _keep_missed(aggs, new_aggs,
                                                             row)
        r, loads = protocol.apply_move(r, loads, winner, machine)
        idle = _next_idle(idle, winner.moved, row)
        if faulty:
            do_repair = jnp.any(row.repair)
            aggs, rd, rc = jax.lax.cond(
                do_repair,
                lambda a: _fault_repair_cols(views, a, r, row.repair,
                                             degraded.repair_tol, k),
                lambda a: (a, zero_f, zero_i), aggs)
            fbytes = carry[7] + faults.round_extra_bytes(row, msg)
        new = (r, loads, aggs, (machine + 1) % k, idle, turns + 1,
               moves + winner.moved.astype(jnp.int32))
        if not faulty:
            return new
        repairs, rcols, rdrift = carry[8:]
        return new + (fbytes, repairs + do_repair.astype(jnp.int32),
                      rcols + rc, jnp.maximum(rdrift, rd))

    init = (state0.assignment, state0.loads, aggs0) + tuple(
        jnp.zeros((), jnp.int32) for _ in range(4))
    if faulty:
        init = init + (zero_i, zero_i, zero_i, zero_f)
    out = jax.lax.while_loop(cond, body, init)
    r, loads, aggs, _, idle, turns, moves = out[:7]
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=idle >= k)
    setup = _nbytes((state0.loads, total_b))
    if not faulty:
        return _with_wire((result,), measure_wire, turns, measured["turn"],
                          setup)
    result, outcome = _fault_close(views, fault_plan, aggs, result,
                                   turns - 1, degraded.repair_tol, k,
                                   out[8:])
    return _with_wire((result, outcome), measure_wire, turns,
                      measured["turn"], setup, out[7])


@partial(jax.jit, static_argnames=("framework", "num_shards", "max_turns",
                                   "cost_fn", "incremental", "measure_wire",
                                   "degraded"))
def _refine_distributed_traced(problem: PartitionProblem, assignment: Array,
                               framework: str = costs.C_FRAMEWORK,
                               num_shards: int | None = None,
                               max_turns: int = 512,
                               tol: float = DEFAULT_TOL,
                               cost_fn: str = "jnp",
                               incremental: bool = True,
                               theta=None, measure_wire: bool = False,
                               fault_plan=None,
                               degraded=faults.DEFAULT_DEGRADED):
    """Fixed-length traced variant; returns ``(RefineResult, Trace)`` with
    the exact semantics (and, in sequential mode, the exact move sequence)
    of :func:`repro.core.refine.refine_traced`.

    On the incremental path the potentials are initialized once from
    per-shard partials and thereafter updated by the winner's 8-byte
    exact-potential deltas (Thm. 3.1/5.1) — O(1) wire + O(K) compute per
    turn, no O(N) pass of any kind.  ``incremental=False`` restores the
    per-turn partial-reduction recompute.  ``theta`` as in
    :func:`refine_distributed`.

    ``measure_wire=True`` (static) returns ``(result, trace, wire)``;
    the per-turn exchange counts the potential deltas (or the recompute
    partials) and the setup the initial-potential partials.

    ``fault_plan`` as in :func:`_refine_distributed`; a repair round also
    recomputes C_0/Ct_0 closed-form from the patched aggregates and
    guard-patches the carried values (relative tolerance, so fault-free
    float noise never patches).  Returns ``(result, trace, ftrace,
    outcome)`` (+ wire).
    """
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    setup = _nbytes((state0.loads, total_b))
    faulty = fault_plan is not None
    aggs0 = c0_init = ct0_init = None
    if faulty:
        rtol = degraded.repair_tol
        msg = faults.message_bytes(traced=True, simultaneous=False,
                                   num_machines=k)
        sq_weights = views.weights * views.weights
    if incremental:
        aggs0 = _init_block_aggregates(views, state0.assignment, k)
        _, c0_init, ct0_init, init_pot_bytes = _vmap_potentials(
            views, state0.assignment, problem.speeds, problem.mu,
            total_b, k, fresh_loads=state0.loads)
        setup = setup + init_pot_bytes
    if faulty:
        zero_i, zero_f = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)

    def step(carry, t):
        r, loads, aggs, c0, ct0, machine, idle = carry[:7]
        active = idle < k
        row = None
        if faulty:
            row = faults.plan_row(fault_plan, t)
            aggs = _fault_inject(aggs, row, active, k)
        cands = _vmap_candidates(
            views, aggs, r, loads, problem.speeds, problem.mu, total_b,
            machine, framework, cost_fn, acc, with_deltas=incremental,
            theta_blocks=theta_blocks)
        if incremental:
            cands, dc0s, dct0s = cands
            measured["turn"] = _nbytes((cands, dc0s, dct0s))
        winner = _elect(cands, acc, loads, problem, framework, machine,
                        row, degraded.stale_penalty)
        if incremental:
            moved = winner.moved & active
            gated = winner._replace(moved=moved)
            aggs_next = _update_block_aggregates(views, aggs, gated, machine)
            if faulty:
                aggs_next = _keep_missed(aggs, aggs_next, row)
            new_r, new_loads = protocol.apply_move(r, loads, gated, machine)
            new_c0 = jnp.where(moved, c0 + dc0s[winner.shard], c0)
            new_ct0 = jnp.where(moved, ct0 + dct0s[winner.shard], ct0)
            idle = _next_idle(idle, moved, row)
        else:
            new_r, new_loads = protocol.apply_move(r, loads, winner, machine)
            new_r = jnp.where(active, new_r, r)
            new_loads = jnp.where(active, new_loads, loads)
            moved = winner.moved & active
            idle = _next_idle(idle, moved)
            _, new_c0, new_ct0, pot_bytes = _vmap_potentials(
                views, new_r, problem.speeds, problem.mu, total_b, k)
            measured["turn"] = _nbytes(cands) + pot_bytes
        if faulty:
            do_repair = jnp.any(row.repair) & active

            def with_repair(ops):
                aggs_, loads_, c0_, ct0_ = ops
                patched, rd, rc = _fault_repair_cols(views, aggs_, new_r,
                                                     row.repair, rtol, k)
                fl, c0f, ct0f, _ = _closed_potentials(
                    views, sq_weights, patched, new_r, problem.speeds,
                    problem.mu, total_b, k)

                def guard(x, fresh):
                    bad = ~(jnp.abs(x - fresh)
                            <= rtol * jnp.maximum(1.0, jnp.abs(fresh)))
                    return jnp.where(bad, fresh, x)

                loads2 = jnp.where(~(jnp.abs(loads_ - fl) <= rtol), fl,
                                   loads_)
                return (patched, loads2, guard(c0_, c0f), guard(ct0_, ct0f),
                        rd, rc)

            aggs_next, new_loads, new_c0, new_ct0, rd, rc = jax.lax.cond(
                do_repair, with_repair, lambda ops: ops + (zero_f, zero_i),
                (aggs_next, new_loads, new_c0, new_ct0))
            fbytes = carry[7] + jnp.where(
                active, faults.round_extra_bytes(row, msg), 0)
        out = _trace_row(winner, moved, machine, new_c0, new_ct0, active)
        carried = ((aggs_next, new_c0, new_ct0) if incremental
                   else (None, None, None))
        new = (new_r, new_loads, *carried, (machine + 1) % k, idle)
        if not faulty:
            return new, out
        return new + (fbytes,), (out, FaultTrace(
            repaired=do_repair, repair_drift=rd, repaired_cols=rc))

    init = (state0.assignment, state0.loads, aggs0, c0_init, ct0_init,
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    if faulty:
        init = init + (zero_i,)
    final, trace = jax.lax.scan(
        step, init, jnp.arange(max_turns) if faulty else None,
        length=max_turns)
    r, loads, aggs, _, _, _, idle = final[:7]
    if faulty:
        trace, ftrace = trace
    moves = jnp.sum(trace.moved.astype(jnp.int32))
    turns = jnp.sum(trace.active.astype(jnp.int32))
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=idle >= k)
    if not faulty:
        return _with_wire((result, trace), measure_wire, turns,
                          measured["turn"], setup)
    result, outcome = _fault_close(views, fault_plan, aggs, result,
                                   turns - 1, rtol, k, ftrace)
    return _with_wire((result, trace, ftrace, outcome), measure_wire,
                      turns, measured["turn"], setup, final[7])


# ---------------------------------------------------------------------------
# §4.5 simultaneous sweeps, distributed
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("framework", "num_shards", "max_sweeps",
                                   "cost_fn", "incremental", "measure_wire",
                                   "degraded"))
def _refine_distributed_simultaneous(problem: PartitionProblem,
                                     assignment: Array,
                                     framework: str = costs.C_FRAMEWORK,
                                     num_shards: int | None = None,
                                     max_sweeps: int = 256,
                                     tol: float = DEFAULT_TOL,
                                     cost_fn: str = "jnp",
                                     incremental: bool = True,
                                     theta=None, measure_wire: bool = False,
                                     fault_plan=None,
                                     degraded=faults.DEFAULT_DEGRADED):
    """Distributed §4.5 sweeps: each shard ships K candidates per sweep
    (one per machine), elections run per machine, all K disjoint moves
    apply at once as a rank-K block-aggregate update.  Exchange per sweep:
    S*K candidates + S load/sq-load/cut partials — still independent of N.

    ``num_moves`` counts actual transfers (sum of per-sweep movers), not
    the K*sweeps upper bound.  ``theta`` as in :func:`refine_distributed`.

    ``measure_wire=True`` (static) returns ``(result, traces, wire)``;
    ``rounds`` counts active sweeps, like ``num_turns``.

    ``fault_plan`` as in :func:`_refine_distributed`: ``done`` latches
    only on a fault-clear no-move round, and the wire counts the executed
    rounds, ``counted = ~done & (any_move | ~clear)`` — the active sweeps
    on a zero plan, and always a prefix, which keeps the host ledger
    (``faults.plan_extra_bytes``) byte-exact.  Returns ``(result, (c0s,
    ct0s, counted), ftrace, outcome)`` (+ wire).
    """
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    sq_weights = views.weights * views.weights
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    faulty = fault_plan is not None
    aggs0 = (_init_block_aggregates(views, state0.assignment, k)
             if incremental else None)
    if faulty:
        msg = faults.message_bytes(traced=False, simultaneous=True,
                                   num_machines=k)
        zero_i, zero_f = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)

    def sweep_candidates(aggs, r, loads):
        """(S, K) candidates from ``aggs``, or the row blocks if None."""
        if aggs is None:
            shard_cost = _shard_cost_fn(cost_fn)

            def one(rb, b, ids, v, th):
                return protocol.local_candidates_all_machines(
                    rb, b, ids, v, r, loads, problem.speeds, problem.mu,
                    total_b, framework, cost_matrix_fn=shard_cost,
                    theta_local=th)

            return _vmap_shards(one, theta_blocks, views.row_block,
                                views.weights, views.ids, views.valid)
        dissat_fn = _shard_dissat_fn(cost_fn)

        def one(agg, b, ids, v, th):
            return protocol.local_candidates_all_machines_from_aggregate(
                agg, b, ids, v, r, loads, problem.speeds, problem.mu,
                total_b, framework, dissat_fn=dissat_fn, theta_local=th)

        return _vmap_shards(one, theta_blocks, aggs, views.weights,
                            views.ids, views.valid)

    def sweep(carry, t):
        r, loads, aggs, done, moves = carry[:5]
        row = None
        if faulty:
            row = faults.plan_row(fault_plan, t)
            aggs = _fault_inject(aggs, row, ~done, k)
        cands = sweep_candidates(aggs, r, loads)                # (S, K)
        winners = _elect(cands, acc, loads, problem, framework, None, row,
                         degraded.stale_penalty)                  # (K,)
        any_move = jnp.any(winners.moved) & ~done
        # Idle machines elect a fallback candidate (all gains -inf) whose
        # node id may collide with a real move — mask their columns / drop
        # their writes instead of racing the update.
        safe_picks = jnp.where(winners.moved, winners.node,
                               jnp.int32(problem.num_nodes))
        new_r = r.at[safe_picks].set(winners.dest, mode="drop")
        new_r = jnp.where(any_move, new_r, r)
        new_aggs = None
        if incremental:
            new_aggs = jax.vmap(
                lambda agg, rb: protocol.update_block_aggregate_sweep(
                    agg, rb, winners.node, winners.dest, winners.moved)
            )(aggs, views.row_block)
            new_aggs = jnp.where(any_move, new_aggs, aggs)
        if faulty:
            new_aggs = _keep_missed(aggs, new_aggs, row)
            do_repair = jnp.any(row.repair) & ~done
            new_aggs, rd, rc = jax.lax.cond(
                do_repair,
                lambda a: _fault_repair_cols(views, a, new_r, row.repair,
                                             degraded.repair_tol, k),
                lambda a: (a, zero_f, zero_i), new_aggs)
        if incremental:
            new_loads, c0, ct0, pot_bytes = _closed_potentials(
                views, sq_weights, new_aggs, new_r, problem.speeds,
                problem.mu, total_b, k)
        else:
            new_loads, c0, ct0, pot_bytes = _vmap_potentials(
                views, new_r, problem.speeds, problem.mu, total_b, k)
        measured["sweep"] = _nbytes(cands) + pot_bytes
        moves = moves + jnp.where(
            any_move, jnp.sum(winners.moved.astype(jnp.int32)), 0)
        if not faulty:
            return ((new_r, new_loads, new_aggs, done | ~any_move, moves),
                    (c0, ct0, any_move))
        counted = ~done & (any_move | ~row.clear)
        fbytes = carry[5] + jnp.where(
            counted, faults.round_extra_bytes(row, msg), 0)
        new_done = done | (~any_move & row.clear)
        return ((new_r, new_loads, new_aggs, new_done, moves, fbytes),
                ((c0, ct0, counted), FaultTrace(
                    repaired=do_repair, repair_drift=rd, repaired_cols=rc)))

    init = (state0.assignment, state0.loads, aggs0, jnp.zeros((), bool),
            jnp.zeros((), jnp.int32))
    if faulty:
        init = init + (zero_i,)
    final, outs = jax.lax.scan(
        sweep, init, jnp.arange(max_sweeps) if faulty else None,
        length=max_sweeps)
    r, loads, aggs, done, moves = final[:5]
    if faulty:
        outs, ftrace = outs
    sweeps = jnp.sum(outs[2].astype(jnp.int32))
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=sweeps, converged=done)
    setup = _nbytes((state0.loads, total_b))
    if not faulty:
        return _with_wire((result, outs), measure_wire, sweeps,
                          measured["sweep"], setup)
    result, outcome = _fault_close(views, fault_plan, aggs, result,
                                   max_sweeps - 1, degraded.repair_tol, k,
                                   ftrace)
    return _with_wire((result, outs, ftrace, outcome), measure_wire, sweeps,
                      measured["sweep"], setup, final[5])


# ---------------------------------------------------------------------------
# Real-mesh driver: shard_map + lax.all_gather
# ---------------------------------------------------------------------------

def refine_distributed_shard_map(problem: PartitionProblem, assignment: Array,
                                 framework: str = costs.C_FRAMEWORK,
                                 num_shards: int | None = None,
                                 max_turns: int = 10_000,
                                 tol: float = DEFAULT_TOL,
                                 devices=None, theta=None,
                                 measure_wire: bool = False,
                                 recorder=None, fault_plan=None,
                                 degraded=None):
    """Sequential-turn refinement with each shard on its own device.

    Row blocks are placed along a 1-D ``Mesh`` axis ``"shards"``; the
    per-turn exchange is a real ``lax.all_gather`` of the 16-byte
    candidates; every device then elects/applies the identical delta to
    its replicated mirror (``check_vma=False`` because the replication
    invariant is ours, established by construction, not inferable by the
    partitioner).  Each device also carries its (Ns, K) block aggregate —
    built once at entry, updated by the same rank-1 delta every turn — so
    per-turn device compute is O(Ns·K), not O(Ns·N·K).  Requires
    ``num_shards`` addressable devices — the bench forces a multi-device
    host platform via ``XLA_FLAGS``; on one device it degenerates to a
    1-shard mesh (still the collective code path).

    ``measure_wire=True`` returns ``(result, wire)``, the payload
    counting the real ``lax.all_gather`` buffers per turn; ``recorder``
    as in :func:`refine_distributed`.  ``fault_plan`` as there too
    (DESIGN.md §15.3): the plan rides replicated (one ``P()`` spec), each
    device masks/injects/repairs only its own block (``lax.axis_index``)
    and the outcome scalars reduce with ``pmax``/``psum``.
    """
    k = problem.num_machines
    if devices is None:
        devices = jax.devices()
    s = _resolve_shards(problem, num_shards)
    if len(devices) < s:
        raise ValueError(
            f"refine_distributed_shard_map: need {s} devices for {s} shards "
            f"but only {len(devices)} are available; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={s} or use "
            f"the emulated refine_distributed driver")
    mesh = Mesh(np.asarray(devices[:s]), ("shards",))
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    # theta is a shard-local per-node input (DESIGN.md §11): placed on the
    # shard axis like the weights, never exchanged.  A zero block is the
    # exact no-threshold game (the subtraction of 0 is lossless in f32).
    theta_blocks = _shard_theta(theta, problem, s)
    if theta_blocks is None:
        theta_blocks = jnp.zeros((s, views.shard_size), jnp.float32)
    acc = _acceptance(problem, state0.assignment, tol)
    faulty = fault_plan is not None
    dm = degraded or faults.DEFAULT_DEGRADED
    rtol = dm.repair_tol
    msg = faults.message_bytes(traced=False, simultaneous=False,
                               num_machines=k)
    measured: dict = {}

    def spmd(rb, b, ids, valid, th, r0, loads0, speeds, mu, tot, *plan):
        rb, b, ids, valid, th = rb[0], b[0], ids[0], valid[0], th[0]
        if faulty:
            (plan,) = plan
            idx = jax.lax.axis_index("shards")
        agg0 = protocol.block_aggregate(rb, r0, k)   # once, O(Ns·N·K)

        def cond(carry):
            idle, turns = carry[4], carry[5]
            return (idle < k) & (turns < max_turns)

        def body(carry):
            r, loads, agg, machine, idle, turns, moves = carry[:7]
            row = None
            if faulty:
                row = faults.plan_row(plan, turns)
                colmask = (jnp.arange(k, dtype=jnp.int32)
                           == row.corrupt_col[idx])
                agg = jnp.where(row.corrupt[idx] & colmask[None, :],
                                row.corrupt_val[idx], agg)
            cand = protocol.local_candidate_from_aggregate(
                agg, b, ids, valid, r, loads, speeds, mu, tot, machine,
                framework, acc, theta_local=th)
            cands = protocol.Candidate(
                gain=jax.lax.all_gather(cand.gain, "shards"),
                node=jax.lax.all_gather(cand.node, "shards"),
                dest=jax.lax.all_gather(cand.dest, "shards"),
                weight=jax.lax.all_gather(cand.weight, "shards"))
            measured["turn"] = _nbytes(cands)
            winner = _elect(cands, acc, loads, problem, framework, machine,
                            row, dm.stale_penalty)
            new_agg = protocol.update_block_aggregate(
                agg, rb, winner.node, machine, winner.dest, winner.moved)
            agg = (new_agg if row is None else
                   jnp.where(row.omit[idx] | row.down[idx], agg, new_agg))
            r, loads = protocol.apply_move(r, loads, winner, machine)
            idle = _next_idle(idle, winner.moved, row)
            if faulty:
                def with_repair(a):
                    fresh = protocol.block_aggregate(rb, r, k)
                    col_dev = jnp.max(jnp.abs(a - fresh), axis=0)   # (K,)
                    colbad = ~(col_dev <= rtol)
                    patched = jnp.where(colbad[None, :], fresh, a)
                    return (patched, jnp.max(_inf_dev(col_dev)),
                            jnp.sum(colbad.astype(jnp.int32)))

                agg, rd, rc = jax.lax.cond(
                    row.repair[idx], with_repair,
                    lambda a: (a, zero_f, zero_i), agg)
                fbytes = carry[7] + faults.round_extra_bytes(row, msg)
            new = (r, loads, agg, (machine + 1) % k, idle, turns + 1,
                   moves + winner.moved.astype(jnp.int32))
            if not faulty:
                return new
            repairs, rcols, rdrift = carry[8:]
            return new + (fbytes, repairs + row.repair[idx].astype(jnp.int32),
                          rcols + rc, jnp.maximum(rdrift, rd))

        zero_i, zero_f = jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32)
        init = (r0, loads0, agg0, zero_i, zero_i, zero_i, zero_i)
        if faulty:
            init = init + (zero_i, zero_i, zero_i, zero_f)
        out = jax.lax.while_loop(cond, body, init)
        r, loads, agg, _, idle, turns, moves = out[:7]
        if not faulty:
            return r, loads, moves, turns, idle >= k
        fbytes, repairs, rcols, rdrift = out[7:]
        converged = idle >= k
        last = jnp.clip(turns - 1, 0, plan.down.shape[0] - 1)
        dead_row = plan.down[last] & ~converged
        fresh = protocol.block_aggregate(rb, r, k)
        col_dev = jnp.max(jnp.abs(agg - fresh), axis=0)
        part = protocol.shard_load_partial(b, ids, valid, r, k)
        fresh_loads = jax.lax.psum(part, "shards")
        load_dev = _inf_dev(jnp.abs(loads - fresh_loads))
        final_drift = jax.lax.pmax(
            jnp.maximum(jnp.max(_inf_dev(col_dev)), jnp.max(load_dev)),
            "shards")
        sel = ~dead_row[idx] & ~(col_dev <= rtol)
        agg = jnp.where(sel[None, :], fresh, agg)
        loads = jnp.where(~(load_dev <= rtol), fresh_loads, loads)
        post_col = jnp.max(jnp.abs(agg - fresh), axis=0)
        post_drift = jax.lax.pmax(
            jnp.maximum(jnp.max(_inf_dev(post_col)),
                        jnp.max(_inf_dev(jnp.abs(loads - fresh_loads)))),
            "shards")
        fcols = jax.lax.psum(jnp.sum(sel.astype(jnp.int32)), "shards")
        return (r, loads, moves, turns, converged, fbytes,
                final_drift, post_drift, jnp.any(dead_row),
                jax.lax.psum(repairs, "shards"),
                jax.lax.psum(rcols, "shards") + fcols,
                jax.lax.pmax(rdrift, "shards"))

    sharded, rep = P("shards"), P()
    fn = jax.shard_map(spmd, mesh=mesh,
                       in_specs=(sharded,) * 5 + (rep,) * (6 if faulty else 5),
                       out_specs=(rep,) * (12 if faulty else 5),
                       check_vma=False)
    args = (views.row_block, views.weights, views.ids, views.valid,
            theta_blocks, state0.assignment, state0.loads, problem.speeds,
            problem.mu, total_b) + ((fault_plan,) if faulty else ())

    def call(mw: bool):
        out = jax.jit(fn)(*args)
        result = RefineResult(assignment=out[0], loads=out[1],
                              num_moves=out[2], num_turns=out[3],
                              converged=out[4])
        outs = ((result,) if not faulty else
                (result._replace(aggregate_drift=out[7]),
                 faults.FaultOutcome(*out[6:])))
        if mw:
            # the fresh jax.jit(fn) above traces on every call, which
            # fills measured["turn"] with the gathered candidates' size
            rounds = int(np.asarray(out[3]))
            fault_bytes = int(np.asarray(out[5])) if faulty else 0
            outs = outs + (WireMeasurement(
                rounds=jnp.int32(rounds),
                payload_bytes=jnp.int32(rounds * measured["turn"]
                                        + fault_bytes),
                setup_bytes=jnp.int32(_nbytes((state0.loads, total_b)))),)
        return outs[0] if len(outs) == 1 else outs

    return _run(_SHARD_MAP, call, problem, assignment, framework, theta, s,
                measure_wire, recorder, fault_plan, dm)


# ---------------------------------------------------------------------------
# Public wrappers: telemetry and recover-or-raise (DESIGN.md §14, §15)
# ---------------------------------------------------------------------------

class _Mode(NamedTuple):
    """How a driver's runs are named and its wire is accounted."""
    runtime: str              # run_start runtime name
    phase: str                # recorder phase around the jitted call
    traced: bool = False
    simultaneous: bool = False


_PLAIN = _Mode("distributed", "distributed.refine")
_TRACED = _Mode("distributed_traced", "distributed.refine_traced",
                traced=True)
_SWEEP = _Mode("distributed_sweep", "distributed.refine_simultaneous",
               simultaneous=True)
_SHARD_MAP = _Mode("shard_map", "distributed.shard_map")


def _run(mode: _Mode, call, problem: PartitionProblem, assignment: Array,
         framework: str, theta, num_shards: int, measure_wire: bool,
         recorder, fault_plan, degraded, **run_fields):
    """The one harness behind the public drivers.  ``call(mw)`` runs the
    jitted driver with ``measure_wire=mw``; with neither ``recorder`` nor
    ``fault_plan`` its output is returned as is (no host work: the DES
    engine calls the wrappers from traced code).  Otherwise the run is
    opened as ``mode.runtime`` (with ``run_fields``), timed under
    ``mode.phase``, its trace/sweeps and reconciled wire recorded; a
    faulty run's outcome becomes an appended :class:`faults.FaultReport`
    and a dead shard or blown recovery budget raises."""
    if recorder is None and fault_plan is None:
        return call(measure_wire)
    faulty = fault_plan is not None
    k = problem.num_machines
    mw = measure_wire or recorder is not None
    run = None
    if recorder is not None:
        run = _open_run(recorder, mode.runtime, problem, assignment,
                        framework, theta, num_shards=num_shards,
                        **run_fields, **({"faults": True} if faulty else {}))
    t0 = time.perf_counter()
    with (recorder.phase(mode.phase, run) if recorder is not None
          else contextlib.nullcontext()):
        out = call(mw)
        jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    parts = list(out)
    wire = parts.pop() if mw else None
    outcome = parts.pop() if faulty else None
    ftrace = (parts.pop() if faulty and (mode.traced or mode.simultaneous)
              else None)
    result, extras = parts[0], tuple(parts[1:])     # extras: trace/sweeps
    rounds = int(result.num_turns)
    if faulty:
        report = faults.build_report(fault_plan, outcome, rounds,
                                     budget=degraded.repair_tol,
                                     raise_on_failure=False)
    if recorder is not None:
        if faulty:
            ft = ftrace or FaultTrace(None, None, None)
            faults.emit_fault_events(
                recorder, run, fault_plan, rounds, repaired=ft.repaired,
                repair_drift=ft.repair_drift, repaired_cols=ft.repaired_cols)
        pots = None
        if mode.traced:
            recorder.record_trace(run, extras[0], problem.node_weights, k)
            pots = (extras[0].c0, extras[0].ct0)
        elif mode.simultaneous:
            recorder.record_sweeps(run, *extras[0])
            pots = extras[0][:2]
        c0 = ct0 = None
        if pots is not None and rounds:
            c0, ct0 = (float(np.asarray(p)[rounds - 1]) for p in pots)
        # measured wire vs the analytic ledger of the same executed run,
        # the plan's retry/repair bytes included
        fault_extra = 0 if not faulty else faults.plan_extra_bytes(
            fault_plan, rounds, faults.message_bytes(
                traced=mode.traced, simultaneous=mode.simultaneous,
                num_machines=k))
        ledger = accounting.ledger_for_run(
            boundary_stats(problem, num_shards), k, int(wire.rounds),
            traced=mode.traced, simultaneous=mode.simultaneous,
            incremental=run_fields.get("incremental", True),
            fault_bytes=fault_extra)
        recorder.record_wire(run, accounting.reconcile(ledger, wire))
        verdict = ({} if not faulty else
                   dict(recovered=report.recovered,
                        recovery_drift=report.recovery_drift))
        recorder.record_result(run, result, wall=wall, c0=c0, ct0=ct0,
                               **verdict)
    outs = (result, *extras) + ((wire,) if measure_wire else ())
    if faulty:
        faults.raise_if_failed(report, budget=degraded.repair_tol)
        outs = outs + (report,)
    return outs[0] if len(outs) == 1 else outs


def _run_emulated(driver, mode: _Mode, cap: dict, problem, assignment,
                  framework, num_shards, tol, cost_fn, incremental, theta,
                  measure_wire, recorder, fault_plan, degraded):
    """:func:`_run` over an emulated driver (``cap``: its step cap)."""
    if fault_plan is not None and not incremental:
        raise ValueError(
            "fault injection requires the incremental protocol: the "
            "carried block aggregates are what faults corrupt and what "
            "repair heals (DESIGN.md §15)")
    s = _resolve_shards(problem, num_shards)
    dm = degraded or faults.DEFAULT_DEGRADED
    plan = ({} if fault_plan is None else
            dict(fault_plan=fault_plan, degraded=dm))

    def call(mw: bool):
        return driver(problem, assignment, framework, num_shards=s, tol=tol,
                      cost_fn=cost_fn, incremental=incremental, theta=theta,
                      measure_wire=mw, **cap, **plan)

    return _run(mode, call, problem, assignment, framework, theta, s,
                measure_wire, recorder, fault_plan, dm,
                incremental=incremental)


def refine_distributed(problem: PartitionProblem, assignment: Array,
                       framework: str = costs.C_FRAMEWORK,
                       num_shards: int | None = None,
                       max_turns: int = 10_000, tol: float = DEFAULT_TOL,
                       cost_fn: str = "jnp",
                       incremental: bool = True,
                       theta=None, measure_wire: bool = False,
                       recorder=None, fault_plan=None, degraded=None):
    """Distributed round-robin refinement (see :func:`_refine_distributed`
    for the protocol).  ``recorder`` (a :class:`repro.obs.Recorder`) opts
    into telemetry: the run is phase-timed, its measured wire reconciled
    against ``accounting.ledger_for_run``, and the stream closes with
    drift + ``run_end``; ``recorder=None`` calls the jitted program.

    ``fault_plan`` (a :class:`faults.FaultPlan`) opts into the fault steps
    under ``degraded``-mode rules (DESIGN.md §15): a
    :class:`faults.FaultReport` is appended to the return (after the
    wire), and ``DeadShardError`` / ``RecoveryFailedError`` raise when the
    run cannot recover to the drift budget — never silently diverging."""
    return _run_emulated(_refine_distributed, _PLAIN,
                         dict(max_turns=max_turns), problem, assignment,
                         framework, num_shards, tol, cost_fn, incremental,
                         theta, measure_wire, recorder, fault_plan, degraded)


def refine_distributed_traced(problem: PartitionProblem, assignment: Array,
                              framework: str = costs.C_FRAMEWORK,
                              num_shards: int | None = None,
                              max_turns: int = 512,
                              tol: float = DEFAULT_TOL,
                              cost_fn: str = "jnp",
                              incremental: bool = True,
                              theta=None, measure_wire: bool = False,
                              recorder=None, fault_plan=None,
                              degraded=None):
    """Traced distributed refinement (see :func:`_refine_distributed_traced`).
    ``recorder`` additionally streams one ``turn`` event per active turn,
    carried potentials included.  ``recorder`` and ``fault_plan`` as in
    :func:`refine_distributed`."""
    return _run_emulated(_refine_distributed_traced, _TRACED,
                         dict(max_turns=max_turns), problem, assignment,
                         framework, num_shards, tol, cost_fn, incremental,
                         theta, measure_wire, recorder, fault_plan, degraded)


def refine_distributed_simultaneous(problem: PartitionProblem,
                                    assignment: Array,
                                    framework: str = costs.C_FRAMEWORK,
                                    num_shards: int | None = None,
                                    max_sweeps: int = 256,
                                    tol: float = DEFAULT_TOL,
                                    cost_fn: str = "jnp",
                                    incremental: bool = True,
                                    theta=None, measure_wire: bool = False,
                                    recorder=None, fault_plan=None,
                                    degraded=None):
    """Distributed §4.5 sweeps (see :func:`_refine_distributed_simultaneous`).
    ``recorder`` additionally streams one ``sweep`` event per active
    sweep.  ``recorder`` and ``fault_plan`` as in
    :func:`refine_distributed`."""
    return _run_emulated(_refine_distributed_simultaneous, _SWEEP,
                         dict(max_sweeps=max_sweeps), problem, assignment,
                         framework, num_shards, tol, cost_fn, incremental,
                         theta, measure_wire, recorder, fault_plan, degraded)
