"""Sharded refinement drivers (DESIGN.md §9).

Three execution modes over the same shard-local kernel + O(K) protocol:

  * :func:`refine_distributed`          — sequential round-robin turns,
    ``lax.while_loop`` to convergence (the production entry point; this is
    what ``repro.des.engine`` calls when ``refine_backend="distributed"``).
  * :func:`refine_distributed_traced`   — fixed-length scan recording the
    per-turn move sequence and both global potentials; move-for-move
    identical to :func:`repro.core.refine.refine_traced` (the equivalence
    the paper's Thm 4.1 convergence argument needs and
    tests/test_distributed.py asserts).
  * :func:`refine_distributed_simultaneous` — the §4.5 sweep mode: every
    machine moves its most dissatisfied node in the same round (descent
    not guaranteed, K× fewer exchange rounds).

Shard-local compute is **incremental by default** (DESIGN.md §10): each
shard carries its (Ns, K) row-block aggregate through the loop — built by
one O(Ns·N·K) matmul at round 0 — and thereafter

  * assembles its candidate costs from the carried block in O(Ns·K)/turn,
  * applies the elected move as the same rank-1 column update the
    controller applies (`A_s[:, s] -= c_s[:, l]; A_s[:, d] += c_s[:, l]`),
    O(Ns), using only its own rows — wire traffic stays the O(K)
    candidate exchange,
  * (traced) attaches the exact-potential-identity deltas (ΔC_0, ΔCt_0,
    Thm. 3.1/5.1) to its candidate — 8 B — so every machine updates its
    replicated potentials without any O(N) pass.

``incremental=False`` restores the recompute path (block aggregate matmul
every turn), which is also what ``cost_fn="pallas"`` drives through the
fused Pallas cost kernel when recomputing; on the incremental path
``cost_fn="pallas"`` routes the per-turn reduction through the fused
aggregate→(dissat, best) kernel instead.

Two drivers realize the SPMD program:

  * the **emulated** driver maps the shard axis with ``vmap`` and performs
    the candidate all-gather as a plain stacked reduction — it runs on a
    single device, is fully jit/cond-compatible (the DES engine embeds
    it), and is bit-identical in protocol terms to the mesh driver;
  * :func:`refine_distributed_shard_map` places each shard's row block on
    its own device of a ``jax.sharding.Mesh`` and exchanges candidates
    with ``lax.all_gather`` — the real-collective path, exercised by
    ``benchmarks/distributed_bench.py`` under a forced multi-device host
    platform.
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

    Produced by the drivers under ``measure_wire=True``: ``payload_bytes``
    is the byte size of the pytrees that actually crossed the emulated
    (or real) exchange each round — measured from the staged buffers via
    :func:`_nbytes`, not from the analytic formulas — times the rounds
    the run executed; ``setup_bytes`` covers the one-time replicated
    state (O(K) loads + total-B scalar, plus the initial-potential
    partials on the incremental traced path).  ``rounds`` follows the
    same convention as ``RefineResult.num_turns`` (active turns/sweeps),
    which is what :func:`repro.distributed.accounting.ledger_for_run`
    is built from — so ``accounting.reconcile`` compares like with like.
    """
    rounds: Array          # int32 — active turns/sweeps (== num_turns)
    payload_bytes: Array   # int32 — per-round exchange, whole run
    setup_bytes: Array     # int32 — one-time replicated state


def _nbytes(tree) -> int:
    """Total byte size of a pytree's array leaves, at trace time.

    Shapes and dtypes are static under tracing, so this is a Python int
    even inside jit — the measured size of the buffers being exchanged.
    """
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
    """(S, Ns) shard blocks of the per-node hysteresis threshold, or None.

    theta never crosses the wire: each shard reads only its own block
    (DESIGN.md §11), mirroring the single controller's (N,) broadcast.
    """
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
    controller) or "pallas" (fused aggregate→(dissat, best) kernel).  Both
    follow the canonical 9-argument ``dissat_fn`` convention — see "The
    ``dissat_fn`` convention" in :mod:`repro.core.refine` — so the same
    ``ops.make_aggregate_dissat_fn`` adapter plugs in everywhere."""
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


def _elect_sweep(cands: protocol.Candidate, acc: Acceptance, loads: Array,
                 problem: PartitionProblem, framework: str,
                 lag: Array | None = None, penalty=None) -> protocol.Winner:
    """Per-machine elections of a sweep over (S, K) candidates."""
    k = problem.num_machines
    thresh = protocol.candidate_thresholds(
        cands, acc, jnp.arange(k, dtype=jnp.int32)[None, :], loads,
        problem.speeds, framework)                                  # (S, K)
    if lag is None:
        return jax.vmap(protocol.elect, in_axes=(1, 1))(cands, thresh)
    return jax.vmap(protocol.elect_degraded, in_axes=(1, 1, None, None))(
        cands, thresh, lag, penalty)


def _init_block_aggregates(views: ShardViews, assignment: Array,
                           num_machines: int) -> Array:
    """(S, Ns, K) carried block aggregates — the one-time matmuls."""
    return jax.vmap(
        lambda rb: protocol.block_aggregate(rb, assignment, num_machines)
    )(views.row_block)


def _vmap_candidates(views: ShardViews, assignment: Array, loads: Array,
                     speeds: Array, mu: Array, total_b: Array,
                     machine: Array, framework: str, cost_fn: str,
                     acc: Acceptance,
                     theta_blocks: Array | None = None) -> protocol.Candidate:
    """Recompute-path emulated exchange: all S candidates, stacked."""
    shard_cost = _shard_cost_fn(cost_fn)

    def one(rb, b, ids, valid, th):
        with jax.named_scope("shard_candidate"):
            return protocol.local_candidate(
                rb, b, ids, valid, assignment, loads, speeds, mu, total_b,
                machine, framework, acc, cost_matrix_fn=shard_cost,
                theta_local=th)

    return _vmap_shards(one, theta_blocks, views.row_block, views.weights,
                        views.ids, views.valid)


def _vmap_candidates_incremental(views: ShardViews, block_aggs: Array,
                                 assignment: Array, loads: Array,
                                 speeds: Array, mu: Array, total_b: Array,
                                 machine: Array, framework: str,
                                 cost_fn: str, acc: Acceptance,
                                 with_deltas: bool = False,
                                 theta_blocks: Array | None = None):
    """Incremental-path emulated exchange from the carried block aggregates."""
    dissat_fn = _shard_dissat_fn(cost_fn)

    def one(agg, b, ids, valid, th):
        with jax.named_scope("shard_candidate_incremental"):
            return protocol.local_candidate_from_aggregate(
                agg, b, ids, valid, assignment, loads, speeds, mu, total_b,
                machine, framework, acc, with_deltas=with_deltas,
                dissat_fn=dissat_fn, theta_local=th)

    return _vmap_shards(one, theta_blocks, block_aggs, views.weights,
                        views.ids, views.valid)


def _update_block_aggregates(views: ShardViews, block_aggs: Array,
                             winner: protocol.Winner,
                             machine: Array) -> Array:
    """Every shard applies the elected rank-1 update to its own block."""
    return jax.vmap(
        lambda agg, rb: protocol.update_block_aggregate(
            agg, rb, winner.node, machine, winner.dest, winner.moved)
    )(block_aggs, views.row_block)


def _vmap_potentials(views: ShardViews, assignment: Array, speeds: Array,
                     mu: Array, total_b: Array, num_machines: int,
                     fresh_loads: Array | None = None):
    """Emulated reduction of the per-shard potential partials (used once to
    initialize the traced potentials, and by the recompute traced path).

    Pass ``fresh_loads`` when the caller already reduced the shard load
    partials for ``assignment`` (the sweep driver does) to skip the
    redundant second reduction.

    Returns ``(c0, ct0, partial_bytes)`` — the third element is the
    measured byte size of the partial arrays this reduction exchanged
    (a trace-time Python int, consumed by the ``measure_wire`` counters
    of DESIGN.md §14.5 and free to ignore otherwise).
    """
    partial_bytes = 0
    if fresh_loads is None:
        load_partials = jax.vmap(
            lambda b, ids, v: protocol.shard_load_partial(
                b, ids, v, assignment, num_machines)
        )(views.weights, views.ids, views.valid)
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
    return c0, ct0, partial_bytes


# ---------------------------------------------------------------------------
# Sequential round-robin turns (paper §4.2 protocol, distributed)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("framework", "num_shards", "max_turns",
                                   "cost_fn", "incremental", "measure_wire"))
def _refine_distributed(problem: PartitionProblem, assignment: Array,
                        framework: str = costs.C_FRAMEWORK,
                        num_shards: int | None = None,
                        max_turns: int = 10_000, tol: float = DEFAULT_TOL,
                        cost_fn: str = "jnp",
                        incremental: bool = True,
                        theta=None, measure_wire: bool = False):
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

    ``measure_wire=True`` (static) additionally returns a
    :class:`WireMeasurement` counting the bytes of the actual per-turn
    candidate exchange — ``(result, wire)`` instead of ``result`` — for
    reconciliation against ``accounting.ledger_for_run`` (DESIGN.md
    §14.5).  The default jaxpr is unchanged.
    """
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}

    if incremental:
        aggs0 = _init_block_aggregates(views, state0.assignment, k)

        def cond(carry):
            _, _, _, _, idle, turns, _ = carry
            return (idle < k) & (turns < max_turns)

        def body(carry):
            r, loads, aggs, machine, idle, turns, moves = carry
            cands = _vmap_candidates_incremental(
                views, aggs, r, loads, problem.speeds, problem.mu, total_b,
                machine, framework, cost_fn, acc, theta_blocks=theta_blocks)
            measured["turn"] = _nbytes(cands)
            winner = protocol.elect(
                cands, protocol.candidate_thresholds(
                    cands, acc, machine, loads, problem.speeds, framework))
            aggs = _update_block_aggregates(views, aggs, winner, machine)
            r, loads = protocol.apply_move(r, loads, winner, machine)
            idle = jnp.where(winner.moved, 0, idle + 1)
            return (r, loads, aggs, (machine + 1) % k, idle, turns + 1,
                    moves + winner.moved.astype(jnp.int32))

        init = (state0.assignment, state0.loads, aggs0,
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        r, loads, _, _, idle, turns, moves = jax.lax.while_loop(
            cond, body, init)
        result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                              num_turns=turns, converged=idle >= k)
        if not measure_wire:
            return result
        return result, WireMeasurement(
            rounds=turns, payload_bytes=turns * measured["turn"],
            setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))

    def cond(carry):
        _, _, _, idle, turns, _ = carry
        return (idle < k) & (turns < max_turns)

    def body(carry):
        r, loads, machine, idle, turns, moves = carry
        cands = _vmap_candidates(views, r, loads, problem.speeds, problem.mu,
                                 total_b, machine, framework, cost_fn,
                                 acc, theta_blocks=theta_blocks)
        measured["turn"] = _nbytes(cands)
        winner = protocol.elect(
            cands, protocol.candidate_thresholds(
                cands, acc, machine, loads, problem.speeds, framework))
        r, loads = protocol.apply_move(r, loads, winner, machine)
        idle = jnp.where(winner.moved, 0, idle + 1)
        return (r, loads, (machine + 1) % k, idle, turns + 1,
                moves + winner.moved.astype(jnp.int32))

    init = (state0.assignment, state0.loads, jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32))
    r, loads, _, idle, turns, moves = jax.lax.while_loop(cond, body, init)
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=idle >= k)
    if not measure_wire:
        return result
    return result, WireMeasurement(
        rounds=turns, payload_bytes=turns * measured["turn"],
        setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))


@partial(jax.jit, static_argnames=("framework", "num_shards", "max_turns",
                                   "cost_fn", "incremental", "measure_wire"))
def _refine_distributed_traced(problem: PartitionProblem, assignment: Array,
                               framework: str = costs.C_FRAMEWORK,
                               num_shards: int | None = None,
                               max_turns: int = 512,
                               tol: float = DEFAULT_TOL,
                               cost_fn: str = "jnp",
                               incremental: bool = True,
                               theta=None, measure_wire: bool = False):
    """Fixed-length traced variant; returns ``(RefineResult, Trace)`` with
    the exact semantics (and, in sequential mode, the exact move sequence)
    of :func:`repro.core.refine.refine_traced`.

    On the incremental path the potentials are initialized once from
    per-shard partials and thereafter updated by the winner's 8-byte
    exact-potential deltas (Thm. 3.1/5.1) — O(1) wire + O(K) compute per
    turn, no O(N) pass of any kind.  ``incremental=False`` restores the
    per-turn partial-reduction recompute.  ``theta`` as in
    :func:`refine_distributed`.

    ``measure_wire=True`` (static) returns ``(result, trace, wire)``
    with a :class:`WireMeasurement` counting the actual per-turn
    exchange (candidates + potential deltas, or + the recompute
    partials) and the one-time setup including the initial-potential
    partials (DESIGN.md §14.5).  The default jaxpr is unchanged.
    """
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    setup_base = _nbytes((state0.loads, total_b))

    if incremental:
        aggs0 = _init_block_aggregates(views, state0.assignment, k)
        c0_init, ct0_init, init_pot_bytes = _vmap_potentials(
            views, state0.assignment, problem.speeds, problem.mu,
            total_b, k, fresh_loads=state0.loads)

        def step(carry, _):
            r, loads, aggs, c0, ct0, machine, idle = carry
            active = idle < k
            cands, dc0s, dct0s = _vmap_candidates_incremental(
                views, aggs, r, loads, problem.speeds, problem.mu, total_b,
                machine, framework, cost_fn, acc, with_deltas=True,
                theta_blocks=theta_blocks)
            measured["turn"] = _nbytes((cands, dc0s, dct0s))
            winner = protocol.elect(
                cands, protocol.candidate_thresholds(
                    cands, acc, machine, loads, problem.speeds, framework))
            moved = winner.moved & active
            gated = winner._replace(moved=moved)
            new_aggs = _update_block_aggregates(views, aggs, gated, machine)
            new_r, new_loads = protocol.apply_move(r, loads, gated, machine)
            new_c0 = jnp.where(moved, c0 + dc0s[winner.shard], c0)
            new_ct0 = jnp.where(moved, ct0 + dct0s[winner.shard], ct0)
            idle = jnp.where(moved, 0, idle + 1)
            out = Trace(
                moved=moved,
                node=jnp.where(winner.moved, winner.node, -1),
                source=jnp.where(winner.moved, machine, -1),
                dest=jnp.where(winner.moved, winner.dest, -1),
                gain=jnp.where(winner.moved, winner.gain, 0.0),
                c0=new_c0, ct0=new_ct0, active=active)
            return (new_r, new_loads, new_aggs, new_c0, new_ct0,
                    (machine + 1) % k, idle), out

        init = (state0.assignment, state0.loads, aggs0, c0_init, ct0_init,
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        (r, loads, _, _, _, _, idle), trace = jax.lax.scan(
            step, init, None, length=max_turns)
        moves = jnp.sum(trace.moved.astype(jnp.int32))
        turns = jnp.sum(trace.active.astype(jnp.int32))
        result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                              num_turns=turns, converged=idle >= k)
        if not measure_wire:
            return result, trace
        return result, trace, WireMeasurement(
            rounds=turns, payload_bytes=turns * measured["turn"],
            setup_bytes=jnp.int32(setup_base + init_pot_bytes))

    def step(carry, _):
        r, loads, machine, idle = carry
        active = idle < k
        cands = _vmap_candidates(views, r, loads, problem.speeds, problem.mu,
                                 total_b, machine, framework, cost_fn,
                                 acc, theta_blocks=theta_blocks)
        winner = protocol.elect(
            cands, protocol.candidate_thresholds(
                cands, acc, machine, loads, problem.speeds, framework))
        new_r, new_loads = protocol.apply_move(r, loads, winner, machine)
        new_r = jnp.where(active, new_r, r)
        new_loads = jnp.where(active, new_loads, loads)
        moved = winner.moved & active
        idle = jnp.where(moved, 0, idle + 1)
        c0, ct0, pot_bytes = _vmap_potentials(views, new_r, problem.speeds,
                                              problem.mu, total_b, k)
        measured["turn"] = _nbytes(cands) + pot_bytes
        out = Trace(
            moved=moved,
            node=jnp.where(winner.moved, winner.node, -1),
            source=jnp.where(winner.moved, machine, -1),
            dest=jnp.where(winner.moved, winner.dest, -1),
            gain=jnp.where(winner.moved, winner.gain, 0.0),
            c0=c0, ct0=ct0, active=active)
        return (new_r, new_loads, (machine + 1) % k, idle), out

    init = (state0.assignment, state0.loads, jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32))
    (r, loads, _, idle), trace = jax.lax.scan(step, init, None,
                                              length=max_turns)
    moves = jnp.sum(trace.moved.astype(jnp.int32))
    turns = jnp.sum(trace.active.astype(jnp.int32))
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=idle >= k)
    if not measure_wire:
        return result, trace
    return result, trace, WireMeasurement(
        rounds=turns, payload_bytes=turns * measured["turn"],
        setup_bytes=jnp.int32(setup_base))


# ---------------------------------------------------------------------------
# §4.5 simultaneous sweeps, distributed
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("framework", "num_shards", "max_sweeps",
                                   "cost_fn", "incremental", "measure_wire"))
def _refine_distributed_simultaneous(problem: PartitionProblem,
                                     assignment: Array,
                                     framework: str = costs.C_FRAMEWORK,
                                     num_shards: int | None = None,
                                     max_sweeps: int = 256,
                                     tol: float = DEFAULT_TOL,
                                     cost_fn: str = "jnp",
                                     incremental: bool = True,
                                     theta=None, measure_wire: bool = False):
    """Distributed §4.5 sweeps: each shard ships K candidates per sweep
    (one per machine), elections run per machine, all K disjoint moves
    apply at once as a rank-K block-aggregate update.  Exchange per sweep:
    S*K candidates + S load/sq-load/cut partials — still independent of N.

    ``num_moves`` counts actual transfers (sum of per-sweep movers), not
    the K*sweeps upper bound.  ``theta`` as in :func:`refine_distributed`.

    ``measure_wire=True`` (static) returns ``(result, traces, wire)``
    with a :class:`WireMeasurement` of the actual per-sweep exchange
    (K candidates per shard + the partial reductions); ``rounds`` counts
    active sweeps, matching ``num_turns`` and the ledger convention
    (DESIGN.md §14.5).  The default jaxpr is unchanged.
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

    def _sweep_cands_incremental(aggs, r, loads, dissat_fn):
        def one(agg, b, ids, v, th):
            return protocol.local_candidates_all_machines_from_aggregate(
                agg, b, ids, v, r, loads, problem.speeds, problem.mu,
                total_b, framework, dissat_fn=dissat_fn, theta_local=th)

        return _vmap_shards(one, theta_blocks, aggs, views.weights,
                            views.ids, views.valid)              # (S, K)

    if incremental:
        aggs0 = _init_block_aggregates(views, state0.assignment, k)
        dissat_fn = _shard_dissat_fn(cost_fn)

        def sweep(carry, _):
            r, loads, aggs, done, moves = carry
            cands = _sweep_cands_incremental(aggs, r, loads, dissat_fn)
            winners = _elect_sweep(cands, acc, loads, problem,
                                   framework)                     # (K,)
            any_move = jnp.any(winners.moved) & ~done
            # Idle machines elect a fallback candidate (all gains -inf)
            # whose node id may collide with a real move — mask their
            # columns / drop their writes instead of racing the update.
            safe_picks = jnp.where(winners.moved, winners.node,
                                   jnp.int32(problem.num_nodes))
            new_r = r.at[safe_picks].set(winners.dest, mode="drop")
            new_r = jnp.where(any_move, new_r, r)
            new_aggs = jax.vmap(
                lambda agg, rb: protocol.update_block_aggregate_sweep(
                    agg, rb, winners.node, winners.dest, winners.moved)
            )(aggs, views.row_block)
            new_aggs = jnp.where(any_move, new_aggs, aggs)
            load_partials = jax.vmap(
                lambda b, ids, v: protocol.shard_load_partial(
                    b, ids, v, new_r, k)
            )(views.weights, views.ids, views.valid)
            new_loads = jnp.sum(load_partials, axis=0)
            sq_partials = jax.vmap(
                lambda b2, ids, v: protocol.shard_load_partial(
                    b2, ids, v, new_r, k)
            )(sq_weights, views.ids, views.valid)
            sq_loads = jnp.sum(sq_partials, axis=0)
            cut_partials = jax.vmap(
                lambda agg, ids, v: protocol.shard_cut_partial_from_aggregate(
                    agg, ids, v, new_r)
            )(new_aggs, views.ids, views.valid)
            measured["sweep"] = _nbytes(
                (cands, load_partials, sq_partials, cut_partials))
            cut = 0.5 * jnp.sum(cut_partials)
            c0, ct0 = agg_mod.potentials_closed_form(
                new_loads, sq_loads, cut, problem.speeds, problem.mu,
                total_b)
            moves = moves + jnp.where(
                any_move, jnp.sum(winners.moved.astype(jnp.int32)), 0)
            return ((new_r, new_loads, new_aggs, done | ~any_move, moves),
                    (c0, ct0, any_move))

        (r, loads, _, done, moves), (c0s, ct0s, active) = jax.lax.scan(
            sweep, (state0.assignment, state0.loads, aggs0,
                    jnp.zeros((), bool), jnp.zeros((), jnp.int32)),
            None, length=max_sweeps)
        sweeps = jnp.sum(active.astype(jnp.int32))
        result = RefineResult(
            assignment=r, loads=loads, num_moves=moves,
            num_turns=sweeps, converged=done)
        if not measure_wire:
            return result, (c0s, ct0s, active)
        return result, (c0s, ct0s, active), WireMeasurement(
            rounds=sweeps, payload_bytes=sweeps * measured["sweep"],
            setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))

    shard_cost = _shard_cost_fn(cost_fn)

    def sweep(carry, _):
        r, loads, done, moves = carry

        def one(rb, b, ids, v, th):
            return protocol.local_candidates_all_machines(
                rb, b, ids, v, r, loads, problem.speeds, problem.mu,
                total_b, framework, cost_matrix_fn=shard_cost,
                theta_local=th)

        cands = _vmap_shards(one, theta_blocks, views.row_block,
                             views.weights, views.ids, views.valid)  # (S, K)
        winners = _elect_sweep(cands, acc, loads, problem,
                               framework)                          # (K,)
        any_move = jnp.any(winners.moved) & ~done
        safe_picks = jnp.where(winners.moved, winners.node,
                               jnp.int32(problem.num_nodes))
        new_r = r.at[safe_picks].set(winners.dest, mode="drop")
        new_r = jnp.where(any_move, new_r, r)
        load_partials = jax.vmap(
            lambda b, ids, v: protocol.shard_load_partial(b, ids, v, new_r, k)
        )(views.weights, views.ids, views.valid)
        new_loads = jnp.sum(load_partials, axis=0)
        c0, ct0, pot_bytes = _vmap_potentials(views, new_r, problem.speeds,
                                              problem.mu, total_b, k,
                                              fresh_loads=new_loads)
        measured["sweep"] = _nbytes((cands, load_partials)) + pot_bytes
        moves = moves + jnp.where(
            any_move, jnp.sum(winners.moved.astype(jnp.int32)), 0)
        return ((new_r, new_loads, done | ~any_move, moves),
                (c0, ct0, any_move))

    (r, loads, done, moves), (c0s, ct0s, active) = jax.lax.scan(
        sweep, (state0.assignment, state0.loads, jnp.zeros((), bool),
                jnp.zeros((), jnp.int32)),
        None, length=max_sweeps)
    sweeps = jnp.sum(active.astype(jnp.int32))
    result = RefineResult(
        assignment=r, loads=loads, num_moves=moves,
        num_turns=sweeps, converged=done)
    if not measure_wire:
        return result, (c0s, ct0s, active)
    return result, (c0s, ct0s, active), WireMeasurement(
        rounds=sweeps, payload_bytes=sweeps * measured["sweep"],
        setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))


# ---------------------------------------------------------------------------
# Fault-injected drivers (DESIGN.md §15)
# ---------------------------------------------------------------------------
#
# The faulty drivers re-run the incremental protocol with a FaultPlan row
# consulted every round: candidates of down / quarantined / undelivered
# shards are masked out of the election, the election itself prices
# staleness (``protocol.elect_degraded`` — the 1109.6925 bounded-staleness
# rule), omitted broadcasts leave a shard's carried aggregate stale, the
# plan's corruption entries overwrite aggregate columns, and its repair
# schedule rebuilds + column-patches flagged shards inside the loop via
# ``lax.cond`` (the rebuild matmul stays off the per-round hot path).  A
# zero-fault plan reproduces the fault-free drivers bitwise: every
# degraded branch is gated by a predicate that is constant-false on a
# clear plan, and ``elect_degraded`` is decision-equivalent to ``elect``
# at lag 0 (the Winner fields that can differ are all downstream-gated on
# ``moved``).  Each driver ends with an oracle audit
# (``_fault_final_audit``): worst carried-vs-recomputed deviation before
# and after a final guarded patch of the still-alive shards — the public
# wrappers turn that FaultOutcome into the recover-or-raise contract.

class FaultTrace(NamedTuple):
    """Per-round repair side channel of the faulty scan drivers."""
    repaired: Array       # (T,) bool  — in-loop repair fired this round
    repair_drift: Array   # (T,) f32   — worst pre-repair column deviation
    repaired_cols: Array  # (T,) i32   — aggregate columns replaced


def _inf_dev(x: Array) -> Array:
    """Deviation → finite-or-inf: NaN counts as infinite drift, so a
    ``<= budget`` recovery check can never be satisfied by NaN soup."""
    return jnp.nan_to_num(x, nan=jnp.inf, posinf=jnp.inf)


def _shard_load_partials(views: ShardViews, weights: Array,
                         assignment: Array, num_machines: int) -> Array:
    """(S, K) per-shard load partials for the given per-shard weights."""
    return jax.vmap(
        lambda b, ids, v: protocol.shard_load_partial(
            b, ids, v, assignment, num_machines)
    )(weights, views.ids, views.valid)


def _fault_inject(aggs: Array, row, gate, num_machines: int) -> Array:
    """Overwrite column ``corrupt_col`` of flagged shards with
    ``corrupt_val`` (set semantics — a NaN payload lands as NaN)."""
    colmask = (jnp.arange(num_machines, dtype=jnp.int32)[None, :]
               == row.corrupt_col[:, None])                     # (S, K)
    zap = (row.corrupt & gate)[:, None] & colmask
    return jnp.where(zap[:, None, :], row.corrupt_val[:, None, None], aggs)


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


def _fault_closed_potentials(views: ShardViews, sq_weights: Array,
                             aggs: Array, assignment: Array, speeds: Array,
                             mu, total_b, num_machines: int):
    """Oracle loads + closed-form potentials from the (patched) aggregates
    — the repair-round resync of the traced driver's carried values."""
    load_partials = _shard_load_partials(views, views.weights, assignment,
                                         num_machines)
    fresh_loads = jnp.sum(load_partials, axis=0)
    sq_loads = jnp.sum(_shard_load_partials(views, sq_weights, assignment,
                                            num_machines), axis=0)
    cut_partials = jax.vmap(
        lambda agg, ids, v: protocol.shard_cut_partial_from_aggregate(
            agg, ids, v, assignment)
    )(aggs, views.ids, views.valid)
    cut = 0.5 * jnp.sum(cut_partials)
    c0, ct0 = agg_mod.potentials_closed_form(fresh_loads, sq_loads, cut,
                                             speeds, mu, total_b)
    return fresh_loads, c0, ct0


def _fault_final_audit(views: ShardViews, fault_plan, aggs: Array,
                       loads: Array, assignment: Array, last_round,
                       converged, rtol: float, num_machines: int):
    """Post-run oracle audit + unconditional guarded patch.

    ``final_drift`` is the worst carried-vs-recomputed deviation (columns
    and loads, NaN → inf) *before* patching; the patch then replaces bad
    columns of still-alive shards and bad load entries, and
    ``post_drift`` re-measures.  A shard down on the last executed round
    of a non-converged run is dead — its columns stay un-patched and the
    wrapper raises ``DeadShardError`` (a converged run necessarily ended
    on a fault-clear round, so ``converged`` gates the dead check)."""
    horizon = fault_plan.down.shape[0] - 1
    last = jnp.clip(last_round, 0, horizon)
    dead_row = fault_plan.down[last] & ~converged               # (S,)
    fresh = _init_block_aggregates(views, assignment, num_machines)
    col_dev = jnp.max(jnp.abs(aggs - fresh), axis=1)            # (S, K)
    fresh_loads = jnp.sum(_shard_load_partials(
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
    cols = jnp.sum(sel.astype(jnp.int32))
    return aggs, loads, dead_row, final_drift, post_drift, cols


@partial(jax.jit, static_argnames=("framework", "num_shards", "max_rounds",
                                   "cost_fn", "degraded", "measure_wire"))
def _refine_distributed_faulty(problem: PartitionProblem, assignment: Array,
                               fault_plan,
                               framework: str = costs.C_FRAMEWORK,
                               num_shards: int | None = None,
                               max_rounds: int = 10_000,
                               tol: float = DEFAULT_TOL,
                               cost_fn: str = "jnp",
                               degraded=faults.DEFAULT_DEGRADED,
                               theta=None, measure_wire: bool = False):
    """Fault-injected round-robin driver (incremental protocol only).

    Same election/apply protocol as :func:`_refine_distributed`, plus the
    per-round degraded machinery described in the section comment above.
    Convergence idles only accumulate on fault-clear rounds (a blocked
    no-move round is not evidence of equilibrium).  Returns
    ``(result, outcome)`` — ``outcome`` is a
    :class:`repro.distributed.faults.FaultOutcome` of device scalars —
    plus a :class:`WireMeasurement` when ``measure_wire`` whose payload
    includes the per-round retry/duplicate/repair extra bytes."""
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    rtol = degraded.repair_tol
    penalty = degraded.stale_penalty
    msg = faults.message_bytes(traced=False, simultaneous=False,
                               num_machines=k)
    aggs0 = _init_block_aggregates(views, state0.assignment, k)
    zero_i = jnp.zeros((), jnp.int32)
    zero_f = jnp.zeros((), jnp.float32)

    def cond(carry):
        return (carry[4] < k) & (carry[5] < max_rounds)

    def body(carry):
        (r, loads, aggs, machine, idle, turns, moves,
         fbytes, repairs, rcols, rdrift) = carry
        row = faults.plan_row(fault_plan, turns)
        aggs = _fault_inject(aggs, row, True, k)
        cands = _vmap_candidates_incremental(
            views, aggs, r, loads, problem.speeds, problem.mu, total_b,
            machine, framework, cost_fn, acc, theta_blocks=theta_blocks)
        measured["turn"] = _nbytes(cands)
        blocked = row.down | row.quarantined | ~row.delivered
        cands = cands._replace(gain=jnp.where(blocked, -jnp.inf, cands.gain))
        winner = protocol.elect_degraded(
            cands, protocol.candidate_thresholds(
                cands, acc, machine, loads, problem.speeds, framework),
            row.lag, penalty)
        new_aggs = _update_block_aggregates(views, aggs, winner, machine)
        miss = (row.omit | row.down)[:, None, None]
        aggs = jnp.where(miss, aggs, new_aggs)
        r, loads = protocol.apply_move(r, loads, winner, machine)
        idle = jnp.where(winner.moved, 0,
                         jnp.where(row.clear, idle + 1, idle))
        do_repair = jnp.any(row.repair)
        aggs, rd, rc = jax.lax.cond(
            do_repair,
            lambda a: _fault_repair_cols(views, a, r, row.repair, rtol, k),
            lambda a: (a, zero_f, zero_i), aggs)
        fbytes = fbytes + faults.round_extra_bytes(row, msg)
        return (r, loads, aggs, (machine + 1) % k, idle, turns + 1,
                moves + winner.moved.astype(jnp.int32), fbytes,
                repairs + do_repair.astype(jnp.int32), rcols + rc,
                jnp.maximum(rdrift, rd))

    init = (state0.assignment, state0.loads, aggs0, zero_i, zero_i, zero_i,
            zero_i, zero_i, zero_i, zero_i, zero_f)
    (r, loads, aggs, _, idle, turns, moves,
     fbytes, repairs, rcols, rdrift) = jax.lax.while_loop(cond, body, init)
    converged = idle >= k
    aggs, loads, dead_row, final_drift, post_drift, fcols = \
        _fault_final_audit(views, fault_plan, aggs, loads, r, turns - 1,
                           converged, rtol, k)
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=converged,
                          aggregate_drift=post_drift)
    outcome = faults.FaultOutcome(
        final_drift=final_drift, post_drift=post_drift,
        dead=jnp.any(dead_row), repairs=repairs,
        repaired_cols=rcols + fcols, max_repair_drift=rdrift)
    if not measure_wire:
        return result, outcome
    return result, outcome, WireMeasurement(
        rounds=turns, payload_bytes=turns * measured["turn"] + fbytes,
        setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))


@partial(jax.jit, static_argnames=("framework", "num_shards", "max_rounds",
                                   "cost_fn", "degraded", "measure_wire"))
def _refine_distributed_traced_faulty(problem: PartitionProblem,
                                      assignment: Array, fault_plan,
                                      framework: str = costs.C_FRAMEWORK,
                                      num_shards: int | None = None,
                                      max_rounds: int = 512,
                                      tol: float = DEFAULT_TOL,
                                      cost_fn: str = "jnp",
                                      degraded=faults.DEFAULT_DEGRADED,
                                      theta=None,
                                      measure_wire: bool = False):
    """Fault-injected traced driver (incremental protocol only).

    Carried C_0/Ct_0 follow the winner's exact-potential deltas between
    repairs; a repair round recomputes them closed-form from the patched
    aggregates and guard-patches the carried values (relative tolerance,
    so fault-free float noise never triggers a patch).  Returns
    ``(result, trace, ftrace, outcome)`` (+ wire)."""
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    setup_base = _nbytes((state0.loads, total_b))
    rtol = degraded.repair_tol
    penalty = degraded.stale_penalty
    msg = faults.message_bytes(traced=True, simultaneous=False,
                               num_machines=k)
    sq_weights = views.weights * views.weights
    aggs0 = _init_block_aggregates(views, state0.assignment, k)
    c0_init, ct0_init, init_pot_bytes = _vmap_potentials(
        views, state0.assignment, problem.speeds, problem.mu,
        total_b, k, fresh_loads=state0.loads)
    zero_i = jnp.zeros((), jnp.int32)
    zero_f = jnp.zeros((), jnp.float32)

    def step(carry, t):
        r, loads, aggs, c0, ct0, machine, idle, fbytes = carry
        active = idle < k
        row = faults.plan_row(fault_plan, t)
        aggs = _fault_inject(aggs, row, active, k)
        cands, dc0s, dct0s = _vmap_candidates_incremental(
            views, aggs, r, loads, problem.speeds, problem.mu, total_b,
            machine, framework, cost_fn, acc, with_deltas=True,
            theta_blocks=theta_blocks)
        measured["turn"] = _nbytes((cands, dc0s, dct0s))
        blocked = row.down | row.quarantined | ~row.delivered
        cands = cands._replace(gain=jnp.where(blocked, -jnp.inf, cands.gain))
        winner = protocol.elect_degraded(
            cands, protocol.candidate_thresholds(
                cands, acc, machine, loads, problem.speeds, framework),
            row.lag, penalty)
        moved = winner.moved & active
        gated = winner._replace(moved=moved)
        new_aggs = _update_block_aggregates(views, aggs, gated, machine)
        miss = (row.omit | row.down)[:, None, None]
        new_aggs = jnp.where(miss, aggs, new_aggs)
        new_r, new_loads = protocol.apply_move(r, loads, gated, machine)
        new_c0 = jnp.where(moved, c0 + dc0s[winner.shard], c0)
        new_ct0 = jnp.where(moved, ct0 + dct0s[winner.shard], ct0)
        idle = jnp.where(moved, 0, jnp.where(row.clear, idle + 1, idle))
        do_repair = jnp.any(row.repair) & active

        def with_repair(ops):
            aggs_, loads_, c0_, ct0_ = ops
            patched, rd, rc = _fault_repair_cols(views, aggs_, new_r,
                                                 row.repair, rtol, k)
            fl, c0f, ct0f = _fault_closed_potentials(
                views, sq_weights, patched, new_r, problem.speeds,
                problem.mu, total_b, k)

            def guard(x, fresh):
                bad = ~(jnp.abs(x - fresh)
                        <= rtol * jnp.maximum(1.0, jnp.abs(fresh)))
                return jnp.where(bad, fresh, x)

            loads2 = jnp.where(~(jnp.abs(loads_ - fl) <= rtol), fl, loads_)
            return patched, loads2, guard(c0_, c0f), guard(ct0_, ct0f), rd, rc

        def without(ops):
            aggs_, loads_, c0_, ct0_ = ops
            return aggs_, loads_, c0_, ct0_, zero_f, zero_i

        new_aggs, new_loads, new_c0, new_ct0, rd, rc = jax.lax.cond(
            do_repair, with_repair, without,
            (new_aggs, new_loads, new_c0, new_ct0))
        fbytes = fbytes + jnp.where(
            active, faults.round_extra_bytes(row, msg), 0)
        out = (Trace(moved=moved,
                     node=jnp.where(winner.moved, winner.node, -1),
                     source=jnp.where(winner.moved, machine, -1),
                     dest=jnp.where(winner.moved, winner.dest, -1),
                     gain=jnp.where(winner.moved, winner.gain, 0.0),
                     c0=new_c0, ct0=new_ct0, active=active),
               FaultTrace(repaired=do_repair, repair_drift=rd,
                          repaired_cols=rc))
        return (new_r, new_loads, new_aggs, new_c0, new_ct0,
                (machine + 1) % k, idle, fbytes), out

    init = (state0.assignment, state0.loads, aggs0, c0_init, ct0_init,
            zero_i, zero_i, zero_i)
    (r, loads, aggs, _, _, _, idle, fbytes), (trace, ftrace) = jax.lax.scan(
        step, init, jnp.arange(max_rounds))
    moves = jnp.sum(trace.moved.astype(jnp.int32))
    turns = jnp.sum(trace.active.astype(jnp.int32))
    converged = idle >= k
    aggs, loads, dead_row, final_drift, post_drift, fcols = \
        _fault_final_audit(views, fault_plan, aggs, loads, r, turns - 1,
                           converged, rtol, k)
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=converged,
                          aggregate_drift=post_drift)
    outcome = faults.FaultOutcome(
        final_drift=final_drift, post_drift=post_drift,
        dead=jnp.any(dead_row),
        repairs=jnp.sum(ftrace.repaired.astype(jnp.int32)),
        repaired_cols=jnp.sum(ftrace.repaired_cols) + fcols,
        max_repair_drift=jnp.max(ftrace.repair_drift))
    if not measure_wire:
        return result, trace, ftrace, outcome
    return result, trace, ftrace, outcome, WireMeasurement(
        rounds=turns, payload_bytes=turns * measured["turn"] + fbytes,
        setup_bytes=jnp.int32(setup_base + init_pot_bytes))


@partial(jax.jit, static_argnames=("framework", "num_shards", "max_rounds",
                                   "cost_fn", "degraded", "measure_wire"))
def _refine_distributed_simultaneous_faulty(problem: PartitionProblem,
                                            assignment: Array, fault_plan,
                                            framework: str = costs.C_FRAMEWORK,
                                            num_shards: int | None = None,
                                            max_rounds: int = 256,
                                            tol: float = DEFAULT_TOL,
                                            cost_fn: str = "jnp",
                                            degraded=faults.DEFAULT_DEGRADED,
                                            theta=None,
                                            measure_wire: bool = False):
    """Fault-injected §4.5 sweep driver (incremental protocol only).

    The sweep can only latch ``done`` on a fault-clear no-move round — a
    blocked round proves nothing about equilibrium.  Wire counts the
    executed (non-done) rounds: ``counted = ~done & (any_move | ~clear)``
    reduces to the fault-free active-sweep count on a zero plan, and the
    counted rounds always form a prefix, which is what keeps the host-side
    ledger (``faults.plan_extra_bytes``) byte-exact against the device
    accumulator.  Returns ``(result, (c0s, ct0s, counted), ftrace,
    outcome)`` (+ wire)."""
    k = problem.num_machines
    s = _resolve_shards(problem, num_shards)
    views = build_views(problem, s)
    state0 = make_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    sq_weights = views.weights * views.weights
    theta_blocks = _shard_theta(theta, problem, s)
    acc = _acceptance(problem, state0.assignment, tol)
    measured: dict = {}
    rtol = degraded.repair_tol
    penalty = degraded.stale_penalty
    msg = faults.message_bytes(traced=False, simultaneous=True,
                               num_machines=k)
    dissat_fn = _shard_dissat_fn(cost_fn)
    aggs0 = _init_block_aggregates(views, state0.assignment, k)
    zero_i = jnp.zeros((), jnp.int32)
    zero_f = jnp.zeros((), jnp.float32)

    def _sweep_cands(aggs, r, loads):
        def one(agg, b, ids, v, th):
            return protocol.local_candidates_all_machines_from_aggregate(
                agg, b, ids, v, r, loads, problem.speeds, problem.mu,
                total_b, framework, dissat_fn=dissat_fn, theta_local=th)

        return _vmap_shards(one, theta_blocks, aggs, views.weights,
                            views.ids, views.valid)              # (S, K)

    def sweep(carry, t):
        r, loads, aggs, done, moves, fbytes = carry
        row = faults.plan_row(fault_plan, t)
        aggs = _fault_inject(aggs, row, ~done, k)
        cands = _sweep_cands(aggs, r, loads)
        blocked = row.down | row.quarantined | ~row.delivered
        cands = cands._replace(
            gain=jnp.where(blocked[:, None], -jnp.inf, cands.gain))
        winners = _elect_sweep(cands, acc, loads, problem, framework,
                               row.lag, penalty)                   # (K,)
        any_move = jnp.any(winners.moved) & ~done
        safe_picks = jnp.where(winners.moved, winners.node,
                               jnp.int32(problem.num_nodes))
        new_r = r.at[safe_picks].set(winners.dest, mode="drop")
        new_r = jnp.where(any_move, new_r, r)
        new_aggs = jax.vmap(
            lambda agg, rb: protocol.update_block_aggregate_sweep(
                agg, rb, winners.node, winners.dest, winners.moved)
        )(aggs, views.row_block)
        new_aggs = jnp.where(any_move, new_aggs, aggs)
        miss = (row.omit | row.down)[:, None, None]
        new_aggs = jnp.where(miss, aggs, new_aggs)
        do_repair = jnp.any(row.repair) & ~done
        new_aggs, rd, rc = jax.lax.cond(
            do_repair,
            lambda a: _fault_repair_cols(views, a, new_r, row.repair,
                                         rtol, k),
            lambda a: (a, zero_f, zero_i), new_aggs)
        load_partials = jax.vmap(
            lambda b, ids, v: protocol.shard_load_partial(
                b, ids, v, new_r, k)
        )(views.weights, views.ids, views.valid)
        new_loads = jnp.sum(load_partials, axis=0)
        sq_partials = jax.vmap(
            lambda b2, ids, v: protocol.shard_load_partial(
                b2, ids, v, new_r, k)
        )(sq_weights, views.ids, views.valid)
        sq_loads = jnp.sum(sq_partials, axis=0)
        cut_partials = jax.vmap(
            lambda agg, ids, v: protocol.shard_cut_partial_from_aggregate(
                agg, ids, v, new_r)
        )(new_aggs, views.ids, views.valid)
        measured["sweep"] = _nbytes(
            (cands, load_partials, sq_partials, cut_partials))
        cut = 0.5 * jnp.sum(cut_partials)
        c0, ct0 = agg_mod.potentials_closed_form(
            new_loads, sq_loads, cut, problem.speeds, problem.mu, total_b)
        moves = moves + jnp.where(
            any_move, jnp.sum(winners.moved.astype(jnp.int32)), 0)
        counted = ~done & (any_move | ~row.clear)
        fbytes = fbytes + jnp.where(
            counted, faults.round_extra_bytes(row, msg), 0)
        new_done = done | (~any_move & row.clear)
        return ((new_r, new_loads, new_aggs, new_done, moves, fbytes),
                ((c0, ct0, counted),
                 FaultTrace(repaired=do_repair, repair_drift=rd,
                            repaired_cols=rc)))

    (r, loads, aggs, done, moves, fbytes), ((c0s, ct0s, active), ftrace) = \
        jax.lax.scan(sweep, (state0.assignment, state0.loads, aggs0,
                             jnp.zeros((), bool), zero_i, zero_i),
                     jnp.arange(max_rounds))
    sweeps = jnp.sum(active.astype(jnp.int32))
    aggs, loads, dead_row, final_drift, post_drift, fcols = \
        _fault_final_audit(views, fault_plan, aggs, loads, r,
                           max_rounds - 1, done, rtol, k)
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=sweeps, converged=done,
                          aggregate_drift=post_drift)
    outcome = faults.FaultOutcome(
        final_drift=final_drift, post_drift=post_drift,
        dead=jnp.any(dead_row),
        repairs=jnp.sum(ftrace.repaired.astype(jnp.int32)),
        repaired_cols=jnp.sum(ftrace.repaired_cols) + fcols,
        max_repair_drift=jnp.max(ftrace.repair_drift))
    if not measure_wire:
        return result, (c0s, ct0s, active), ftrace, outcome
    return result, (c0s, ct0s, active), ftrace, outcome, WireMeasurement(
        rounds=sweeps, payload_bytes=sweeps * measured["sweep"] + fbytes,
        setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))


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

    ``measure_wire=True`` returns ``(result, wire)`` with a
    :class:`WireMeasurement` whose payload counts the real
    ``lax.all_gather`` output buffers per turn (DESIGN.md §14.5).
    ``recorder`` (a :class:`repro.obs.Recorder`) opts into run telemetry:
    a phase-timed ``run_start``/``wire``/``run_end`` stream with the
    measured bytes reconciled against the analytic ledger.
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

    if fault_plan is not None:
        return _shard_map_faulty_run(
            problem, assignment, fault_plan, framework, s, mesh, views,
            state0, total_b, theta_blocks, theta, max_turns, acc,
            degraded or faults.DEFAULT_DEGRADED, measure_wire, recorder)

    measured: dict = {}

    def spmd(rb, b, ids, valid, th, r0, loads0, speeds, mu, tot):
        rb, b, ids, valid, th = rb[0], b[0], ids[0], valid[0], th[0]
        agg0 = protocol.block_aggregate(rb, r0, k)   # once, O(Ns·N·K)

        def cond(carry):
            _, _, _, _, idle, turns, _ = carry
            return (idle < k) & (turns < max_turns)

        def body(carry):
            r, loads, agg, machine, idle, turns, moves = carry
            cand = protocol.local_candidate_from_aggregate(
                agg, b, ids, valid, r, loads, speeds, mu, tot, machine,
                framework, acc, theta_local=th)
            cands = protocol.Candidate(
                gain=jax.lax.all_gather(cand.gain, "shards"),
                node=jax.lax.all_gather(cand.node, "shards"),
                dest=jax.lax.all_gather(cand.dest, "shards"),
                weight=jax.lax.all_gather(cand.weight, "shards"))
            measured["turn"] = _nbytes(cands)
            winner = protocol.elect(
                cands, protocol.candidate_thresholds(
                    cands, acc, machine, loads, problem.speeds, framework))
            agg = protocol.update_block_aggregate(
                agg, rb, winner.node, machine, winner.dest, winner.moved)
            r, loads = protocol.apply_move(r, loads, winner, machine)
            idle = jnp.where(winner.moved, 0, idle + 1)
            return (r, loads, agg, (machine + 1) % k, idle, turns + 1,
                    moves + winner.moved.astype(jnp.int32))

        init = (r0, loads0, agg0, jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32))
        r, loads, _, _, idle, turns, moves = jax.lax.while_loop(
            cond, body, init)
        return r, loads, moves, turns, idle >= k

    sharded = P("shards")
    rep = P()
    fn = jax.shard_map(spmd, mesh=mesh,
                       in_specs=(sharded, sharded, sharded, sharded, sharded,
                                 rep, rep, rep, rep, rep),
                       out_specs=(rep, rep, rep, rep, rep),
                       check_vma=False)
    run = (None if recorder is None else
           _open_run(recorder, "shard_map", problem, assignment, framework,
                     theta, num_shards=s))
    args = (views.row_block, views.weights, views.ids, views.valid,
            theta_blocks, state0.assignment, state0.loads, problem.speeds,
            problem.mu, total_b)
    t0 = time.perf_counter()
    if recorder is None:
        r, loads, moves, turns, converged = jax.jit(fn)(*args)
    else:
        with recorder.phase("distributed.shard_map", run):
            out = jax.jit(fn)(*args)
            jax.block_until_ready(out)
        r, loads, moves, turns, converged = out
    wall = time.perf_counter() - t0
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=converged)
    if not (measure_wire or recorder is not None):
        return result
    # jax.jit(fn) is freshly constructed above, so tracing always ran
    # this call and populated measured["turn"] with the gathered
    # candidates' buffer size.
    rounds = int(np.asarray(turns))
    wire = WireMeasurement(
        rounds=jnp.int32(rounds),
        payload_bytes=jnp.int32(rounds * measured["turn"]),
        setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))
    if recorder is not None:
        _record_wire(recorder, run, problem, s, wire)
        recorder.record_result(run, result, wall=wall)
    return (result, wire) if measure_wire else result


def _shard_map_faulty_run(problem: PartitionProblem, assignment: Array,
                          fault_plan, framework: str, s: int, mesh, views,
                          state0, total_b, theta_blocks, theta,
                          max_turns: int, acc: Acceptance, degraded,
                          measure_wire: bool, recorder):
    """Real-mesh faulty path (DESIGN.md §15.3): the FaultPlan rides
    replicated (one ``P()`` spec covers the whole pytree); each device
    masks/injects/repairs only its *own* block (``lax.axis_index``), the
    outcome scalars reduce with ``pmax``/``psum``, and the wrapper-level
    recover-or-raise audit is identical to the emulated drivers."""
    k = problem.num_machines
    rtol = degraded.repair_tol
    penalty = degraded.stale_penalty
    msg = faults.message_bytes(traced=False, simultaneous=False,
                               num_machines=k)
    horizon = int(np.asarray(fault_plan.down).shape[0]) - 1
    measured: dict = {}

    def spmd(rb, b, ids, valid, th, r0, loads0, speeds, mu, tot, plan):
        rb, b, ids, valid, th = rb[0], b[0], ids[0], valid[0], th[0]
        idx = jax.lax.axis_index("shards")
        agg0 = protocol.block_aggregate(rb, r0, k)
        zero_i = jnp.zeros((), jnp.int32)
        zero_f = jnp.zeros((), jnp.float32)

        def cond(carry):
            return (carry[4] < k) & (carry[5] < max_turns)

        def body(carry):
            (r, loads, agg, machine, idle, turns, moves,
             fbytes, repairs, rcols, rdrift) = carry
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
            blocked = row.down | row.quarantined | ~row.delivered
            cands = cands._replace(
                gain=jnp.where(blocked, -jnp.inf, cands.gain))
            winner = protocol.elect_degraded(
                cands, protocol.candidate_thresholds(
                    cands, acc, machine, loads, problem.speeds, framework),
                row.lag, penalty)
            new_agg = protocol.update_block_aggregate(
                agg, rb, winner.node, machine, winner.dest, winner.moved)
            agg = jnp.where(row.omit[idx] | row.down[idx], agg, new_agg)
            r, loads = protocol.apply_move(r, loads, winner, machine)
            idle = jnp.where(winner.moved, 0,
                             jnp.where(row.clear, idle + 1, idle))

            def with_repair(a):
                fresh = protocol.block_aggregate(rb, r, k)
                col_dev = jnp.max(jnp.abs(a - fresh), axis=0)    # (K,)
                colbad = ~(col_dev <= rtol)
                patched = jnp.where(colbad[None, :], fresh, a)
                return (patched, jnp.max(_inf_dev(col_dev)),
                        jnp.sum(colbad.astype(jnp.int32)))

            agg, rd, rc = jax.lax.cond(
                row.repair[idx], with_repair,
                lambda a: (a, zero_f, zero_i), agg)
            fbytes = fbytes + faults.round_extra_bytes(row, msg)
            return (r, loads, agg, (machine + 1) % k, idle, turns + 1,
                    moves + winner.moved.astype(jnp.int32), fbytes,
                    repairs + row.repair[idx].astype(jnp.int32),
                    rcols + rc, jnp.maximum(rdrift, rd))

        init = (r0, loads0, agg0) + tuple(
            jnp.zeros((), jnp.int32) for _ in range(7)) + (
            jnp.zeros((), jnp.float32),)
        (r, loads, agg, _, idle, turns, moves, fbytes,
         repairs, rcols, rdrift) = jax.lax.while_loop(cond, body, init)
        converged = idle >= k
        last = jnp.clip(turns - 1, 0, horizon)
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
                       in_specs=(sharded,) * 5 + (rep,) * 6,
                       out_specs=(rep,) * 12, check_vma=False)
    run = (None if recorder is None else
           _open_run(recorder, "shard_map", problem, assignment, framework,
                     theta, num_shards=s, faults=True))
    args = (views.row_block, views.weights, views.ids, views.valid,
            theta_blocks, state0.assignment, state0.loads, problem.speeds,
            problem.mu, total_b, fault_plan)
    t0 = time.perf_counter()
    if recorder is None:
        out = jax.jit(fn)(*args)
        jax.block_until_ready(out)
    else:
        with recorder.phase("distributed.shard_map", run):
            out = jax.jit(fn)(*args)
            jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    (r, loads, moves, turns, converged, fbytes, final_drift, post_drift,
     dead, repairs, rcols, rdrift) = out
    result = RefineResult(assignment=r, loads=loads, num_moves=moves,
                          num_turns=turns, converged=converged,
                          aggregate_drift=post_drift)
    outcome = faults.FaultOutcome(
        final_drift=final_drift, post_drift=post_drift, dead=dead,
        repairs=repairs, repaired_cols=rcols, max_repair_drift=rdrift)
    rounds = int(np.asarray(turns))
    report = faults.build_report(fault_plan, outcome, rounds,
                                 budget=degraded.repair_tol,
                                 raise_on_failure=False)
    wire = None
    if measure_wire or recorder is not None:
        wire = WireMeasurement(
            rounds=jnp.int32(rounds),
            payload_bytes=jnp.int32(rounds * measured["turn"]
                                    + int(np.asarray(fbytes))),
            setup_bytes=jnp.int32(_nbytes((state0.loads, total_b))))
    if recorder is not None:
        faults.emit_fault_events(recorder, run, fault_plan, rounds)
        _record_wire(recorder, run, problem, s, wire,
                     fault_extra=faults.plan_extra_bytes(
                         fault_plan, rounds, msg))
        recorder.record_result(run, result, wall=wall,
                               recovered=report.recovered,
                               recovery_drift=report.recovery_drift)
    faults.raise_if_failed(report, budget=degraded.repair_tol)
    if measure_wire:
        return result, wire, report
    return result, report


# ---------------------------------------------------------------------------
# Telemetry wrappers (DESIGN.md §14)
# ---------------------------------------------------------------------------

def _record_wire(recorder, run: str, problem: PartitionProblem,
                 num_shards: int, wire: WireMeasurement, *,
                 traced: bool = False, simultaneous: bool = False,
                 incremental: bool = True, fault_extra: int = 0) -> None:
    """Reconcile a driver's measured wire counters against the analytic
    ledger for the same executed run and emit the ``wire`` event.
    ``fault_extra`` is the plan-derived retry/repair byte total of a
    fault-injected run (``faults.plan_extra_bytes``)."""
    stats = boundary_stats(problem, num_shards)
    ledger = accounting.ledger_for_run(
        stats, problem.num_machines, int(wire.rounds), traced=traced,
        simultaneous=simultaneous, incremental=incremental,
        fault_bytes=fault_extra)
    recorder.record_wire(run, accounting.reconcile(ledger, wire))


def _run_faulty_emulated(mode: str, problem: PartitionProblem,
                         assignment: Array, fault_plan, framework,
                         num_shards, max_rounds: int, tol: float,
                         cost_fn: str, incremental: bool, theta, degraded,
                         measure_wire: bool, recorder):
    """Shared recover-or-raise harness behind the three emulated public
    wrappers: run the faulty driver, audit its FaultOutcome into a
    :class:`faults.FaultReport`, stream telemetry when asked, and raise
    the typed error on a dead shard / blown recovery budget."""
    if not incremental:
        raise ValueError(
            "fault injection requires the incremental protocol: the "
            "carried block aggregates are what faults corrupt and what "
            "repair heals (DESIGN.md §15)")
    dm = degraded or faults.DEFAULT_DEGRADED
    s = _resolve_shards(problem, num_shards)
    k = problem.num_machines
    traced = mode == "traced"
    simultaneous = mode == "sweep"
    impl = {"plain": _refine_distributed_faulty,
            "traced": _refine_distributed_traced_faulty,
            "sweep": _refine_distributed_simultaneous_faulty}[mode]
    phase = {"plain": "distributed.refine",
             "traced": "distributed.refine_traced",
             "sweep": "distributed.refine_simultaneous"}[mode]
    runtime_name = {"plain": "distributed", "traced": "distributed_traced",
                    "sweep": "distributed_sweep"}[mode]
    mw = measure_wire or recorder is not None
    run = None
    if recorder is not None:
        run = _open_run(recorder, runtime_name, problem, assignment,
                        framework, theta, num_shards=s, incremental=True,
                        faults=True)
    ctx = (recorder.phase(phase, run) if recorder is not None
           else contextlib.nullcontext())
    t0 = time.perf_counter()
    with ctx:
        out = impl(problem, assignment, fault_plan, framework,
                   num_shards=s, max_rounds=max_rounds, tol=tol,
                   cost_fn=cost_fn, degraded=dm, theta=theta,
                   measure_wire=mw)
        jax.block_until_ready(out)
    wall = time.perf_counter() - t0
    wire = out[-1] if mw else None
    core = out[:-1] if mw else out
    ftrace = None
    if mode == "plain":
        result, outcome = core
        extras = ()
    elif mode == "traced":
        result, trace, ftrace, outcome = core
        extras = (trace,)
    else:
        result, outs, ftrace, outcome = core
        extras = (outs,)
    rounds = int(result.num_turns)
    report = faults.build_report(fault_plan, outcome, rounds,
                                 budget=dm.repair_tol,
                                 raise_on_failure=False)
    if recorder is not None:
        if ftrace is not None:
            faults.emit_fault_events(
                recorder, run, fault_plan, rounds,
                repair_drift=ftrace.repair_drift,
                repaired_cols=ftrace.repaired_cols,
                repaired=ftrace.repaired)
        else:
            faults.emit_fault_events(recorder, run, fault_plan, rounds)
        last = max(rounds - 1, 0)
        c0 = ct0 = None
        if mode == "traced":
            recorder.record_trace(run, extras[0], problem.node_weights, k)
            if rounds:
                c0 = float(np.asarray(extras[0].c0)[last])
                ct0 = float(np.asarray(extras[0].ct0)[last])
        elif mode == "sweep":
            recorder.record_sweeps(run, *extras[0])
            if rounds:
                c0 = float(np.asarray(extras[0][0])[last])
                ct0 = float(np.asarray(extras[0][1])[last])
        _record_wire(recorder, run, problem, s, wire, traced=traced,
                     simultaneous=simultaneous, incremental=True,
                     fault_extra=faults.plan_extra_bytes(
                         fault_plan, rounds, faults.message_bytes(
                             traced=traced, simultaneous=simultaneous,
                             num_machines=k)))
        recorder.record_result(run, result, wall=wall, c0=c0, ct0=ct0,
                               recovered=report.recovered,
                               recovery_drift=report.recovery_drift)
    faults.raise_if_failed(report, budget=dm.repair_tol)
    if measure_wire:
        return (result, *extras, wire, report)
    return (result, *extras, report)


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
    into run telemetry: the run is phase-timed, its measured wire bytes
    are reconciled against ``accounting.ledger_for_run``, and the stream
    closes with drift + ``run_end`` events.  ``recorder=None`` dispatches
    straight to the identical jitted program — same cache entry.

    ``fault_plan`` (a :class:`repro.distributed.faults.FaultPlan`) opts
    into the fault-injected driver under ``degraded``-mode rules
    (DESIGN.md §15): returns ``(result, report[, wire in between])`` with
    a :class:`faults.FaultReport` appended, raising ``DeadShardError`` /
    ``RecoveryFailedError`` when the run cannot recover to the drift
    budget — never silently diverging."""
    if fault_plan is not None:
        return _run_faulty_emulated(
            "plain", problem, assignment, fault_plan, framework,
            num_shards, max_turns, tol, cost_fn, incremental, theta,
            degraded, measure_wire, recorder)
    if recorder is None:
        return _refine_distributed(
            problem, assignment, framework, num_shards=num_shards,
            max_turns=max_turns, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=measure_wire)
    s = _resolve_shards(problem, num_shards)
    run = _open_run(recorder, "distributed", problem, assignment, framework,
                    theta, num_shards=s, incremental=incremental)
    t0 = time.perf_counter()
    with recorder.phase("distributed.refine", run):
        result, wire = _refine_distributed(
            problem, assignment, framework, num_shards=s,
            max_turns=max_turns, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=True)
        jax.block_until_ready(result)
    wall = time.perf_counter() - t0
    _record_wire(recorder, run, problem, s, wire, incremental=incremental)
    recorder.record_result(run, result, wall=wall)
    return (result, wire) if measure_wire else result


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
    ``recorder`` additionally streams one ``turn`` event per active turn
    (from the returned trace — the carried exact-potential values ride
    along) and the measured-vs-ledger ``wire`` reconciliation.
    ``fault_plan`` as in :func:`refine_distributed` — the return tuple
    gains a trailing :class:`faults.FaultReport`."""
    if fault_plan is not None:
        return _run_faulty_emulated(
            "traced", problem, assignment, fault_plan, framework,
            num_shards, max_turns, tol, cost_fn, incremental, theta,
            degraded, measure_wire, recorder)
    if recorder is None:
        return _refine_distributed_traced(
            problem, assignment, framework, num_shards=num_shards,
            max_turns=max_turns, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=measure_wire)
    s = _resolve_shards(problem, num_shards)
    run = _open_run(recorder, "distributed_traced", problem, assignment,
                    framework, theta, num_shards=s, incremental=incremental)
    t0 = time.perf_counter()
    with recorder.phase("distributed.refine_traced", run):
        result, trace, wire = _refine_distributed_traced(
            problem, assignment, framework, num_shards=s,
            max_turns=max_turns, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=True)
        jax.block_until_ready(result)
    wall = time.perf_counter() - t0
    recorder.record_trace(run, trace, problem.node_weights,
                          problem.num_machines)
    _record_wire(recorder, run, problem, s, wire, traced=True,
                 incremental=incremental)
    turns = int(result.num_turns)
    last = max(turns - 1, 0)
    recorder.record_result(
        run, result, wall=wall,
        c0=float(np.asarray(trace.c0)[last]) if turns else None,
        ct0=float(np.asarray(trace.ct0)[last]) if turns else None)
    return (result, trace, wire) if measure_wire else (result, trace)


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
    ``recorder`` streams one ``sweep`` event per active sweep plus the
    measured-vs-ledger ``wire`` reconciliation.  ``fault_plan`` as in
    :func:`refine_distributed` — the return tuple gains a trailing
    :class:`faults.FaultReport`."""
    if fault_plan is not None:
        return _run_faulty_emulated(
            "sweep", problem, assignment, fault_plan, framework,
            num_shards, max_sweeps, tol, cost_fn, incremental, theta,
            degraded, measure_wire, recorder)
    if recorder is None:
        return _refine_distributed_simultaneous(
            problem, assignment, framework, num_shards=num_shards,
            max_sweeps=max_sweeps, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=measure_wire)
    s = _resolve_shards(problem, num_shards)
    run = _open_run(recorder, "distributed_sweep", problem, assignment,
                    framework, theta, num_shards=s, incremental=incremental)
    t0 = time.perf_counter()
    with recorder.phase("distributed.refine_simultaneous", run):
        result, (c0s, ct0s, active), wire = _refine_distributed_simultaneous(
            problem, assignment, framework, num_shards=s,
            max_sweeps=max_sweeps, tol=tol, cost_fn=cost_fn,
            incremental=incremental, theta=theta, measure_wire=True)
        jax.block_until_ready(result)
    wall = time.perf_counter() - t0
    recorder.record_sweeps(run, c0s, ct0s, active)
    _record_wire(recorder, run, problem, s, wire, simultaneous=True,
                 incremental=incremental)
    sweeps = int(result.num_turns)
    last = max(sweeps - 1, 0)
    recorder.record_result(
        run, result, wall=wall,
        c0=float(np.asarray(c0s)[last]) if sweeps else None,
        ct0=float(np.asarray(ct0s)[last]) if sweeps else None)
    return ((result, (c0s, ct0s, active), wire) if measure_wire
            else (result, (c0s, ct0s, active)))
