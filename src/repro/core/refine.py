"""Iterative partition refinement (paper §4.2, Fig. 1/2).

Machines take sequential round-robin turns.  On its turn, a machine finds the
*most dissatisfied* node it owns (Eq. 4) and transfers it to that node's
best-response machine; if the node's dissatisfaction is zero the machine
forsakes its turn.  The algorithm converges (Thm. 4.1) because every transfer
strictly decreases the potential C_0 (or Ct_0 for the second framework);
convergence is declared after K consecutive forsaken turns.

Two execution modes:
  * ``refine``        — ``lax.while_loop`` until convergence (production use;
                        bounded by ``max_turns`` as a safety net).
  * ``refine_traced`` — fixed-length ``lax.scan`` that records per-turn moves
                        and BOTH global potentials; powers the Table I /
                        §5.1 discrepancy study and the convergence tests.

Two cost paths (DESIGN.md §10), selected by ``incremental``:

  * **incremental** (default) — an :class:`~repro.core.aggregate.AggregateState`
    lives in the loop carry; each turn assembles the (N, K) cost matrix from
    the carried aggregate in O(NK), and a move applies a rank-1 column
    update plus exact-potential-identity deltas (Thm. 3.1 / 5.1) — per-turn
    work O(NK), independent of the O(N^2 K) rebuild.  ``verify_every=M``
    cross-checks against a from-scratch rebuild every M turns (recording
    the observed drift in ``RefineResult.aggregate_drift``) and resyncs.
  * **recompute** — the original O(N^2 K)-per-turn path (also selected
    implicitly by passing ``cost_matrix_fn``, e.g. the fused Pallas cost
    kernel); ``refine_traced`` additionally pays two O(N^2) global-potential
    passes per turn.  Kept as the oracle the benchmarks and tests compare
    the incremental path against.

Also implements the paper-§4.5 *simultaneous transfer* mode (one move per
machine per sweep, descent not guaranteed — measured in benchmarks), which
applies a rank-K aggregate update per sweep and re-derives both potentials
via the O(K) closed forms of :mod:`repro.core.aggregate`.  It has one
loop: ``refine_simultaneous`` is the degenerate configuration of the
multi-move ``refine_sweeps`` (DESIGN.md §17) and runs its jitted
``lax.while_loop``, which stops at the first sweep with no candidate.

Sparse problems (DESIGN.md §13): all three entry points accept a
:class:`~repro.core.sparse.SparseProblem` in place of the dense
``PartitionProblem`` — the per-turn math is unchanged (costs still
assemble from the carried (N, K) aggregate via the one shared formula),
but the aggregate is initialized by a ``segment_sum`` over the edge
list, a move scatters only the moved node's O(deg) incident-edge
window, and the traced potentials use the O(K) closed forms — so
nothing in the loop touches an O(N^2) array and N=10^5-10^6 graphs
refine on hardware where the dense adjacency cannot exist.

Migration-aware hysteresis (DESIGN.md §11): every entry point takes a
per-node threshold ``theta`` (scalar or (N,), the node's migration price).
A node is movable only when its Eq.-4 dissatisfaction EXCEEDS ``theta_i``;
the recorded gain is net of it.  Convergence (Thm. 4.1) is preserved
because every accepted move still strictly descends the potential — by at
least ``2*theta_i`` for C_0 (Thm. 3.1) and ``theta_i`` for Ct_0
(Thm. 5.1).  ``theta=None`` (default) and ``theta=0`` reproduce today's
move sequences bitwise.

The ``dissat_fn`` convention
----------------------------

THE canonical calling convention for a pluggable per-turn reduction —
everything that accepts a ``dissat_fn`` (``refine`` here, the shard
candidates of :mod:`repro.distributed`, the kernel adapters of
:mod:`repro.kernels.ops`) uses exactly this 9-argument signature::

    dissat_fn(aggregate, assignment, node_weights, loads, speeds, mu,
              framework, total_weight, theta) -> (dissat, best_machine)

1. ``aggregate``    — (rows, K) f32, ``A[i, k] = sum_j c_ij 1[r_j = k]``
   for the rows being evaluated (the full graph, or a shard's row block).
2. ``assignment``   — (rows,) i32, the rows' OWN current machines.
3. ``node_weights`` — (rows,) f32, the rows' computational loads ``b_i``.
4. ``loads``        — (K,) f32, GLOBAL machine loads ``L_k``.
5. ``speeds``       — (K,) f32, machine capacities ``w_k``.
6. ``mu``           — () f32, inter-machine cost weight (paper §3.1).
7. ``framework``    — static str, ``"c"`` (Eq. 1) or ``"ct"`` (Eq. 6).
8. ``total_weight`` — () f32, the global weight sum ``B``.  The Ct
   framework needs it and a row block cannot compute it locally.
9. ``theta``        — ``None`` or (rows,) f32 per-node migration price
   (DESIGN.md §11, added in PR 3).  The returned dissatisfaction is NET
   of it; ``None`` means no threshold and must match ``theta=0`` bitwise.

Returns ``(dissat (rows,), best_machine (rows,))``: the net Eq.-4
dissatisfaction and the LOWEST-INDEX arg-best machine (the DESIGN.md §7
tie-break).  On the jnp path the tie-break is ``jnp.argmin``'s
first-minimum; every Pallas implementation realizes the identical
semantics in ONE place — the shared ``reduce_dissat_tile`` epilogue of
:mod:`repro.kernels.dissatisfaction` (the iota-min trick), which all
three fused kernels (``_dissat_kernel``, the edge-block
``_edge_dissat_kernel`` and the sweep-candidate ``_edge_sweep_kernel``)
call as their final reduction step.  Reference implementation:
``costs.cost_matrix_from_aggregate`` followed by
``costs.dissatisfaction_from_cost`` (the default when
``dissat_fn=None``); fused implementation:
``repro.kernels.ops.make_aggregate_dissat_fn`` — which under ``jax.vmap``
(the batched sweeps of DESIGN.md §12) stays on the fused batch-grid
kernel rather than falling back.
"""
from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple, Protocol

import numpy as np

import jax
import jax.numpy as jnp

from . import aggregate as agg_mod
from . import checkpoint as ckpt_mod
from . import costs
from .problem import PartitionProblem, PartitionState, make_state
from .sparse import SparseProblem

Array = jax.Array


class DissatFn(Protocol):
    """THE canonical 9-argument ``dissat_fn`` convention (see "The
    ``dissat_fn`` convention" in the module docstring above).

    Every factory producing a pluggable per-turn reduction returns this
    Protocol (``repro.kernels.ops.make_aggregate_dissat_fn`` /
    ``make_edge_dissat_fn``, ``sweeps.runtime._kernel_dissat_fn``,
    ``distributed.runtime._shard_dissat_fn``), and every consumer
    (``refine`` here, ``protocol.local_candidate_from_aggregate``) calls
    it with exactly these 9 positionals.  The contract linter
    (``repro.analysis``, DESIGN.md §16) anchors its signature rule on
    this annotation — not on a magic arity — so annotate new factories
    with ``-> DissatFn``.
    """

    def __call__(self, aggregate: Array, assignment: Array,
                 node_weights: Array, loads: Array, speeds: Array,
                 mu, framework: str, total_weight,
                 theta=None) -> tuple[Array, Array]:
        """Returns ``(dissat (rows,), best_machine (rows,))``."""
        ...

# The acceptance test.  A move is accepted only when its net gain exceeds
# ``tol`` PLUS a round-off allowance of ROUNDOFF_ULPS ulps of the largest
# term in the two costs it compares (:func:`acceptance_threshold`, the one
# helper every acceptance site calls).  The allowance is what makes the
# loop terminate on any backend.  A gain is the difference of two f32
# costs of magnitude S (b·L/w ≈ 1e4 on the §5.1 instance), each assembled
# from at most four terms no larger than S with a few roundings apiece, so
# its error is a few ulp(S): about 12 counted term by term in the worst
# case.  Gains of that size are round-off, and an absolute 1e-6 floor let
# them through: on XLA:CPU (jax 0.9) nodes already on their best machine
# showed gains of 1-2 ulp(S) (4.9e-4 and 9.8e-4 at S ≈ 1.2e4) and five of
# them cycled for all 10,000 turns.  16 ulps (up to 1.6e-2 on §5.1, whose
# measured need lies between 1e-4 and 1e-3) stays about 1e-6 of the
# costs, far below any real move's gain.  The scale is rounded to its
# binade by ``jnp.spacing``, so two paths whose scales differ in the last
# bit (sparse vs dense degree sums, vmap vs loop) agree on the threshold.
DEFAULT_TOL = 1e-6
ROUNDOFF_ULPS = 16


class Acceptance(NamedTuple):
    """Per-run constants of the acceptance threshold (:func:`acceptance`)."""
    tol: Array            # () absolute floor
    cut_scale: Array      # () 0.5·mu·max_i deg_i — the largest cut term
    total_weight: Array   # () B


def acceptance(problem, aggregate: Array, tol) -> Acceptance:
    """Build the run's :class:`Acceptance` from any (N, K) aggregate of
    ``problem`` (row sums are the weighted degrees)."""
    degree = jnp.sum(aggregate, axis=-1)
    return Acceptance(tol=jnp.asarray(tol, aggregate.dtype),
                      cut_scale=0.5 * problem.mu * jnp.max(degree),
                      total_weight=jnp.sum(problem.node_weights))


def acceptance_threshold(acc: Acceptance, framework: str, b: Array,
                         source: Array, dest: Array, loads: Array,
                         speeds: Array) -> Array:
    """THE acceptance threshold of a move of weight ``b`` from machine
    ``source`` to ``dest`` (elementwise over candidates): ``tol`` plus
    ROUNDOFF_ULPS ulps of the largest term of the two costs compared —
    see the note above DEFAULT_TOL for the bound and its reasoning.

    A sequential turn elects its machine's most dissatisfied node among
    those whose gain clears their own threshold, so a converged run has
    every node within its allowance (``reference.check_equilibrium``);
    the sweep modes test each machine's elected candidates."""
    def magnitude(k):
        x = b / speeds[k]
        y = loads[k] / speeds[k]
        if framework == costs.C_FRAMEWORK:
            load = b * y
        else:
            load = x * (x + 2.0 * (y + acc.total_weight))
        return load + acc.cut_scale
    scale = jnp.maximum(magnitude(source), magnitude(dest))
    return acc.tol + ROUNDOFF_ULPS * jnp.spacing(scale)

class TurnResult(NamedTuple):
    moved: Array          # bool   — did this turn transfer a node?
    node: Array           # int32  — the node transferred (or -1)
    source: Array         # int32  — machine that owned it
    dest: Array           # int32  — machine it moved to
    gain: Array           # float  — dissatisfaction of the moved node
    c0: Array             # float  — C_0 after the turn
    ct0: Array            # float  — Ct_0 after the turn


def _resolve_theta(theta, num_nodes: int) -> Array | None:
    """Normalize the hysteresis threshold to None or an (N,) f32 array."""
    if theta is None:
        return None
    theta = jnp.asarray(theta, jnp.float32)
    return jnp.broadcast_to(theta, (num_nodes,))


def _raw_best_gain(dissat: Array, owned: Array, theta) -> Array:
    """Telemetry side quantity: the machine's best gain BEFORE the θ
    hysteresis netting (DESIGN.md §14.1).  ``dissat`` is net of theta
    (the one subtraction site, :func:`costs.dissatisfaction_from_cost`),
    so the raw value is recovered exactly as ``net + theta``.  Lets the
    recorder label a rejected turn "hysteresis" (raw gain cleared tol,
    net did not) vs "satisfied".  Only evaluated on telemetry paths."""
    raw = dissat if theta is None else dissat + theta
    return jnp.max(jnp.where(owned, raw, -jnp.inf))


def _turn(problem: PartitionProblem, state: PartitionState, machine: Array,
          framework: str, acc: Acceptance, cost_matrix_fn=None, theta=None,
          want_raw: bool = False):
    """One machine turn, recompute path: rebuild costs from scratch."""
    if cost_matrix_fn is None:
        cost = costs.cost_matrix(problem, state, framework)
    else:
        cost = cost_matrix_fn(problem, state, framework)
    dissat, best = costs.dissatisfaction(problem, state, framework, cost=cost,
                                         theta=theta)
    owned = state.assignment == machine
    thresh = acceptance_threshold(acc, framework, problem.node_weights,
                                  state.assignment, best, state.loads,
                                  problem.speeds)
    masked = jnp.where(owned & (dissat > thresh), dissat, -jnp.inf)
    node = jnp.argmax(masked).astype(jnp.int32)
    gain = masked[node]
    do_move = gain > thresh[node]

    dest = best[node]
    new_assignment = jnp.where(
        do_move, state.assignment.at[node].set(dest), state.assignment)
    b_node = problem.node_weights[node]
    new_loads = jnp.where(
        do_move,
        state.loads.at[machine].add(-b_node).at[dest].add(b_node),
        state.loads,
    )
    new_state = PartitionState(new_assignment, new_loads)
    res = TurnResult(
        moved=do_move,
        node=jnp.where(do_move, node, -1),
        source=jnp.where(do_move, machine, -1),
        dest=jnp.where(do_move, dest, -1),
        gain=jnp.where(do_move, gain, 0.0),
    c0=jnp.zeros(()), ct0=jnp.zeros(()))  # potentials filled by callers that want them
    if want_raw:
        return new_state, res, _raw_best_gain(dissat, owned, theta)
    return new_state, res


def _turn_incremental(problem: PartitionProblem, agg: agg_mod.AggregateState,
                      machine: Array, framework: str, acc: Acceptance,
                      total_b: Array, dissat_fn=None, theta=None,
                      want_raw: bool = False):
    """One machine turn, incremental path: O(NK) costs from the carried
    aggregate, O(N) rank-1 move (DESIGN.md §10).

    ``dissat_fn`` follows the canonical 9-argument convention (module
    docstring) and substitutes e.g. the fused Pallas kernel
    (``repro.kernels.ops.make_aggregate_dissat_fn``) for the jnp assembly.
    """
    with jax.named_scope("elect"):
        if dissat_fn is None:
            cost = costs.cost_matrix_from_aggregate(
                agg.aggregate, agg.assignment, problem.node_weights,
                agg.loads, problem.speeds, problem.mu, framework,
                total_weight=total_b)
            dissat, best = costs.dissatisfaction_from_cost(
                cost, agg.assignment, theta)
        else:
            dissat, best = dissat_fn(agg.aggregate, agg.assignment,
                                     problem.node_weights, agg.loads,
                                     problem.speeds, problem.mu, framework,
                                     total_b, theta)
        owned = agg.assignment == machine
        thresh = acceptance_threshold(acc, framework, problem.node_weights,
                                      agg.assignment, best, agg.loads,
                                      problem.speeds)
        masked = jnp.where(owned & (dissat > thresh), dissat, -jnp.inf)
        node = jnp.argmax(masked).astype(jnp.int32)
        gain = masked[node]
        do_move = gain > thresh[node]
        dest = best[node]

    with jax.named_scope("apply"):
        new_agg = agg_mod.apply_move(problem, agg, node, machine, dest,
                                     do_move, total_b)
    res = TurnResult(
        moved=do_move,
        node=jnp.where(do_move, node, -1),
        source=jnp.where(do_move, machine, -1),
        dest=jnp.where(do_move, dest, -1),
        gain=jnp.where(do_move, gain, 0.0),
        c0=new_agg.c0, ct0=new_agg.ct0)
    if want_raw:
        return new_agg, res, _raw_best_gain(dissat, owned, theta)
    return new_agg, res


class RefineResult(NamedTuple):
    assignment: Array       # (N,) final assignment
    loads: Array            # (K,)
    num_moves: Array        # int32 — total node transfers ("iterations" in Table I)
    num_turns: Array        # int32 — total machine turns taken
    converged: Array        # bool
    # max deviation observed at verify_every cross-checks (0 when disabled
    # or on the recompute path — there is nothing to drift there)
    aggregate_drift: Array | float = 0.0
    # sweeps of the unbounded refine_sweeps mode whose movers' edges
    # outgrew the largest edge buffer and took the O(E) rebuild (0 elsewhere)
    num_rebuilds: Array | int = 0
    # sweeps up to and including the first with no candidate (max_sweeps
    # when every sweep had one): the sweeps refine_sweeps (and so
    # refine_simultaneous) runs before it stops (0 outside the sweep modes)
    num_sweeps: Array | int = 0
    # edge slots the unbounded sparse refine_sweeps apply scattered: the sum
    # of the edge buffers its sweeps took (0 elsewhere)
    apply_slots: Array | int = 0


@partial(jax.jit, static_argnames=("framework", "max_turns", "cost_matrix_fn",
                                   "incremental", "verify_every",
                                   "repair_every", "dissat_fn", "on_turn"))
@jax.named_scope("refine")
def _refine(problem: PartitionProblem, assignment: Array,
            framework: str = costs.C_FRAMEWORK,
            max_turns: int = 10_000, tol: float = DEFAULT_TOL,
            cost_matrix_fn=None, incremental: bool = True,
            verify_every: int = 0, repair_every: int = 0, dissat_fn=None,
            theta=None, on_turn=None) -> RefineResult:
    """Jitted while-loop body of :func:`refine`.

    ``on_turn`` (static; telemetry only) is a host callback fired once
    per turn via ``jax.debug.callback`` with the raw turn row — see
    ``repro.obs.recorder.Recorder._on_turn_row``.  ``on_turn=None``
    (the default) stages the exact pre-telemetry computation: no
    callback primitive and no raw-gain side quantity appear in the
    jaxpr, so the disabled path is bitwise-identical and callback-free
    (DESIGN.md §14.3).
    """
    K = problem.num_machines
    theta = _resolve_theta(theta, problem.num_nodes)
    if cost_matrix_fn is not None:
        incremental = False

    if not incremental:
        state0 = make_state(problem, assignment)
        acc = acceptance(problem, costs.problem_aggregate(problem, assignment,
                                                          K), tol)

        def cond(carry):
            _, _, idle, turns, _ = carry
            return (idle < K) & (turns < max_turns)

        def body(carry):
            state, machine, idle, turns, moves = carry
            if on_turn is None:
                state, res = _turn(problem, state, machine, framework, acc,
                                   cost_matrix_fn, theta)
            else:
                state, res, raw_gain = _turn(problem, state, machine,
                                             framework, acc, cost_matrix_fn,
                                             theta, want_raw=True)
                jax.debug.callback(on_turn, turns, machine, res.moved,
                                   res.node, res.source, res.dest, res.gain,
                                   res.c0, res.ct0, raw_gain)
            idle = jnp.where(res.moved, 0, idle + 1)
            return (state, (machine + 1) % K, idle, turns + 1,
                    moves + res.moved.astype(jnp.int32))

        init = (state0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
        state, _, idle, turns, moves = jax.lax.while_loop(cond, body, init)
        return RefineResult(assignment=state.assignment, loads=state.loads,
                            num_moves=moves, num_turns=turns,
                            converged=idle >= K,
                            aggregate_drift=jnp.zeros(()))

    with jax.named_scope("init"):
        agg0 = agg_mod.init_aggregate_state(problem, assignment)
        total_b = jnp.sum(problem.node_weights)
        acc = acceptance(problem, agg0.aggregate, tol)

    def cond(carry):
        idle, turns = carry[2], carry[3]
        return (idle < K) & (turns < max_turns)

    def body(carry):
        agg, machine, idle, turns, moves, max_drift = carry[:6]
        if on_turn is None:
            agg, res = _turn_incremental(problem, agg, machine, framework,
                                         acc, total_b, dissat_fn, theta)
        else:
            agg, res, raw_gain = _turn_incremental(
                problem, agg, machine, framework, acc, total_b, dissat_fn,
                theta, want_raw=True)
            jax.debug.callback(on_turn, turns, machine, res.moved, res.node,
                               res.source, res.dest, res.gain, res.c0,
                               res.ct0, raw_gain)
        idle = jnp.where(res.moved, 0, idle + 1)
        turns = turns + 1
        moves = moves + res.moved.astype(jnp.int32)
        if verify_every:
            agg, max_drift = jax.lax.cond(
                turns % verify_every == 0,
                lambda a, d: _resync_max(problem, a, d),
                lambda a, d: (a, d), agg, max_drift)
        if repair_every:
            ckpt = carry[6]
            agg, max_drift, ckpt = jax.lax.cond(
                turns % repair_every == 0,
                lambda a, d, c: _heal_take(problem, a, d, c, turns),
                lambda a, d, c: (a, d, c), agg, max_drift, ckpt)
            return (agg, (machine + 1) % K, idle, turns, moves, max_drift,
                    ckpt)
        return (agg, (machine + 1) % K, idle, turns, moves, max_drift)

    init = (agg0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros(()))
    if repair_every:
        init = init + (ckpt_mod.take(agg0, jnp.zeros((), jnp.int32)),)
    out = jax.lax.while_loop(cond, body, init)
    agg, _, idle, turns, moves, max_drift = out[:6]
    return RefineResult(assignment=agg.assignment, loads=agg.loads,
                        num_moves=moves, num_turns=turns,
                        converged=idle >= K, aggregate_drift=max_drift)


def _open_run(recorder, runtime: str, problem, assignment, framework: str,
              theta, **extra) -> str:
    """Emit a ``run_start`` with the replay seed: initial (K,) machine
    loads (host-side scatter, O(N)) and the machine speeds."""
    b = np.asarray(problem.node_weights)
    r0 = np.asarray(assignment)
    k = problem.num_machines
    loads0 = np.zeros(k)
    np.add.at(loads0, r0, b)
    return recorder.new_run(
        runtime, framework=framework, n=problem.num_nodes, k=k,
        theta=theta is not None, loads=loads0,
        speeds=np.asarray(problem.speeds), **extra)


@partial(jax.profiler.annotate_function, name="repro.refine")
def refine(problem: PartitionProblem, assignment: Array,
           framework: str = costs.C_FRAMEWORK,
           max_turns: int = 10_000, tol: float = DEFAULT_TOL,
           cost_matrix_fn=None, incremental: bool = True,
           verify_every: int = 0, repair_every: int = 0,
           dissat_fn: DissatFn | None = None,
           theta=None, recorder=None) -> RefineResult:
    """Run round-robin refinement to convergence (K consecutive idle turns).

    ``incremental=True`` (default) carries the aggregate state; passing
    ``cost_matrix_fn`` forces the recompute path (a custom cost function
    rebuilds from the full adjacency).  ``verify_every=M > 0`` rebuilds the
    carry from scratch every M turns and records the drift (incremental
    path only).  ``repair_every=M > 0`` (DESIGN.md §15.3) goes further:
    every M turns the carry is *healed* — rolled back to the last
    checkpoint if any float leaf went non-finite, then column-repaired
    against the recompute oracle (only deviating columns are patched, so
    an undrifted carry is untouched bitwise) and re-checkpointed.  The
    default ``0`` stages the exact pre-repair program (same jaxpr).
    ``theta`` (scalar or (N,)) is the per-node migration-price
    hysteresis threshold (DESIGN.md §11); ``None``/``0`` reproduces the
    threshold-free move sequence bitwise.

    ``recorder`` (an :class:`repro.obs.Recorder`, DESIGN.md §14) opts
    into telemetry: per-turn events stream host-side through a buffered
    ``jax.debug.callback`` and the run closes with drift + ``run_end``
    events.  ``recorder=None`` (default) calls the identical jitted
    program as before — same cache entry, zero callbacks.
    """
    if recorder is None:
        return _refine(problem, assignment, framework, max_turns=max_turns,
                       tol=tol, cost_matrix_fn=cost_matrix_fn,
                       incremental=incremental, verify_every=verify_every,
                       repair_every=repair_every, dissat_fn=dissat_fn,
                       theta=theta)
    run = _open_run(recorder, "refine", problem, assignment, framework,
                    theta, incremental=incremental and cost_matrix_fn is None)
    recorder.begin_rows()
    t0 = time.perf_counter()
    with recorder.phase("core.refine", run):
        result = _refine(problem, assignment, framework,
                         max_turns=max_turns, tol=tol,
                         cost_matrix_fn=cost_matrix_fn,
                         incremental=incremental, verify_every=verify_every,
                         repair_every=repair_every, dissat_fn=dissat_fn,
                         theta=theta, on_turn=recorder._on_turn_row)
        jax.block_until_ready(result)
        jax.effects_barrier()
    wall = time.perf_counter() - t0
    carried = incremental and cost_matrix_fn is None
    rows = recorder.take_rows()
    recorder.record_turn_rows(run, rows, problem.node_weights,
                              carried=carried)
    last = max(rows, key=lambda r: int(r[0])) if rows else None
    recorder.record_result(
        run, result, wall=wall,
        c0=float(last[7]) if carried and last is not None else None,
        ct0=float(last[8]) if carried and last is not None else None)
    return result


def _resync_max(problem, agg, max_drift):
    fresh, observed = agg_mod.resync(problem, agg)
    return fresh, jnp.maximum(max_drift, observed)


def _heal_take(problem, agg, max_drift, ckpt, turn):
    """One ``repair_every`` boundary (DESIGN.md §15.3): heal the carry
    (rollback over NaN, then column repair against the recompute
    oracle), fold the observed pre-repair drift into the running max,
    and re-checkpoint the now-known-good state."""
    agg, observed, _cols, _rolled = ckpt_mod.heal(problem, agg, ckpt)
    return (agg, jnp.maximum(max_drift, observed), ckpt_mod.take(agg, turn))


class Trace(NamedTuple):
    """Per-turn record from ``refine_traced`` (fixed length = max_turns)."""
    moved: Array    # (T,) bool
    node: Array     # (T,) int32
    source: Array   # (T,) int32
    dest: Array     # (T,) int32
    gain: Array     # (T,) float
    c0: Array       # (T,) float — C_0 after each turn
    ct0: Array      # (T,) float — Ct_0 after each turn
    active: Array   # (T,) bool  — False once converged


@partial(jax.jit, static_argnames=("framework", "max_turns", "incremental",
                                   "verify_every", "telemetry"))
def _refine_traced(problem: PartitionProblem, assignment: Array,
                   framework: str = costs.C_FRAMEWORK,
                   max_turns: int = 512, tol: float = DEFAULT_TOL,
                   incremental: bool = True, verify_every: int = 0,
                   theta=None, telemetry: bool = False):
    """Jitted scan body of :func:`refine_traced`.

    Returns ``(RefineResult, Trace, raw_gains)`` where ``raw_gains`` is
    the (T,) telemetry side output (θ-free best gain per turn, for
    rejection labeling) when ``telemetry=True`` and ``None`` otherwise —
    the ``telemetry=False`` jaxpr is the exact pre-telemetry program.
    """
    K = problem.num_machines
    theta = _resolve_theta(theta, problem.num_nodes)

    if not incremental:
        state0 = make_state(problem, assignment)
        acc = acceptance(problem, costs.problem_aggregate(problem, assignment,
                                                          K), tol)

        def step(carry, _):
            state, machine, idle = carry
            active = idle < K
            if telemetry:
                new_state, res, raw_gain = _turn(
                    problem, state, framework=framework, acc=acc,
                    machine=machine, theta=theta, want_raw=True)
            else:
                new_state, res = _turn(problem, state, framework=framework,
                                       acc=acc, machine=machine, theta=theta)
            new_state = jax.tree.map(
                lambda new, old: jnp.where(active, new, old), new_state, state)
            moved = res.moved & active
            idle = jnp.where(moved, 0, idle + 1)
            c0 = costs.global_cost_c0(problem, new_state.assignment)
            ct0 = costs.global_cost_ct0(problem, new_state.assignment)
            out = Trace(moved=moved, node=res.node, source=res.source,
                        dest=res.dest, gain=res.gain, c0=c0, ct0=ct0,
                        active=active)
            if telemetry:
                out = (out, raw_gain)
            return (new_state, (machine + 1) % K, idle), out

        (state, _, idle), trace = jax.lax.scan(
            step, (state0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)),
            None, length=max_turns)
        raw_gains = None
        if telemetry:
            trace, raw_gains = trace
        moves = jnp.sum(trace.moved.astype(jnp.int32))
        turns = jnp.sum(trace.active.astype(jnp.int32))
        result = RefineResult(assignment=state.assignment, loads=state.loads,
                              num_moves=moves, num_turns=turns,
                              converged=idle >= K,
                              aggregate_drift=jnp.zeros(()))
        return result, trace, raw_gains

    agg0 = agg_mod.init_aggregate_state(problem, assignment)
    total_b = jnp.sum(problem.node_weights)
    acc = acceptance(problem, agg0.aggregate, tol)

    def step(carry, turn_idx):
        agg, machine, idle, max_drift = carry
        active = idle < K
        if telemetry:
            new_agg, res, raw_gain = _turn_incremental(
                problem, agg, machine, framework, acc, total_b, theta=theta,
                want_raw=True)
        else:
            new_agg, res = _turn_incremental(problem, agg, machine, framework,
                                             acc, total_b, theta=theta)
        new_agg = jax.tree.map(
            lambda new, old: jnp.where(active, new, old), new_agg, agg)
        moved = res.moved & active
        idle = jnp.where(moved, 0, idle + 1)
        if verify_every:
            new_agg, max_drift = jax.lax.cond(
                (turn_idx + 1) % verify_every == 0,
                lambda a, d: _resync_max(problem, a, d),
                lambda a, d: (a, d), new_agg, max_drift)
        out = Trace(moved=moved, node=res.node, source=res.source,
                    dest=res.dest, gain=res.gain, c0=new_agg.c0,
                    ct0=new_agg.ct0, active=active)
        if telemetry:
            out = (out, raw_gain)
        return (new_agg, (machine + 1) % K, idle, max_drift), out

    init = (agg0, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros(()))
    (agg, _, idle, max_drift), trace = jax.lax.scan(
        init=init, f=step, xs=jnp.arange(max_turns, dtype=jnp.int32))
    raw_gains = None
    if telemetry:
        trace, raw_gains = trace
    moves = jnp.sum(trace.moved.astype(jnp.int32))
    turns = jnp.sum(trace.active.astype(jnp.int32))
    result = RefineResult(assignment=agg.assignment, loads=agg.loads,
                          num_moves=moves, num_turns=turns,
                          converged=idle >= K, aggregate_drift=max_drift)
    return result, trace, raw_gains


def refine_traced(problem: PartitionProblem, assignment: Array,
                  framework: str = costs.C_FRAMEWORK,
                  max_turns: int = 512, tol: float = DEFAULT_TOL,
                  incremental: bool = True, verify_every: int = 0,
                  theta=None, recorder=None):
    """Fixed-length scan variant recording both potentials after every turn.

    Returns (RefineResult, Trace).  Turns after convergence are no-ops with
    ``active=False`` so downstream statistics can mask them out.

    On the incremental path (default) the recorded potentials are the
    carried values, updated per move by the exact-potential identities —
    no O(N^2) pass per turn.  On the recompute path they are evaluated
    from scratch each turn (the oracle ``tests/test_incremental.py``
    compares against).  ``theta`` as in :func:`refine`; recorded gains are
    net of it, while the traced potentials remain the actual C_0/Ct_0
    values (which descend by at least 2*theta/theta per accepted move).

    ``recorder`` opts into telemetry (DESIGN.md §14): the returned trace
    is ingested host-side into per-turn events — plus a θ-free raw-gain
    side output for hysteresis-vs-satisfied rejection labels — and the
    run closes with drift + ``run_end`` events.  ``recorder=None``
    (default) runs the identical pre-telemetry program.
    """
    if recorder is None:
        result, trace, _ = _refine_traced(
            problem, assignment, framework, max_turns=max_turns, tol=tol,
            incremental=incremental, verify_every=verify_every, theta=theta)
        return result, trace
    run = _open_run(recorder, "refine_traced", problem, assignment,
                    framework, theta, incremental=incremental)
    t0 = time.perf_counter()
    with recorder.phase("core.refine_traced", run):
        result, trace, raw_gains = _refine_traced(
            problem, assignment, framework, max_turns=max_turns, tol=tol,
            incremental=incremental, verify_every=verify_every, theta=theta,
            telemetry=True)
        jax.block_until_ready(result)
    wall = time.perf_counter() - t0
    recorder.record_trace(run, trace, problem.node_weights,
                          problem.num_machines, raw_gain=raw_gains)
    turns = int(result.num_turns)
    last = max(turns - 1, 0)
    recorder.record_result(run, result, wall=wall,
                           c0=float(trace.c0[last]),
                           ct0=float(trace.ct0[last]))
    return result, trace


def refine_simultaneous(problem: PartitionProblem, assignment: Array,
                        framework: str = costs.C_FRAMEWORK,
                        max_sweeps: int = 256, tol: float = DEFAULT_TOL,
                        theta=None, recorder=None):
    """§4.5 asynchronous mode: every machine moves its most dissatisfied node
    in the same sweep.  Faster wall-clock (one cost evaluation per sweep
    serves all K machines) but descent is NOT guaranteed; ``refine_traced``
    style potentials are returned per sweep so benchmarks can count ascents.

    Incremental throughout: costs come from the carried aggregate (O(NK)
    per sweep), the K disjoint moves apply as one rank-K column update,
    and both potentials are re-derived via the O(K) closed forms of
    :func:`repro.core.aggregate.potentials_closed_form` (simultaneous
    moves are not unilateral, so the exact-potential identities do not
    apply — DESIGN.md §10).

    ``num_moves`` counts ACTUAL transfers (``sum(will_move)`` per sweep),
    not the ``K * sweeps`` upper bound.  ``theta`` as in :func:`refine`
    (each machine's pick maximizes — and its move gate tests — the
    dissatisfaction net of the node's migration price).

    Tie-breaks are deterministic throughout (DESIGN.md §7): each
    machine's pick is ``jnp.argmax``'s first maximum (lowest node
    index), and each node's destination is the lowest-index arg-best
    machine — the latter realized on every kernel path by the shared
    ``reduce_dissat_tile`` epilogue (see "The ``dissat_fn`` convention"
    in the module docstring; three fused kernels share it).

    ``recorder`` opts into telemetry (DESIGN.md §14): per-sweep events
    (with a movers-per-sweep side output) plus drift + ``run_end``;
    ``recorder=None`` (default) runs the identical pre-telemetry
    program.

    This is the degenerate configuration of :func:`refine_sweeps`
    (``moves_per_machine=1, move_prob=1, epsilon=0``), run by the same
    jitted loop, so it stops after the first sweep with no candidate;
    the per-sweep outputs keep length ``max_sweeps``.
    """
    return _run_sweeps("refine_simultaneous", {}, recorder, problem,
                       assignment, None, framework, max_sweeps=max_sweeps,
                       tol=tol, theta=theta, moves_per_machine=1,
                       move_prob=1.0, epsilon=0.0, dissat_fn=None,
                       sweep_fn=None)


class SweepCandidateFn(Protocol):
    """Fused sweep-election convention (DESIGN.md §17.4): the same 9
    positional arguments as :class:`DissatFn`, but returning the
    per-MACHINE election instead of the per-node reduction::

        sweep_fn(aggregate, assignment, node_weights, loads, speeds, mu,
                 framework, total_weight, theta)
            -> (gains (K,), picks (K,), dests (K,))

    ``gains[m]`` is the best net dissatisfaction among machine m's owned
    nodes, ``picks[m]`` that node (lowest index on ties — the same
    DESIGN.md §7 tie-break ``jnp.argmax`` applies) and ``dests[m]`` its
    lowest-index arg-best machine.  Factory:
    ``repro.kernels.ops.make_edge_sweep_fn`` (the edge-streaming Pallas
    kernel whose epilogue extends ``reduce_dissat_tile``).  Consumed by
    :func:`refine_sweeps` with ``moves_per_machine=1``.
    """

    def __call__(self, aggregate: Array, assignment: Array,
                 node_weights: Array, loads: Array, speeds: Array,
                 mu, framework: str, total_weight,
                 theta=None) -> tuple[Array, Array, Array]:
        """Returns ``(gains (K,), picks (K,), dests (K,))``."""
        ...


@partial(jax.jit, static_argnames=("framework", "max_sweeps",
                                   "moves_per_machine", "move_prob",
                                   "epsilon", "dissat_fn", "sweep_fn",
                                   "telemetry"))
@jax.named_scope("refine_sweeps")
def _refine_sweeps(problem: PartitionProblem, assignment: Array, key=None,
                   framework: str = costs.C_FRAMEWORK,
                   max_sweeps: int = 256, tol: float = DEFAULT_TOL,
                   theta=None, moves_per_machine: int | None = 1,
                   move_prob: float = 1.0, epsilon: float = 0.0,
                   dissat_fn=None, sweep_fn=None, telemetry: bool = False):
    """Jitted sweep loop of :func:`refine_sweeps`: a ``lax.while_loop``
    that stops after the first sweep with no candidate, or after
    ``max_sweeps`` sweeps.

    Returns ``(RefineResult, (c0s, ct0s, active), movers)`` (``movers``
    is ``None`` unless ``telemetry=True``; the default jaxpr is the
    pre-telemetry program).
    The per-sweep outputs keep length ``max_sweeps``: the slots of the
    sweeps not run hold what a sweep after convergence writes (the final
    potentials, ``active`` false, no movers), so they equal the outputs of
    a loop that runs all ``max_sweeps``.
    """
    K = problem.num_machines
    n = problem.num_nodes
    theta = _resolve_theta(theta, n)
    with jax.named_scope("init"):
        agg0 = agg_mod.init_aggregate_state(problem, assignment)
        total_b = jnp.sum(problem.node_weights)
        acc = acceptance(problem, agg0.aggregate, tol)

    sparse = isinstance(problem, SparseProblem)

    def sweep(carry, sweep_idx):
        agg, done, moves, rebuilds, slots = carry

        with jax.named_scope("elect"):
            def threshold(b, source, dest):
                # ε-gain threshold (arXiv:1305.3354, approximate congestion
                # games): a configuration is an ε-equilibrium once no player
                # can improve by more than ε times the per-node average
                # potential, so the acceptance floor scales with the CARRIED
                # potential and the loop stops at an ε-Nash point instead of
                # chasing O(tol) tail gains.  epsilon=0 is statically elided:
                # the threshold is then the plain acceptance threshold of
                # the §4.5 mode (refine_simultaneous).
                thresh = acceptance_threshold(acc, framework, b, source, dest,
                                              agg.loads, problem.speeds)
                if epsilon:
                    pot = agg.c0 if framework == costs.C_FRAMEWORK else agg.ct0
                    thresh = thresh + epsilon * jnp.abs(pot) / n
                return thresh

            if sweep_fn is not None:
                # fused election: gains/picks/dests straight off the kernel
                gains, pick, dest_k = sweep_fn(
                    agg.aggregate, agg.assignment, problem.node_weights,
                    agg.loads, problem.speeds, problem.mu, framework, total_b,
                    theta)
            else:
                if dissat_fn is None:
                    cost = costs.cost_matrix_from_aggregate(
                        agg.aggregate, agg.assignment, problem.node_weights,
                        agg.loads, problem.speeds, problem.mu, framework,
                        total_weight=total_b)
                    dissat, best = costs.dissatisfaction_from_cost(
                        cost, agg.assignment, theta)
                else:
                    dissat, best = dissat_fn(agg.aggregate, agg.assignment,
                                             problem.node_weights, agg.loads,
                                             problem.speeds, problem.mu,
                                             framework, total_b, theta)

            if sweep_fn is not None or moves_per_machine == 1:
                if sweep_fn is None:
                    owned = jax.nn.one_hot(agg.assignment, K,
                                           dtype=dissat.dtype)       # (N,K)
                    masked = jnp.where(owned.T > 0, dissat[None, :],
                                       -jnp.inf)                     # (K,N)
                    pick = jnp.argmax(masked, axis=1) \
                        .astype(jnp.int32)                           # (K,)
                    gains = jnp.max(masked, axis=1)
                    dest_k = best[pick]
                cand = gains > threshold(problem.node_weights[pick],
                                         jnp.arange(K, dtype=jnp.int32),
                                         dest_k)                     # (K,)
            elif moves_per_machine is not None:
                owned = jax.nn.one_hot(agg.assignment, K, dtype=dissat.dtype)
                masked = jnp.where(owned.T > 0, dissat[None, :], -jnp.inf)
                gains, pick = jax.lax.top_k(masked,
                                            moves_per_machine)       # (K,M)
                gains = gains.reshape(-1)                            # (K·M,)
                pick = pick.reshape(-1).astype(jnp.int32)
                dest_k = best[pick]
                cand = gains > threshold(problem.node_weights[pick],
                                         agg.assignment[pick], dest_k)
            else:
                # unbounded: every node clearing the threshold is a
                # candidate
                cand = dissat > threshold(problem.node_weights,
                                          agg.assignment, best)      # (N,)

            # Probabilistic acceptance (arXiv:cs/0506098, Berenbrink et al.,
            # distributed selfish load balancing): simultaneous best
            # responses can overshoot their destinations, so each candidate
            # migrates only with an independent per-candidate coin.  With
            # unilateral gains g_i, the accepted set drops the potential by
            # Σp_i·g_i in expectation while the collision overshoot scales
            # as Σ_{i≠j sharing a dest} p_i·p_j·b_i·b_j, so E[ΔΦ] < 0
            # whenever each destination's EXPECTED accepted inflow stays
            # below its load deficit — the expected-drop bound.  In the
            # unbounded mode (where overshoot is O(N)-wide) the coin rate is
            # DERIVED from that bound per candidate:
            #     p_i = move_prob · min(1, gap_i / W_{d_i}),
            # gap_i being half the source→destination normalized-load
            # imbalance (the weight that equalizes the pair) and W_d the
            # total candidate weight targeting d, so each destination's
            # expected inflow is at most move_prob · its absorbable weight.
            # The elected modes (≤ K·M movers) keep the flat ``move_prob``
            # coin — their overshoot is already bounded by the election.
            # ``move_prob >= 1`` is statically elided: ``accept`` IS
            # ``cand`` (same tensor, no PRNG op staged), which is what makes
            # the degenerate config the §4.5 mode
            # (:func:`refine_simultaneous`).
            if move_prob < 1.0:
                coin_key = jax.random.fold_in(key, sweep_idx)
                if sweep_fn is None and moves_per_machine is None:
                    norm = agg.loads / problem.speeds                    # (K,)
                    gap = 0.5 * (norm[agg.assignment] - norm[best]) \
                        * problem.speeds[best]                           # (N,)
                    w_dest = jax.ops.segment_sum(
                        jnp.where(cand, problem.node_weights,
                                  jnp.zeros((), dissat.dtype)),
                        best, num_segments=K)                            # (K,)
                    frac = gap / jnp.maximum(w_dest[best],
                                             jnp.asarray(1e-30, dissat.dtype))
                    coin = jax.random.bernoulli(
                        coin_key, move_prob * jnp.clip(frac, 0.0, 1.0))
                    # A candidate whose destination gap is non-positive has
                    # acceptance probability 0 on every future sweep too (its
                    # coin rate only rises if loads change, and loads only
                    # change through moves) — once ALL candidates are in that
                    # state the chain is absorbed, so they must not keep the
                    # convergence test alive.
                    cand = cand & (frac > 0)
                else:
                    coin = jax.random.bernoulli(coin_key, move_prob,
                                                cand.shape)
                accept = cand & coin
            else:
                accept = cand

            any_cand = jnp.any(cand) & ~done

        with jax.named_scope("apply"):
            if sweep_fn is not None or moves_per_machine == 1:
                new_agg = agg_mod.apply_sweep(problem, agg, pick, dest_k,
                                              accept, total_b)
            elif moves_per_machine is not None:
                new_agg = agg_mod.apply_moves(problem, agg, pick, dest_k,
                                              accept, total_b)
            elif sparse:
                # Unbounded apply: the movers' incident edges, in the
                # smallest edge buffer that holds them, or the O(E) rebuild
                new_agg, took = agg_mod.apply_mover_edges(
                    problem, agg, accept, best, total_b)
                rebuilds = rebuilds + (took == 0).astype(jnp.int32)
                slots = slots + took
            else:
                # dense unbounded apply: all N slots, one matmul
                n_acc = jnp.sum(accept.astype(jnp.int32))
                idx = jnp.nonzero(accept, size=n, fill_value=0)[0] \
                    .astype(jnp.int32)
                new_agg = agg_mod.apply_moves(problem, agg, idx, best[idx],
                                              jnp.arange(n) < n_acc, total_b)
            new_agg = jax.tree.map(
                lambda new, old: jnp.where(any_cand, new, old), new_agg, agg)
        sweep_movers = jnp.where(any_cand,
                                 jnp.sum(accept.astype(jnp.int32)), 0)
        moves = moves + sweep_movers
        out = (new_agg.c0, new_agg.ct0, any_cand)
        if telemetry:
            out = out + (sweep_movers,)
        return (new_agg, done | ~any_cand, moves, rebuilds, slots), out

    # Stopping after the first sweep with no candidate changes no result:
    # every later sweep would find the same empty set and keep the state.
    # Each sweep writes its slot of the (max_sweeps,) buffers; the slots of
    # the sweeps not run are filled after the loop.
    zero = jnp.zeros((), jnp.int32)
    bufs = (jnp.zeros((max_sweeps,), agg0.c0.dtype),
            jnp.zeros((max_sweeps,), agg0.ct0.dtype),
            jnp.zeros((max_sweeps,), bool))
    if telemetry:
        bufs = bufs + (jnp.zeros((max_sweeps,), jnp.int32),)

    def cond(carry):
        done, sweep_idx = carry[1], carry[5]
        return ~done & (sweep_idx < max_sweeps)

    def body(carry):
        state, sweep_idx, bufs = carry[:5], carry[5], carry[6]
        state, out = sweep(state, sweep_idx)
        bufs = tuple(buf.at[sweep_idx].set(o) for buf, o in zip(bufs, out))
        return (*state, sweep_idx + 1, bufs)

    agg, done, moves, rebuilds, slots, num_sweeps, bufs = jax.lax.while_loop(
        cond, body,
        (agg0, jnp.zeros((), bool), zero, zero, zero, zero, bufs))
    ran = jnp.arange(max_sweeps) < num_sweeps
    c0s = jnp.where(ran, bufs[0], agg.c0)
    ct0s = jnp.where(ran, bufs[1], agg.ct0)
    active = bufs[2]
    movers = bufs[3] if telemetry else None
    result = RefineResult(
        assignment=agg.assignment, loads=agg.loads,
        num_moves=moves,
        num_turns=jnp.sum(active.astype(jnp.int32)),
        converged=done, aggregate_drift=jnp.zeros(()),
        num_rebuilds=rebuilds, num_sweeps=num_sweeps, apply_slots=slots)
    return result, (c0s, ct0s, active), movers


def _run_sweeps(runtime: str, run_fields: dict, recorder, problem,
                assignment, key, framework, **sweep_args):
    """Run :func:`_refine_sweeps` (``sweep_args``: its keyword options)
    for the public sweep entry points.

    ``recorder=None`` calls the jitted loop and returns ``(result, outs)``;
    otherwise the run opens as ``runtime`` with ``run_fields`` on its
    ``run_start``, is timed under the phase ``core.<runtime>``, and
    streams the per-sweep events (with movers) and ``run_end``."""
    if recorder is None:
        result, outs, _ = _refine_sweeps(problem, assignment, key, framework,
                                         **sweep_args)
        return result, outs
    run = _open_run(recorder, runtime, problem, assignment, framework,
                    sweep_args["theta"], **run_fields)
    t0 = time.perf_counter()
    with recorder.phase(f"core.{runtime}", run):
        result, outs, movers = _refine_sweeps(
            problem, assignment, key, framework, **sweep_args,
            telemetry=True)
        jax.block_until_ready(result)
    wall = time.perf_counter() - t0
    c0s, ct0s, active = outs
    recorder.record_sweeps(run, c0s, ct0s, active, movers=movers)
    turns = int(result.num_turns)
    last = max(turns - 1, 0)
    recorder.record_result(run, result, wall=wall, c0=float(c0s[last]),
                           ct0=float(ct0s[last]))
    return result, outs


@partial(jax.profiler.annotate_function, name="repro.refine_sweeps")
def refine_sweeps(problem: PartitionProblem, assignment: Array,
                  framework: str = costs.C_FRAMEWORK,
                  max_sweeps: int = 256, tol: float = DEFAULT_TOL,
                  theta=None, moves_per_machine: int | None = 1,
                  move_prob: float = 1.0, epsilon: float = 0.0, key=None,
                  dissat_fn: DissatFn | None = None,
                  sweep_fn: SweepCandidateFn | None = None, recorder=None):
    """Multi-move probabilistic sweeps (DESIGN.md §17): the §4.5
    simultaneous mode generalized so convergence is O(sweeps), not
    O(moves).

    Per sweep, candidates are elected by the static ``moves_per_machine``:

      * ``1`` (default) — each machine's single most dissatisfied node,
        exactly :func:`refine_simultaneous`'s election;
      * ``M > 1`` — each machine's top-M owned nodes (``lax.top_k``),
        applied as one rank-K·M update
        (:func:`repro.core.aggregate.apply_moves`);
      * ``None`` — unbounded: EVERY node whose net dissatisfaction
        clears the threshold migrates to its best response.  On a sparse
        problem the movers' incident edges are compacted into the
        smallest static edge buffer that holds them and applied at two
        element updates per edge
        (:func:`repro.core.aggregate.apply_mover_edges`); a sweep whose
        movers' edges outgrow the largest buffer (a quarter of E) takes
        the drift-free O(E) rebuild instead — the million-node-in-seconds
        mode of ROADMAP item 1.  A dense problem applies all movers as one
        (N, N) @ (N, K) matmul (:func:`repro.core.aggregate.apply_moves`).

    ``move_prob < 1`` then thins the candidates with independent coins:
    a flat ``move_prob`` rate in the elected modes, and in the
    unbounded mode per-candidate rates DERIVED from the cs/0506098
    expected-drop bound — ``move_prob · min(1, gap_i / W_dest)``, so
    each destination's expected inflow never overshoots its load
    deficit (see the derivation comment in the sweep body).
    ``epsilon`` raises the acceptance threshold by ``ε·|Φ|/N`` — the
    ε-equilibrium threshold of 1305.3354.  Convergence is declared when
    no CANDIDATE clears the threshold (coin luck never extends or ends
    the run); the unbounded adaptive mode additionally drops candidates
    whose destination gap is non-positive — their coin rate is 0 on this
    and every future sweep, so a sweep where ALL candidates are in that
    state is an absorbing stochastic fixed point and counts as
    converged.

    The degenerate config — ``moves_per_machine=1, move_prob=1.0,
    epsilon=0`` — is the §4.5 mode: :func:`refine_simultaneous` runs it.
    Its move sequences, potentials and mover counts are pinned bitwise
    on dense and sparse problems against recorded reference outputs
    (``tests/data/refine_simultaneous_ref.npz``).

    ``key`` (a ``jax.random`` PRNG key) is required when
    ``move_prob < 1``; per-sweep coins derive via ``fold_in(key, sweep)``
    so results are reproducible per (key, config).  ``dissat_fn`` is the
    canonical 9-argument seam (module docstring) — e.g.
    ``repro.kernels.ops.make_edge_dissat_fn`` streams the candidate
    pass's edges once per sweep; ``sweep_fn``
    (:class:`SweepCandidateFn`) fuses the per-machine election into the
    kernel epilogue itself (``moves_per_machine=1`` only).

    The loop stops after the first sweep with no candidate: every later
    sweep would find none either and leave the state unchanged, so
    ``max_sweeps`` is only a cap.  ``RefineResult.num_sweeps`` counts
    the sweeps run: ``num_turns + 1`` when converged before the cap,
    else ``max_sweeps``.

    Returns ``(RefineResult, (c0s, ct0s, active))`` like
    :func:`refine_simultaneous`, each per-sweep output of length
    ``max_sweeps``: after the last sweep run, ``c0s``/``ct0s`` repeat
    the final potentials and ``active`` is false.  ``recorder`` opts
    into the identical telemetry shape (per-sweep potentials + movers,
    and ``num_sweeps`` and ``apply_slots`` on ``run_end``).
    """
    if move_prob < 1.0 and key is None:
        raise ValueError("refine_sweeps(move_prob < 1) needs a PRNG `key` "
                         "for the per-sweep acceptance coins")
    if sweep_fn is not None and moves_per_machine != 1:
        raise ValueError("sweep_fn fuses the one-move-per-machine election "
                         "(moves_per_machine=1); use dissat_fn for the "
                         "other modes")
    if sweep_fn is not None and dissat_fn is not None:
        raise ValueError("pass sweep_fn or dissat_fn, not both (sweep_fn "
                         "subsumes the per-node reduction)")
    run_fields = dict(moves_per_machine=(-1 if moves_per_machine is None
                                         else moves_per_machine),
                      move_prob=move_prob, epsilon=epsilon)
    return _run_sweeps("refine_sweeps", run_fields, recorder, problem,
                       assignment, key, framework, max_sweeps=max_sweeps,
                       tol=tol, theta=theta,
                       moves_per_machine=moves_per_machine,
                       move_prob=move_prob, epsilon=epsilon,
                       dissat_fn=dissat_fn, sweep_fn=sweep_fn)


def count_discrepancies(trace: Trace, framework: str, initial_other: Array,
                        rel_tol: float = 1e-4) -> Array:
    """§5.1: a C_0-discrepancy is a move that *increases* C_0 while using
    Ct_i as the local criterion (and vice versa).  ``framework`` names the
    criterion that *was* used; we count ascents of the OTHER potential.
    ``initial_other`` is that potential's value before the first turn.

    ``rel_tol`` sets what counts as an ascent: the potentials are O(1e6)
    f32 sums over N^2 terms, so sub-1e-5-relative deltas are accumulation
    noise; 1e-4 keeps every O(0.01%)-or-larger true ascent (measured
    ascents under the wrong criterion are 0.03-0.3% relative) while
    rejecting noise.  The paper does not publish its counting rule; the
    claim we reproduce is the ORDERING: Ct_0-discrepancies >> C_0-ones.
    """
    other = trace.c0 if framework == costs.CT_FRAMEWORK else trace.ct0
    prev = jnp.concatenate([initial_other[None], other[:-1]])
    ascent = (other - prev > rel_tol * jnp.abs(prev)) & trace.moved
    return jnp.sum(ascent.astype(jnp.int32))
