"""Jitted public wrappers around the Pallas kernels.

``interpret`` defaults to backend auto-detection (compiled on a TPU
backend, interpret mode elsewhere); pass an explicit bool to override.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from ..core.refine import DissatFn, SweepCandidateFn
from . import ref
from .decode_attention import decode_attention_pallas
from .dissatisfaction import (cost_matrix_pallas,
                              dissatisfaction_from_aggregate_batched_pallas,
                              dissatisfaction_from_aggregate_pallas,
                              resolve_interpret)

Array = jax.Array

# Declared asymptotic budgets for the kernel-reduction entry points,
# consumed by the complexity analyzers (DESIGN.md §18) and keyed by
# registered entry-point name.  The dense aggregate kernel consumes the
# (N, N) adjacency (dense budget); the edge kernel streams fixed tiles
# of the COO edge list, so its peak intermediate is O(E) and its work
# O(E * K) — the same contract as the jnp sparse path it replaces.
KERNEL_COMPLEXITY = {
    "refine.kernel": {
        "mem": {"n": 2.0, "k": 1.0},
        "ops": {"n": 2.0, "k": 1.0},
    },
    "refine.sparse.edge_kernel": {
        "mem": {"n": 1.0, "e": 1.0, "k": 1.0},
        "ops": {"n": 1.0, "e": 1.0, "k": 1.0},
    },
}


def _default_interpret() -> bool:
    return resolve_interpret(None)


@partial(jax.jit, static_argnames=("framework", "interpret"))
def cost_matrix(adjacency: Array, assignment: Array, node_weights: Array,
                loads: Array, speeds: Array, mu, framework: str = "c",
                interpret: bool | None = None) -> Array:
    """(N, K) node-cost matrix via the fused Pallas kernel."""
    if interpret is None:
        interpret = _default_interpret()
    return cost_matrix_pallas(adjacency, assignment, node_weights, loads,
                              speeds, mu, framework, interpret=interpret)


@partial(jax.jit, static_argnames=("framework",))
def cost_matrix_reference(adjacency: Array, assignment: Array,
                          node_weights: Array, loads: Array, speeds: Array,
                          mu, framework: str = "c") -> Array:
    return ref.cost_matrix_ref(adjacency, assignment, node_weights, loads,
                               speeds, mu, framework)


def make_core_cost_matrix_fn(interpret: bool | None = None):
    """Adapter with the (problem, state, framework) signature expected by
    repro.core.refine(..., cost_matrix_fn=...), so the recompute-path
    refinement loop can run on the Pallas kernel instead of the jnp path."""
    def fn(problem, state, framework):
        return cost_matrix(problem.adjacency, state.assignment,
                           problem.node_weights, state.loads, problem.speeds,
                           problem.mu, framework, interpret=interpret)
    return fn


@lru_cache(maxsize=None)
def _vmappable_aggregate_dissat(framework: str, interpret: bool):
    """The fused aggregate→(dissat, best) reduction as a ``custom_vmap``
    callable: called plain it runs the unbatched Pallas kernel; under
    ``jax.vmap`` it runs the batch-grid kernel
    (:func:`~repro.kernels.dissatisfaction.dissatisfaction_from_aggregate_batched_pallas`,
    DESIGN.md §12.3) instead of an unrolled per-element fallback.  All
    operands are arrays (``theta`` rides as explicit zeros when absent —
    bitwise identical, the kernel always subtracts its theta operand)."""

    @jax.custom_batching.custom_vmap
    def fn(aggregate, row_assignment, node_weights, loads, speeds, mu,
           total_weight, theta):
        return dissatisfaction_from_aggregate_pallas(
            aggregate, row_assignment, node_weights, loads, speeds, mu,
            framework, theta=theta, total_weight=total_weight,
            interpret=interpret)

    @fn.def_vmap
    def _batch_rule(axis_size, in_batched, *args):
        stacked = [x if hit else
                   jnp.broadcast_to(x, (axis_size,) + jnp.shape(x))
                   for x, hit in zip(args, in_batched)]
        agg, r_rows, b, loads, speeds, mu, total_w, theta = stacked
        out = dissatisfaction_from_aggregate_batched_pallas(
            agg, r_rows, b, loads, speeds, mu, framework, theta=theta,
            total_weight=total_w, interpret=interpret)
        return out, (True, True)

    return fn


@partial(jax.jit, static_argnames=("framework", "interpret"))
def dissatisfaction_from_aggregate(aggregate: Array, row_assignment: Array,
                                   node_weights: Array, loads: Array,
                                   speeds: Array, mu, total_weight,
                                   framework: str = "c",
                                   theta: Array | None = None,
                                   interpret: bool | None = None):
    """(dissat, best_machine) from a carried aggregate via the fused kernel
    — the incremental refinement hot path (no (N, K) cost matrix in HBM).
    ``theta`` (rows,) subtracts the per-node migration price inside the
    fused reduction (DESIGN.md §11); the result is net dissatisfaction.
    Under ``jax.vmap`` (the batched sweep runtime, DESIGN.md §12) this
    dispatches to the batch-grid kernel, staying one fused program."""
    if interpret is None:
        interpret = _default_interpret()
    rows = jnp.shape(row_assignment)[-1]
    if theta is None:
        theta = jnp.zeros((rows,), jnp.float32)
    else:
        theta = jnp.broadcast_to(jnp.asarray(theta, jnp.float32), (rows,))
    return _vmappable_aggregate_dissat(framework, interpret)(
        jnp.asarray(aggregate), jnp.asarray(row_assignment, jnp.int32),
        jnp.asarray(node_weights), jnp.asarray(loads), jnp.asarray(speeds),
        jnp.asarray(mu, jnp.float32), jnp.asarray(total_weight, jnp.float32),
        theta)


def make_edge_dissat_fn(problem, interpret: bool | None = None) -> DissatFn:
    """The ``dissat_fn`` convention (see :mod:`repro.core.refine`) on the
    fused Pallas EDGE-BLOCK kernel (DESIGN.md §13.3): the per-turn
    reduction is recomputed straight from ``problem``'s edge list — the
    carried ``aggregate`` argument is ignored, making this the
    drift-free sparse oracle (nothing accumulates across turns), at
    O(E·K) kernel work per turn instead of the aggregate kernel's
    O(N·K) read.  ``problem`` is a concrete
    :class:`~repro.core.sparse.SparseProblem`; its edge-tile layout is
    built host-side once here and closed over.  Plugs into
    ``repro.core.refine(..., dissat_fn=...)`` like any other; unbatched
    only (the batched sweep runtime keeps the aggregate kernel).
    """
    from .edge_block import (build_edge_tile_layout,
                             dissatisfaction_from_edges_pallas)
    layout = build_edge_tile_layout(problem)

    def fn(aggregate, assignment, node_weights, loads, speeds, mu,
           framework, total_weight, theta=None):
        del aggregate   # recomputed from edges — see docstring
        return dissatisfaction_from_edges_pallas(
            layout, assignment, node_weights, loads, speeds, mu, framework,
            theta=theta, total_weight=total_weight, interpret=interpret)
    return fn


def make_edge_sweep_fn(problem,
                       interpret: bool | None = None) -> SweepCandidateFn:
    """The :class:`~repro.core.refine.SweepCandidateFn` convention on the
    fused Pallas edge-block SWEEP kernel (DESIGN.md §17.4): one edge
    stream per sweep produces the whole per-machine election
    ``(gains, picks, dests)`` — the carried ``aggregate`` argument is
    ignored (recomputed from edges, drift-free like
    :func:`make_edge_dissat_fn`), and only O(T·K) election partials ever
    leave the kernel.  ``problem`` is a concrete
    :class:`~repro.core.sparse.SparseProblem`; its edge-tile layout is
    built host-side once here and closed over.  Plugs into
    ``repro.core.refine_sweeps(..., sweep_fn=...)``
    (``moves_per_machine=1`` only — the election IS one per machine).
    """
    from .edge_block import (build_edge_tile_layout,
                             sweep_candidates_from_edges_pallas)
    layout = build_edge_tile_layout(problem)

    def fn(aggregate, assignment, node_weights, loads, speeds, mu,
           framework, total_weight, theta=None):
        del aggregate   # recomputed from edges — see docstring
        return sweep_candidates_from_edges_pallas(
            layout, assignment, node_weights, loads, speeds, mu, framework,
            theta=theta, total_weight=total_weight, interpret=interpret)
    return fn


def make_aggregate_dissat_fn(interpret: bool | None = None) -> DissatFn:
    """Adapter implementing THE ``dissat_fn`` calling convention — see the
    canonical 9-argument spec in :mod:`repro.core.refine` ("The
    ``dissat_fn`` convention") — on the fused Pallas kernel, so the
    incremental loop's per-turn reduction never materializes the (N, K)
    cost matrix.  Plugs into ``repro.core.refine(..., dissat_fn=...)``
    and the distributed shards alike, batched or not (DESIGN.md §12.3).
    """
    def fn(aggregate, assignment, node_weights, loads, speeds, mu,
           framework, total_weight, theta=None):
        return dissatisfaction_from_aggregate(
            aggregate, assignment, node_weights, loads, speeds, mu,
            total_weight, framework, theta=theta, interpret=interpret)
    return fn


@partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q: Array, k: Array, v: Array, length: Array,
                     interpret: bool | None = None) -> Array:
    """GQA single-token decode attention (flash-decoding style)."""
    if interpret is None:
        interpret = _default_interpret()
    return decode_attention_pallas(q, k, v, length, interpret=interpret)


decode_attention_reference = jax.jit(ref.decode_attention_ref)


@partial(jax.jit, static_argnames=("interpret",))
def flash_attention(q: Array, k: Array, v: Array,
                    interpret: bool | None = None) -> Array:
    """Blocked causal GQA attention (flash-attention forward) — the
    train/prefill hot-spot kernel; S x S logits never touch HBM."""
    from .flash_attention import flash_attention_pallas
    if interpret is None:
        interpret = _default_interpret()
    return flash_attention_pallas(q, k, v, interpret=interpret)


flash_attention_reference = jax.jit(ref.flash_attention_ref)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: Array, dt: Array, a: Array, bm: Array, cm: Array,
             chunk: int = 128, interpret: bool | None = None):
    """Mamba2 SSD chunked scan — the SSM train/prefill hot-spot kernel;
    the recurrent state lives in VMEM across chunks."""
    from .ssd_scan import ssd_scan_pallas
    if interpret is None:
        interpret = _default_interpret()
    return ssd_scan_pallas(x, dt, a, bm, cm, chunk, interpret=interpret)


ssd_scan_reference = jax.jit(ref.ssd_scan_ref)
