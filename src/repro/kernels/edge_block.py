"""Fused Pallas edge-block dissatisfaction kernel (DESIGN.md §13.3).

The sparse runtime's from-scratch per-turn reduction: edges in, Eq.-4
``(dissat, best_machine)`` out, with neither the (N, K) aggregate nor
the (N, K) cost matrix ever written to HBM.  This is the edge-list twin
of :func:`repro.kernels.dissatisfaction.cost_matrix_pallas` — O(E)
kernel traffic instead of the dense kernel's O(N^2) adjacency read —
reached through the same canonical 9-argument ``dissat_fn`` convention
via :func:`repro.kernels.ops.make_edge_dissat_fn`.

Layout (:func:`build_edge_tile_layout`, built host-side once per
problem): the sender-sorted edge list is re-blocked into per-row-tile
slabs — row tile i (``tile_n`` nodes) owns the contiguous edge range
whose senders fall in ``[i*tile_n, (i+1)*tile_n)``, padded to the fleet
maximum ``EB`` (multiple of ``tile_e``).  Stored per edge:

  * ``local_senders`` (T, 1, EB) — sender minus the tile's row offset, so
    a one-hot against a TN-iota scatters the edge to its row *inside
    VREGs*; padding points at row ``tile_n`` (matches nothing).
  * ``recv_index``    (T, 1, EB) — global receiver id.  The wrapper
    gathers ``assignment[recv_index]`` (one O(E) XLA gather, the only
    assignment-dependent prep) so the kernel itself never gathers.
  * ``edge_w``        (T, 1, EB) — weight, 0.0 on padding (exact +0.0
    contributions, the DESIGN.md §13.1 padding rule).

The slabs are 3-D with a unit middle axis so that every block's last two
dims, ``(1, tile_e)``, either equal the array's or divide (8, 128) — the
TPU compiler's block-shape rule, which a ``(1, tile_e)`` block over a
``(T, EB)`` array breaks.  The sweep kernel's ``(T, 1, k_pad)`` outputs
follow the same rule.

Grid ``(T, EB/tile_e)``, edge blocks innermost.  Per step the kernel
forms the (TN, TE) sender one-hot and the weighted (TE, K) receiver
one-hot and accumulates their product on the MXU:

    acc(TN, K) += onehot_send @ (w * onehot_recv)

— i.e. the segment-sum aggregate of DESIGN.md §13.2 as a matmul.  At
the last edge block the tile's aggregate is complete in VMEM scratch
and the shared epilogue
(:func:`repro.kernels.dissatisfaction.reduce_dissat_tile` — the same
ops in the same order as the aggregate kernels, preserving the §7
tie-break) reduces it straight to the dissatisfaction rows.

Two kernels share that layout and accumulation
(:func:`_accumulate_edge_block`): :func:`_edge_dissat_kernel` emits the
per-node ``(dissat, best)`` rows, and :func:`_edge_sweep_kernel`
(DESIGN.md §17.4) goes one reduction further — its epilogue
(:func:`~repro.kernels.dissatisfaction.reduce_sweep_tile`, which calls
``reduce_dissat_tile`` first) folds each row tile to per-MACHINE sweep
election partials, so :func:`sweep_candidates_from_edges_pallas` feeds
``refine_sweeps``'s whole candidate pass from ONE edge stream per
sweep, with only O(T·K) partials leaving the kernel.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dissatisfaction import (DEFAULT_TILE_N, pad_dissat_operands,
                              reduce_dissat_tile, reduce_sweep_tile,
                              resolve_interpret)

Array = jax.Array

DEFAULT_TILE_E = 128


class EdgeTileLayout(NamedTuple):
    """Row-tile-aligned edge slabs (see module docstring)."""
    local_senders: Array   # (T, 1, EB) int32; padding = tile_n
    recv_index: Array      # (T, 1, EB) int32; padding = 0 (weight-0 slot)
    edge_w: Array          # (T, 1, EB) float32; padding = 0.0
    num_nodes: int
    tile_n: int
    tile_e: int


def build_edge_tile_layout(sp, tile_n: int = DEFAULT_TILE_N,
                           tile_e: int = DEFAULT_TILE_E) -> EdgeTileLayout:
    """Re-block a :class:`~repro.core.sparse.SparseProblem`'s edge list
    into per-row-tile slabs (host-side numpy, once per problem — the
    layout depends only on the static graph, not on any assignment)."""
    senders = np.asarray(sp.senders)
    receivers = np.asarray(sp.receivers)
    weights = np.asarray(sp.edge_weights, np.float32)
    n = sp.num_nodes
    num_tiles = -(-n // tile_n)
    # sender-sorted => each tile's edges are one contiguous range
    bounds = np.searchsorted(senders,
                             np.arange(num_tiles + 1) * tile_n, side="left")
    counts = np.diff(bounds)
    eb = -(-max(int(counts.max(initial=1)), 1) // tile_e) * tile_e
    ls = np.full((num_tiles, 1, eb), tile_n, np.int32)
    ri = np.zeros((num_tiles, 1, eb), np.int32)
    ew = np.zeros((num_tiles, 1, eb), np.float32)
    for t in range(num_tiles):
        lo, hi = int(bounds[t]), int(bounds[t + 1])
        c = hi - lo
        ls[t, 0, :c] = senders[lo:hi] - t * tile_n
        ri[t, 0, :c] = receivers[lo:hi]
        ew[t, 0, :c] = weights[lo:hi]
    return EdgeTileLayout(local_senders=jnp.asarray(ls),
                          recv_index=jnp.asarray(ri),
                          edge_w=jnp.asarray(ew),
                          num_nodes=n, tile_n=tile_n, tile_e=tile_e)


def _accumulate_edge_block(ls_ref, ra_ref, ew_ref, loads_ref, acc_ref):
    """The shared per-step edge-slab accumulation (module docstring):
    acc(TN, K) += onehot_send @ (w * onehot_recv) on the MXU.  Both
    edge-block kernels (dissatisfaction and sweep election) run exactly
    this, so their carried aggregates are bitwise identical."""
    kpad = loads_ref.shape[-1]
    tn = acc_ref.shape[0]
    te = ls_ref.shape[-1]
    ls = ls_ref[0, 0, :]                                       # (TE,)
    ra = ra_ref[0, 0, :]                                       # (TE,)
    w = ew_ref[0, 0, :].astype(jnp.float32)                    # (TE,)
    send_oh = (jax.lax.broadcasted_iota(jnp.int32, (tn, te), 0)
               == ls[None, :]).astype(jnp.float32)             # (TN, TE)
    recv_oh = (ra[:, None]
               == jax.lax.broadcasted_iota(jnp.int32, (te, kpad), 1)
               ).astype(jnp.float32) * w[:, None]              # (TE, K)
    acc_ref[...] += jax.lax.dot(send_oh, recv_oh,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)


def _edge_dissat_kernel(ls_ref, ra_ref, ew_ref, r_rows_ref, b_rows_ref,
                        theta_rows_ref, loads_ref, speeds_ref, scalars_ref,
                        dissat_ref, best_ref, acc_ref, *, framework: str,
                        k_real: int, num_e: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_edge_block(ls_ref, ra_ref, ew_ref, loads_ref, acc_ref)

    @pl.when(e == num_e - 1)
    def _finish():
        dissat, best = reduce_dissat_tile(
            acc_ref[...], r_rows_ref[0, :], b_rows_ref[0, :],
            theta_rows_ref[0, :], loads_ref[0, :], speeds_ref[0, :],
            scalars_ref[0, 0], scalars_ref[0, 1],
            framework=framework, k_real=k_real)
        dissat_ref[0, :] = dissat
        best_ref[0, :] = best


def _edge_in_specs(tile_e: int, tile_n: int, k_pad: int):
    """The shared input BlockSpecs of both edge-block kernels: edge
    slabs stream (tile, edge-block)-wise, row operands per row tile,
    (K,) operands and scalars broadcast to every step."""
    return [
        pl.BlockSpec((1, 1, tile_e), lambda i, e: (i, 0, e)),  # local send
        pl.BlockSpec((1, 1, tile_e), lambda i, e: (i, 0, e)),  # recv assign
        pl.BlockSpec((1, 1, tile_e), lambda i, e: (i, 0, e)),  # edge weight
        pl.BlockSpec((1, tile_n), lambda i, e: (0, i)),    # r (rows)
        pl.BlockSpec((1, tile_n), lambda i, e: (0, i)),    # b (rows)
        pl.BlockSpec((1, tile_n), lambda i, e: (0, i)),    # theta (rows)
        pl.BlockSpec((1, k_pad), lambda i, e: (0, 0)),     # loads
        pl.BlockSpec((1, k_pad), lambda i, e: (0, 0)),     # speeds
        pl.BlockSpec((1, 2), lambda i, e: (0, 0)),         # mu, B
    ]


def dissatisfaction_from_edges_pallas(
        layout: EdgeTileLayout, assignment: Array, node_weights: Array,
        loads: Array, speeds: Array, mu, framework: str = "c", *,
        theta: Array | None = None, total_weight: Array | None = None,
        interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused Eq.-4 reduction straight from edge slabs (module docstring).

    ``assignment``/``node_weights``/``theta`` are full-graph (N,) arrays;
    the receiver-assignment gather happens here (one XLA gather), all
    remaining work inside the kernel.  Returns ``(dissat (N,), best (N,))``
    matching :func:`...dissatisfaction_from_aggregate_pallas` fed the
    segment-sum aggregate — same epilogue ops, so identical tie-breaks.
    """
    interpret = resolve_interpret(interpret)
    n = layout.num_nodes
    tile_n, tile_e = layout.tile_n, layout.tile_e
    num_tiles, _, eb = layout.local_senders.shape
    rows_pad = num_tiles * tile_n
    k = loads.shape[0]
    k_pad = -(-k // 128) * 128
    if total_weight is None:
        total_weight = jnp.sum(node_weights)

    recv_assign = jnp.take(jnp.asarray(assignment, jnp.int32),
                           layout.recv_index)                  # (T, 1, EB)
    r_rows, b, t, l_pad, w_pad, scalars = pad_dissat_operands(
        assignment, node_weights, theta, loads, speeds, mu, total_weight,
        n, rows_pad, k, k_pad)

    num_e = eb // tile_e
    dissat, best = pl.pallas_call(
        functools.partial(_edge_dissat_kernel, framework=framework,
                          k_real=k, num_e=num_e),
        grid=(num_tiles, num_e),
        in_specs=_edge_in_specs(tile_e, tile_n, k_pad),
        out_specs=[
            pl.BlockSpec((1, tile_n), lambda i, e: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i, e: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, rows_pad), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_n, k_pad), jnp.float32)],
        interpret=interpret,
    )(layout.local_senders, recv_assign, layout.edge_w, r_rows, b, t,
      l_pad, w_pad, scalars)
    return dissat[0, :n], best[0, :n]


def _edge_sweep_kernel(ls_ref, ra_ref, ew_ref, r_rows_ref, b_rows_ref,
                       theta_rows_ref, loads_ref, speeds_ref, scalars_ref,
                       gain_ref, node_ref, dest_ref, acc_ref, *,
                       framework: str, k_real: int, num_e: int, n_real: int):
    e = pl.program_id(1)
    row_base = pl.program_id(0) * acc_ref.shape[0]

    @pl.when(e == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate_edge_block(ls_ref, ra_ref, ew_ref, loads_ref, acc_ref)

    @pl.when(e == num_e - 1)
    def _finish():
        gain, node, dest = reduce_sweep_tile(
            acc_ref[...], r_rows_ref[0, :], b_rows_ref[0, :],
            theta_rows_ref[0, :], loads_ref[0, :], speeds_ref[0, :],
            scalars_ref[0, 0], scalars_ref[0, 1], row_base,
            framework=framework, k_real=k_real, n_real=n_real)
        gain_ref[0, 0, :] = gain
        node_ref[0, 0, :] = node
        dest_ref[0, 0, :] = dest


def sweep_candidates_from_edges_pallas(
        layout: EdgeTileLayout, assignment: Array, node_weights: Array,
        loads: Array, speeds: Array, mu, framework: str = "c", *,
        theta: Array | None = None, total_weight: Array | None = None,
        interpret: bool | None = None) -> tuple[Array, Array, Array]:
    """Fused per-machine sweep election straight from edge slabs
    (DESIGN.md §17.4): one pass over the edges per SWEEP, not per node.

    Same grid, operands and per-step accumulation as
    :func:`dissatisfaction_from_edges_pallas`; the last edge block runs
    :func:`~repro.kernels.dissatisfaction.reduce_sweep_tile` — which
    extends the shared ``reduce_dissat_tile`` epilogue — writing each
    row tile's (K,) election partials (best gain / winning node / its
    destination).  The (T, K) partials combine host-side by a
    first-maximum argmax over the tile axis: the lowest winning tile
    contains the globally lowest winning node index, so the combined
    election matches the jnp path's ``jnp.argmax`` tie-break
    (DESIGN.md §7) exactly.

    Returns ``(gains (K,), picks (K,), dests (K,))`` — the
    :class:`~repro.core.refine.SweepCandidateFn` payload.  Machines
    owning no node get gain ``-3e38`` (never above any threshold).
    """
    interpret = resolve_interpret(interpret)
    n = layout.num_nodes
    tile_n, tile_e = layout.tile_n, layout.tile_e
    num_tiles, _, eb = layout.local_senders.shape
    rows_pad = num_tiles * tile_n
    k = loads.shape[0]
    k_pad = -(-k // 128) * 128
    if total_weight is None:
        total_weight = jnp.sum(node_weights)

    recv_assign = jnp.take(jnp.asarray(assignment, jnp.int32),
                           layout.recv_index)                  # (T, 1, EB)
    r_rows, b, t, l_pad, w_pad, scalars = pad_dissat_operands(
        assignment, node_weights, theta, loads, speeds, mu, total_weight,
        n, rows_pad, k, k_pad)

    num_e = eb // tile_e
    gains_t, nodes_t, dests_t = pl.pallas_call(
        functools.partial(_edge_sweep_kernel, framework=framework,
                          k_real=k, num_e=num_e, n_real=n),
        grid=(num_tiles, num_e),
        in_specs=_edge_in_specs(tile_e, tile_n, k_pad),
        out_specs=[
            pl.BlockSpec((1, 1, k_pad), lambda i, e: (i, 0, 0)),
            pl.BlockSpec((1, 1, k_pad), lambda i, e: (i, 0, 0)),
            pl.BlockSpec((1, 1, k_pad), lambda i, e: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_tiles, 1, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((num_tiles, 1, k_pad), jnp.int32),
            jax.ShapeDtypeStruct((num_tiles, 1, k_pad), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((tile_n, k_pad), jnp.float32)],
        interpret=interpret,
    )(layout.local_senders, recv_assign, layout.edge_w, r_rows, b, t,
      l_pad, w_pad, scalars)
    # host combine: first-maximum over tiles = globally lowest node index
    g = gains_t[:, 0, :k]                                      # (T, K)
    win_tile = jnp.argmax(g, axis=0)
    karange = jnp.arange(k)
    return (jnp.max(g, axis=0), nodes_t[win_tile, 0, karange],
            dests_t[win_tile, 0, karange])
