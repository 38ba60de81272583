"""Fused Pallas TPU kernels for the refinement hot spot (DESIGN.md §3.2, §10).

Three kernels:

* :func:`cost_matrix_pallas` — the recompute path.  Every from-scratch
  cost evaluation needs the aggregate  A[i, k] = sum_j c_ij * 1[r_j = k]
  — an (N x N) @ (N x K) matmul.  Computing A with jnp and then assembling
  costs reads the (N, K) intermediates from HBM several times; this kernel
  tiles the adjacency into VMEM blocks, accumulates A on the MXU, and
  fuses the entire cost assembly (load term + cut term for either
  framework) into the final grid step, so the adjacency is read exactly
  once and nothing but the (N, K) cost matrix is written back.

  Grid: (N/TN, N/TJ), j innermost.  Per (i, j) step:
    * build the one-hot of the column block's assignments (TJ, K) in VREGs,
    * acc(TN, K) += C_block(TN, TJ) @ onehot  (MXU),
    * at j == last: assemble the cost block and write it out.

* :func:`dissatisfaction_from_aggregate_pallas` — the incremental path
  (DESIGN.md §10).  The refinement loop already carries A, so no matmul is
  needed at all: this kernel reads the (N, K) aggregate once, assembles
  the cost block in VREGs, and reduces it to the Eq.-4 dissatisfaction and
  arg-best machine in the same grid step — the (N, K) cost matrix never
  touches HBM.  Per-turn kernel traffic is O(NK) in, O(N) out.

* :func:`dissatisfaction_from_aggregate_batched_pallas` — the same fused
  reduction over a STACK of B independent problems (DESIGN.md §12.3).
  The grid grows a leading batch dimension, ``grid=(B, rows/TN)``, and
  every operand's BlockSpec indexes its element's slab, so one
  ``pallas_call`` serves a whole scenario fleet while each (b, i) step
  runs the identical op sequence on the identical tile the unbatched
  kernel would see — per-element outputs are bitwise those of B separate
  unbatched calls.  ``repro.kernels.ops`` routes ``jax.vmap`` of the
  unbatched entry point here via ``jax.custom_batching.custom_vmap``, so
  the batched sweep runtime (:mod:`repro.sweeps`) keeps the hot path
  fused instead of falling back to an unrolled per-element kernel.

All tile dims are multiples of the 128-lane MXU width; K is padded to 128
lanes by the wrappers.

``interpret`` defaults to backend auto-detection (:func:`resolve_interpret`):
compiled on a TPU backend, interpret mode everywhere else; an explicit
bool overrides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_TILE_N = 128
DEFAULT_TILE_J = 128

_BIG = 3.0e38   # finite "+inf" for masked K lanes (0*inf = nan)


def resolve_interpret(interpret: bool | None) -> bool:
    """Backend auto-detection for the ``interpret`` flag: explicit values
    win; otherwise compiled on a TPU backend and interpret mode
    everywhere else."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _kernel(c_ref, r_cols_ref, r_rows_ref, b_rows_ref, loads_ref, speeds_ref,
            scalars_ref, out_ref, acc_ref, *, framework: str, num_j: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kpad = loads_ref.shape[-1]
    r_cols = r_cols_ref[0, :]                                  # (TJ,) int32
    onehot = (r_cols[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (1, kpad), 1)
              ).astype(jnp.float32)                            # (TJ, K)
    acc_ref[...] += jax.lax.dot(
        c_ref[...].astype(jnp.float32), onehot,
        precision=jax.lax.Precision.HIGHEST,      # edge weights stay f32
        preferred_element_type=jnp.float32)

    @pl.when(j == num_j - 1)
    def _finish():
        aggregate = acc_ref[...]                               # (TN, K)
        mu = scalars_ref[0, 0]
        total_b = scalars_ref[0, 1]
        b = b_rows_ref[0, :].astype(jnp.float32)[:, None]      # (TN, 1)
        r_rows = r_rows_ref[0, :]                              # (TN,)
        own = (r_rows[:, None]
               == jax.lax.broadcasted_iota(jnp.int32, (1, kpad), 1)
               ).astype(jnp.float32)
        loads = loads_ref[0, :][None, :]                       # (1, K)
        inv_w = 1.0 / speeds_ref[0, :][None, :]
        degree = jnp.sum(aggregate, axis=-1, keepdims=True)
        others = loads - b * own
        cut_term = 0.5 * mu * (degree - aggregate)
        if framework == "c":
            cost = (b * inv_w) * others + cut_term
        else:
            cost = (b * b) * inv_w * inv_w \
                + 2.0 * b * inv_w * inv_w * others \
                - 2.0 * b * inv_w * total_b + cut_term
        out_ref[...] = cost


def cost_matrix_pallas(adjacency: Array, assignment: Array, node_weights: Array,
                       loads: Array, speeds: Array, mu,
                       framework: str = "c", *,
                       tile_n: int = DEFAULT_TILE_N,
                       tile_j: int = DEFAULT_TILE_J,
                       interpret: bool | None = None,
                       row_assignment: Array | None = None,
                       total_weight: Array | None = None) -> Array:
    """Padded + tiled pallas_call; returns the (rows, K) cost matrix.

    ``adjacency`` may be rectangular: a ``(rows, N)`` row block of a larger
    graph, as produced by :mod:`repro.distributed.views` — the grid tiles
    rows and columns independently and the contraction runs over the full
    column extent, so each machine of the distributed runtime can drive
    this same kernel on nothing but its shard.  In the row-block case pass
    ``row_assignment`` (length ``rows``, the block nodes' own machines;
    ``assignment`` then covers the N *columns*), ``node_weights`` of length
    ``rows``, and ``total_weight`` = the global sum of b (the Ct framework
    needs B, which a row block cannot compute locally).  Square callers
    keep the original signature: both default to ``assignment`` /
    ``sum(node_weights)``.

    ``interpret=None`` auto-detects (interpret mode unless the backend is
    a real TPU — see :func:`resolve_interpret`); pass an explicit bool to
    override.
    """
    interpret = resolve_interpret(interpret)
    n_rows, n_cols = adjacency.shape
    k = loads.shape[0]
    if row_assignment is None:
        row_assignment = assignment
    if total_weight is None:
        total_weight = jnp.sum(node_weights)
    rows_pad = -(-n_rows // tile_n) * tile_n
    cols_pad = -(-n_cols // tile_j) * tile_j
    k_pad = -(-k // 128) * 128

    c = jnp.zeros((rows_pad, cols_pad), adjacency.dtype)
    c = c.at[:n_rows, :n_cols].set(adjacency)
    # padded rows/columns point at a padded machine so they never pollute
    # real K (and padded rows carry zero weight)
    r_cols = jnp.full((1, cols_pad), k_pad - 1, jnp.int32).at[0, :n_cols].set(
        jnp.asarray(assignment, jnp.int32))
    r_rows = jnp.full((1, rows_pad), k_pad - 1, jnp.int32).at[0, :n_rows].set(
        jnp.asarray(row_assignment, jnp.int32))
    b = jnp.zeros((1, rows_pad), jnp.float32).at[0, :n_rows].set(
        node_weights.astype(jnp.float32))
    l_pad = jnp.zeros((1, k_pad), jnp.float32).at[0, :k].set(
        loads.astype(jnp.float32))
    w_pad = jnp.ones((1, k_pad), jnp.float32).at[0, :k].set(
        speeds.astype(jnp.float32))
    scalars = jnp.stack([jnp.asarray(mu, jnp.float32),
                         jnp.asarray(total_weight, jnp.float32)])[None, :]

    num_i = rows_pad // tile_n
    num_j = cols_pad // tile_j
    out = pl.pallas_call(
        functools.partial(_kernel, framework=framework, num_j=num_j),
        grid=(num_i, num_j),
        in_specs=[
            pl.BlockSpec((tile_n, tile_j), lambda i, j: (i, j)),   # adjacency
            pl.BlockSpec((1, tile_j), lambda i, j: (0, j)),        # r (cols)
            pl.BlockSpec((1, tile_n), lambda i, j: (0, i)),        # r (rows)
            pl.BlockSpec((1, tile_n), lambda i, j: (0, i)),        # b (rows)
            pl.BlockSpec((1, k_pad), lambda i, j: (0, 0)),         # loads
            pl.BlockSpec((1, k_pad), lambda i, j: (0, 0)),         # speeds
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),             # mu, B
        ],
        out_specs=pl.BlockSpec((tile_n, k_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, k_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_n, k_pad), jnp.float32)],
        interpret=interpret,
    )(c, r_cols, r_rows, b, l_pad, w_pad, scalars)
    return out[:n_rows, :k]


# ---------------------------------------------------------------------------
# incremental path: (dissat, best) straight from the carried aggregate
# ---------------------------------------------------------------------------

def reduce_dissat_tile(aggregate, r_rows, b_rows, theta_rows, loads_row,
                       speeds_row, mu, total_b, *, framework: str,
                       k_real: int):
    """THE fused cost-assembly + Eq.-4 reduction over one (TN, K) tile,
    shared (same ops, same order — the bitwise contract) by every kernel
    that ends in a dissatisfaction reduction: the aggregate kernels here
    and the edge-block kernel of :mod:`repro.kernels.edge_block`.

    Returns ``(dissat (TN,), best (TN,))``: net-of-theta dissatisfaction
    (DESIGN.md §11) and the lowest-index arg-best machine (§7).
    """
    tn, kpad = aggregate.shape
    b = b_rows.astype(jnp.float32)[:, None]                    # (TN, 1)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (tn, kpad), 1)
    own = (r_rows[:, None] == kidx).astype(jnp.float32)
    loads = loads_row[None, :]                                 # (1, K)
    inv_w = 1.0 / speeds_row[None, :]
    degree = jnp.sum(aggregate, axis=-1, keepdims=True)
    others = loads - b * own
    cut_term = 0.5 * mu * (degree - aggregate)
    if framework == "c":
        cost = (b * inv_w) * others + cut_term
    else:
        cost = (b * b) * inv_w * inv_w \
            + 2.0 * b * inv_w * inv_w * others \
            - 2.0 * b * inv_w * total_b + cut_term
    # Padded K lanes must not win the min; keep them finite (0 * inf = nan).
    cost = jnp.where(kidx < k_real, cost, _BIG)
    best_val = jnp.min(cost, axis=1)
    # lowest-index argmin (DESIGN.md §7) via the iota-min trick
    best_idx = jnp.min(jnp.where(cost <= best_val[:, None], kidx, kpad),
                       axis=1).astype(jnp.int32)
    current = jnp.sum(jnp.where(own > 0, cost, 0.0), axis=1)
    # net-of-migration-price Eq. 4 (DESIGN.md §11); theta rows default to 0
    return current - best_val - theta_rows, best_idx


def reduce_sweep_tile(aggregate, r_rows, b_rows, theta_rows, loads_row,
                      speeds_row, mu, total_b, row_base, *, framework: str,
                      k_real: int, n_real: int):
    """The per-MACHINE sweep election over one (TN, K) tile (DESIGN.md
    §17.4) — EXTENDS :func:`reduce_dissat_tile` (calls it first, so the
    per-node ``(dissat, best)`` semantics and tie-breaks stay in the one
    shared place) and then reduces the tile to each machine's election
    partials:

      * ``tile_gain (K,)`` — max net dissatisfaction among the tile's
        rows owned by machine k (``-_BIG`` when it owns none here);
      * ``tile_node (K,)`` — the GLOBAL id of that row (lowest row on
        ties — the same first-maximum tie-break ``jnp.argmax`` realizes
        on the jnp election path, via the iota-min trick);
      * ``tile_dest (K,)`` — that row's lowest-index arg-best machine.

    ``row_base`` is the tile's global row offset; rows at or beyond
    ``n_real`` (padding) are masked out of every election.  The host
    combine (argmax over the tile axis — first maximum = lowest tile,
    hence globally lowest node index) finishes the election.
    """
    dissat, best = reduce_dissat_tile(
        aggregate, r_rows, b_rows, theta_rows, loads_row, speeds_row, mu,
        total_b, framework=framework, k_real=k_real)
    tn, kpad = aggregate.shape
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (tn, kpad), 0)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (tn, kpad), 1)
    valid = (row_base + row_iota) < n_real
    own = (r_rows[:, None] == kidx) & valid
    masked = jnp.where(own, dissat[:, None], -_BIG)            # (TN, K)
    tile_gain = jnp.max(masked, axis=0)
    # lowest winning row per machine (first-maximum tie-break)
    win = masked >= tile_gain[None, :]
    tile_row = jnp.min(jnp.where(win, row_iota, tn), axis=0)
    tile_node = (row_base + tile_row).astype(jnp.int32)
    # gather the winning row's best machine, again via the min trick
    tile_dest = jnp.min(jnp.where(row_iota == tile_row[None, :],
                                  best[:, None], jnp.int32(2**31 - 1)),
                        axis=0).astype(jnp.int32)
    return tile_gain, tile_node, tile_dest


def _dissat_kernel(agg_ref, r_rows_ref, b_rows_ref, theta_rows_ref,
                   loads_ref, speeds_ref, scalars_ref, dissat_ref, best_ref,
                   *, framework: str, k_real: int):
    dissat, best = reduce_dissat_tile(
        agg_ref[...].astype(jnp.float32), r_rows_ref[0, :],
        b_rows_ref[0, :], theta_rows_ref[0, :], loads_ref[0, :],
        speeds_ref[0, :], scalars_ref[0, 0], scalars_ref[0, 1],
        framework=framework, k_real=k_real)
    dissat_ref[0, :] = dissat
    best_ref[0, :] = best


def pad_dissat_operands(row_assignment, node_weights, theta, loads, speeds,
                        mu, total_weight, n_rows: int, rows_pad: int,
                        k: int, k_pad: int):
    """Shared operand padding for every dissatisfaction wrapper (the
    aggregate kernels here and :mod:`repro.kernels.edge_block`) — the
    conventions are load-bearing and must never desync: padded rows
    point at padded machine ``k_pad - 1`` with zero weight/theta (their
    outputs are sliced off), padded speeds are 1.0 (no div-by-zero),
    ``theta=None`` rides an exact zero operand.  Returns
    ``(r_rows, b, theta, loads, speeds, scalars)`` in kernel layout."""
    r_rows = jnp.full((1, rows_pad), k_pad - 1, jnp.int32).at[0, :n_rows].set(
        jnp.asarray(row_assignment, jnp.int32))
    b = jnp.zeros((1, rows_pad), jnp.float32).at[0, :n_rows].set(
        node_weights.astype(jnp.float32))
    t = jnp.zeros((1, rows_pad), jnp.float32)
    if theta is not None:
        t = t.at[0, :n_rows].set(
            jnp.broadcast_to(jnp.asarray(theta, jnp.float32), (n_rows,)))
    l_pad = jnp.zeros((1, k_pad), jnp.float32).at[0, :k].set(
        loads.astype(jnp.float32))
    w_pad = jnp.ones((1, k_pad), jnp.float32).at[0, :k].set(
        speeds.astype(jnp.float32))
    scalars = jnp.stack([jnp.asarray(mu, jnp.float32),
                         jnp.asarray(total_weight, jnp.float32)])[None, :]
    return r_rows, b, t, l_pad, w_pad, scalars


def dissatisfaction_from_aggregate_pallas(
        aggregate: Array, row_assignment: Array, node_weights: Array,
        loads: Array, speeds: Array, mu, framework: str = "c", *,
        theta: Array | None = None, total_weight: Array | None = None,
        tile_n: int = DEFAULT_TILE_N,
        interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused Eq.-4 reduction over an already-built (rows, K) aggregate.

    Returns ``(dissat (rows,), best_machine (rows,))`` without ever
    materializing the (rows, K) cost matrix in HBM: each grid step reads
    one aggregate tile, assembles its cost block in VREGs, and reduces to
    the dissatisfaction + lowest-index arg-best machine in place.  This is
    the per-turn kernel of the incremental refinement path (the aggregate
    itself is maintained by rank-1 carry updates, DESIGN.md §10); row
    blocks of the distributed runtime drive it the same way (pass the
    shard's ``row_assignment`` / ``node_weights`` slices and the global
    ``total_weight``).

    ``theta`` is the optional (rows,) per-node migration-price threshold
    (DESIGN.md §11): the returned dissatisfaction is net of it (subtracted
    in the same fused reduction — still one aggregate read, O(rows) out).
    ``None`` rides a zero operand through the same subtraction, which is
    exact for the finite Eq.-4 values.
    """
    interpret = resolve_interpret(interpret)
    n_rows, k = aggregate.shape
    assert loads.shape[0] == k, (aggregate.shape, loads.shape)
    if total_weight is None:
        total_weight = jnp.sum(node_weights)
    rows_pad = -(-n_rows // tile_n) * tile_n
    k_pad = -(-k // 128) * 128

    a = jnp.zeros((rows_pad, k_pad), jnp.float32)
    a = a.at[:n_rows, :k].set(aggregate.astype(jnp.float32))
    r_rows, b, t, l_pad, w_pad, scalars = pad_dissat_operands(
        row_assignment, node_weights, theta, loads, speeds, mu,
        total_weight, n_rows, rows_pad, k, k_pad)

    num_i = rows_pad // tile_n
    dissat, best = pl.pallas_call(
        functools.partial(_dissat_kernel, framework=framework, k_real=k),
        grid=(num_i,),
        in_specs=[
            pl.BlockSpec((tile_n, k_pad), lambda i: (i, 0)),   # aggregate
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),       # r (rows)
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),       # b (rows)
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),       # theta (rows)
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),        # loads
            pl.BlockSpec((1, k_pad), lambda i: (0, 0)),        # speeds
            pl.BlockSpec((1, 2), lambda i: (0, 0)),            # mu, B
        ],
        out_specs=[
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
            pl.BlockSpec((1, tile_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, rows_pad), jnp.int32),
        ],
        interpret=interpret,
    )(a, r_rows, b, t, l_pad, w_pad, scalars)
    return dissat[0, :n_rows], best[0, :n_rows]


# ---------------------------------------------------------------------------
# batch-grid variant: one fused call over a stack of B problems (§12.3)
# ---------------------------------------------------------------------------

def _dissat_kernel_batched(agg_ref, r_rows_ref, b_rows_ref, theta_rows_ref,
                           loads_ref, speeds_ref, scalars_ref, dissat_ref,
                           best_ref, *, framework: str, k_real: int):
    """Per-(b, i) grid step — the *identical* op sequence of
    :func:`_dissat_kernel` on batch element b's row tile i (the leading
    block axes are size-1 slabs), so per-element outputs are bitwise
    those of the unbatched kernel."""
    kpad = loads_ref.shape[-1]
    tn = agg_ref.shape[1]
    aggregate = agg_ref[0].astype(jnp.float32)                 # (TN, K)
    mu = scalars_ref[0, 0, 0]
    total_b = scalars_ref[0, 0, 1]
    b = b_rows_ref[0, 0, :].astype(jnp.float32)[:, None]       # (TN, 1)
    r_rows = r_rows_ref[0, 0, :]                               # (TN,)
    kidx = jax.lax.broadcasted_iota(jnp.int32, (tn, kpad), 1)
    own = (r_rows[:, None] == kidx).astype(jnp.float32)
    loads = loads_ref[0, 0, :][None, :]                        # (1, K)
    inv_w = 1.0 / speeds_ref[0, 0, :][None, :]
    degree = jnp.sum(aggregate, axis=-1, keepdims=True)
    others = loads - b * own
    cut_term = 0.5 * mu * (degree - aggregate)
    if framework == "c":
        cost = (b * inv_w) * others + cut_term
    else:
        cost = (b * b) * inv_w * inv_w \
            + 2.0 * b * inv_w * inv_w * others \
            - 2.0 * b * inv_w * total_b + cut_term
    cost = jnp.where(kidx < k_real, cost, _BIG)
    best_val = jnp.min(cost, axis=1)
    best_idx = jnp.min(jnp.where(cost <= best_val[:, None], kidx, kpad),
                       axis=1).astype(jnp.int32)
    current = jnp.sum(jnp.where(own > 0, cost, 0.0), axis=1)
    dissat_ref[0, 0, :] = current - best_val - theta_rows_ref[0, 0, :]
    best_ref[0, 0, :] = best_idx


def dissatisfaction_from_aggregate_batched_pallas(
        aggregate: Array, row_assignment: Array, node_weights: Array,
        loads: Array, speeds: Array, mu: Array, framework: str = "c", *,
        theta: Array | None = None, total_weight: Array | None = None,
        tile_n: int = DEFAULT_TILE_N,
        interpret: bool | None = None) -> tuple[Array, Array]:
    """Fused Eq.-4 reduction over a (B, rows, K) aggregate stack.

    The batch-grid layout of DESIGN.md §12.3: ``grid=(B, rows/TN)`` with
    row tiles innermost; every operand gains a leading batch axis whose
    BlockSpec picks element b's slab, so the one kernel invocation stays
    a single fused program over the whole fleet.  Batched operands:
    ``aggregate (B, rows, K)``, ``row_assignment``/``node_weights``/
    optional ``theta`` ``(B, rows)``, ``loads``/``speeds`` ``(B, K)``,
    ``mu``/optional ``total_weight`` ``(B,)``.  Returns
    ``(dissat (B, rows), best_machine (B, rows))``, per element bitwise
    equal to :func:`dissatisfaction_from_aggregate_pallas` on that
    element's operands.  Reached automatically by ``jax.vmap`` of the
    :mod:`repro.kernels.ops` wrapper (``custom_vmap`` routes here), which
    is how the batched sweep runtime keeps the refinement hot path fused.
    """
    interpret = resolve_interpret(interpret)
    bsz, n_rows, k = aggregate.shape
    assert loads.shape == (bsz, k), (aggregate.shape, loads.shape)
    if total_weight is None:
        total_weight = jnp.sum(node_weights, axis=-1)
    rows_pad = -(-n_rows // tile_n) * tile_n
    k_pad = -(-k // 128) * 128

    a = jnp.zeros((bsz, rows_pad, k_pad), jnp.float32)
    a = a.at[:, :n_rows, :k].set(aggregate.astype(jnp.float32))
    # padded rows point at a padded machine with zero weight (as in the
    # unbatched wrapper); their outputs are sliced off below
    r_rows = jnp.full((bsz, 1, rows_pad), k_pad - 1, jnp.int32)
    r_rows = r_rows.at[:, 0, :n_rows].set(
        jnp.asarray(row_assignment, jnp.int32))
    b = jnp.zeros((bsz, 1, rows_pad), jnp.float32).at[:, 0, :n_rows].set(
        node_weights.astype(jnp.float32))
    t = jnp.zeros((bsz, 1, rows_pad), jnp.float32)
    if theta is not None:
        t = t.at[:, 0, :n_rows].set(
            jnp.broadcast_to(jnp.asarray(theta, jnp.float32),
                             (bsz, n_rows)))
    l_pad = jnp.zeros((bsz, 1, k_pad), jnp.float32).at[:, 0, :k].set(
        loads.astype(jnp.float32))
    w_pad = jnp.ones((bsz, 1, k_pad), jnp.float32).at[:, 0, :k].set(
        speeds.astype(jnp.float32))
    scalars = jnp.stack(
        [jnp.broadcast_to(jnp.asarray(mu, jnp.float32), (bsz,)),
         jnp.broadcast_to(jnp.asarray(total_weight, jnp.float32), (bsz,))],
        axis=-1)[:, None, :]                                   # (B, 1, 2)

    num_i = rows_pad // tile_n
    dissat, best = pl.pallas_call(
        functools.partial(_dissat_kernel_batched, framework=framework,
                          k_real=k),
        grid=(bsz, num_i),
        in_specs=[
            pl.BlockSpec((1, tile_n, k_pad), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, tile_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, tile_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, tile_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, k_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, k_pad), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, 2), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tile_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, tile_n), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1, rows_pad), jnp.float32),
            jax.ShapeDtypeStruct((bsz, 1, rows_pad), jnp.int32),
        ],
        interpret=interpret,
    )(a, r_rows, b, t, l_pad, w_pad, scalars)
    return dissat[:, 0, :n_rows], best[:, 0, :n_rows]
