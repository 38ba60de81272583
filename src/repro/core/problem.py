"""Partition-game problem container.

The paper partitions an undirected weighted graph G = (V, E) of logical
processes among K machines.  ``PartitionProblem`` carries everything the
two cost frameworks (Eq. 1 and Eq. 6) need:

  * ``adjacency``  — dense symmetric (N, N) float matrix of edge weights
                     ``c_ij`` (zero diagonal).  Dense is the TPU-native
                     representation: the refinement hot spot is
                     ``adjacency @ one_hot(r)`` which maps onto the MXU.
  * ``node_weights`` — (N,) computational load ``b_i`` per LP.
  * ``speeds``       — (K,) normalized machine capacities ``w_k`` (sum 1).
  * ``mu``           — relative weight of the inter-machine potential
                       rollback-delay cost (paper §3.1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


class ProblemValidationError(ValueError):
    """Typed error for malformed problem inputs (DESIGN.md §15.7).

    Raised by the ``validate()`` methods and
    :func:`validate_assignment` instead of letting bad inputs fail deep
    inside jit as shape errors or NaN-poisoned results.  Value checks
    (NaN, negativity, symmetry, range) run only on concrete arrays —
    under a trace only the shape checks apply."""


def _is_concrete(*arrays) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionProblem:
    adjacency: Array      # (N, N) float, symmetric, zero diagonal
    node_weights: Array   # (N,)  float
    speeds: Array         # (K,)  float, sums to 1
    mu: Array             # scalar float

    @property
    def num_nodes(self) -> int:
        return self.adjacency.shape[0]

    @property
    def num_machines(self) -> int:
        return self.speeds.shape[0]

    def validate(self) -> None:
        """Raise :class:`ProblemValidationError` on malformed fields."""
        import numpy as np
        n = self.num_nodes
        if self.adjacency.ndim != 2 \
                or self.adjacency.shape != (n, n):
            raise ProblemValidationError(
                f"adjacency must be square (N, N); got "
                f"{self.adjacency.shape}")
        if self.node_weights.shape != (n,):
            raise ProblemValidationError(
                f"node_weights shape {self.node_weights.shape} does not "
                f"match N={n}")
        if self.speeds.ndim != 1:
            raise ProblemValidationError(
                f"speeds must be (K,); got shape {self.speeds.shape}")
        if not _is_concrete(self.adjacency, self.node_weights, self.speeds):
            return
        adj = np.asarray(self.adjacency)
        if np.isnan(adj).any():
            raise ProblemValidationError("adjacency contains NaN edge "
                                         "weights")
        if (adj < 0).any():
            raise ProblemValidationError("adjacency contains negative edge "
                                         "weights")
        if not np.array_equal(adj, adj.T):
            raise ProblemValidationError("adjacency is not symmetric (the "
                                         "graph is undirected; use "
                                         "make_problem to symmetrize)")
        b = np.asarray(self.node_weights)
        if np.isnan(b).any() or (b < 0).any():
            raise ProblemValidationError("node_weights must be finite and "
                                         "non-negative")
        w = np.asarray(self.speeds)
        if np.isnan(w).any() or (w <= 0).any():
            raise ProblemValidationError("speeds must be finite and "
                                         "positive")


def make_problem(
    adjacency,
    node_weights,
    speeds,
    mu: float = 8.0,
    *,
    normalize_speeds: bool = True,
    dtype=jnp.float32,
) -> PartitionProblem:
    """Build a :class:`PartitionProblem`, symmetrizing and normalizing inputs."""
    adjacency = jnp.asarray(adjacency, dtype)
    # Symmetrize and clear the diagonal: the paper's graph is undirected and
    # self-edges are meaningless for a cut.
    adjacency = 0.5 * (adjacency + adjacency.T)
    adjacency = adjacency * (1.0 - jnp.eye(adjacency.shape[0], dtype=dtype))
    node_weights = jnp.asarray(node_weights, dtype)
    speeds = jnp.asarray(speeds, dtype)
    if normalize_speeds:
        speeds = speeds / jnp.sum(speeds)
    prob = PartitionProblem(adjacency, node_weights, speeds, jnp.asarray(mu, dtype))
    prob.validate()
    return prob


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PartitionState:
    """Assignment vector plus the machine-level aggregate the paper exchanges.

    ``loads`` is the only *global* state a machine needs (paper §4.5): the
    per-machine sums ``L_k = sum_{j: r_j = k} b_j``.  Keeping it in the state
    (instead of recomputing) mirrors the paper's ``common variable array``.
    """
    assignment: Array  # (N,) int32 in [0, K)
    loads: Array       # (K,) float

    @property
    def num_machines(self) -> int:
        return self.loads.shape[0]


def validate_assignment(assignment, num_machines: int,
                        num_nodes: int | None = None) -> None:
    """Raise :class:`ProblemValidationError` on a malformed assignment
    vector: wrong dtype/shape, or (concrete arrays only) machine ids
    outside ``[0, num_machines)``."""
    import numpy as np
    if getattr(assignment, "ndim", None) != 1:
        raise ProblemValidationError(
            f"assignment must be a 1-D vector; got "
            f"{getattr(assignment, 'shape', type(assignment))}")
    if not jnp.issubdtype(assignment.dtype, jnp.integer):
        raise ProblemValidationError(
            f"assignment must be integer-typed; got {assignment.dtype}")
    if num_nodes is not None and assignment.shape[0] != num_nodes:
        raise ProblemValidationError(
            f"assignment has {assignment.shape[0]} entries for "
            f"{num_nodes} nodes")
    if not _is_concrete(assignment):
        return
    r = np.asarray(assignment)
    if r.size and (r.min() < 0 or r.max() >= num_machines):
        raise ProblemValidationError(
            f"assignment entries must lie in [0, {num_machines}); got "
            f"range [{r.min()}, {r.max()}]")


def machine_loads(node_weights: Array, assignment: Array, num_machines: int) -> Array:
    """L_k = sum of b_j over nodes assigned to machine k.

    One masked reduction over the nodes, not a scatter-add: a scatter
    into K slots adds its N terms one after another, and at a million
    LPs its float32 error reached tens of load units (on a TPU v5e),
    enough to let a converged placement miss its ε-equilibrium in
    float64.  The reduction sums in a tree."""
    owned = assignment[:, None] == jnp.arange(num_machines)
    return jnp.sum(jnp.where(owned, node_weights[:, None], 0), axis=0)


def make_state(problem: PartitionProblem, assignment) -> PartitionState:
    assignment = jnp.asarray(assignment, jnp.int32)
    loads = machine_loads(problem.node_weights, assignment, problem.num_machines)
    return PartitionState(assignment=assignment, loads=loads)
