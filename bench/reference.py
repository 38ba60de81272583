"""Float64 host reference of the partition game (NumPy only).

The yardstick that decides ``correct``.  It imports nothing of the
program: the aggregate is an ``np.bincount`` over the benchmark's own
edge list, the costs are Eq. 1 of arXiv:1111.0875 in float64, and the
acceptance arithmetic a converged refinement is held to is copied here
(``ACCEPT_TOL``, ``ROUNDOFF_ULPS`` and :func:`acceptance_threshold`), so
a later change to the program's threshold cannot move the check with
it.

A node is at equilibrium when its float64 best-response gain is at most
twice its float32 acceptance threshold less the floor (the threshold
once more for the round-off of the f32 gain the program compared), plus
``epsilon·|C_0|/N`` for the ε-stop of ``refine_sweeps``.  The same
allowance as the program's own float64 check.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .instances import Instance

ACCEPT_TOL = 1e-6       # the absolute floor of the acceptance test
ROUNDOFF_ULPS = 16      # ulps of the largest compared cost term


@dataclasses.dataclass
class Graph:
    """An instance's directed edges, held once for every check."""
    inst: Instance
    senders: np.ndarray
    receivers: np.ndarray
    weights: np.ndarray     # float64
    degree: np.ndarray      # (N,) weighted degree, float64

    @classmethod
    def of(cls, inst: Instance) -> "Graph":
        s, r, w = inst.directed()
        w = w.astype(np.float64)
        return cls(inst, s, r, w,
                   np.bincount(s, w, minlength=inst.num_nodes))


def aggregate(g: Graph, assignment: np.ndarray) -> np.ndarray:
    """(N, K) float64 ``A[i, k] = sum_j c_ij [r_j = k]``."""
    n, k = g.inst.num_nodes, g.inst.num_machines
    flat = np.bincount(g.senders * k + assignment[g.receivers], g.weights,
                       minlength=n * k)
    return flat.reshape(n, k)


def costs(g: Graph, assignment: np.ndarray, speeds: np.ndarray,
          agg: np.ndarray | None = None) -> np.ndarray:
    """(N, K) float64 Eq.-1 costs: entry [i, k] is node i's cost were it
    on machine k, every other node held fixed."""
    r = assignment
    n, k = g.inst.num_nodes, g.inst.num_machines
    if agg is None:
        agg = aggregate(g, r)
    b = g.inst.node_weights.astype(np.float64)
    loads = np.bincount(r, b, minlength=k)
    own = np.zeros((n, k), bool)
    own[np.arange(n), r] = True
    others = loads[None, :] - np.where(own, b[:, None], 0.0)
    cut = 0.5 * g.inst.mu * (g.degree[:, None] - agg)
    return b[:, None] / speeds[None, :] * others + cut


def potential(g: Graph, assignment: np.ndarray, speeds: np.ndarray) -> float:
    """C_0, the Thm. 3.1 potential: the sum of every node's own cost."""
    c = costs(g, assignment, speeds)
    return float(c[np.arange(assignment.size), assignment].sum())


def acceptance_threshold(b, source, dest, loads, speeds, cut_scale,
                         tol: float = ACCEPT_TOL):
    """The program's float32 acceptance threshold of moving weight ``b``
    from ``source`` to ``dest``: ``tol`` plus ROUNDOFF_ULPS ulps of the
    larger of the two costs' largest terms."""
    f32 = np.float32
    b = np.asarray(b, f32)
    y = np.asarray(loads, f32) / np.asarray(speeds, f32)
    scale = np.maximum(b * y[source], b * y[dest]) + f32(cut_scale)
    return f32(tol) + f32(ROUNDOFF_ULPS) * np.spacing(scale.astype(f32))


@dataclasses.dataclass
class Verdict:
    """What the reference finds in one rebalance's result."""
    equilibrium_ratio: float  # max over nodes of gain / allowance (<= 1 ok)
    c0: float                 # float64 C_0 of the result
    loads: np.ndarray         # (K,) float64 loads of the result
    dissatisfied: int         # nodes whose gain exceeds their allowance


def check(g: Graph, assignment: np.ndarray, speeds: np.ndarray, *,
          epsilon: float = 0.0, tol: float = ACCEPT_TOL) -> Verdict:
    """Hold ``assignment`` to the (ε-)equilibrium under ``speeds``."""
    r = np.asarray(assignment, np.int64)
    n, k = g.inst.num_nodes, g.inst.num_machines
    speeds = np.asarray(speeds, np.float64)
    agg = aggregate(g, r)
    cost = costs(g, r, speeds, agg)
    rows = np.arange(n)
    best = np.argmin(cost, axis=1)
    gain = cost[rows, r] - cost[rows, best]
    b = g.inst.node_weights.astype(np.float64)
    loads = np.bincount(r, b, minlength=k)
    cut_scale = np.float32(0.5 * g.inst.mu * g.degree.max())
    thresh = acceptance_threshold(
        g.inst.node_weights, r, best,
        np.bincount(r, b, minlength=k).astype(np.float32),
        speeds.astype(np.float32), cut_scale, tol).astype(np.float64)
    c0 = float(cost[rows, r].sum())
    allowed = 2.0 * thresh - tol + epsilon * abs(c0) / n
    ratio = gain / allowed
    return Verdict(equilibrium_ratio=float(ratio.max()), c0=c0, loads=loads,
                   dissatisfied=int((ratio > 1.0).sum()))
