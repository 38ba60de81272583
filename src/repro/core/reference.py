"""Float64 host reference for checking a refinement result (NumPy only).

Independent of the device code it checks: the aggregate is a float64
matmul (dense) or an ``np.bincount`` segment sum (sparse), and the costs
are Eq. 1 / Eq. 6 evaluated in float64.  :func:`check_equilibrium` then
shows that no node has a best-response gain above its acceptance
threshold — the fixed point ``refine`` claims when it reports
``converged``.  ``chip_smoke.py`` and the regression tests share it.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax.numpy as jnp

from .costs import C_FRAMEWORK
from .refine import DEFAULT_TOL, Acceptance, acceptance_threshold
from .sparse import SparseProblem


def host_aggregate(problem, assignment) -> np.ndarray:
    """(N, K) float64 aggregate ``A[i, k] = sum_j c_ij [r_j = k]``."""
    r = np.asarray(assignment, np.int64)
    k = problem.num_machines
    n = r.shape[0]
    if isinstance(problem, SparseProblem):
        slot = (np.asarray(problem.senders, np.int64) * k
                + r[np.asarray(problem.receivers)])
        flat = np.bincount(slot, np.asarray(problem.edge_weights, np.float64),
                           minlength=n * k)
        return flat.reshape(n, k)
    adj = np.asarray(problem.adjacency, np.float64)
    return adj @ np.eye(k)[r]


def host_costs(problem, assignment, framework: str,
               aggregate: np.ndarray | None = None) -> np.ndarray:
    """(N, K) float64 cost matrix: entry [i, k] is node i's Eq.-1 (or
    Eq.-6) cost were it on machine k, every other node held fixed."""
    r = np.asarray(assignment, np.int64)
    k = problem.num_machines
    if aggregate is None:
        aggregate = host_aggregate(problem, r)
    b = np.asarray(problem.node_weights, np.float64)
    w = np.asarray(problem.speeds, np.float64)
    mu = float(problem.mu)
    loads = np.bincount(r, b, minlength=k)
    others = loads[None, :] - b[:, None] * np.eye(k)[r]
    cut = 0.5 * mu * (aggregate.sum(axis=1, keepdims=True) - aggregate)
    x = b[:, None] / w[None, :]
    if framework == C_FRAMEWORK:
        return x * others + cut
    return x * x + 2.0 * x * others / w[None, :] - 2.0 * x * b.sum() + cut


def host_potentials(problem, assignment) -> tuple[float, float]:
    """(C_0, Ct_0) in float64 (Thm. 3.1 potential, Eq. 8)."""
    r = np.asarray(assignment, np.int64)
    agg = host_aggregate(problem, r)
    c0 = host_costs(problem, r, C_FRAMEWORK, agg)[np.arange(r.size), r].sum()
    b = np.asarray(problem.node_weights, np.float64)
    w = np.asarray(problem.speeds, np.float64)
    loads = np.bincount(r, b, minlength=problem.num_machines)
    cut = 0.5 * (agg.sum(axis=1) - agg[np.arange(r.size), r]).sum()
    ct0 = ((loads / w - b.sum()) ** 2).sum() + 0.5 * float(problem.mu) * cut
    return float(c0), float(ct0)


class EquilibriumCheck(NamedTuple):
    ok: bool
    max_gain: float       # largest float64 best-response gain
    worst_excess: float   # max over nodes of gain - allowed (<= 0 when ok)
    violations: int       # nodes whose gain exceeds what they are allowed


def check_equilibrium(problem, assignment, framework: str, *,
                      tol: float = DEFAULT_TOL,
                      epsilon: float = 0.0) -> EquilibriumCheck:
    """Float64 check that ``assignment`` is the (ε-)equilibrium refinement
    stops at.  Node i is allowed a gain up to its acceptance threshold
    (``refine.acceptance_threshold``) plus that threshold's round-off
    allowance once more — the f32 gain the loop compared may sit that
    far from the float64 one — plus ``epsilon·|Φ|/N`` for the ε-stop of
    ``refine_sweeps``, Φ being the framework's own potential."""
    r = np.asarray(assignment, np.int64)
    k = problem.num_machines
    agg = host_aggregate(problem, r)
    cost = host_costs(problem, r, framework, agg)
    rows = np.arange(r.size)
    best = np.argmin(cost, axis=1)
    gain = cost[rows, r] - cost[rows, best]
    b = np.asarray(problem.node_weights, np.float64)
    acc = Acceptance(
        tol=jnp.float32(tol),
        cut_scale=jnp.float32(0.5 * float(problem.mu)
                              * agg.sum(axis=1).max()),
        total_weight=jnp.float32(b.sum()))
    thresh = np.asarray(acceptance_threshold(
        acc, framework, jnp.asarray(b, jnp.float32), jnp.asarray(r, jnp.int32),
        jnp.asarray(best, jnp.int32),
        jnp.asarray(np.bincount(r, b, minlength=k), jnp.float32),
        jnp.asarray(problem.speeds, jnp.float32)), np.float64)
    allowed = 2.0 * thresh - tol
    if epsilon:
        pot = host_potentials(problem, r)[0 if framework == C_FRAMEWORK
                                          else 1]
        allowed = allowed + epsilon * abs(pot) / r.size
    excess = gain - allowed
    return EquilibriumCheck(ok=bool((excess <= 0).all()),
                            max_gain=float(gain.max()),
                            worst_excess=float(excess.max()),
                            violations=int((excess > 0).sum()))
