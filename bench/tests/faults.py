"""Faults planted under the timed path, for the tests that ``correct``
comes out false.  Each wraps a real entry module and breaks its answer
where it is produced; the harness runs it as it runs the real one."""
from __future__ import annotations

import types

import jax.numpy as jnp

from bench.entries import Outcome


def _wrap(entry, change):
    def rebalance(problem, request, args):
        return change(problem, request, entry.rebalance(problem, request,
                                                        args))

    return types.SimpleNamespace(rebalance=rebalance)


def state_unchanged(entry):
    """The step returns the placement it was given: no move is made, and
    the loads and counts it reports are those of the start."""
    def change(problem, request, out):
        start = request.start
        k = problem.num_machines
        loads = jnp.zeros(k, problem.node_weights.dtype).at[start].add(
            problem.node_weights)
        pots = None if out.potentials is None else out.potentials[:1]
        return Outcome(start, loads, jnp.int32(0), jnp.int32(1), pots)
    return _wrap(entry, change)


def half_left_out(entry):
    """Only the first half of the LPs is refined: the second half keeps
    its starting machines, the rest of the answer is reported as is."""
    def change(problem, request, out):
        n = out.assignment.shape[0]
        keep = jnp.arange(n) < n // 2
        return out._replace(
            assignment=jnp.where(keep, out.assignment, request.start))
    return _wrap(entry, change)


def answer_altered(entry):
    """The answer is altered as it is produced: the LPs of one machine are
    handed to the next, as an off-by-one in a machine index would.  (One
    LP moved alone can lie within the ε-equilibrium's allowance, and
    within the float32 round-off of the loads, so no check could see it.)"""
    def change(problem, request, out):
        k = problem.num_machines
        a = out.assignment
        return out._replace(assignment=jnp.where(a == 0, 1 % k, a))
    return _wrap(entry, change)


FAULTS = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
          "answer_altered": answer_altered}

