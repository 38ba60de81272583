"""``repro.core.refine.refine_sweeps``: multi-move probabilistic sweeps."""
from __future__ import annotations

from repro.core.refine import refine_sweeps

from . import Outcome


def rebalance(problem, request, args) -> Outcome:
    result, (c0s, _, _) = refine_sweeps(problem, request.start,
                                        key=request.key, **args)
    return Outcome(result.assignment, result.loads, result.num_moves,
                   result.num_turns, c0s)
