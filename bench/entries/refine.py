"""``repro.core.refine.refine``: round-robin single-move refinement."""
from __future__ import annotations

from repro.core.refine import refine

from . import Outcome


def rebalance(problem, request, args) -> Outcome:
    result = refine(problem, request.start, **args)
    return Outcome(result.assignment, result.loads, result.num_moves,
                   result.num_turns, None)
