"""How each public entry point is called, one module per entry.

A module defines ``rebalance(problem, request, args) -> Outcome``: one
call of the entry as a user makes it, with the configuration's
``entry_args`` and nothing of the benchmark's own.
"""
from __future__ import annotations

from typing import NamedTuple


class Outcome(NamedTuple):
    """One rebalance's answer, left on the device until the window ends."""
    assignment: object
    loads: object
    num_moves: object
    num_turns: object      # sweeps for the sweep entries, turns otherwise
    potentials: object     # per-step carried C_0, or None
