"""LP transfers each rebalance applied (``num_moves``, an exact count),
averaged over the window."""


def read(run):
    if not run.moves:
        return None
    return sum(run.moves) / len(run.moves)
