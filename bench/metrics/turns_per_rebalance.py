"""Machine turns each rebalance took to converge (``num_turns`` of the
single-move loops, an exact count), averaged over the window."""


def read(run):
    if run.config["step"] != "turn" or not run.turns:
        return None
    return sum(run.turns) / len(run.turns)
