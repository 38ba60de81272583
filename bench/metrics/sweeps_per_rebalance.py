"""Sweeps each rebalance took to its ε-equilibrium (``num_turns`` of
``refine_sweeps``, an exact count), averaged over the window."""


def read(run):
    if run.config["step"] != "sweep" or not run.turns:
        return None
    return sum(run.turns) / len(run.turns)
