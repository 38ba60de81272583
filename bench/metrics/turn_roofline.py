"""Share of the HBM roofline the one-chip single-move loop reaches: the
least bytes of every turn of the traced window
(``bench.roofline.turn_bytes``) over the HBM peak, divided by the
device's busy time in that window."""
from bench import roofline


def read(run):
    if (run.config["step"] != "turn" or run.trace is None or run.chips != 1
            or not run.turns):
        return None
    n, k = run.num_nodes, run.num_machines
    work = sum(roofline.turn_bytes(n, k, False) * (t - m)
               + roofline.turn_bytes(n, k, True) * m
               for t, m in zip(run.turns, run.moves))
    least_s = work / roofline.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
