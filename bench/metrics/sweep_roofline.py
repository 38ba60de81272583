"""Share of the HBM roofline the sparse sweeps reach: the least bytes of
every active sweep of the traced window (``bench.roofline.sweep_bytes``)
over the HBM peak, divided by the device's busy time in that window.
Sweeps the scan runs after convergence count as busy time, not work."""
from bench import roofline


def read(run):
    if run.config["step"] != "sweep" or run.trace is None or not run.turns:
        return None
    work = sum(run.turns) * roofline.sweep_bytes(
        run.num_nodes, run.num_edges, run.num_machines)
    least_s = work / roofline.peak(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace.busy_s
