"""Peaks of the chips the benchmark runs on, and the least bytes a step
of the refinement must move.

The partitioner's steps do a few operations per byte they touch, so HBM
bandwidth, not the FLOP rate, bounds them: a roofline share here is the
least bytes of the work over the HBM peak, divided by the device's busy
time.  The byte counts depend only on N, the padded edge count and K, so
they read the same work whether jnp or a Pallas kernel does it.
"""
from __future__ import annotations

# Published peaks by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": "Google Cloud TPU documentation, 'TPU v5e': 16 GB HBM2 "
                  "at 819 GB/s, 197 TFLOP/s bf16 per chip",
    },
}

F32 = I32 = 4


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to bench/roofline.py "
                         "with their source") from None


def sweep_bytes(num_nodes: int, num_edges: int, num_machines: int) -> int:
    """One sweep of the sparse election: every padded edge's sender,
    receiver, weight and the receiver's machine read once; the (N, K)
    aggregate written once and read once; each node's machine and
    weight read once."""
    edges = num_edges * (I32 + I32 + F32 + I32)
    aggregate = 2 * num_nodes * num_machines * F32
    nodes = num_nodes * (I32 + F32)
    return edges + aggregate + nodes


def turn_bytes(num_nodes: int, num_machines: int, moved: bool) -> int:
    """One turn of the dense single-move loop: the (N, K) aggregate and
    each node's machine and weight read once; when the turn moves a node,
    its adjacency row read and the two changed aggregate columns
    written."""
    read = num_nodes * num_machines * F32 + num_nodes * (I32 + F32)
    if moved:
        read += num_nodes * F32 + 2 * num_nodes * F32
    return read
