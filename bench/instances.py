"""A deployment's instance, drawn from a seed, and its problem object.

:class:`Instance` is the host-side truth the float64 reference checks
against: the graph a model module in ``bench/graphs`` draws, and the
machines' base speeds from the configuration.  :func:`device_problem`
turns it into the program's problem object once, at set-up.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class Instance:
    """One deployment's graph, on the host, as undirected edges ``a < b``."""
    num_nodes: int
    num_machines: int
    a: np.ndarray             # (M,) int64 lower endpoint
    b: np.ndarray             # (M,) int64 upper endpoint
    edge_weights: np.ndarray  # (M,) float32
    node_weights: np.ndarray  # (N,) float32
    base_speeds: np.ndarray   # (K,) float64, sums to 1
    mu: float

    def directed(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Both orientations of every edge: (senders, receivers, weights)."""
        return (np.concatenate([self.a, self.b]),
                np.concatenate([self.b, self.a]),
                np.concatenate([self.edge_weights, self.edge_weights]))

    def degrees(self) -> np.ndarray:
        return np.bincount(np.concatenate([self.a, self.b]),
                           minlength=self.num_nodes)


def make_instance(config: dict, seed: int) -> Instance:
    """The deployment ``config`` describes, drawn from ``seed``: its graph
    from the model module ``bench/graphs/<graph.model>.py`` and its
    machines' base speeds as the configuration lists them."""
    graph = config["graph"]
    model = importlib.import_module(f"bench.graphs.{graph['model']}")
    speeds = np.asarray(config["speeds"], np.float64)
    if speeds.size != config["num_machines"] or (speeds <= 0).any():
        raise ValueError(f"{config['name']}: speeds {config['speeds']} do "
                         f"not give {config['num_machines']} machines")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    a, b, edge_w, node_w = model.draw(rng, config["num_nodes"], graph)
    return Instance(num_nodes=config["num_nodes"],
                    num_machines=speeds.size, a=a, b=b, edge_weights=edge_w,
                    node_weights=node_w, base_speeds=speeds / speeds.sum(),
                    mu=float(config["mu"]))


def device_problem(inst: Instance, representation: str, *,
                   dtype: str = "float32",
                   edge_capacity: int | None = None,
                   degree_capacity: int | None = None):
    """The program's problem object for ``inst``, on the default device.

    ``"sparse"`` builds a ``SparseProblem`` padded to ``edge_capacity``
    directed edges and a ``max_degree`` of ``degree_capacity``, so every
    seed of a configuration has the same shapes and shares one compiled
    program; ``"dense"`` builds the (N, N) ``PartitionProblem``.  Its
    weights, speeds and cut weight are in ``dtype``."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)
    if representation == "sparse":
        return _sparse_problem(inst, edge_capacity, degree_capacity, dtype)
    if representation == "dense":
        from repro.core.problem import make_problem

        n = inst.num_nodes
        adj = np.zeros((n, n), np.float32)
        adj[inst.a, inst.b] = inst.edge_weights
        adj[inst.b, inst.a] = inst.edge_weights
        return make_problem(adj, inst.node_weights, inst.base_speeds,
                            mu=inst.mu, dtype=dtype)
    raise ValueError(f"unknown representation {representation!r}")


def _sparse_problem(inst: Instance, edge_capacity: int,
                    degree_capacity: int, dtype):
    """The padded, sender-sorted layout ``SparseProblem`` documents: both
    orientations sorted by (sender, receiver), then padding slots
    (sender N-1, receiver 0, weight 0) up to ``edge_capacity``; the
    speeds normalised to sum to 1.  Built here in one sort instead of
    the program's general constructor, which also merges duplicate edges
    and validates (several seconds at a million LPs)."""
    import jax.numpy as jnp
    from repro.core.sparse import SparseProblem

    n = inst.num_nodes
    s, r, w = inst.directed()
    degree = np.bincount(s, minlength=n)
    if s.size > edge_capacity or degree.max() > degree_capacity:
        raise ValueError(
            f"instance needs {s.size} edge slots and degree {degree.max()}; "
            f"the configuration pads to {edge_capacity} and "
            f"{degree_capacity}")
    order = np.argsort(s * n + r)
    pad = edge_capacity - s.size
    senders = np.concatenate([s[order], np.full(pad, n - 1)])
    receivers = np.concatenate([r[order], np.zeros(pad, np.int64)])
    weights = np.concatenate([w[order], np.zeros(pad, np.float32)])
    row_start = np.zeros(n, np.int64)
    np.cumsum(degree[:-1], out=row_start[1:])
    speeds = inst.base_speeds / inst.base_speeds.sum()
    return SparseProblem(
        senders=jnp.asarray(senders, jnp.int32),
        receivers=jnp.asarray(receivers, jnp.int32),
        edge_weights=jnp.asarray(weights, dtype),
        row_start=jnp.asarray(row_start, jnp.int32),
        node_weights=jnp.asarray(inst.node_weights, dtype),
        speeds=jnp.asarray(speeds, dtype),
        mu=jnp.asarray(inst.mu, dtype),
        max_degree=degree_capacity)


def with_speeds(problem, speeds: np.ndarray):
    """``problem`` with its machine speeds replaced (sums to 1, as the
    program's constructors normalise them)."""
    import jax.numpy as jnp

    s = np.asarray(speeds, np.float64)
    return dataclasses.replace(
        problem, speeds=jnp.asarray(s / s.sum(), problem.speeds.dtype))
