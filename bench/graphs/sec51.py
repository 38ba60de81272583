"""The §5.1 graph model of arXiv:1111.0875, drawn in bulk.

Each LP links to d ~ U{degree_min..degree_max} distinct other LPs, the
links are undirected (a pair drawn from both ends is one edge), stray
components are joined to the largest by one edge each, and node and
edge weights are U(0, 2·weight_mean).  This is the benchmark's own copy
of the model, kept apart from the program's generators: it is
vectorised over all LPs, so a million-LP instance takes seconds on the
host, not the quarter of a minute of the program's per-stage generator.
"""
from __future__ import annotations

import numpy as np


def _distinct_targets(rng: np.random.Generator, senders: np.ndarray,
                      n: int) -> np.ndarray:
    """A uniform non-self target per (sender, slot), redrawing a slot that
    repeats an earlier target of the same sender (``senders`` sorted)."""
    t = rng.integers(0, n - 1, size=senders.size)
    t += t >= senders
    while True:
        key = senders * n + t
        order = np.argsort(key, kind="stable")
        ks = key[order]
        dup = order[1:][ks[1:] == ks[:-1]]
        if dup.size == 0:
            return t
        fresh = rng.integers(0, n - 1, size=dup.size)
        t[dup] = fresh + (fresh >= senders[dup])


def _join_components(n: int, a: np.ndarray, b: np.ndarray,
                     rng: np.random.Generator):
    """Edges joining each stray component to the largest one: one random
    member of each to one random member of the largest."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(a, minlength=n), out=indptr[1:])
    graph = csr_matrix((np.ones(a.size, np.int8), b, indptr), shape=(n, n))
    count, labels = connected_components(graph, directed=False)
    if count == 1:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    sizes = np.bincount(labels, minlength=count)
    giant = int(np.argmax(sizes))
    members = np.argsort(labels, kind="stable")
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    stray = np.flatnonzero(np.arange(count) != giant)
    x = members[starts[stray] + rng.integers(0, sizes[stray])]
    y = members[starts[giant] + rng.integers(0, sizes[giant], stray.size)]
    return x.astype(np.int64), y.astype(np.int64)


def draw(rng: np.random.Generator, num_nodes: int, params: dict):
    """``(a, b, edge_weights, node_weights)`` of one §5.1 graph."""
    n = num_nodes
    dmin, dmax = params["degree_min"], params["degree_max"]
    d = np.minimum(rng.integers(dmin, dmax + 1, size=n), n - 1)
    senders = np.repeat(np.arange(n, dtype=np.int64), d)
    targets = _distinct_targets(rng, senders, n)
    code = np.unique(np.minimum(senders, targets) * n
                     + np.maximum(senders, targets))
    x, y = _join_components(n, code // n, code % n, rng)
    if x.size:
        code = np.unique(np.concatenate(
            [code, np.minimum(x, y) * n + np.maximum(x, y)]))
    a, b = code // n, code % n
    hi = 2.0 * params["weight_mean"]
    node_w = rng.uniform(0.0, hi, size=n).astype(np.float32)
    edge_w = rng.uniform(0.0, hi, size=a.size).astype(np.float32)
    return a, b, edge_w, node_w
