"""The unbounded sweep apply of sparse problems (DESIGN.md §17.2):
``aggregate.apply_mover_edges`` against the incident-window scatter of
``apply_moves`` and the from-scratch rebuild, its edge-buffer ladder, and
the ``num_rebuilds`` / ``apply_slots`` counters of ``refine_sweeps``."""
from __future__ import annotations

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aggregate as agg_mod
from repro.core.batch import refine_sweeps_batched, stack_problems
from repro.core.refine import refine_sweeps
from repro.core.sparse import make_sparse_problem
from repro.graphs.generators import (random_degree_graph_edges,
                                     random_weights_edges)

refine_mod = importlib.import_module("repro.core.refine")

SPEEDS = [0.1, 0.2, 0.3, 0.3, 0.1]
UNBOUNDED = dict(moves_per_machine=None, move_prob=0.5, epsilon=1e-3)


def _problem(n: int, seed: int = 1, **pad):
    s, r = random_degree_graph_edges(n, seed=seed)
    b, w = random_weights_edges(n, s, seed=seed + 1)
    return make_sparse_problem(s, r, w, b, SPEEDS, **pad)


def _degrees(sp) -> np.ndarray:
    ends = np.append(np.asarray(sp.row_start)[1:], sp.num_edges)
    return np.minimum(ends - np.asarray(sp.row_start), sp.max_degree)


@pytest.fixture(scope="module")
def state():
    """A 16,384-LP problem (E = 147,840: buffers of 1,024 to 32,768
    slots) at a random placement, with each LP's other machine drawn."""
    sp = _problem(16384)
    rng = np.random.default_rng(0)
    r0 = jnp.asarray(rng.integers(0, 5, sp.num_nodes), jnp.int32)
    dests = (np.asarray(r0) + rng.integers(1, 5, sp.num_nodes)) % 5
    agg = agg_mod.init_aggregate_state(sp, r0)
    return sp, agg, jnp.asarray(dests, jnp.int32), jnp.sum(sp.node_weights)


def _movers_for(sp, edges: int, seed: int = 0,
                lowest_degree: bool = False) -> np.ndarray:
    """A random mover set whose edges just reach ``edges``, drawn from
    the LPs of lowest degree first if ``lowest_degree``."""
    order = np.random.default_rng(seed).permutation(sp.num_nodes)
    if lowest_degree:
        order = order[np.argsort(_degrees(sp)[order], kind="stable")]
    count = int(np.searchsorted(np.cumsum(_degrees(sp)[order]), edges)) + 1
    accept = np.zeros(sp.num_nodes, bool)
    accept[order[:count]] = True
    return accept


def _bits(x) -> bytes:
    return np.atleast_1d(np.asarray(x)).tobytes()


def test_edge_buffer_sizes_scale_with_the_edge_count():
    assert agg_mod.edge_buffer_sizes(147_840) == (1024, 2048, 8192, 32768)
    assert agg_mod.edge_buffer_sizes(9_469_952) == (
        1 << 13, 1 << 15, 1 << 17, 1 << 19, 1 << 21)
    assert agg_mod.edge_buffer_sizes(128) == (1024,)
    for e in (2_304, 147_840, 1 << 20, 9_469_952, 1 << 26):
        sizes = agg_mod.edge_buffer_sizes(e)
        assert len(sizes) <= agg_mod.EDGE_BUFFER_COUNT
        assert max(sizes) <= max(e // agg_mod.REBUILD_CROSSOVER,
                                 agg_mod.EDGE_BUFFER_MIN)


@pytest.mark.parametrize("edges", [0, 1000, 1030, 2040, 2060, 8180, 8200,
                                   32700])
def test_apply_mover_edges_bitwise_equals_the_window_scatter(state, edges):
    """Mover sets of up to 4,096 LPs, on both sides of each buffer's
    boundary: the aggregate, assignment and loads are bitwise those of
    apply_moves' incident windows over the same movers, ascending."""
    sp, agg, dests, total_b = state
    accept = _movers_for(sp, edges) if edges else np.zeros(sp.num_nodes,
                                                            bool)
    assert accept.sum() <= 4096
    new, slots = jax.jit(agg_mod.apply_mover_edges)(
        sp, agg, jnp.asarray(accept), dests, total_b)
    idx = jnp.nonzero(jnp.asarray(accept), size=4096, fill_value=0)[0]
    old = jax.jit(agg_mod.apply_moves)(
        sp, agg, idx.astype(jnp.int32), dests[idx],
        jnp.arange(4096) < int(accept.sum()), total_b)
    for name in ("aggregate", "assignment", "loads"):
        assert _bits(getattr(new, name)) == _bits(getattr(old, name)), name
    for name in ("c0", "ct0"):
        np.testing.assert_allclose(float(getattr(new, name)),
                                   float(getattr(old, name)), rtol=1e-6)
    d = int(_degrees(sp)[accept].sum())
    sizes = agg_mod.edge_buffer_sizes(sp.num_edges)
    assert int(slots) == min(s for s in sizes if s >= d)


@pytest.mark.parametrize("edges", [30_000, 40_000])
def test_apply_mover_edges_beyond_4096_movers_matches_the_rebuild(state,
                                                                  edges):
    """More than 4,096 movers: the incremental buffer (30,000 edges) and
    the rebuild past the largest buffer (40,000 edges) both give the
    rebuilt state, the rebuild exactly."""
    sp, agg, dests, total_b = state
    accept = _movers_for(sp, edges, seed=1, lowest_degree=True)
    assert accept.sum() > 4096
    new, slots = jax.jit(agg_mod.apply_mover_edges)(
        sp, agg, jnp.asarray(accept), dests, total_b)
    want = agg_mod.rebuild_state(
        sp, jnp.where(jnp.asarray(accept), dests, agg.assignment), total_b)
    rebuilt = edges > max(agg_mod.edge_buffer_sizes(sp.num_edges))
    assert int(slots) == (0 if rebuilt else 32768)
    scale = float(jnp.max(jnp.abs(want.aggregate)))
    np.testing.assert_allclose(np.asarray(new.aggregate),
                               np.asarray(want.aggregate),
                               rtol=0, atol=1e-5 * scale)
    for name in ("c0", "ct0"):
        np.testing.assert_allclose(float(getattr(new, name)),
                                   float(getattr(want, name)), rtol=1e-5)
    if rebuilt:
        assert _bits(new.aggregate) == _bits(want.aggregate)


@pytest.mark.parametrize("n,size", [(1000, 200), (5000, 1600),
                                    (65536, 4096), (300, 64)])
def test_compact_is_nonzero(n, size):
    keep = np.random.default_rng(n).random(n) < 0.2
    got = jax.jit(agg_mod._compact, static_argnums=1)(jnp.asarray(keep),
                                                       size)
    want = jnp.nonzero(jnp.asarray(keep), size=size, fill_value=n)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 127, 128, 129, 20_000])
def test_scan_is_cumsum_and_cummax(n):
    x = jnp.asarray(np.random.default_rng(n).integers(-50, 50, n),
                    jnp.int32)
    np.testing.assert_array_equal(np.asarray(agg_mod._scan(x, jnp.add, 0)),
                                  np.cumsum(np.asarray(x)))
    pairs = jnp.stack([x, -x], axis=1)
    np.testing.assert_array_equal(
        np.asarray(agg_mod._scan(pairs, jnp.maximum, -100)),
        np.maximum.accumulate(np.asarray(pairs), axis=0))


def _sweep_edges(sp, r0, key, sweeps: int, **cfg) -> list[int]:
    """The accepted movers' edges of each of the first sweeps, replayed:
    run i stops after sweep i, and an accepted LP always changes
    machine."""
    before = np.asarray(r0)
    out = []
    for i in range(1, sweeps + 1):
        res, _, _ = refine_mod._refine_sweeps(sp, r0, key, "c",
                                              max_sweeps=i, **cfg)
        after = np.asarray(res.assignment)
        out.append(int(_degrees(sp)[after != before].sum()))
        before = after
    return out


def _start(n: int, start: str, seed: int):
    """A uniform random placement, or every LP on the slowest machine:
    then about half of them move in the first sweep."""
    if start == "one_machine":
        return jnp.zeros((n,), jnp.int32)
    return jnp.asarray(np.random.default_rng(seed).integers(0, 5, n),
                       jnp.int32)


@pytest.mark.parametrize("start", ["one_machine", "uniform"])
def test_counters_follow_each_sweeps_movers(start):
    """``num_rebuilds`` counts the sweeps whose movers' edges exceed the
    largest buffer, and ``apply_slots`` sums the buffers the others
    took.  From one machine the first sweep overflows."""
    sp = _problem(2048, seed=3)
    r0 = _start(2048, start, 3)
    key = jax.random.PRNGKey(4)
    sizes = agg_mod.edge_buffer_sizes(sp.num_edges)
    edges = _sweep_edges(sp, r0, key, 3, **UNBOUNDED)
    res, _, _ = refine_mod._refine_sweeps(sp, r0, key, "c", max_sweeps=3,
                                          **UNBOUNDED)
    assert int(res.num_rebuilds) == sum(d > sizes[-1] for d in edges)
    assert int(res.apply_slots) == sum(
        min((s for s in sizes if s >= d), default=0) for d in edges)
    assert (edges[0] > sizes[-1]) == (start == "one_machine")


def test_run_end_carries_apply_slots():
    from repro.obs import Recorder

    sp = _problem(512)
    r0 = jnp.asarray(np.random.default_rng(5).integers(0, 5, 512),
                     jnp.int32)
    rec = Recorder()
    result, _ = refine_sweeps(sp, r0, key=jax.random.PRNGKey(6),
                              recorder=rec, max_sweeps=64, **UNBOUNDED)
    end = [e for e in rec.events if e["kind"] == "run_end"][-1]
    assert end["apply_slots"] == int(result.apply_slots) > 0
    one, _ = refine_sweeps(sp, r0, max_sweeps=16, recorder=rec)
    end = [e for e in rec.events if e["kind"] == "run_end"][-1]
    assert int(one.apply_slots) == 0 and "apply_slots" not in end


@pytest.mark.parametrize("start", ["one_machine", "uniform"])
def test_unbounded_sparse_batched_equals_looped(start):
    """Under vmap the buffer switch runs every branch and selects; each
    element still equals its own looped run, bitwise.  From one machine
    the first sweep rebuilds and the later ones take buffers."""
    problems = [_problem(1500, seed=s, pad_edges_multiple=4096)
                for s in (7, 8, 9)]
    assert len({p.num_edges for p in problems}) == 1
    assert len({p.max_degree for p in problems}) == 1
    r0s = [_start(1500, start, s) for s in (7, 8, 9)]
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    cfg = dict(UNBOUNDED, max_sweeps=48)
    res_b, outs_b = refine_sweeps_batched(stack_problems(problems),
                                          jnp.stack(r0s), "c", keys=keys,
                                          **cfg)
    for i in range(3):
        res_l, outs_l = refine_sweeps(problems[i], r0s[i], "c", key=keys[i],
                                      **cfg)
        for a, b in zip(jax.tree.leaves((res_l, outs_l)),
                        jax.tree.leaves((res_b, outs_b))):
            assert _bits(a) == _bits(np.asarray(b)[i])
        assert int(res_l.num_moves) > 0 and int(res_l.apply_slots) > 0
        assert (int(res_l.num_rebuilds) > 0) == (start == "one_machine")
