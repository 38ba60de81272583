"""Optimistic-DES archetype invariants (paper §6 + Appendix B).

The strongest oracle: a thread with hop budget c injected at src must
eventually be seen by EXACTLY the nodes within c hops of src — regardless
of machine placement, transfer delays, stragglers and rollbacks.  The
engine's whole Time Warp machinery exists to preserve that semantics while
executing optimistically.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.des.engine import (DESConfig, DESState, des_tick,
                              make_initial_state, run_simulation)
from repro.des.workload import flooded_packet_workload
from repro.graphs.generators import (preferential_attachment,
                                     random_degree_graph)


def _hop_closure(adj: np.ndarray, src: int, hops: int) -> np.ndarray:
    mask = np.zeros(adj.shape[0], bool)
    mask[src] = True
    nbr = adj > 0
    for _ in range(hops):
        mask = mask | (mask @ nbr)
    return mask


def _run(cfg, adj, spec, machine=None):
    n = cfg.num_lps
    m0 = jnp.arange(n, dtype=jnp.int32) % cfg.num_machines \
        if machine is None else jnp.asarray(machine, jnp.int32)
    state = make_initial_state(cfg, m0, spec.src, spec.time, spec.count)
    return run_simulation(cfg, jnp.asarray(adj, jnp.float32), state)


@pytest.mark.parametrize("num_machines,seed", [(1, 0), (3, 1), (5, 2)])
def test_flood_closure_oracle(num_machines, seed):
    """Final 'seen' sets == exact k-hop closures, for any machine count."""
    n, t = 24, 6
    adj = random_degree_graph(n, seed=seed, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, seed + 10, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=num_machines, num_threads=t,
                    event_capacity=32, history_capacity=64, max_ticks=40_000)
    out = _run(cfg, adj, spec)
    assert bool(out.done), f"not drained after {int(out.tick)} ticks"
    assert int(out.dropped) == 0 and int(out.hist_evict) == 0
    seen = np.asarray(out.seen)
    for j in range(t):
        want = _hop_closure(adj, int(spec.src[j]), int(spec.count[j]))
        np.testing.assert_array_equal(
            seen[:, j], want,
            err_msg=f"thread {j} src={spec.src[j]} scope={spec.count[j]}")


def test_processed_counts_match_closure():
    """Each flood event is processed exactly once per (node, thread) pair
    that the closure admits (no double-processing after rollbacks)."""
    n, t = 16, 4
    adj = random_degree_graph(n, seed=3, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, 5, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=2, num_threads=t,
                    event_capacity=32, history_capacity=64, max_ticks=40_000)
    out = _run(cfg, adj, spec)
    assert bool(out.done)
    expect = sum(int(_hop_closure(adj, int(spec.src[j]),
                                  int(spec.count[j])).sum())
                 for j in range(t))
    # processed counts include rollback re-executions; net completions must
    # be at least the closure size and exactly it when no rollbacks occurred
    assert int(out.processed) >= expect
    if int(out.rollbacks) == 0:
        assert int(out.processed) == expect


def test_gvt_monotone_nondecreasing():
    n, t = 16, 5
    adj = preferential_attachment(n, seed=1, m=2)
    spec = flooded_packet_workload(adj, 2, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=3, num_threads=t,
                    event_capacity=32, history_capacity=64, max_ticks=5_000)
    m0 = jnp.arange(n, dtype=jnp.int32) % 3
    state = make_initial_state(cfg, m0, spec.src, spec.time, spec.count)
    tick = jax.jit(partial(des_tick, cfg), static_argnums=())
    adjj = jnp.asarray(adj, jnp.float32)
    prev_gvt = -1.0
    for _ in range(400):
        state = tick(adjj, state)
        g = float(state.gvt)
        assert g >= prev_gvt - 1e-6, "GVT regressed"
        prev_gvt = g
        if bool(state.done):
            break
    assert bool(state.done)


def test_single_machine_never_needs_intermachine_delay():
    """On one machine every transfer uses intra_delay; a huge inter_delay
    must not change the outcome."""
    n, t = 12, 3
    adj = random_degree_graph(n, seed=7, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, 8, num_threads=t, scope=2,
                                   max_per_lp=3)
    outs = []
    for inter in (2, 50):
        cfg = DESConfig(num_lps=n, num_machines=1, num_threads=t,
                        event_capacity=32, history_capacity=64,
                        inter_delay=inter, max_ticks=40_000)
        outs.append(_run(cfg, adj, spec, machine=np.zeros(n)))
    assert int(outs[0].tick) == int(outs[1].tick)
    np.testing.assert_array_equal(np.asarray(outs[0].seen),
                                  np.asarray(outs[1].seen))


def test_intermachine_delay_slows_simulation():
    """Cross-machine placement with large transfer delay costs wall-clock
    ticks vs an all-on-one-machine placement of the same workload — the
    rollback-risk mechanism the partition game's edge weights model."""
    n, t = 20, 5
    adj = random_degree_graph(n, seed=11, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, 12, num_threads=t, scope=3,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=2, num_threads=t,
                    event_capacity=48, history_capacity=96,
                    inter_delay=25, intra_delay=1, max_ticks=60_000)
    # adversarial placement: alternate machines along node index
    bad = _run(cfg, adj, spec, machine=np.arange(n) % 2)
    # everything on machine 0 (machine speed model penalizes density, but
    # avoids all transfer delay)
    good = _run(cfg, adj, spec, machine=np.zeros(n))
    assert bool(bad.done) and bool(good.done)
    assert int(bad.rollbacks) >= int(good.rollbacks)


def test_refinement_runs_and_migrates():
    n, t = 24, 8
    adj = preferential_attachment(n, seed=4, m=2)
    spec = flooded_packet_workload(adj, 6, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=3, num_threads=t,
                    event_capacity=32, history_capacity=64,
                    refine_freq=150, max_ticks=40_000)
    out = _run(cfg, adj, spec)
    assert bool(out.done)
    assert int(out.refines) >= 1
    # machine ids stay valid after migrations
    m = np.asarray(out.machine)
    assert m.min() >= 0 and m.max() < 3


def test_load_trace_recorded():
    n, t = 16, 4
    adj = random_degree_graph(n, seed=6, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, 3, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=2, num_threads=t,
                    event_capacity=32, history_capacity=64,
                    trace_stride=10, max_ticks=40_000)
    out = _run(cfg, adj, spec)
    assert int(out.trace_ptr) > 0
    tr = np.asarray(out.trace)[:int(out.trace_ptr)]
    assert np.all(tr >= 0)


def test_determinism():
    """Identical inputs -> identical simulation (pure function of state)."""
    n, t = 14, 4
    adj = random_degree_graph(n, seed=9, dmin=2, dmax=3)
    spec = flooded_packet_workload(adj, 1, num_threads=t, scope=2,
                                   max_per_lp=3)
    cfg = DESConfig(num_lps=n, num_machines=2, num_threads=t,
                    event_capacity=32, history_capacity=64, max_ticks=40_000)
    a = _run(cfg, adj, spec)
    b = _run(cfg, adj, spec)
    assert int(a.tick) == int(b.tick)
    assert int(a.processed) == int(b.processed)
    np.testing.assert_array_equal(np.asarray(a.seen), np.asarray(b.seen))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_insert_proposals_matches_sequential_reference(seed):
    """Capacity-ranked insertion against a plain loop: each receiver takes
    its valid proposals in order into its free slots in order, and the
    rest are dropped."""
    from repro.des.engine import EventLists, _insert_proposals

    rng = np.random.default_rng(seed)
    n, e, p = 12, 6, 20
    valid = rng.random((n, e)) < 0.5
    valid[0] = True                                   # a full receiver
    ev = EventLists(rng.random((n, e)).astype(np.float32),
                    *(rng.integers(-1, 9, (n, e)).astype(np.int32)
                      for _ in range(6)), valid)
    prop_valid = rng.random((p, n)) < 0.3
    prop_valid[:, 1] = False                          # no proposals at all
    props = (rng.random((p, n)).astype(np.float32),
             *(rng.integers(-1, 9, (p, n)).astype(np.int32)
               for _ in range(6)))

    want = [np.array(f) for f in ev]
    dropped = 0
    for r in range(n):
        slots = [s for s in range(e) if not valid[r, s]]
        for q, src in enumerate(np.nonzero(prop_valid[:, r])[0]):
            if q >= len(slots):
                dropped += 1
                continue
            for f, prop in zip(want[:-1], props):
                f[r, slots[q]] = prop[src, r]
            want[-1][r, slots[q]] = True

    got, got_dropped = _insert_proposals(
        EventLists(*map(jnp.asarray, ev)), jnp.asarray(prop_valid),
        tuple(map(jnp.asarray, props)))
    assert int(got_dropped) == dropped
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_lower_coalesced_matches_scatter_min():
    """The coalesce step against the scatter-min it replaces: every
    (receiver, sender) pair writes its announcement (or the +inf
    stand-in) into the slot its ROLLBACK event matched."""
    from repro.des.engine import _INF, _lower_coalesced

    rng = np.random.default_rng(3)
    r, e, s = 9, 5, 11
    time = rng.random((r, e)).astype(np.float32)
    sender = rng.integers(-1, s, (r, e)).astype(np.int32)
    match = (rng.random((r, e)) < 0.6)[:, :, None] \
        & (sender[:, :, None] == np.arange(s))                 # (R, E, S)
    slot_rb = match.argmax(axis=1).astype(np.int32)
    coalesce = match.any(axis=1) & (rng.random((r, s)) < 0.7)
    ann_time = (rng.random(s) - 0.5).astype(np.float32)
    upd = np.where(coalesce, ann_time[None, :], _INF)
    want = np.asarray(jnp.asarray(time).at[
        np.broadcast_to(np.arange(r)[:, None], (r, s)), slot_rb].min(upd))
    got = _lower_coalesced(*map(jnp.asarray, (time, sender, slot_rb,
                                              coalesce, ann_time)))
    assert (want != time).any()
    np.testing.assert_array_equal(np.asarray(got), want)
