#!/usr/bin/env python3
"""Smoke run of the partitioner's main path on a TPU.

    python chip_smoke.py             # one chip: dense, sparse and DES phases
    python chip_smoke.py --chips 4   # four chips: the shard_map driver only

Each phase drives the entry points a user calls (``refine``,
``refine_sweeps``, ``run_simulation``, ``refine_distributed_shard_map``)
and checks what comes out against an independent float64 host reference
(``repro.core.reference``); any failed check raises, and the script exits
non-zero.  Kernel phases must compile to a Mosaic kernel
(``tpu_custom_call`` in the compiled HLO): a kernel that ran interpreted
fails the smoke.  Earlier lines report each run's compile time, steady
wall time and move/turn/sweep counts, read on the host clock and for
information only.  The last line is ``{"ok": true, "device": {...}}``.
Without a TPU it exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PAPER_SPEEDS = (0.1, 0.2, 0.3, 0.3, 0.1)   # §5.1
MU = 8.0
DRIFT_BUDGET = 1e-3          # relative potential agreement with the oracle
SPARSE_EPSILON = 1e-3
SPARSE_MAX_SWEEPS = 24


def log(msg: str) -> None:
    print(msg, flush=True)


def _compile(fn, *args):
    """AOT-compile ``fn`` for ``args``; returns (compiled, seconds)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _run(compiled, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def check(ok, message) -> None:
    """Raise :class:`SmokeFailure` unless ``ok`` (kept under ``-O``)."""
    if not ok:
        raise SmokeFailure(message)


def _require_kernel(compiled, what: str, required: bool) -> None:
    check(not required or "tpu_custom_call" in compiled.as_text(),
          f"{what}: no Mosaic kernel in the compiled program (it ran "
          "interpreted or fell back)")


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# instances
# ---------------------------------------------------------------------------

def paper_instance():
    """§5.1: 230 LPs of degree 3..6, weights of mean 5, K=5, mu=8."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.problem import make_problem
    from repro.graphs.generators import random_degree_graph, random_weights

    adj = random_degree_graph(230, seed=0)
    b, c = random_weights(adj, seed=1, mean=5.0)
    prob = make_problem(c, b, PAPER_SPEEDS, mu=MU)
    r0 = jnp.asarray(np.random.default_rng(42).integers(0, 5, 230),
                     jnp.int32)
    return prob, r0


def dense_instance(n: int, k: int, seed: int = 0):
    """The §5.1 graph model at ``n`` LPs on ``k`` equal machines."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.problem import make_problem
    from repro.graphs.generators import random_degree_graph, random_weights

    adj = random_degree_graph(n, seed=seed)
    b, c = random_weights(adj, seed=seed + 1, mean=5.0)
    prob = make_problem(c, b, np.ones(k) / k, mu=MU)
    r0 = jnp.asarray(np.random.default_rng(seed + 2).integers(0, k, n),
                     jnp.int32)
    return prob, r0


def sparse_instance(n: int, k: int, seed: int = 0):
    """The §5.1 model as an edge list (about 4.5 undirected edges per LP)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core.sparse import make_sparse_problem
    from repro.graphs.generators import (random_degree_graph_edges,
                                         random_weights_edges)

    s, r = random_degree_graph_edges(n, seed=seed)
    b, w = random_weights_edges(n, s, seed=seed + 1, mean=5.0)
    sp = make_sparse_problem(s, r, w, b, np.ones(k) / k, mu=MU)
    r0 = jnp.asarray(np.random.default_rng(seed + 2).integers(0, k, n),
                     jnp.int32)
    return sp, r0


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def dense_phase(name: str, prob, r0, *, require_kernels: bool) -> dict:
    """``refine`` three ways on one dense instance: the incremental jnp
    path (defaults), the fused aggregate kernel and the recompute oracle.
    Each must converge to a float64-verified equilibrium whose potentials
    match the oracle's within the drift budget.  A fourth run resyncs
    the carry every 64 turns (``verify_every``) and holds the drift it
    observes to the same budget."""
    import numpy as np
    from repro.core import costs
    from repro.core.reference import (check_equilibrium, host_aggregate,
                                      host_potentials)
    from repro.core.refine import refine
    from repro.kernels import ops

    agg = np.asarray(costs.adjacency_aggregate(prob.adjacency, r0,
                                               prob.num_machines))
    ref = host_aggregate(prob, r0)
    agg_err = float(np.abs(agg - ref).max() / np.abs(ref).max())
    log(f"[dense {name}] N={prob.num_nodes} K={prob.num_machines}: dense "
        f"aggregate vs float64 host, max rel err {agg_err:.3e}")
    check(agg_err < 1e-6,
          f"dense aggregate off the float64 host: {agg_err}")

    kernel_fn = ops.make_aggregate_dissat_fn()
    paths = {
        "incremental": lambda p, r: refine(p, r),
        "kernel": lambda p, r: refine(p, r, dissat_fn=kernel_fn),
        "oracle": lambda p, r: refine(p, r, incremental=False),
        "verify": lambda p, r: refine(p, r, verify_every=64),
    }
    out = {}
    for path, fn in paths.items():
        compiled, t_c = _compile(fn, prob, r0)
        if path == "kernel":
            _require_kernel(compiled, f"dense {name} kernel", require_kernels)
        res, t_r = _run(compiled, prob, r0)
        moves, turns = int(res.num_moves), int(res.num_turns)
        log(f"[dense {name}] {path:11s} compile {t_c:.3f} s, steady "
            f"{t_r:.3f} s, {moves} moves, {turns} turns, "
            f"drift {float(res.aggregate_drift):.3e}")
        check(bool(res.converged),
              f"dense {name} {path}: not converged after {turns} turns")
        eq = check_equilibrium(prob, res.assignment, costs.C_FRAMEWORK)
        check(eq.ok, f"dense {name} {path}: {eq}")
        out[path] = (res, host_potentials(prob, res.assignment))
    c0_ref, ct0_ref = out["oracle"][1]
    for path in ("incremental", "kernel"):
        c0, ct0 = out[path][1]
        check(_rel(c0, c0_ref) <= DRIFT_BUDGET
              and _rel(ct0, ct0_ref) <= DRIFT_BUDGET,
              f"dense {name} {path}: potentials ({c0}, {ct0}) vs oracle "
              f"({c0_ref}, {ct0_ref})")
    # verify_every swaps the carry for a rebuild every 64 turns; the
    # rebuild differs from the carry in the last bits, which may break a
    # near-tie the other way and reach another (verified) equilibrium, so
    # only its drift is held to the budget.  The largest carried
    # magnitudes are the potentials.
    drift = float(out["verify"][0].aggregate_drift)
    check(drift <= DRIFT_BUDGET * max(abs(c0_ref), abs(ct0_ref)),
          f"dense {name}: carried state drifted {drift}")
    return {path: int(res.num_moves) for path, (res, _) in out.items()}


def _near_tie(cost, rows, a, b, tol) -> bool:
    return bool((abs(cost[rows, a] - cost[rows, b]) <= tol).all())


def sparse_phase(n: int, k: int, *, require_kernels: bool) -> dict:
    """``refine_sweeps`` to an ε-equilibrium at N LPs, with the jnp
    election and with the compiled edge kernel, plus one call of each
    edge kernel against the jnp reduction on the same state."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import costs
    from repro.core.reference import check_equilibrium, host_potentials
    from repro.core.refine import refine_sweeps
    from repro.kernels import ops
    from repro.kernels.edge_block import (build_edge_tile_layout,
                                          dissatisfaction_from_edges_pallas,
                                          sweep_candidates_from_edges_pallas)

    t0 = time.perf_counter()
    sp, r0 = sparse_instance(n, k)
    log(f"[sparse] N={n} K={k} E(padded)={sp.num_edges} "
        f"max_degree={sp.max_degree}, set-up {time.perf_counter() - t0:.3f} s")

    # one call of each edge kernel against the jnp reduction; the edge
    # arrays are arguments, not constants baked into the program
    layout = build_edge_tile_layout(sp)
    slabs = (layout.local_senders, layout.recv_index, layout.edge_w)
    loads = jax.ops.segment_sum(sp.node_weights, r0, num_segments=k)

    def jnp_path(p, r, loads):
        agg = costs.adjacency_aggregate_sparse(p, r)
        cost = costs.cost_matrix_from_aggregate(
            agg, r, p.node_weights, loads, p.speeds, p.mu, "c",
            total_weight=jnp.sum(p.node_weights))
        dissat, best = costs.dissatisfaction_from_cost(cost, r)
        return cost, dissat, best

    def edge_kernel(call):
        def fn(p, slabs, r, loads):
            lay = layout._replace(local_senders=slabs[0],
                                  recv_index=slabs[1], edge_w=slabs[2])
            return call(lay, r, p.node_weights, loads, p.speeds, p.mu, "c",
                        total_weight=jnp.sum(p.node_weights))
        return fn

    cost, d_ref, b_ref = (np.asarray(x)
                          for x in jax.jit(jnp_path)(sp, r0, loads))
    tol = 64 * np.spacing(np.float32(np.abs(cost).max()))
    edge_dissat = edge_kernel(dissatisfaction_from_edges_pallas)
    edge_sweep = edge_kernel(sweep_candidates_from_edges_pallas)
    for what, fn in (("edge dissat kernel", edge_dissat),
                     ("edge sweep kernel", edge_sweep)):
        compiled, t_c = _compile(fn, sp, slabs, r0, loads)
        _require_kernel(compiled, what, require_kernels)
        got, t_r = _run(compiled, sp, slabs, r0, loads)
        log(f"[sparse] {what}: compile {t_c:.3f} s, steady {t_r:.3f} s")
        if fn is edge_dissat:
            d, b = np.asarray(got[0]), np.asarray(got[1])
            check(np.abs(d - d_ref).max() <= tol,
                  f"{what}: dissat off by {np.abs(d - d_ref).max()}")
            rows = np.nonzero(b != b_ref)[0]
            check(_near_tie(cost, rows, b[rows], b_ref[rows], tol),
                  f"{what}: best machine differs at {rows[:8]}")
            log(f"[sparse] {what}: max |dissat - jnp| "
                f"{np.abs(d - d_ref).max():.3e}, best differs at "
                f"{rows.size} near-tied rows")
        else:
            gains, picks, dests = (np.asarray(x) for x in got)
            owned = np.asarray(r0)[None, :] == np.arange(k)[:, None]
            masked = np.where(owned, d_ref[None, :], -np.inf)
            want_gain = masked.max(axis=1)
            check(np.abs(gains - want_gain).max() <= tol,
                  f"{what}: gains {gains} vs {want_gain}")
            check((np.abs(d_ref[picks] - want_gain) <= tol).all(),
                  f"{what}: picks {picks} are not their machine's best")
            check(_near_tie(cost, picks, dests, b_ref[picks], tol),
                  f"{what}: dests {dests} vs {b_ref[picks]}")

    cfg = dict(moves_per_machine=None, move_prob=0.5,
               epsilon=SPARSE_EPSILON, key=jax.random.PRNGKey(0),
               max_sweeps=SPARSE_MAX_SWEEPS)
    edge_fn = ops.make_edge_dissat_fn(sp)
    runs = {
        "jnp election": lambda p, r: refine_sweeps(p, r, **cfg),
        "edge kernel": lambda p, r: refine_sweeps(p, r, dissat_fn=edge_fn,
                                                  **cfg),
    }
    c0s = {}
    for path, fn in runs.items():
        compiled, t_c = _compile(fn, sp, r0)
        if path == "edge kernel":
            _require_kernel(compiled, "sparse refine_sweeps edge kernel",
                            require_kernels)
        (res, _), t_r = _run(compiled, sp, r0)
        sweeps, moves = int(res.num_turns), int(res.num_moves)
        log(f"[sparse] {path:12s} compile {t_c:.3f} s, steady {t_r:.3f} s, "
            f"{sweeps} sweeps, {moves} moves")
        check(bool(res.converged),
              f"sparse {path}: not converged in {sweeps} sweeps")
        eq = check_equilibrium(sp, res.assignment, costs.C_FRAMEWORK,
                               epsilon=SPARSE_EPSILON)
        check(eq.ok, f"sparse {path}: {eq}")
        c0s[path] = host_potentials(sp, res.assignment)[0]
    check(_rel(c0s["edge kernel"], c0s["jnp election"]) <= DRIFT_BUDGET,
          f"sparse: C_0 {c0s}")
    return c0s


def des_phase(n: int, k: int, *, threads: int = 8,
              capacity: int = 32) -> dict:
    """``run_simulation`` with in-sim repartitioning while one machine
    fails and recovers, once per refine backend.  The simulation must
    drain (every event processed), drop nothing and report a finite
    completion time (the latest LP clock, in simulated time).

    The machine is down outright (``scenarios.true_failure``): under
    ``scenarios.failure_recovery``'s 0.02 speed floor a job started in
    the window costs 50x its ticks, which the engine charges at the
    start, so one such job outlasts any tick budget a smoke can afford."""
    import numpy as np
    import jax.numpy as jnp
    from repro.des import scenarios
    from repro.des.engine import DESConfig, make_initial_state, run_simulation
    from repro.des.workload import flooded_packet_workload
    from repro.graphs.generators import random_degree_graph

    adj = random_degree_graph(n, seed=5)
    spec = flooded_packet_workload(adj, 6, num_threads=threads, scope=2,
                                   max_per_lp=4)
    sched = scenarios.true_failure(k, machine=0, fail_tick=200,
                                   recover_tick=800)
    out = {}
    for backend in ("single", "distributed"):
        cfg = DESConfig(num_lps=n, num_machines=k, num_threads=threads,
                        event_capacity=capacity,
                        history_capacity=2 * capacity,
                        refine_freq=250, max_ticks=20_000,
                        refine_backend=backend)
        state = make_initial_state(cfg, jnp.arange(n, dtype=jnp.int32) % k,
                                   spec.src, spec.time, spec.count)

        def fn(adj_, state_, sched_):
            return run_simulation(cfg, adj_, state_, sched_)

        args = (jnp.asarray(adj, jnp.float32), state, sched)
        compiled, t_c = _compile(fn, *args)
        final, t_r = _run(compiled, *args)
        ticks = int(final.tick)
        end_time = float(np.max(np.asarray(final.local_time)))
        log(f"[des] {backend:11s} N={n} K={k}: compile {t_c:.3f} s, "
            f"steady {t_r:.3f} s, {ticks} ticks, {int(final.processed)} "
            f"events, {int(final.rollbacks)} rollbacks, "
            f"{int(final.refines)} refinements, {int(final.moves)} "
            f"migrations, completion time {end_time:.3f}")
        check(bool(final.done),
              f"des {backend}: not drained after {ticks} ticks")
        check(int(final.dropped) == 0 and int(final.hist_evict) == 0,
              f"des {backend}: {int(final.dropped)} proposals dropped, "
              f"{int(final.hist_evict)} history records evicted")
        check(int(final.refines) > 0, f"des {backend}: never repartitioned")
        check(np.isfinite(end_time) and end_time > 0,
              f"des {backend}: completion time {end_time}")
        out[backend] = ticks
    return out


def multichip_phase(n: int, k: int, num_shards: int) -> dict:
    """``refine_distributed_shard_map`` across ``num_shards`` devices
    against the controller's ``refine`` on the same instance: the same
    assignment and the same move count, on distinct devices."""
    import numpy as np
    import jax
    from repro.core.refine import refine
    from repro.distributed.runtime import refine_distributed_shard_map

    prob, r0 = dense_instance(n, k)
    t0 = time.perf_counter()
    ref = jax.block_until_ready(refine(prob, r0))
    log(f"[multichip] controller refine: {time.perf_counter() - t0:.3f} s "
        f"(compile included), {int(ref.num_moves)} moves")
    t0 = time.perf_counter()
    res = jax.block_until_ready(
        refine_distributed_shard_map(prob, r0, "c", num_shards=num_shards))
    devices = res.assignment.sharding.device_set
    log(f"[multichip] shard_map on {num_shards} shards: "
        f"{time.perf_counter() - t0:.3f} s (compile included), "
        f"{int(res.num_moves)} moves, {int(res.num_turns)} turns, devices "
        f"{sorted(d.id for d in devices)}")
    check(len(devices) == num_shards,
          f"shard_map result lives on {len(devices)} devices")
    check(bool(res.converged) and bool(ref.converged), "not converged")
    check(np.array_equal(np.asarray(res.assignment),
                         np.asarray(ref.assignment)), "assignments differ")
    check(int(res.num_moves) == int(ref.num_moves),
          f"moves {int(res.num_moves)} vs controller {int(ref.num_moves)}")
    return {"moves": int(res.num_moves)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform}); nothing "
              "was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2
    log(f"device {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {cache}")

    if args.chips == 4:
        multichip_phase(4096, 8, num_shards=4)
    else:
        dense_phase("paper", *paper_instance(), require_kernels=True)
        dense_phase("N=4096", *dense_instance(4096, 8), require_kernels=True)
        sparse_phase(1 << 20, 8, require_kernels=True)
        des_phase(1024, 4)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
