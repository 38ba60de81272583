"""Software archetype of an optimistic (Time Warp) parallel DES (paper §6 + App. B).

This is the paper's evaluation substrate, re-expressed as a vectorized JAX
program: one wall-clock tick is one fused XLA computation over all LPs
(DESIGN.md §3.1).  The model implements, faithfully to the paper's Fig. 3-6
pseudocode:

  * per-LP event lists / histories with ``event-tick`` wall-clock transfer
    delays (inter-machine > intra-machine — the rollback-risk mechanism),
  * optimistic execution: an idle LP picks its lowest-timestamp ready event
    and advances its local virtual time,
  * ``busy-time = (#LPs on my machine) x process_time(type)`` — the paper's
    machine-speed model (speed inversely proportional to resident LPs),
  * non-causal stragglers -> rollback: history entries with time > the
    straggler's timestamp are restored to the event list and re-executed,
  * anti-messages: a rolling-back LP sends a ROLLBACK event to its neighbors
    carrying the minimum invalidated child timestamp; the receiver cancels
    matching unprocessed events and cascades if it already processed them
    (classic rollback-announcement Time Warp, see DESIGN.md §3),
  * GVT = min(local times, event timestamps) and fossil collection of
    history entries older than GVT,
  * the limited-scope flooded packet-flow workload: completed events with
    hop count > 0 forward to every neighbor that has not yet seen the
    thread,
  * periodic partition refinement: every ``refine_freq`` ticks node/edge
    weights are measured from the live event lists (b_i = event-list length,
    c_ij = mutual pending-spawn counts, §6.1) and the game-theoretic
    refinement reassigns LPs to machines.

Deviations from the prose (documented in DESIGN.md §3/§8):

  * per (sender, receiver) pair at most one message per tick — multiple
    anti-messages coalesce into one announcement carrying the min cancelled
    timestamp, which is the standard Time Warp optimization;
  * the paper's Fig. 6 dedup ("if current-event not present in event list
    or history of neighbor") reads the receiver's *optimistic wall-clock*
    state, which is not causally safe: a node that optimistically received
    a thread via a long path would refuse the (simulation-time-earlier)
    short-path copy and flood with a smaller hop budget than sequential
    execution would — the one thing a Time Warp simulator must never do.
    We implement the timestamp-aware variant: ``seen_time[n, t]`` tracks
    the earliest receipt timestamp per (LP, thread); a copy is forwarded
    iff strictly earlier than the receiver's current earliest, received
    later copies are consumed as duplicates (recorded in history so
    cancellations can revive them), and ``seen_time`` is recomputed from
    the live records each tick so rollbacks restore it automatically.
    tests/test_des.py::test_flood_closure_oracle proves the result: the
    final seen-sets equal the exact k-hop closures under any placement,
    delays, stragglers and rollbacks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import costs as game_costs
from ..core.problem import PartitionProblem
from ..core.refine import refine
from .scenarios import SpeedSchedule, segment_at, speeds_at

Array = jax.Array

NORMAL = 0
ROLLBACK = 1

_INF = jnp.float32(3.0e38)
_BIG_I = jnp.int32(0x3FFFFFFF)

# Declared asymptotic budget for the DES tick, consumed by the
# complexity analyzers (DESIGN.md §18).  The engine consumes the dense
# (N, N) topology and the router scatters over (lp, slot, dest-lp)
# windows, so the tick legitimately stages O(N^2)-shaped intermediates
# (event_capacity is a static constant, not a problem dimension).
DES_COMPLEXITY = {
    "mem": {"n": 2.0, "k": 1.0},
    "ops": {"n": 2.0, "k": 1.0},
}


@dataclasses.dataclass(frozen=True)
class DESConfig:
    num_lps: int
    num_machines: int
    num_threads: int
    event_capacity: int = 24
    history_capacity: int = 48
    proc_ticks: int = 2           # get_process_time(NORMAL) base cost
    inter_delay: int = 6          # event-tick for cross-machine transfer
    intra_delay: int = 1          # event-tick for same-machine transfer
    hop_sim_latency: float = 1.0  # simulation-time increment per hop
    max_ticks: int = 20_000
    # heterogeneous machines (DESIGN.md §11): relative per-machine speeds
    # (1.0 = nominal; busy-time divides by the resident machine's speed).
    # None = uniform.  A SpeedSchedule passed to run_simulation/des_tick
    # overrides this per tick (speed churn scenarios, des/scenarios.py).
    machine_speeds: tuple[float, ...] | None = None
    # partition refinement
    refine_freq: int = 0          # 0 = never refine
    refine_framework: str = game_costs.C_FRAMEWORK
    refine_max_turns: int = 256
    refine_mu: float = 8.0
    # "single" = the single-controller loop of core/refine.py;
    # "distributed" = the sharded O(K)-exchange runtime of
    # repro.distributed (DESIGN.md §9) — same fixed points, but the
    # repartition step itself runs as the sharded protocol.
    refine_backend: str = "single"
    refine_num_shards: int = 0    # 0 = one shard per machine
    # Both backends run the incremental aggregate-state path (DESIGN.md
    # §10) by default; for the single backend, refine_verify_every=M > 0
    # additionally cross-checks the carried aggregate against a rebuild
    # every M turns of each refinement round (drift-bounding knob for
    # long-running simulations).
    refine_incremental: bool = True
    refine_verify_every: int = 0
    # migration-aware hysteresis (DESIGN.md §11): an LP migrates only when
    # its dissatisfaction exceeds theta_i = refine_theta_scale * its live
    # state size (event-list + history occupancy — the records a migration
    # must ship).  0 = migration treated as free (today's behavior).
    refine_theta_scale: float = 0.0
    # transfer freeze: a migrated LP is frozen for
    # round(migration_freeze * state_size * inter_delay) wall ticks (the
    # state transfer it must wait for), so load traces reflect thrashing.
    # 0 = instantaneous migration (today's behavior).
    migration_freeze: float = 0.0
    # load trace (Figs 9/10)
    trace_stride: int = 50
    max_trace: int = 512


class EventLists(NamedTuple):
    time: Array     # (N, E) f32 — simulation timestamp
    thread: Array   # (N, E) i32 — flood-thread id (-1 for rollback events)
    typ: Array      # (N, E) i32 — NORMAL / ROLLBACK
    tick: Array     # (N, E) i32 — wall ticks before the event is processable
    count: Array    # (N, E) i32 — remaining hop count (NORMAL) or the
                    #              invalidated send-epoch (ROLLBACK)
    sender: Array   # (N, E) i32 — LP that sent the event (-1 = initial)
    epoch: Array    # (N, E) i32 — sender's send-epoch when the message left
    valid: Array    # (N, E) bool


class History(NamedTuple):
    time: Array     # (N, H) f32
    thread: Array   # (N, H) i32
    count: Array    # (N, H) i32
    sender: Array   # (N, H) i32
    epoch: Array    # (N, H) i32
    dup: Array      # (N, H) bool — consumed as duplicate (never processed/
                    #               forwarded); revived if the canonical copy
                    #               is cancelled
    valid: Array    # (N, H) bool


class DESState(NamedTuple):
    ev: EventLists
    hist: History
    local_time: Array   # (N,) f32
    busy: Array         # (N,) bool
    busy_tick: Array    # (N,) i32
    cur_time: Array     # (N,) f32 — event currently being processed
    cur_thread: Array   # (N,) i32
    cur_count: Array    # (N,) i32
    cur_sender: Array   # (N,) i32 — sender of the event being processed
    machine: Array      # (N,) i32
    seen_time: Array    # (N, T) f32 — earliest receipt timestamp (_INF = never)
    epoch: Array        # (N,) i32 — per-LP send epoch; bumped on every
                        #            rollback so anti-messages cancel ONLY
                        #            messages sent before the rollback
                        #            (re-sends carry the new epoch and are
                        #            immune — the 1:1 anti-message pairing
                        #            of classic Time Warp, aggregated)
    tick: Array         # ()  i32 — wall clock
    gvt: Array          # ()  f32 — global virtual time
    done: Array         # ()  bool
    # statistics
    rollbacks: Array    # () i32 — rollback occurrences (straggler + anti-msg)
    processed: Array    # () i32 — events processed to completion
    dropped: Array      # () i32 — proposals dropped for capacity (should be 0)
    hist_evict: Array   # () i32 — history evictions (should be 0)
    refines: Array      # () i32 — refinement rounds executed
    moves: Array        # () i32 — LP migrations applied by refinement
    # load trace (Figs 9/10): mean event-list length per machine over time
    trace: Array        # (max_trace, K) f32
    # speed-normalized machine backlog Q_k / w_k at the same trace ticks:
    # drain rate is proportional to machine speed, so equal Q_k/w_k means
    # equal time-to-drain — the L_k/w_k balance of Eq. 8 (DESIGN.md §11)
    trace_wload: Array  # (max_trace, K) f32
    trace_ptr: Array    # () i32

    @property
    def seen(self) -> Array:
        """(N, T) bool — which LPs have (validly) received each thread."""
        return self.seen_time < _INF / 2


def make_initial_state(cfg: DESConfig, machine0: Array,
                       thread_src: Array, thread_time: Array,
                       thread_count: Array) -> DESState:
    """Seed each flood thread into its source LP's event list at t=0."""
    N, E, H, T = (cfg.num_lps, cfg.event_capacity, cfg.history_capacity,
                  cfg.num_threads)
    ev = EventLists(
        time=jnp.full((N, E), _INF),
        thread=jnp.full((N, E), -1, jnp.int32),
        typ=jnp.zeros((N, E), jnp.int32),
        tick=jnp.zeros((N, E), jnp.int32),
        count=jnp.zeros((N, E), jnp.int32),
        sender=jnp.full((N, E), -1, jnp.int32),
        epoch=jnp.zeros((N, E), jnp.int32),
        valid=jnp.zeros((N, E), bool),
    )
    # place thread t into slot = running count of earlier threads at the
    # same source (host-side guarantees counts fit in E)
    thread_src = jnp.asarray(thread_src, jnp.int32)
    same_src_before = jnp.sum(
        (thread_src[None, :] == thread_src[:, None])
        & (jnp.arange(T)[None, :] < jnp.arange(T)[:, None]), axis=1)
    slots = same_src_before.astype(jnp.int32)
    ev = ev._replace(
        time=ev.time.at[thread_src, slots].set(jnp.asarray(thread_time, jnp.float32)),
        thread=ev.thread.at[thread_src, slots].set(jnp.arange(T, dtype=jnp.int32)),
        count=ev.count.at[thread_src, slots].set(jnp.asarray(thread_count, jnp.int32)),
        valid=ev.valid.at[thread_src, slots].set(True),
    )
    # seen_time starts unknown everywhere; the injected event-list records
    # themselves define the sources' receipt times (recomputed every tick).
    seen_time0 = jnp.full((N, T), _INF)
    hist = History(
        time=jnp.full((N, H), _INF),
        thread=jnp.full((N, H), -1, jnp.int32),
        count=jnp.zeros((N, H), jnp.int32),
        sender=jnp.full((N, H), -1, jnp.int32),
        epoch=jnp.zeros((N, H), jnp.int32),
        dup=jnp.zeros((N, H), bool),
        valid=jnp.zeros((N, H), bool),
    )
    K = cfg.num_machines
    return DESState(
        ev=ev, hist=hist,
        local_time=jnp.zeros((N,), jnp.float32),
        busy=jnp.zeros((N,), bool),
        busy_tick=jnp.zeros((N,), jnp.int32),
        cur_time=jnp.full((N,), _INF),
        cur_thread=jnp.full((N,), -1, jnp.int32),
        cur_count=jnp.zeros((N,), jnp.int32),
        cur_sender=jnp.full((N,), -1, jnp.int32),
        machine=jnp.asarray(machine0, jnp.int32),
        seen_time=seen_time0,
        epoch=jnp.zeros((N,), jnp.int32),
        tick=jnp.zeros((), jnp.int32),
        gvt=jnp.zeros((), jnp.float32),
        done=jnp.zeros((), bool),
        rollbacks=jnp.zeros((), jnp.int32),
        processed=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        hist_evict=jnp.zeros((), jnp.int32),
        refines=jnp.zeros((), jnp.int32),
        moves=jnp.zeros((), jnp.int32),
        trace=jnp.zeros((cfg.max_trace, K), jnp.float32),
        trace_wload=jnp.zeros((cfg.max_trace, K), jnp.float32),
        trace_ptr=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# One wall-clock tick
# ---------------------------------------------------------------------------

def _base_speeds(cfg: DESConfig) -> Array:
    """(K,) static relative machine speeds from the config (1.0 = nominal)."""
    if cfg.machine_speeds is None:
        return jnp.ones((cfg.num_machines,), jnp.float32)
    if len(cfg.machine_speeds) != cfg.num_machines:
        raise ValueError(
            f"machine_speeds has {len(cfg.machine_speeds)} entries for "
            f"{cfg.num_machines} machines")
    return jnp.asarray(cfg.machine_speeds, jnp.float32)


def _live_state_size(state: DESState) -> Array:
    """(N,) per-LP live state size: event-list + history occupancy — the
    records a migration must ship (sizes theta and the transfer freeze)."""
    return (jnp.sum(state.ev.valid, axis=1)
            + jnp.sum(state.hist.valid, axis=1)).astype(jnp.float32)


def _select_events(ev: EventLists, idle: Array):
    """Per LP: pick the lowest-timestamp ready event (tick == 0); among ties
    prefer ROLLBACK events, then the lowest slot.  Returns (has, slot)."""
    ready = ev.valid & (ev.tick == 0)
    ts = jnp.where(ready, ev.time, _INF)
    mints = jnp.min(ts, axis=1)
    has = idle & (mints < _INF)
    E = ev.time.shape[1]
    cand = ready & (ts <= mints[:, None])
    score = jnp.where(cand,
                      (ev.typ == ROLLBACK).astype(jnp.int32) * (2 * E)
                      + (E - 1 - jnp.arange(E)[None, :]),
                      -1)
    slot = jnp.argmax(score, axis=1).astype(jnp.int32)
    return has, slot


def _lower_coalesced(time: Array, sender: Array, slot_rb: Array,
                     coalesce: Array, ann_time: Array) -> Array:
    """Lower each coalesced ROLLBACK event to its sender's new threshold:
    ``time[r, e] = min(time[r, e], ann_time[s])`` where ``s = sender[r,
    e]`` coalesces into receiver r (``coalesce[r, s]``) at this very slot
    (``slot_rb[r, s] == e``).  Only a slot's own sender can match it, so
    an (R, E) gather gives what a scatter-min of the (R, S) announcements
    into (R, E) would — without the scatter's colliding writes, which a
    TPU serialises."""
    s = jnp.clip(sender, 0)                                    # (R, E)
    e_ids = jnp.arange(time.shape[1], dtype=slot_rb.dtype)[None, :]
    hit = (jnp.take_along_axis(coalesce, s, axis=1)
           & (jnp.take_along_axis(slot_rb, s, axis=1) == e_ids))
    return jnp.where(hit, jnp.minimum(time, ann_time[s]), time)


def _insert_proposals(ev: EventLists, prop_valid: Array,
                      props: tuple) -> tuple[EventLists, Array]:
    """Capacity-ranked insertion: receiver r's q-th valid proposal (in
    proposal order, ``prop_valid`` being (P, N)) fills its q-th free slot
    (in slot order); proposals beyond its free slots are dropped.
    ``props`` holds the (P, N) proposal fields in :class:`EventLists`
    order (``valid`` excluded).  Returns the new lists and the number of
    dropped proposals.

    Each free slot gathers its proposal — the first p whose running
    count of valid proposals reaches the slot's free rank + 1 — so no
    (P, N)-update scatter is staged (a TPU serialises its writes)."""
    free = ~ev.valid                                           # (N, E)
    free_rank = jnp.cumsum(free.astype(jnp.int32), axis=1) - 1    # (N, E)
    prop_csum = jnp.cumsum(prop_valid.astype(jnp.int32), axis=0)  # (P, N)
    num_props = prop_csum[-1]                                  # (N,)
    dropped = jnp.sum(num_props - jnp.minimum(num_props,
                                              jnp.sum(free, axis=1)))
    fill = free & (free_rank < num_props[:, None])             # (N, E)
    src = jax.vmap(jnp.searchsorted, in_axes=(1, 0))(
        prop_csum, free_rank + 1)                              # (N, E)
    src = jnp.minimum(src, prop_valid.shape[0] - 1).T          # (E, N)

    def insert(field, prop):
        got = jnp.take_along_axis(prop, src, axis=0).T         # (N, E)
        return jnp.where(fill, got.astype(field.dtype), field)

    fields = [insert(f, p) for f, p in zip(ev[:-1], props, strict=True)]
    return EventLists(*fields, valid=ev.valid | fill), dropped


def des_tick(cfg: DESConfig, adj: Array, state: DESState,
             speed_schedule: SpeedSchedule | None = None,
             emit_tick=None, emit_refine=None) -> DESState:
    """Advance the simulator by one wall-clock tick.

    ``speed_schedule`` (optional) supplies the per-machine speeds in
    effect this tick (speed-churn scenarios, :mod:`repro.des.scenarios`);
    otherwise ``cfg.machine_speeds`` applies throughout.

    ``emit_tick`` / ``emit_refine`` (DESIGN.md §14.3) are host callback
    targets for telemetry: at ``trace_stride`` cadence a cond-gated
    ``jax.debug.callback`` streams one tick row (GVT, counters, backlog
    CV, schedule segment, frozen-LP count), and each executed refinement
    round streams one refine row.  ``None`` (default) traces the exact
    pre-telemetry program — no callbacks in the jaxpr.
    """
    N, E, H = cfg.num_lps, cfg.event_capacity, cfg.history_capacity
    K = cfg.num_machines
    ev, hist = state.ev, state.hist
    nbr = adj > 0
    rows = jnp.arange(N)
    speeds = _base_speeds(cfg) if speed_schedule is None \
        else speeds_at(speed_schedule, state.tick)
    # speed <= 0 means "machine down" (DESIGN.md §15.5): its LPs are
    # quarantined for the segment — no event selection, no busy-time
    # countdown, no completions — so the queue freezes in place instead of
    # dividing by zero (the old code fed speed=0 straight into the busy
    # ceil, producing inf -> int32).  Frozen local clocks hold GVT back,
    # so no surviving LP can fossil-collect past the down machine's
    # unprocessed events; when the schedule restores the speed the queue
    # drains normally.  All-positive speeds leave every gate constant-
    # false and the tick bitwise-identical.
    lp_down = speeds[state.machine] <= 0.0

    # ---- P0: transfer-delay countdown (only events already in lists) -------
    ev = ev._replace(tick=jnp.maximum(ev.tick - (ev.valid & (ev.tick > 0)), 0))

    # ---- P0b: recompute seen_time from the live records --------------------
    # seen_time[n, t] = earliest receipt timestamp of thread t at LP n,
    # derived from (a) pending event-list copies, (b) history (processed or
    # duplicate) copies, (c) the permanent part: receipts older than GVT can
    # never be rolled back (their records fossil-collect at exactly the same
    # threshold).  Recomputing instead of patching makes cancellation /
    # restore automatically consistent (DESIGN.md deviation note).
    Tn = cfg.num_threads
    tids = jnp.arange(Tn, dtype=jnp.int32)
    ev_match = ev.valid[:, :, None] & (ev.thread[:, :, None] == tids)
    ev_seen = jnp.min(jnp.where(ev_match, ev.time[:, :, None], _INF), axis=1)
    hist_match = hist.valid[:, :, None] & (hist.thread[:, :, None] == tids)
    hist_seen = jnp.min(jnp.where(hist_match, hist.time[:, :, None], _INF),
                        axis=1)
    perm = jnp.where(state.seen_time < state.gvt, state.seen_time, _INF)
    seen_time = jnp.minimum(jnp.minimum(ev_seen, hist_seen), perm)

    # ---- P1: busy LPs advance; completions forward the flood ---------------
    # (down machines' LPs neither count down nor complete — frozen mid-job)
    was_busy = state.busy
    busy_tick = jnp.where(was_busy & ~lp_down, state.busy_tick - 1,
                          state.busy_tick)
    completed = was_busy & ~lp_down & (busy_tick <= 0)
    still_busy = was_busy & ~completed
    # transfer-freeze completions (cur_thread == -1, no event in flight —
    # see _refine_partition) release the LP without counting as processed
    processed = state.processed + jnp.sum(
        (completed & (state.cur_thread >= 0)).astype(jnp.int32))

    fwd_send = completed & (state.cur_count > 0)
    fwd_thread = state.cur_thread
    fwd_time = state.cur_time + cfg.hop_sim_latency
    fwd_count = state.cur_count - 1

    # ---- P2: idle LPs select and locally handle one event ------------------
    # (down machines' LPs are quarantined: they select nothing this tick)
    idle = ~was_busy & ~lp_down
    has, slot = _select_events(ev, idle)
    sel_time = ev.time[rows, slot]
    sel_thread = ev.thread[rows, slot]
    sel_typ = ev.typ[rows, slot]
    sel_count = ev.count[rows, slot]
    sel_sender = ev.sender[rows, slot]

    # duplicate: a strictly earlier copy of this thread is already known —
    # consume without processing (sequential semantics discard duplicates).
    # Recorded in history below so a cancellation of the earlier copy can
    # restore and re-canonicalize this one.
    sel_seen = seen_time[rows, jnp.clip(sel_thread, 0)]
    dup = has & (sel_typ == NORMAL) & (sel_time > sel_seen + 1e-6)

    is_rb = has & (sel_typ == ROLLBACK)
    normal = has & (sel_typ == NORMAL) & ~dup \
        & (sel_time >= state.local_time)
    straggler = has & (sel_typ == NORMAL) & ~dup \
        & (sel_time < state.local_time)

    # consume the selected slot
    ev_valid = ev.valid.at[rows, slot].set(
        jnp.where(has, False, ev.valid[rows, slot]))
    ev = ev._replace(valid=ev_valid)

    # -- rollback-event handling (anti-message with threshold sel_time) -----
    # A ROLLBACK event carries the sender's invalidated send-epoch in its
    # ``count`` field: only messages sent at-or-before that epoch cancel.
    # Messages the sender re-emits AFTER rolling back carry a later epoch
    # and must survive (classic Time Warp 1:1 message/anti-message pairing,
    # aggregated per (sender, epoch, time-threshold)).
    rb_epoch = sel_count
    # cancel unprocessed events from that sender at/after the threshold
    cancel_ev = (is_rb[:, None] & ev.valid
                 & (ev.sender == sel_sender[:, None])
                 & (ev.typ == NORMAL)
                 & (ev.epoch <= rb_epoch[:, None])
                 & (ev.time >= sel_time[:, None] - 1e-6))
    # cascaded rollback: processed events from that sender at/after threshold
    cancel_hist = (is_rb[:, None] & hist.valid
                   & (hist.sender == sel_sender[:, None])
                   & (hist.epoch <= rb_epoch[:, None])
                   & (hist.time >= sel_time[:, None] - 1e-6))
    any_casc = jnp.any(cancel_hist, axis=1)
    t_inv = jnp.min(jnp.where(cancel_hist, hist.time, _INF), axis=1)

    # restore masks: straggler restores history strictly after its timestamp;
    # cascaded rollback restores history at/after the first invalidated time
    # (minus the cancelled entries themselves, which are deleted).
    restore = jnp.where(
        straggler[:, None], hist.valid & (hist.time > sel_time[:, None]),
        jnp.where((is_rb & any_casc)[:, None],
                  hist.valid & (hist.time >= t_inv[:, None]) & ~cancel_hist,
                  False))

    rolled_back = straggler | (is_rb & any_casc)
    rollbacks = state.rollbacks + jnp.sum(rolled_back.astype(jnp.int32))

    # duplicate revival: if a cancellation removed copies of thread t at this
    # LP, any surviving history entry consumed as a DUPLICATE of that thread
    # becomes a candidate canonical again — push it back to the event list.
    Tn_ = cfg.num_threads
    tids_ = jnp.arange(Tn_, dtype=jnp.int32)
    cancelled_threads = (
        jnp.any(cancel_ev[:, :, None]
                & (ev.thread[:, :, None] == tids_), axis=1)
        | jnp.any(cancel_hist[:, :, None]
                  & (hist.thread[:, :, None] == tids_), axis=1))  # (N, T)
    revive = (hist.valid & hist.dup & (hist.thread >= 0) & ~cancel_hist
              & jnp.take_along_axis(
                  cancelled_threads, jnp.clip(hist.thread, 0), axis=1))
    restore = restore | revive

    # announcements: min invalidated *child* timestamp per rolling-back LP.
    # children were forwarded only for PROCESSED entries with hop count > 0
    # (duplicate entries never forwarded — excluding them keeps the cancel
    # threshold tight so valid earlier sends are not over-cancelled).
    inval = (restore | cancel_hist) & (hist.count > 0) & ~hist.dup
    ann_time = jnp.min(jnp.where(inval, hist.time, _INF), axis=1) \
        + cfg.hop_sim_latency
    ann_send = rolled_back & jnp.any(inval, axis=1)
    # the announcement invalidates everything this LP sent up to its CURRENT
    # epoch; the rollback itself then opens a new epoch for the re-sends
    ann_epoch = state.epoch
    new_epoch = state.epoch + rolled_back.astype(jnp.int32)

    # apply cancellations / deletions (seen_time recomputes next tick, so
    # cancelled copies automatically stop counting as received)
    ev = ev._replace(valid=ev.valid & ~cancel_ev)
    hist = hist._replace(valid=hist.valid & ~cancel_hist & ~restore)

    # -- start processing (normal + straggler) -------------------------------
    # busy-time = (#resident LPs x process_time) / machine speed: the
    # paper's density model scaled by the machine's current relative speed
    # (heterogeneity + churn, DESIGN.md §11; speed 1.0 is bit-for-bit the
    # original integer cost)
    starts = normal | straggler
    nlps = jnp.zeros((K,), jnp.int32).at[state.machine].add(1)
    # a down machine's LPs never start (idle excludes them), so the guard
    # value 1.0 is never consumed — it only keeps 0-speed out of the
    # divide (inf cast to int32 is implementation-defined)
    live_speed = jnp.where(speeds[state.machine] > 0.0,
                           speeds[state.machine], 1.0)
    busy_cost = jnp.maximum(jnp.ceil(
        (nlps[state.machine] * cfg.proc_ticks).astype(jnp.float32)
        / live_speed).astype(jnp.int32), 1)
    busy = still_busy | starts
    busy_tick = jnp.where(starts, busy_cost, busy_tick)
    cur_time = jnp.where(starts, sel_time, state.cur_time)
    cur_thread = jnp.where(starts, sel_thread, state.cur_thread)
    cur_count = jnp.where(starts, sel_count, state.cur_count)
    cur_sender = jnp.where(starts, sel_sender, state.cur_sender)
    local_time = jnp.where(starts, sel_time, state.local_time)
    local_time = jnp.where(is_rb & any_casc,
                           jnp.minimum(local_time, t_inv), local_time)

    # push started + duplicate events into history (first free slot; evict
    # oldest if full).  Duplicates are retained so that cancellation of the
    # canonical copy restores them as the new canonical.
    free_h = ~hist.valid
    has_free = jnp.any(free_h, axis=1)
    first_free = jnp.argmax(free_h, axis=1)
    oldest = jnp.argmin(jnp.where(hist.valid, hist.time, _INF), axis=1)
    hslot = jnp.where(has_free, first_free, oldest).astype(jnp.int32)
    put = starts | dup
    hist_evict = state.hist_evict + jnp.sum(
        (put & ~has_free).astype(jnp.int32))
    sel_epoch = ev.epoch[rows, slot]
    hist = History(
        time=hist.time.at[rows, hslot].set(
            jnp.where(put, sel_time, hist.time[rows, hslot])),
        thread=hist.thread.at[rows, hslot].set(
            jnp.where(put, sel_thread, hist.thread[rows, hslot])),
        count=hist.count.at[rows, hslot].set(
            jnp.where(put, sel_count, hist.count[rows, hslot])),
        sender=hist.sender.at[rows, hslot].set(
            jnp.where(put, sel_sender, hist.sender[rows, hslot])),
        epoch=hist.epoch.at[rows, hslot].set(
            jnp.where(put, sel_epoch, hist.epoch[rows, hslot])),
        dup=hist.dup.at[rows, hslot].set(
            jnp.where(put, dup, hist.dup[rows, hslot])),
        valid=hist.valid.at[rows, hslot].set(
            jnp.where(put, True, hist.valid[rows, hslot])),
    )

    # ---- P3: proposals (forwards + announcements + self-restores) ----------
    same_machine = state.machine[:, None] == state.machine[None, :]
    link_tick = jnp.where(same_machine, cfg.intra_delay, cfg.inter_delay
                          ).astype(jnp.int32)

    # forwards: ALWAYS re-forward except where suppression is provably safe.
    # Optimistic reads of the receiver's state (the paper's Fig. 6 check)
    # lose messages under rollback races, so the only two safe gates are:
    #   (a) echo suppression — never send back along the edge the copy
    #       arrived on (the parent's receipt is a causal ancestor of this
    #       send, so if this send is valid the parent has the thread);
    #   (b) permanent receipt — the receiver's earliest receipt is older
    #       than GVT, hence can never be rolled back.
    # Everything else is delivered and consumed as a duplicate at the
    # receiver (recorded in history, revivable on cancellation).
    s_grid = jnp.arange(N, dtype=jnp.int32)[:, None]
    fwd_pair = fwd_send[:, None] & nbr                       # (S, R)
    fwd_pair = fwd_pair & (jnp.arange(N)[None, :] != cur_sender[:, None])
    perm_seen = jnp.where(seen_time < state.gvt, seen_time, _INF)
    recv_perm = perm_seen.T[fwd_thread.clip(0)]              # (S, R)
    fwd_pair = fwd_pair & ~(recv_perm <= fwd_time[:, None] + 1e-6)

    ann_pair = ann_send[:, None] & nbr                       # (S, R)

    # Coalesce announcements: if the receiver already holds a ROLLBACK event
    # from the same sender *and the same epoch*, lower its threshold in
    # place instead of queueing a second one (only the minimum cancel-time
    # matters within an epoch; across epochs the events must stay distinct
    # or re-sends would be over-cancelled).
    sender_ids = jnp.arange(N, dtype=jnp.int32)
    rb_match = (ev.valid & (ev.typ == ROLLBACK))[:, :, None] \
        & (ev.sender[:, :, None] == sender_ids[None, None, :]) \
        & (ev.count[:, :, None] == ann_epoch[None, None, :])     # (R, E, S)
    has_rb = jnp.any(rb_match, axis=1)                           # (R, S)
    slot_rb = jnp.argmax(rb_match, axis=1).astype(jnp.int32)     # (R, S)
    coalesce = ann_pair.T & has_rb                               # (R, S)
    ev = ev._replace(time=_lower_coalesced(ev.time, ev.sender, slot_rb,
                                           coalesce, ann_time))
    ann_pair = ann_pair & ~coalesce.T

    P = N + H
    prop_valid = jnp.zeros((P, N), bool)
    prop_valid = prop_valid.at[:N].set(fwd_pair | ann_pair)
    prop_valid = prop_valid.at[N:].set(restore.T)

    def sender_field(fwd_f, ann_f):
        return jnp.where(fwd_pair, fwd_f[:, None],
                         jnp.where(ann_pair, ann_f[:, None], 0))

    prop_time = jnp.concatenate([
        jnp.where(fwd_pair, fwd_time[:, None],
                  jnp.where(ann_pair, ann_time[:, None], _INF)),
        jnp.where(restore.T, state.hist.time.T, _INF),
    ], axis=0)
    prop_thread = jnp.concatenate([
        sender_field(fwd_thread, jnp.full((N,), -1, jnp.int32)),
        jnp.where(restore.T, state.hist.thread.T, -1),
    ], axis=0).astype(jnp.int32)
    prop_typ = jnp.concatenate([
        jnp.where(ann_pair, ROLLBACK, NORMAL).astype(jnp.int32),
        jnp.zeros((H, N), jnp.int32),
    ], axis=0)
    prop_count = jnp.concatenate([
        sender_field(fwd_count, ann_epoch),          # RB carries its epoch
        jnp.where(restore.T, state.hist.count.T, 0),
    ], axis=0).astype(jnp.int32)
    prop_tick = jnp.concatenate([
        jnp.where(fwd_pair | ann_pair, link_tick, 0),
        jnp.zeros((H, N), jnp.int32),
    ], axis=0).astype(jnp.int32)
    prop_sender = jnp.concatenate([
        jnp.where(fwd_pair | ann_pair, s_grid, -1),
        jnp.where(restore.T, state.hist.sender.T, -1),
    ], axis=0).astype(jnp.int32)
    # forwards are stamped with the sender's POST-rollback epoch (a sender
    # never both completes a forward and rolls back in the same tick, so
    # for actual forwarders new_epoch == old epoch); restores keep the
    # original message's epoch so later anti-messages still match them.
    prop_epoch = jnp.concatenate([
        jnp.where(fwd_pair | ann_pair, new_epoch[:, None], 0),
        jnp.where(restore.T, state.hist.epoch.T, 0),
    ], axis=0).astype(jnp.int32)

    # ---- P4: capacity-ranked insertion -------------------------------------
    ev, n_dropped = _insert_proposals(
        ev, prop_valid, (prop_time, prop_thread, prop_typ, prop_tick,
                         prop_count, prop_sender, prop_epoch))
    dropped = state.dropped + n_dropped

    # accepted forwards enter the receiver's event list, so next tick's
    # seen_time recomputation picks them up automatically.

    # ---- P5: GVT, fossil collection, termination, trace ---------------------
    ev_min = jnp.min(jnp.where(ev.valid, ev.time, _INF))
    busy_min = jnp.min(jnp.where(busy, cur_time, _INF))
    lt_min = jnp.min(local_time)
    gvt = jnp.minimum(jnp.minimum(ev_min, busy_min), lt_min)
    hist = hist._replace(valid=hist.valid & (hist.time >= gvt))
    done = (~jnp.any(ev.valid)) & (~jnp.any(busy))

    tick = state.tick + 1
    lens = jnp.sum(ev.valid, axis=1).astype(jnp.float32)
    nlps_f = jnp.maximum(
        jnp.zeros((K,), jnp.float32).at[state.machine].add(1.0), 1.0)
    total_len = jnp.zeros((K,), jnp.float32).at[state.machine].add(lens)
    mean_len = total_len / nlps_f
    wload = total_len / jnp.maximum(speeds, 1e-6)
    # the trace stops (rather than overwriting its last row) once full:
    # trace_ptr is clamped to max_trace so downstream slicing with it is
    # always in bounds
    do_trace = (tick % cfg.trace_stride == 0) \
        & (state.trace_ptr < cfg.max_trace)
    ptr = jnp.clip(state.trace_ptr, 0, cfg.max_trace - 1)
    trace = jnp.where(do_trace,
                      state.trace.at[ptr].set(mean_len), state.trace)
    trace_wload = jnp.where(do_trace,
                            state.trace_wload.at[ptr].set(wload),
                            state.trace_wload)
    trace_ptr = jnp.minimum(state.trace_ptr + do_trace.astype(jnp.int32),
                            cfg.max_trace)

    new_state = state._replace(
        ev=ev, hist=hist, local_time=local_time, busy=busy,
        busy_tick=busy_tick, cur_time=cur_time, cur_thread=cur_thread,
        cur_count=cur_count, cur_sender=cur_sender, seen_time=seen_time,
        epoch=new_epoch, tick=tick, gvt=gvt, done=done,
        rollbacks=rollbacks, processed=processed, dropped=dropped,
        hist_evict=hist_evict, trace=trace, trace_wload=trace_wload,
        trace_ptr=trace_ptr)

    # ---- P6: periodic partition refinement (the paper's contribution) ------
    if cfg.refine_freq > 0:
        new_state = jax.lax.cond(
            (tick % cfg.refine_freq == 0) & ~done,
            lambda s: _refine_partition(cfg, adj, s, speeds,
                                        emit_refine=emit_refine),
            lambda s: s, new_state)

    # ---- P7: telemetry (DESIGN.md §14.3) -----------------------------------
    if emit_tick is not None:
        segment = (jnp.zeros((), jnp.int32) if speed_schedule is None
                   else segment_at(speed_schedule, state.tick))
        frozen = jnp.sum((new_state.busy
                          & (new_state.cur_thread == -1)).astype(jnp.int32))
        wmean = jnp.mean(wload)
        wload_cv = jnp.std(wload) / jnp.maximum(wmean, 1e-12)
        row = (tick, gvt, new_state.processed, new_state.rollbacks,
               new_state.refines, new_state.moves, jnp.mean(mean_len),
               wload_cv, segment, frozen)
        jax.lax.cond(tick % cfg.trace_stride == 0,
                     lambda: jax.debug.callback(emit_tick, *row),
                     lambda: None)
    return new_state


def _refine_partition(cfg: DESConfig, adj: Array, state: DESState,
                      speeds: Array, emit_refine=None) -> DESState:
    """Measure node/edge weights from live event lists and refine (§6.1).

    ``speeds`` is the (K,) vector of LIVE relative machine speeds this
    tick — normalized into the ``w_k`` of the cost frameworks (Eq. 1/6),
    so refinement optimizes the game the machines are actually playing.
    With ``refine_theta_scale > 0`` each LP's hysteresis threshold is
    sized by its live state (event-list + history records a migration
    must ship), and with ``migration_freeze > 0`` migrated LPs pay the
    transfer as a busy freeze (DESIGN.md §11).
    """
    K = cfg.num_machines
    b = jnp.sum(state.ev.valid, axis=1).astype(jnp.float32)
    spawn = jnp.sum(state.ev.valid & (state.ev.count > 0),
                    axis=1).astype(jnp.float32)
    c = (adj > 0).astype(jnp.float32) * (spawn[:, None] + spawn[None, :])
    live = jnp.maximum(speeds.astype(jnp.float32), 1e-6)
    prob = PartitionProblem(
        adjacency=c, node_weights=b,
        speeds=live / jnp.sum(live),
        mu=jnp.asarray(cfg.refine_mu, jnp.float32))
    state_size = _live_state_size(state)
    theta = cfg.refine_theta_scale * state_size \
        if cfg.refine_theta_scale > 0 else None
    if cfg.refine_backend == "distributed":
        from ..distributed.runtime import refine_distributed
        res = refine_distributed(prob, state.machine, cfg.refine_framework,
                                 num_shards=cfg.refine_num_shards or K,
                                 max_turns=cfg.refine_max_turns,
                                 incremental=cfg.refine_incremental,
                                 theta=theta)
    elif cfg.refine_backend == "single":
        res = refine(prob, state.machine, cfg.refine_framework,
                     max_turns=cfg.refine_max_turns,
                     incremental=cfg.refine_incremental,
                     verify_every=cfg.refine_verify_every,
                     theta=theta)
    else:
        raise ValueError(f"unknown refine_backend {cfg.refine_backend!r}")
    moved_mask = res.assignment != state.machine
    new_state = state._replace(
        machine=res.assignment,
        refines=state.refines + 1,
        moves=state.moves + jnp.sum(moved_mask.astype(jnp.int32)))
    frozen_count = jnp.zeros((), jnp.int32)
    if cfg.migration_freeze > 0:
        # the state transfer freezes the migrated LP for ticks proportional
        # to (records shipped) x (inter-machine delay); an LP mid-event
        # simply finishes that much later, an idle LP becomes busy with a
        # no-op marker (cur_thread = -1: no forward, not counted processed)
        freeze = jnp.round(cfg.migration_freeze * state_size
                           * cfg.inter_delay).astype(jnp.int32)
        frozen = moved_mask & (freeze > 0)
        newly_busy = frozen & ~state.busy
        busy_tick = jnp.where(
            frozen & state.busy, state.busy_tick + freeze,
            jnp.where(newly_busy, freeze, state.busy_tick))
        new_state = new_state._replace(
            busy=state.busy | frozen,
            busy_tick=busy_tick,
            cur_time=jnp.where(newly_busy, state.local_time, state.cur_time),
            cur_thread=jnp.where(newly_busy, -1, state.cur_thread),
            cur_count=jnp.where(newly_busy, 0, state.cur_count),
            cur_sender=jnp.where(newly_busy, -1, state.cur_sender),
        )
        frozen_count = jnp.sum(frozen.astype(jnp.int32))
    if emit_refine is not None:
        # fires only when the refinement cond branch actually executes
        jax.debug.callback(emit_refine, state.tick,
                           jnp.sum(moved_mask.astype(jnp.int32)),
                           frozen_count)
    return new_state


@partial(jax.jit, static_argnames=("cfg", "emit_tick", "emit_refine"))
def _run_simulation(cfg: DESConfig, adj: Array, state: DESState,
                    speed_schedule: SpeedSchedule | None = None,
                    emit_tick=None, emit_refine=None) -> DESState:
    def cond(s):
        return (~s.done) & (s.tick < cfg.max_ticks)

    def body(s):
        return des_tick(cfg, adj, s, speed_schedule,
                        emit_tick=emit_tick, emit_refine=emit_refine)

    return jax.lax.while_loop(cond, body, state)


def run_simulation(cfg: DESConfig, adj: Array, state: DESState,
                   speed_schedule: SpeedSchedule | None = None,
                   recorder=None) -> DESState:
    """Run ticks until all event lists drain (or max_ticks).

    ``speed_schedule`` drives per-tick machine-speed churn (slowdown /
    failure / recovery scenarios, :mod:`repro.des.scenarios`); ``None``
    keeps ``cfg.machine_speeds`` (or uniform) throughout.

    ``recorder`` (a :class:`repro.obs.Recorder`, DESIGN.md §14) opts
    into telemetry: one ``tick`` event per ``trace_stride`` ticks
    (GVT, cumulative counters, backlog CV, schedule segment, frozen
    LPs), one ``des_refine`` event per executed refinement round, and a
    closing ``run_end``.  ``recorder=None`` (default) dispatches to the
    identical jitted program — same cache entry, zero callbacks.
    """
    if recorder is None:
        return _run_simulation(cfg, adj, state, speed_schedule)
    run = recorder.new_run(
        "des", n=cfg.num_lps, k=cfg.num_machines,
        refine_freq=cfg.refine_freq, backend=cfg.refine_backend,
        trace_stride=cfg.trace_stride, theta=cfg.refine_theta_scale > 0)
    recorder.begin_rows()
    with recorder.phase("des.run_simulation", run):
        final = _run_simulation(cfg, adj, state, speed_schedule,
                                emit_tick=recorder._on_tick_row,
                                emit_refine=recorder._on_refine_row)
        jax.block_until_ready(final)
        jax.effects_barrier()
    recorder.record_des_rows(run)
    recorder.emit(
        "run_end", run, num_moves=int(final.moves),
        num_turns=int(final.tick), converged=bool(final.done),
        processed=int(final.processed), rollbacks=int(final.rollbacks),
        refines=int(final.refines), gvt=float(final.gvt))
    return final


# ---------------------------------------------------------------------------
# batched scenario fleets (DESIGN.md §12.4)
# ---------------------------------------------------------------------------

DEFAULT_BATCH_CHUNK = 256


@partial(jax.jit, static_argnames=("cfg", "chunk"))
def _run_simulation_batch(cfg: DESConfig, adjs: Array, states: DESState,
                          speed_schedules: SpeedSchedule | None = None,
                          chunk: int = DEFAULT_BATCH_CHUNK) -> DESState:
    """:func:`run_simulation` over a stack of B scenarios in one program.

    ``adjs`` is ``(B, N, N)``, ``states`` a :class:`DESState` whose
    leaves carry a leading batch axis (stack B
    :func:`make_initial_state` results), and ``speed_schedules`` is
    ``None`` or a stacked :class:`~repro.des.scenarios.SpeedSchedule`
    (``(B, S)`` times / ``(B, S, K)`` speeds — see
    :func:`repro.des.scenarios.stack_schedules`).  ``cfg`` is shared:
    the config is compile-time structure (capacities, cadences), while
    everything data-like (graph, workload, speeds) varies per element.

    A naive ``vmap(run_simulation)`` would pay the refinement branch of
    the per-tick ``lax.cond`` on EVERY tick for the whole batch (a
    batched predicate executes both branches).  Instead ticks run in
    chunks of ``cfg.refine_freq`` with refinement compiled out of the
    tick, and one vmapped refinement round applies after each chunk,
    masked per element — the same per-element cadence and cost profile
    as the looped engine (DESIGN.md §12.4).  Elements that drain (or hit
    ``max_ticks``) mid-chunk are select-masked exactly like the batched
    ``while_loop`` rule would, so every element's final state — traces
    included — is bitwise the state its own looped :func:`run_simulation`
    produces (``tests/test_sweeps.py`` + ``benchmarks/sweep_bench.py``
    pin this).  ``chunk`` only applies when ``cfg.refine_freq == 0``
    (no cadence to align with).
    """
    inner_cfg = dataclasses.replace(cfg, refine_freq=0)
    chunk = cfg.refine_freq if cfg.refine_freq > 0 \
        else max(1, min(chunk, cfg.max_ticks))
    sched_axes = None if speed_schedules is None \
        else jax.tree.map(lambda _: 0, speed_schedules)

    def masked(pred, new, old):
        return jax.tree.map(lambda a, b: jnp.where(pred, a, b), new, old)

    def tick_one(adj, s, sched):
        alive = (~s.done) & (s.tick < cfg.max_ticks)   # the while_loop cond
        return masked(alive, des_tick(inner_cfg, adj, s, sched), s)

    def refine_one(adj, s, sched, advanced):
        # des_tick refines at the END of a tick whose post-increment tick
        # hits the cadence, using that tick's live speeds — i.e. the
        # schedule row at s.tick - 1.  ``advanced`` (the element ticked
        # during this chunk) keeps an element frozen at ``max_ticks`` on
        # a cadence boundary from being re-refined every outer iteration
        # — the looped engine refines such an element exactly once.
        speeds = _base_speeds(cfg) if sched is None \
            else speeds_at(sched, s.tick - 1)
        pred = (s.tick % cfg.refine_freq == 0) & ~s.done & advanced
        return masked(pred, _refine_partition(cfg, adj, s, speeds), s)

    def chunk_body(ss):
        prev_tick = ss.tick
        def scan_body(carry, _):
            return jax.vmap(tick_one, in_axes=(0, 0, sched_axes))(
                adjs, carry, speed_schedules), None
        ss, _ = jax.lax.scan(scan_body, ss, None, length=chunk)
        if cfg.refine_freq > 0:
            ss = jax.vmap(refine_one, in_axes=(0, 0, sched_axes, 0))(
                adjs, ss, speed_schedules, ss.tick != prev_tick)
        return ss

    def cond(ss):
        return jnp.any((~ss.done) & (ss.tick < cfg.max_ticks))

    return jax.lax.while_loop(cond, chunk_body, states)


def run_simulation_batch(cfg: DESConfig, adjs: Array, states: DESState,
                         speed_schedules: SpeedSchedule | None = None,
                         chunk: int = DEFAULT_BATCH_CHUNK,
                         recorder=None) -> DESState:
    """Public batched entry point; see :func:`_run_simulation_batch`.

    ``recorder`` opts into telemetry: per-tick streaming is not
    available under the batched cond (a batched predicate executes both
    branches — exactly why refinement is hoisted out of the tick), so
    the run emits one host-side ``element`` summary per scenario after
    the fleet drains (ticks, counters, time-averaged weighted-backlog
    CV over the trace rows) plus a closing ``run_end``.
    """
    if recorder is None:
        return _run_simulation_batch(cfg, adjs, states, speed_schedules,
                                     chunk)
    from ..sweeps.metrics import time_averaged_cv
    batch = int(adjs.shape[0])
    run = recorder.new_run(
        "des_batch", n=cfg.num_lps, k=cfg.num_machines, batch=batch,
        refine_freq=cfg.refine_freq, backend=cfg.refine_backend)
    with recorder.phase("des.run_simulation_batch", run):
        final = _run_simulation_batch(cfg, adjs, states, speed_schedules,
                                      chunk)
        jax.block_until_ready(final)
    ticks = np.asarray(final.tick)
    processed = np.asarray(final.processed)
    rollbacks = np.asarray(final.rollbacks)
    refines = np.asarray(final.refines)
    moves = np.asarray(final.moves)
    done = np.asarray(final.done)
    wload = np.asarray(final.trace_wload)
    ptrs = np.asarray(final.trace_ptr)
    for i in range(batch):
        recorder.emit(
            "element", run, batch=i, ticks=int(ticks[i]),
            processed=int(processed[i]), rollbacks=int(rollbacks[i]),
            refines=int(refines[i]), moves=int(moves[i]),
            converged=bool(done[i]),
            wload_cv=time_averaged_cv(wload[i][:int(ptrs[i])]))
    recorder.emit("run_end", run, num_moves=int(moves.sum()),
                  num_turns=int(ticks.max()) if batch else 0,
                  converged=bool(done.all()))
    return final
