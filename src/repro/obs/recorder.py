"""The Recorder: event buffering, phase timing, device-row draining
(DESIGN.md §14.2–§14.3).

The Recorder is the single object an instrumented entry point needs: it
assigns run ids, buffers every event in ``self.events`` (the canonical
in-process stream), fans events out to attached sinks, times wall-clock
phases, and bridges device→host telemetry.

Host-side only: this module imports numpy, the standard library and
:mod:`repro.obs.spans`, which imports ``jax.profiler`` on first use
only (``phase`` opens a profiler annotation of its name).  All
potentially-hot device work stays in the instrumented modules; what
crosses here is either post-run arrays (trace ingestion) or the
buffered rows of a ``jax.debug.callback`` stream.

Device-row bridge
-----------------
``lax.while_loop`` runs (``repro.core.refine.refine``) cannot return
per-turn arrays, so with telemetry enabled the loop body fires one
``jax.debug.callback`` per turn at the recorder's bound method
:meth:`Recorder._on_turn_row`.  The callback only appends raw numpy
scalars to a buffer — no JSON, no sink I/O on the callback thread — and
the entry-point wrapper drains the buffer *after* ``block_until_ready``,
sorting rows by turn index (debug callbacks are unordered) before
emitting ``turn`` events.  Bound methods compare equal across attribute
accesses, so passing ``recorder._on_turn_row`` as a jit-static argument
re-uses one compile cache entry per recorder instance.

Hashing: a Recorder is hashable *by identity* (no ``__eq__``), which is
what lets instrumented entry points accept it as a jit-static argument
without ever baking its mutable state into a trace.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Iterable, Sequence

import numpy as np

from .events import make_event
from .spans import annotate

# Standing accuracy budget for carried quantities (ROADMAP contract).
DRIFT_BUDGET = 1e-3


class Recorder:
    """Buffers typed telemetry events and fans them out to sinks."""

    def __init__(self, sinks: Sequence = (), tol: float = 1e-6):
        self.sinks = list(sinks)
        self.events: list[dict] = []
        self.tol = float(tol)
        self._next_run = 0
        self._last_run: str | None = None
        self._rows: list[tuple] = []
        self._tick_rows: list[tuple] = []
        self._refine_rows: list[tuple] = []

    # ------------------------------------------------------------------
    # core emission
    # ------------------------------------------------------------------
    def new_run(self, runtime: str, **meta) -> str:
        """Open a run; returns its id (``r0000``, ``r0001``, ...)."""
        run = f"r{self._next_run:04d}"
        self._next_run += 1
        self._last_run = run
        self.emit("run_start", run, runtime=runtime, **meta)
        return run

    def emit(self, kind: str, run: str, **fields) -> dict:
        event = make_event(kind, run, **fields)
        self.events.append(event)
        for sink in self.sinks:
            sink.write(event)
        return event

    def flush(self) -> None:
        for sink in self.sinks:
            sink.flush()

    def close(self) -> None:
        for sink in self.sinks:
            close = getattr(sink, "close", None)
            if close is not None:
                close()

    @contextmanager
    def phase(self, name: str, run: str | None = None):
        """Wall-clock a span; emits one ``phase`` event on exit.  The span
        is also a ``jax.profiler.TraceAnnotation`` of ``name``, so it
        lands on the profiler's clock in any profile taken meanwhile.

        If the wrapped block raises (e.g. a jit failure before
        ``block_until_ready``), the span is still closed, a terminal
        ``run_aborted`` event records the error, and the sinks are
        flushed — everything buffered up to the abort survives on disk
        instead of being lost with the process (DESIGN.md §15.6)."""
        t0 = time.perf_counter()
        try:
            with annotate(name):
                yield
        except BaseException as exc:
            rid = run or self._last_run or "r----"
            self.emit("phase", rid, name=name, ts=t0,
                      dur=time.perf_counter() - t0)
            self.emit("run_aborted", rid, error=repr(exc),
                      pending_rows=len(self._rows) + len(self._tick_rows)
                      + len(self._refine_rows))
            self.flush()
            raise
        else:
            self.emit("phase", run or self._last_run or "r----",
                      name=name, ts=t0, dur=time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # device-row bridge (jax.debug.callback target)
    # ------------------------------------------------------------------
    def _on_turn_row(self, *cols) -> None:
        """Per-turn callback target: buffer raw scalars, nothing else."""
        self._rows.append(tuple(np.asarray(c) for c in cols))

    def _on_tick_row(self, *cols) -> None:
        """Per-DES-tick callback target (trace_stride cadence)."""
        self._tick_rows.append(tuple(np.asarray(c) for c in cols))

    def _on_refine_row(self, *cols) -> None:
        """Per-DES-refinement-round callback target."""
        self._refine_rows.append(tuple(np.asarray(c) for c in cols))

    def begin_rows(self) -> None:
        self._rows = []
        self._tick_rows = []
        self._refine_rows = []

    def take_rows(self) -> list[tuple]:
        rows, self._rows = self._rows, []
        return rows

    def record_des_rows(self, run: str) -> int:
        """Emit ``tick`` + ``des_refine`` events from the drained DES
        callback buffers (sorted by tick — callbacks are unordered).

        Tick rows are ``(t, gvt, processed, rollbacks, refines, moves,
        mean_len, wload_cv, segment, frozen)``; refine rows are
        ``(t, moves, frozen)`` — one per executed refinement round.
        """
        tick_rows, self._tick_rows = self._tick_rows, []
        refine_rows, self._refine_rows = self._refine_rows, []
        for (t, gvt, processed, rollbacks, refines, moves, mean_len,
             wload_cv, segment, frozen) in sorted(
                 tick_rows, key=lambda r: int(r[0])):
            self.emit("tick", run, t=int(t), gvt=float(gvt),
                      processed=int(processed), rollbacks=int(rollbacks),
                      refines=int(refines), moves=int(moves),
                      mean_len=float(mean_len), wload_cv=float(wload_cv),
                      segment=int(segment), frozen=int(frozen))
        for (t, moves, frozen) in sorted(refine_rows,
                                         key=lambda r: int(r[0])):
            self.emit("des_refine", run, t=int(t), moves=int(moves),
                      frozen=int(frozen))
        return len(tick_rows) + len(refine_rows)

    def record_turn_rows(self, run: str, rows: Iterable[tuple],
                         node_weights, *, carried: bool = True,
                         batch=None) -> int:
        """Emit ``turn`` events from drained device rows.

        Each row is ``(t, machine, moved, node, source, dest, gain, c0,
        ct0, raw_gain)`` as produced by the instrumented while-loop body.
        Rows are sorted by turn index (callbacks are unordered) before
        emission.
        """
        b = np.asarray(node_weights)
        rows = sorted(rows, key=lambda r: int(r[0]))
        for (t, machine, moved, node, source, dest, gain, c0, ct0,
             raw_gain) in rows:
            self._emit_turn(run, int(t), int(machine), bool(moved),
                            int(node), int(source), int(dest), float(gain),
                            float(c0) if carried else None,
                            float(ct0) if carried else None,
                            float(raw_gain), b, batch)
        return len(rows)

    # ------------------------------------------------------------------
    # post-run trace ingestion (scan entry points, distributed drivers)
    # ------------------------------------------------------------------
    def record_trace(self, run: str, trace, node_weights, num_machines: int,
                     *, raw_gain=None, carried: bool = True,
                     batch=None) -> int:
        """Emit ``turn`` events from a ``refine_traced``-shape ``Trace``.

        Works on any object with ``moved/node/source/dest/gain/c0/ct0/
        active`` arrays (the core and distributed traced drivers share
        the shape).  Only active turns are emitted; the sequential
        round-robin convention fixes the acting machine as ``t % K``.
        ``raw_gain`` (the θ-free best gain, from the telemetry side
        output) enables hysteresis-vs-satisfied rejection labels.
        """
        b = np.asarray(node_weights)
        active = np.asarray(trace.active)
        moved = np.asarray(trace.moved)
        node = np.asarray(trace.node)
        source = np.asarray(trace.source)
        dest = np.asarray(trace.dest)
        gain = np.asarray(trace.gain)
        c0 = np.asarray(trace.c0)
        ct0 = np.asarray(trace.ct0)
        raw = None if raw_gain is None else np.asarray(raw_gain)
        count = 0
        for t in range(moved.shape[0]):
            if not active[t]:
                continue
            self._emit_turn(run, t, t % int(num_machines), bool(moved[t]),
                            int(node[t]), int(source[t]), int(dest[t]),
                            float(gain[t]),
                            float(c0[t]) if carried else None,
                            float(ct0[t]) if carried else None,
                            None if raw is None else float(raw[t]),
                            b, batch)
            count += 1
        return count

    def _emit_turn(self, run, t, machine, moved, node, source, dest, gain,
                   c0, ct0, raw_gain, b, batch) -> None:
        if moved:
            reject = None
        elif raw_gain is None:
            reject = "unknown"
        else:
            reject = "hysteresis" if raw_gain > self.tol else "satisfied"
        fields = dict(t=t, machine=machine, moved=moved,
                      node=node if moved else None,
                      source=source if moved else None,
                      dest=dest if moved else None,
                      gain=gain if moved else None,
                      weight=float(b[node]) if moved else None,
                      c0=c0, ct0=ct0, reject=reject)
        if raw_gain is not None and np.isfinite(raw_gain):
            fields["raw_gain"] = float(raw_gain)
        if batch is not None:
            fields["batch"] = int(batch)
        self.emit("turn", run, **fields)

    def record_sweeps(self, run: str, c0s, ct0s, active, movers=None,
                      batch=None) -> int:
        """Emit ``sweep`` events from simultaneous-mode per-sweep outputs."""
        c0s = np.asarray(c0s)
        ct0s = np.asarray(ct0s)
        act = np.asarray(active)
        mv = None if movers is None else np.asarray(movers)
        count = 0
        for t in range(act.shape[0]):
            if not act[t]:
                continue
            fields = dict(t=t, movers=-1 if mv is None else int(mv[t]),
                          c0=float(c0s[t]), ct0=float(ct0s[t]),
                          active=bool(act[t]))
            if batch is not None:
                fields["batch"] = int(batch)
            self.emit("sweep", run, **fields)
            count += 1
        return count

    # ------------------------------------------------------------------
    # run closure, drift, wire reconciliation
    # ------------------------------------------------------------------
    def record_result(self, run: str, result, *, wall: float | None = None,
                      c0=None, ct0=None,
                      drift_budget: float = DRIFT_BUDGET, **extra) -> None:
        """Emit the ``drift`` check and the closing ``run_end`` event.

        ``result`` is any ``RefineResult``-shaped object (duck-typed:
        ``num_moves/num_turns/converged/loads/aggregate_drift``, and
        ``num_sweeps`` and ``apply_slots`` where a sweep runtime sets
        them).
        ``extra`` fields ride on the ``run_end`` verbatim — fault-
        injected runs attach ``recovered``/``recovery_drift``
        (DESIGN.md §15.6)."""
        drift = float(np.asarray(result.aggregate_drift))
        self.emit("drift", run, value=drift, budget=drift_budget,
                  ok=drift <= drift_budget)
        fields = dict(num_moves=int(np.asarray(result.num_moves)),
                      num_turns=int(np.asarray(result.num_turns)),
                      converged=bool(np.asarray(result.converged)),
                      loads=np.asarray(result.loads),
                      aggregate_drift=drift)
        for counter in ("num_sweeps", "apply_slots"):
            value = int(np.asarray(getattr(result, counter, 0)))
            if value:
                fields[counter] = value
        if wall is not None:
            fields["wall"] = float(wall)
        if c0 is not None:
            fields["c0"] = float(c0)
        if ct0 is not None:
            fields["ct0"] = float(ct0)
        fields.update(extra)
        self.emit("run_end", run, **fields)

    def record_wire(self, run: str, check) -> None:
        """Emit a ``wire`` event from an ``accounting.WireCheck``."""
        self.emit("wire", run, rounds=int(check.rounds),
                  measured_payload=int(check.measured_payload),
                  predicted_payload=int(check.predicted_payload),
                  measured_setup=int(check.measured_setup),
                  predicted_setup=int(check.predicted_setup),
                  ok=bool(check.ok))

    # ------------------------------------------------------------------
    # convenience views
    # ------------------------------------------------------------------
    def events_for(self, run: str) -> list[dict]:
        return [e for e in self.events if e["run"] == run]
