"""Run telemetry layer (DESIGN.md §14).

Structured, typed event streams from every execution axis of the repo —
the three ``repro.core.refine`` entry points, the DES engine ticks, the
four ``repro.distributed`` drivers (with *measured* wire-byte counters
reconciled against the analytic ledger), and the batched sweep runtime —
plus sinks (JSONL run logs, Chrome-trace/Perfetto phase timing), a
replay/report CLI (``python -m repro.obs.report``), host spans on the
profiler's clock and a process-wide compile counter (``spans``).

Telemetry is strictly opt-in: every instrumented entry point takes
``recorder=None`` and the ``None`` path is the exact pre-telemetry
computation — same jaxpr, no host callbacks, bitwise-identical results
(``tests/test_obs.py`` pins both properties).
"""
from .events import EVENT_KINDS, make_event, validate_event
from .recorder import Recorder
from .sinks import JsonlSink, MemorySink, chrome_trace, read_jsonl
from .spans import Compiles, compiles

__all__ = [
    "Compiles",
    "EVENT_KINDS",
    "JsonlSink",
    "MemorySink",
    "Recorder",
    "chrome_trace",
    "compiles",
    "make_event",
    "read_jsonl",
    "validate_event",
]
