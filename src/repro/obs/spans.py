"""Host spans on the profiler's clock and a process-wide compile counter
(DESIGN.md §14.6).

:func:`annotate` opens a ``jax.profiler.TraceAnnotation``: a named span
on the host line of any profile taken while it is open, on the same
clock as the device ops.  With no profile running it costs a few hundred
nanoseconds.  The public refinement entry points open
``repro.refine`` / ``repro.refine_sweeps`` around their host work, and
``Recorder.phase`` opens one of its phase name.

:func:`compiles` reads a counter of the backend compilations this
process has made since the counter's first read: a ``jax.monitoring``
duration listener on ``/jax/core/compile/backend_compile_duration``.
JAX records that event around ``compile_or_get_cached``, so a load from
the persistent compilation cache counts too, with its retrieval time as
its seconds.  Reading it needs no callback and changes no program.

JAX is imported on first use only, so ``repro.obs.recorder`` stays
importable without it.
"""
from __future__ import annotations

import threading
from typing import NamedTuple

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def annotate(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` (a context
    manager)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class Compiles(NamedTuple):
    count: int        # backend compilations (persistent-cache loads included)
    seconds: float    # their summed duration


_lock = threading.Lock()
_count = 0
_seconds = 0.0
_installed = False


def _on_duration(event: str, duration: float, **_) -> None:
    global _count, _seconds
    if event == COMPILE_EVENT:
        with _lock:
            _count += 1
            _seconds += float(duration)


def compiles() -> Compiles:
    """Compilations since this function was first called in the process.

    The first call installs the listener and reads zero; take the
    difference of two reads to count what happened between them."""
    global _installed
    with _lock:
        if not _installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _installed = True
        return Compiles(_count, _seconds)
