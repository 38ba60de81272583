"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; :func:`reduce` reads it with
``jax.profiler.ProfileData`` and returns a :class:`TraceSummary`:

* ``busy_s`` — per chip, the union of the intervals in which an XLA
  operation ran (the ``XLA Ops`` line of each ``/device:`` plane),
  clipped to the traced window and averaged over the chips used;
* ``window_s`` — the length of the benchmark's ``bench.window`` span;
* ``ops`` — device seconds per operation name, summed over the chips and
  divided by their number, most expensive first;
* ``collective_s`` — seconds per chip of ``all-gather`` operations;
* ``gaps`` — idle seconds of the first chip, each gap attributed to the
  benchmark's host span (``bench.*``, other than the window) that covers
  most of it, summed by span name, longest first.

All of it works on plain ``(name, start_ns, end_ns)`` tuples, so the
arithmetic is tested on a recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float
    ops: list[tuple[str, float]]
    collective_s: float
    gaps: list[tuple[str, float]]


@dataclasses.dataclass
class Events:
    """The parts of a trace the reduction reads."""
    device_ops: dict[int, list[tuple[str, int, int]]]   # chip -> ops
    host_spans: list[tuple[str, int, int]]              # bench.* spans


def _chip_id(plane_name: str) -> int | None:
    if not plane_name.startswith("/device:") or "CUSTOM" in plane_name:
        return None
    tail = plane_name.rsplit(":", 1)[-1]
    return int(tail) if tail.isdigit() else None


def read_events(path: str) -> Events:
    """The device ops and the benchmark's host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    spans: list = []
    for plane in data.planes:
        chip = _chip_id(plane.name)
        for line in plane.lines:
            if chip is not None and line.name == OPS_LINE:
                ops.setdefault(chip, []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith("bench."))
    return Events(device_ops=ops, host_spans=spans)


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(found)}")
    return found[0]


def merge(intervals) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals, as disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def attribute_gaps(busy, lo: int, hi: int, spans) -> dict[str, int]:
    """Idle nanoseconds in ``[lo, hi]`` outside ``busy``, each gap given
    to the host span that overlaps it most (``"untraced host"`` when
    none does).  The benchmark's host spans follow one another, so a
    gap is compared only with the spans that end after it starts."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [a for _, a, _ in spans]
    out: dict[str, int] = {}
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        best, best_cover = "untraced host", 0
        k = bisect.bisect_left(starts, e) - 1
        while k >= 0 and spans[k][2] > s:
            name, a, b = spans[k]
            cover = min(b, e) - max(a, s)
            if cover > best_cover:
                best, best_cover = name, cover
            k -= 1
        out[best] = out.get(best, 0) + (e - s)
    return out


def summarize(events: Events, chips: int) -> TraceSummary:
    windows = [sp for sp in events.host_spans if sp[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    _, lo, hi = windows[0]
    used = sorted(events.device_ops)[:chips]
    if not used:
        raise RuntimeError("the trace holds no device operations")
    busy_ns, per_op, collective = 0, {}, 0
    for chip in used:
        inside = [(n, max(s, lo), min(e, hi))
                  for n, s, e in events.device_ops[chip] if e > lo and s < hi]
        busy_ns += sum(e - s for s, e in merge((s, e) for _, s, e in inside))
        for name, s, e in inside:
            per_op[name] = per_op.get(name, 0) + (e - s)
            if "all-gather" in name:
                collective += e - s
    first = merge(clip(((s, e) for _, s, e in events.device_ops[used[0]]),
                       lo, hi))
    host = [sp for sp in events.host_spans if sp[0] != WINDOW_SPAN]
    gaps = attribute_gaps(first, lo, hi, host)
    n = len(used)
    return TraceSummary(
        chips=n, window_s=(hi - lo) / 1e9, busy_s=busy_ns / n / 1e9,
        ops=sorted(((k, v / n / 1e9) for k, v in per_op.items()),
                   key=lambda kv: -kv[1]),
        collective_s=collective / n / 1e9,
        gaps=sorted(((k, v / 1e9) for k, v in gaps.items()),
                    key=lambda kv: -kv[1]))


def reduce(logdir: str, chips: int) -> TraceSummary:
    return summarize(read_events(find_xplane(logdir)), chips)
