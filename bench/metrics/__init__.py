"""One reader per per-layer metric, found by the metric's name.

A reader is ``read(run: bench.run.RunView) -> float | None``.  It
returns ``None`` where it finds nothing to read, and the metric is then
left out of the result line.
"""
