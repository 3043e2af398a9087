"""Per-layer metrics, one reader per file, found by the metric's name.

Each module has ``read(run)`` returning the metric's value, or ``None``
when the run gives it nothing to read (the harness then leaves the metric
out of the result line).  ``run`` is :class:`bench.run.RunData`.
"""
