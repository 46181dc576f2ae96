"""Per-layer metrics, one module each, found by the metric's name.

A module ``<name>.py`` defines ``read(ctx) -> float | None``; ``ctx`` is a
:class:`bench.metrics.Context`.  ``None`` means the metric found nothing
to read in this run, and the result line leaves it out.
"""
