"""Per-layer metric readers, one file each, named as in ``BENCHMARK.json``.

Each defines ``read(ctx)`` and returns the metric's value, or None where
the run has nothing for it to read.  ``ctx`` holds the reduced trace
(``trace``, ``devices``, ``window`` in trace nanoseconds), the chip's
peaks (``peak``) and what the cell's runner counted."""
