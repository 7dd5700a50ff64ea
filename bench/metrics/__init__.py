"""One reader per metric: ``read(run)`` returns the metric's value from a
``bench.harness.Run``, or ``None`` where the run holds nothing to read."""
