"""One reader per metric, found by the metric's name: ``read(run)`` takes
a :class:`bench.harness.Run` and returns the metric's value, or None when
the run holds nothing to read it from."""
