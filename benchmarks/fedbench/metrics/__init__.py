"""One reader per metric of ``BENCHMARK.json``, found by the metric's
name.  ``read(ctx)`` returns the metric's value, or ``None`` when the
run has nothing to read for it (the harness then leaves it out)."""
