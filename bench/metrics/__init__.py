"""Per-layer metrics: one module per metric, named as in
``BENCHMARK.json``.  Each has ``read(run)``, which takes a
:class:`bench.harness.TracedRun` and returns the number, or ``None``
where the run holds nothing to read it from."""
