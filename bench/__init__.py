"""On-chip benchmark of ``SimdramDevice(backend="chip").dispatch``.

``bench/run.py`` runs one cell of ``BENCHMARK.json``; everything that
defines a cell (deployment, traffic mix, per-layer metric) is a file of
its own under this directory, found by the name the cell gives it.
"""
