"""The chip benchmark: one cell (a model configuration under a traffic mix)
per run of ``bench/run.py``.  Everything that belongs to one configuration,
traffic mix, cell or per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it."""
