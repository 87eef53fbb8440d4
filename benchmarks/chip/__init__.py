"""On-chip serving benchmark: ``run.py`` runs one cell of the root
``BENCHMARK.json`` once (see ``harness.py``)."""
