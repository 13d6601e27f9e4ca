"""On-chip benchmark of the search engine's served path: see ``run.py``
and ``BENCHMARK.json`` at the repository root."""
