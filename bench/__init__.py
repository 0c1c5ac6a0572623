"""On-chip benchmark harness, driven by ``BENCHMARK.json`` (see README.md)."""
