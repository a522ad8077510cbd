"""The chip benchmark: BENCHMARK.json's harness, traffic, reference,
check and trace reduction.  ``run.py`` is the entry point."""
