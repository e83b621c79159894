"""The on-chip benchmark: one command runs one cell of ``BENCHMARK.json``
once and prints one JSON line. See ``chipbench/README.md``."""
