"""One reader per per-layer metric, found by the metric's name: module
``chipbench/layers/<metric>.py`` with ``read(obs) -> float | None``."""
