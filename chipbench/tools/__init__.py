"""Hand tools around the benchmark (none is run by a cell)."""
