"""Backend compiles after the window opened, by the benchmark's own
``jax.monitoring`` listener. Must be 0, else ``correct`` is false."""


def read(o):
    return float(o.compiles_in_window)
