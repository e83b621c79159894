"""Plain float32 ``jax.numpy`` references, one per family, independent
of the program's ``models/``."""
