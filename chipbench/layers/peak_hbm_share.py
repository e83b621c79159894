"""Peak device memory over the device's limit, fullest chip.
Source: ``memory_stats()`` (a program counter of the runtime)."""


def read(o):
    if not o.memory_limit_bytes:
        return None
    return 100.0 * o.memory_peak_bytes / o.memory_limit_bytes
