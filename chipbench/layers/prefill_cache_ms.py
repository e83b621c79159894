"""Device milliseconds a prefill dispatch spends on the engine's own cache
traffic, outside the model's path (a bucket's rows gathered from the
pools, rows written back, a pool copied):
summed over the traced range's ``prefill_chunk`` modules, by their
number (the range is the same iterations in every run, so the mix of
four-row and one-row dispatches is fixed).
Source: trace + the program's ``program_map`` events
(``chipbench/split.py``); None from a program that writes none."""

from chipbench import split


def read(o):
    return split.prefill_ms(o, "cache")
