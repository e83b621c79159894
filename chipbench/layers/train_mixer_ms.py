"""Device milliseconds of a train step in the token mixers (attention:
projections and the kernels under it), forward and backward: the
median over the traced ``train_step`` modules.
Source: trace + the program's ``program_map`` events
(``chipbench/split.py``); None from a program that writes none."""

from chipbench import split


def read(o):
    return split.train_ms(o, "mixer")
