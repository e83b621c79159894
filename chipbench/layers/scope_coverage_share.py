"""Device seconds of the operations that some ``program_map`` holds under
a component other than ``other``, over the device's busy seconds in the
trace, every program counted: where it falls, the per-component
milliseconds beside it are hollow.
Source: trace + the program's ``program_map`` events
(``chipbench/split.py``); None from a program that writes none."""

from chipbench import split


def read(o):
    return split.coverage_share(o)
