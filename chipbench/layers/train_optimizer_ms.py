"""Device milliseconds of a train step in the optimizer (what the step
does outside the model's path and the loss; a weight-gradient matmul
fused with its update goes where the fusion's root goes): the median
over the traced ``train_step`` modules.
Source: trace + the program's ``program_map`` events
(``chipbench/split.py``); None from a program that writes none."""

from chipbench import split


def read(o):
    return split.train_ms(o, "optimizer")
