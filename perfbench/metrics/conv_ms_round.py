"""Model: device milliseconds a decode round of the decode step's operations
in part ``conv``: a gated short convolution's two projections, its taps and
the update of the slots' cached columns (``perfbench/scope_time.py``). 0.0
on a program that registers no decode step or names no such part."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("conv",))
