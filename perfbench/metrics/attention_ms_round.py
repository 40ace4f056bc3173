"""Model: device milliseconds a decode round (divisor:
``program_spans.rounds_in``) of the DECODE step's operations in part
``attention``: the paged kernel, the row writer, the projections around them
(``perfbench/scope_time.py``). 0.0 on a program that registers no decode
step or in a window without rounds."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("attention",))
