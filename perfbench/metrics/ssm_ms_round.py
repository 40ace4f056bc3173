"""Model: device milliseconds a decode round of the decode step's operations
in part ``ssm``: ``ssm_update`` and the projections around it
(``perfbench/scope_time.py``). 0.0 on a program that registers no decode
step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("ssm",))
