"""Model: device milliseconds a decode round of the decode step's operations
in part ``mlp`` (a MoE model's shared experts count where the code scopes
them: under ``moe``) (``perfbench/scope_time.py``). 0.0 on a program that
registers no decode step."""
from perfbench import scope_time


def read(run):
    return scope_time.ms_per_round(run, "decode", ("mlp",))
